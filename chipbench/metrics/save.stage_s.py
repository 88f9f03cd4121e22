"""``save.stage_s``: mean ``save.stage`` span per save in the window — the
blocking device→host cut of the whole state (``snapshot_state``)."""

import statistics


def read(ctx):
    if getattr(ctx, "save_steps", None) is None:
        return None
    t0, t1 = ctx.window_us
    d = [r["dur_us"] / 1e6 for r in ctx.spans
         if r["name"] == "save.stage" and t0 <= r["ts_us"] < t1]
    return statistics.fmean(d) if d else None
