"""``save.write_s``: mean ``save.async_job`` span per save requested in the
window — the background write, fsync and commit on the I/O pool."""

import statistics


def read(ctx):
    if getattr(ctx, "save_steps", None) is None:
        return None
    d = [r["dur_us"] / 1e6 for r in ctx.spans
         if r["name"] == "save.async_job" and r["attrs"].get("step") in ctx.save_steps]
    return statistics.fmean(d) if d else None
