"""``train.step_s``: mean ``train.step`` span (dispatch to
``block_until_ready``) over the window's steps that no save stalls.

``Trainer.run`` closes the span of step ``s`` before it calls
``manager.save(state, s)``: the cut itself lies between two spans, and the
stall it leaves behind (the next step's late dispatch) lands in the span of
step ``s + 1``.  That step is left out; the saving step ``s`` is kept."""

import statistics


def read(ctx):
    if getattr(ctx, "save_steps", None) is None:
        return None
    t0, t1 = ctx.window_us
    d = [r["dur_us"] / 1e6 for r in ctx.spans
         if r["name"] == "train.step" and t0 <= r["ts_us"] < t1
         and r["attrs"].get("step", 0) - 1 not in ctx.save_steps]
    return statistics.fmean(d) if d else None
