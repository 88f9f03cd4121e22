"""``train.mfu``: model FLOPs of one step (``chipbench.lib.model.
train_flops_per_token``, no remat) over ``train.step_s`` (the mean
``train.step`` span of the steps no save stalls), as a share of the chips'
bf16 peak."""

from chipbench.lib.harness import load_module


def read(ctx):
    step_s = load_module("metrics", "train.step_s").read(ctx)
    if step_s is None or not getattr(ctx, "peak", None):
        return None
    return 100.0 * ctx.flops_per_step / step_s / (ctx.chips * ctx.peak["bf16_flops_per_s"])
