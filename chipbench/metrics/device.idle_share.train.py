"""``device.idle_share.train``: 1 - (union of device op intervals / traced
window), in percent, from the profiler trace of a stretch of training that
holds one save (``chipbench.lib.trace.reduce``)."""


def read(ctx):
    if getattr(ctx, "save_steps", None) is None or ctx.device is None:
        return None
    return 100.0 * ctx.device["idle_share"]
