"""``save.fsync_share``: the share of the shard writers' time spent in
``fsync``, over the window's saves — summed ``save.fsync`` spans over
summed ``save.shard`` spans, in percent.  Both are on the I/O pool's
threads, where writes overlap, so the base is summed worker time and not
the save's wall time (the arithmetic of ``benchmarks/run.py:_obs_derived``)."""


def read(ctx):
    if getattr(ctx, "save_steps", None) is None:
        return None
    shard = sum(r["dur_us"] for r in ctx.spans if r["name"] == "save.shard")
    fsync = sum(r["dur_us"] for r in ctx.spans if r["name"] == "save.fsync")
    return 100.0 * fsync / shard if shard else None
