"""``restore.read_s``: per resume in the window, the self time of its
``restore.prefetch`` spans (host region reads), averaged over resumes."""

from chipbench.lib.trace import self_time_s


def read(ctx):
    return _per_resume(ctx, "restore.prefetch")


def _per_resume(ctx, name):
    resumes = getattr(ctx, "resumes", None)
    if not resumes:
        return None
    per = [sum(self_time_s(ctx.spans, name, a, b)) for a, b in resumes]
    return sum(per) / len(per) if any(per) else None
