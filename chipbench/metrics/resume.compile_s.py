"""``resume.compile_s``: per resume in the window, the union of the
``jit.trace``, ``jit.lower`` and ``jit.compile`` spans (``repro.obs``
records them from JAX's compile events) on the thread that ran the
resume's ``train.step``, averaged over resumes: the fresh ``Trainer``'s
re-trace, lowering and compile-cache load.  None where the program records
no such span."""

from chipbench.lib.spans import JIT
from chipbench.lib.trace import _union


def read(ctx):
    resumes = getattr(ctx, "resumes", None)
    if not resumes:
        return None
    out = []
    for a, b in resumes:
        inside = [r for r in ctx.spans if a <= r["ts_us"] < b]
        tids = {r["tid"] for r in inside if r["name"] == "train.step"}
        u = _union([(r["ts_us"], r["ts_us"] + r["dur_us"]) for r in inside
                    if r["name"] in JIT and r["tid"] in tids])
        out.append(sum(y - x for x, y in u) / 1e6)
    return sum(out) / len(out) if any(out) else None
