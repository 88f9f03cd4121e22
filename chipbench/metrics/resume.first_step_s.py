"""``resume.first_step_s``: per resume in the window, from the end of
``init_or_restore`` (the benchmark's ``chipbench.init_or_restore`` span) to
the end of the first ``train.step`` after it — re-trace, compile-cache
load and the step — averaged over resumes."""


def read(ctx):
    resumes = getattr(ctx, "resumes", None)
    if not resumes:
        return None
    out = []
    for a, b in resumes:
        inside = [r for r in ctx.spans if a <= r["ts_us"] < b]
        ends = [r["ts_us"] + r["dur_us"] for r in inside if r["name"] == "chipbench.init_or_restore"]
        steps = [r["ts_us"] + r["dur_us"] for r in inside if r["name"] == "train.step"]
        if ends and steps:
            out.append((min(s for s in steps if s >= ends[0]) - ends[0]) / 1e6)
    return sum(out) / len(out) if out else None
