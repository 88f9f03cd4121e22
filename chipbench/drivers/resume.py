"""Traffic kind ``resume``: back-to-back resumes from one committed
checkpoint, on the layout it was saved from.

Set-up trains one step on ``save_mesh`` from the seed's state, saves it
(blocking) under ``TMPDIR``, records a checksum of the saved state, then
runs the next step for the loss and the state a resume must reproduce.
One resume runs in set-up as warm-up.

The window holds nothing but resumes, each as a relaunched job pays it: a
fresh ``Trainer`` on ``resume_mesh`` over the checkpoint directory,
``init_or_restore`` (``mode`` must be the workload's tier), one step
through ``Trainer.run`` to ``block_until_ready``, then teardown.  The window
closes at the end of the first resume that ends past ``--seconds``.

* ``resume_s`` — the window's seconds over the resumes completed in it.

``correct``: for every resume, the restored state's checksum equals the
saved one, and its next loss and the state after that step equal set-up's,
all bit for bit.  Only the same layout is taken: a resume onto another
layout cannot be compared bit for bit, and its step has to be held against
the plain reference, which this driver does not run.
"""

from __future__ import annotations

import gc
import time
import types

import numpy as np

from chipbench.lib import compare, model
from chipbench.lib.harness import Outcome, dir_bytes, log

NEVER = 1 << 30  # save interval of a trainer that must not save


def _policy(r, **kw):
    from repro.ckpt.policy import CheckpointPolicy

    return CheckpointPolicy(save_interval=NEVER, keep_last=1,
                            codec=r.variant.get("codec", r.wl["codec"]), **kw)


def run(r) -> Outcome:
    import jax

    import repro.obs as obs
    from repro.core.plan import ResumeMode

    wl = r.wl
    want_mode = ResumeMode(wl["mode"])
    if wl["save_mesh"] != wl["resume_mesh"]:
        raise ValueError(f"{r.cell['name']}: the resume driver takes one layout, "
                         f"got {wl['save_mesh']} -> {wl['resume_mesh']}")
    t_setup = time.perf_counter()
    ckpt = r.scratch("ckpt")
    csum = compare.checksum_fn()
    feed = r.feed()

    t = r.trainer(wl["save_mesh"], str(ckpt), _policy(r, async_save=False))
    t.batch = feed
    r.plant(t)
    state = model.make_state_fn(t)(*model.seed_words(r.seed))
    state, _ = t.run(state, 0, 1)
    saved = jax.device_get(csum(state))
    t.manager.save(state, 1, block=True)
    state, hist = t.run(state, 1, 1)
    want_loss, want_after = hist[0]["loss"], jax.device_get(csum(state))
    written = dir_bytes(ckpt)
    del state
    t.manager.close()
    del t
    gc.collect()

    results: list[dict] = []
    resume_feed = r.feed("resume")

    def resume():
        with obs.span("chipbench.resume"):
            tr = r.trainer(wl["resume_mesh"], str(ckpt), _policy(r))
            tr.batch = resume_feed
            r.plant(tr, "resume")
            with obs.span("chipbench.init_or_restore"), r.phase("chipbench.restore"):
                st, info = tr.init_or_restore()
            restored = csum(st)
            with r.phase("chipbench.first_step"):
                st, h = tr.run(st, 1, 1)
            after = csum(st)
            del st
            tr.manager.close()
        results.append({"info": info, "restored": restored, "after": after,
                        "loss": h[0]["loss"]})

    resume()  # warm-up: the same work once, outside the window
    setup_s = time.perf_counter() - t_setup
    log("setup", setup_s=setup_s, bytes_written=written, saved_loss=want_loss)

    tracer = obs.enable(obs.Tracer()) if r.traced else None
    reduced = None
    n0 = len(results)
    t0 = time.perf_counter()
    deadline = t0 + r.seconds
    while True:
        if r.traced and len(results) == n0:
            with _Profile(r) as prof:
                resume()
            reduced = prof.reduced
        else:
            resume()
        t1 = time.perf_counter()
        if t1 >= deadline:
            break
    seconds = t1 - t0
    records = tracer.span_records() if tracer is not None else []
    if tracer is not None:
        obs.disable(tracer)
    r.read_memory_peak(r.devices)
    n = len(results) - n0

    # Every resume, the warm-up included, against what set-up saved.
    bad_mode = sum(1 for x in results
                   if x["info"] is None or x["info"].mode is not want_mode
                   or x["info"].step != 1)
    differing = sum(compare.mismatched_leaves(jax.device_get(x["restored"]), saved)
                    for x in results)
    loss_gap = float(np.max([abs(x["loss"] - want_loss) for x in results]))
    r.check("resume_mode_wrong", bad_mode, 0)
    r.check("restored_state_leaves_differing", differing, 0)
    after = sum(compare.mismatched_leaves(jax.device_get(x["after"]), want_after)
                for x in results)
    r.check("next_state_leaves_differing", after, 0)
    r.check("next_loss_gap", loss_gap, 0)
    log("window", resumes=n, seconds=seconds,
        modes=sorted({x["info"].mode.value for x in results if x["info"]}),
        restore_s=[x["info"].wall_time_s for x in results if x["info"]],
        losses=[x["loss"] for x in results])

    ctx = None
    if r.traced:
        wins = [x for x in records if x["name"] == "chipbench.resume"]
        ctx = types.SimpleNamespace(
            spans=records, resumes=[(x["ts_us"], x["ts_us"] + x["dur_us"]) for x in wins])
    return Outcome(e2e={"resume_s": seconds / n, "setup_s": setup_s}, attempted=len(results),
                   failed=bad_mode, ctx=ctx, trace=reduced)


class _Profile:
    """Profile one resume; the annotation ``chipbench.traced`` bounds it."""

    def __init__(self, r):
        self.r = r
        self.reduced = None

    def __enter__(self):
        import jax

        self.dir = self.r.scratch("profile")
        jax.profiler.start_trace(str(self.dir))
        self.ann = jax.profiler.TraceAnnotation("chipbench.traced")
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax

        from chipbench.lib import trace

        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        compact = trace.compact(self.dir)
        log("profile", device_lines=compact["lines"])
        self.reduced = trace.reduce(compact)
        return False
