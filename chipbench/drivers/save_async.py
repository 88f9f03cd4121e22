"""Traffic kind ``save_async``: training with asynchronous saves.

Set-up builds one ``Trainer`` over the workload's mesh with the async
``CheckpointManager`` (checkpoints under ``TMPDIR``), makes the state on
the device from the seed in one call, and drives it through its first
``setup_steps`` steps with ``Trainer.run`` — the window's own call and feed.
Those steps are what the plain reference follows.  Set-up then trains on
to ``window_start_step``, which places the window's saves (every
``save_interval`` steps) away from its two ends.

The window continues the same trainer with ``Trainer.run``: a save every
``save_interval`` steps (``manager.save`` → ``AsyncSaver.submit``: the
blocking device→host cut, then the background write).  It closes at the
end of the first step that ends past ``--seconds``; saves still in flight
are awaited after it.

* ``train_tokens_per_s`` — tokens of every step in the window over the
  window's seconds, save stalls and backpressure included;
* ``save_commit_s`` — for each save requested in the window, from entering
  ``manager.save`` to its ``COMMIT`` marker on disk, averaged.

``correct``: the set-up steps against the plain reference (loss, first
gradient, change of the parameters), and the newest checkpoint committed
in the window, restored through the normal path after it, against the
state checksum and next loss the window recorded.
"""

from __future__ import annotations

import gc
import statistics
import threading
import time
import types

from chipbench.lib import compare, model
from chipbench.lib.harness import Outcome, log


class _Closed(Exception):
    """Raised from the per-step callback once the window's time is up."""


def policy(r, **kw):
    from repro.ckpt.policy import CheckpointPolicy

    wl = r.wl
    return CheckpointPolicy(
        save_interval=wl["save_interval"], keep_last=wl["keep_last"],
        max_pending_saves=wl["max_pending_saves"], save_mode=wl["save_mode"],
        codec=r.variant.get("codec", wl["codec"]), **kw,
    )


def first_steps(r, trainer, seed: int, n: int) -> tuple[dict, object]:
    """Make the state from ``seed`` and train ``n`` steps through
    ``Trainer.run``; return the readings the reference is compared with and
    the state."""
    import jax

    hi, lo = model.seed_words(seed)
    state = model.make_state_fn(trainer)(hi, lo)
    state, hist = trainer.run(state, 0, 1)
    b1 = r.raw["optimizer"]["adam_b1"]
    grad = {k: float(v) / (1 - b1) for k, v in compare.leaf_norms_fn()(state.exp_avg).items()}
    state, more = trainer.run(state, 1, n - 1)
    change = compare.change_norms_fn(model.param_shapes(trainer))(state.params, hi, lo)
    readings = {"losses": [h["loss"] for h in hist + more], "grad": grad,
                "change": {k: float(v) for k, v in jax.device_get(change).items()}}
    return readings, state


def reference(r, shapes: dict, feed, n: int, ref=None, half: bool = False) -> dict:
    """The plain reference (``ref``, default float32) over the same seed,
    weights and rows."""
    import jax

    from chipbench.lib.reference import Reference

    ref = ref or Reference(r.raw)
    hi, lo = model.seed_words(feed.seed)
    params0 = jax.jit(lambda a, b: model.gen_params(a, b, shapes))(hi, lo)
    return ref.train(params0, [feed.tokens(i) for i in range(n)], half=half)


def check_training(r, prog: dict, ref: dict) -> dict:
    gaps = compare.training_gaps(prog, ref)
    lim = r.wl["limits"]
    for k in ("loss_gap", "grad_gap", "change_gap"):
        r.check(k, gaps[k], lim[k])
    log("reference", program_losses=prog["losses"], reference_losses=ref["losses"],
        **{k: gaps[k] for k in ("grad_gap_leaf", "change_gap_leaf", "still_leaves")})
    return gaps


def run(r) -> Outcome:
    import jax

    import repro.obs as obs

    wl = r.wl
    n0 = wl["setup_steps"]
    t_setup = time.perf_counter()
    ckpt = r.scratch("ckpt")
    trainer = r.trainer(wl["mesh"], str(ckpt), policy(r))
    feed = r.feed()
    trainer.batch = feed
    r.plant(trainer)
    prog, state = first_steps(r, trainer, r.seed, n0)
    start = wl["window_start_step"]
    state, more = trainer.run(state, n0, start - n0)
    shapes = model.param_shapes(trainer)
    csum = compare.checksum_fn()
    jax.block_until_ready(csum(state))
    setup_s = time.perf_counter() - t_setup
    log("setup", setup_s=setup_s, setup_losses=prog["losses"] + [h["loss"] for h in more])

    w = _Window(r, trainer, csum)
    state = w.run(state, start)
    del state
    mgr = trainer.manager
    written = sum(res.bytes_written for res in mgr.wait())
    w.drain()
    if w.tracer is not None:
        obs.disable(w.tracer)
    r.read_memory_peak(r.devices)

    steps = w.last_step - start
    tokens = steps * wl["batch"] * wl["seq"]
    commits = [w.commit[s] - w.enter[s] for s in sorted(w.enter) if s in w.commit]
    failed = len(w.enter) - len(commits)
    log("window", steps=steps, seconds=w.seconds, saves=sorted(w.enter),
        commit_s=commits, failed_saves=failed, bytes_written=written)
    e2e = {
        "train_tokens_per_s": tokens / w.seconds,
        "save_commit_s": statistics.fmean(commits) if commits else float("nan"),
        "setup_s": setup_s,
    }

    # What the window produced: its newest commit, read back through the
    # normal restore path on a fresh trainer.
    mgr.close()
    t_check = time.perf_counter()
    _check_checkpoint(r, ckpt, w)
    del trainer, mgr
    gc.collect()
    t_ref = time.perf_counter()
    check_training(r, prog, reference(r, shapes, feed, n0))
    log("after_window", checkpoint_check_s=t_ref - t_check,
        reference_s=time.perf_counter() - t_ref)

    ctx = None
    if r.traced:
        ctx = types.SimpleNamespace(
            spans=w.records, window_us=w.window_us, save_steps=set(w.enter),
            flops_per_step=model.train_flops_per_token(r.model_config(), wl["seq"])
            * wl["batch"] * wl["seq"],
            chips=len(r.devices), peak=r.peak, device=w.reduced,
        )
    return Outcome(e2e=e2e, attempted=len(w.enter), failed=failed, ctx=ctx,
                   trace=w.reduced)


def _check_checkpoint(r, ckpt, w) -> None:
    import jax

    from repro.core.plan import ResumeMode

    newest = max(w.commit) if w.commit else None
    if newest is None:
        r.check("checkpoint_missing", 1, 0)
        return
    t = r.trainer(r.wl["mesh"], str(ckpt), policy(r, async_save=False))
    t.batch = w.trainer.batch
    state, info = t.init_or_restore()
    mode_ok = info is not None and info.mode is ResumeMode.DIRECT and info.step == newest
    r.check("checkpoint_restore_mode", 0 if mode_ok else 1, 0)
    got = jax.device_get(w.csum(state))
    r.check("checkpoint_state_leaves_differing",
            compare.mismatched_leaves(got, jax.device_get(w.sums[newest])), 0)
    state, hist = t.run(state, newest, 1)
    want = w.losses.get(newest + 1)
    gap = abs(hist[0]["loss"] - want) if want is not None else float("nan")
    r.check("checkpoint_next_loss_gap", gap, 0)
    log("checkpoint", step=newest, mode=info and info.mode.value,
        restore_s=info and info.wall_time_s, next_loss=hist[0]["loss"], window_loss=want)
    del state
    t.manager.close()


class _Window:
    """The measured window: ``Trainer.run`` until the time is up, with the
    manager's ``save`` wrapped to time each save and checksum what it saves,
    and a watcher that notes when each save's ``COMMIT`` lands."""

    def __init__(self, r, trainer, csum):
        self.r, self.trainer, self.csum = r, trainer, csum
        self.enter: dict[int, float] = {}
        self.commit: dict[int, float] = {}
        self.sums: dict[int, object] = {}
        self.losses: dict[int, float] = {}
        self.tracer = None
        self.records: list[dict] = []
        self.window_us = (0.0, 0.0)
        self.reduced = None
        self._stop = threading.Event()
        self._watcher = threading.Thread(target=self._watch, daemon=True)
        self._profile_dir = None
        self._annotation = None
        mgr = trainer.manager
        save = mgr.save

        def timed_save(state, step, **kw):
            self.enter[step] = time.perf_counter()
            self.sums[step] = self.csum(state)
            with self.r.phase("chipbench.save"):
                save(state, step, **kw)

        mgr.save = timed_save
        if r.traced:
            feed, step_fn = trainer.batch, trainer.step_fn

            def traced_feed(i):
                with self.r.phase("chipbench.feed"):
                    return feed(i)

            def traced_step(*a):
                with self.r.phase("chipbench.dispatch"):
                    return step_fn(*a)

            trainer.batch, trainer.step_fn = traced_feed, traced_step

    def _watch(self):
        mgr = self.trainer.manager
        while not self._stop.is_set():
            for s in list(self.enter):
                if s not in self.commit and (mgr.step_dir(s) / "COMMIT").exists():
                    self.commit[s] = time.perf_counter()
            time.sleep(0.002)

    def run(self, state, start: int):
        import repro.obs as obs

        r = self.r
        interval = r.wl["save_interval"]
        first_save = (start // interval + 1) * interval
        trace_from, trace_to = first_save - 2, first_save + 3
        if r.traced:
            self.tracer = obs.enable(obs.Tracer())
            if trace_from <= start:
                self._start_profile()
        self._watcher.start()
        t0 = time.perf_counter()
        deadline = t0 + r.seconds

        def on_step(rec):
            step = rec["step"]
            self.losses[step] = rec["loss"]
            if r.traced and step == trace_from:
                self._start_profile()
            if r.traced and step == trace_to and self._annotation is not None:
                self._stop_profile()
            if time.perf_counter() >= deadline:
                raise _Closed(step, time.perf_counter())

        try:
            with obs.span("chipbench.window"):
                self.trainer.run(state, start, 1 << 30, log=on_step)
        except _Closed as c:
            self.last_step, t1 = c.args
        self.seconds = t1 - t0
        if self._annotation is not None:
            self._stop_profile()

    def drain(self, timeout_s: float = 300.0) -> None:
        end = time.perf_counter() + timeout_s
        while len(self.commit) < len(self.enter) and time.perf_counter() < end:
            time.sleep(0.01)
        self._stop.set()
        self._watcher.join()
        if self.tracer is not None:
            self.records = self.tracer.span_records()
            win = next(x for x in self.records if x["name"] == "chipbench.window")
            self.window_us = (win["ts_us"], win["ts_us"] + win["dur_us"])

    def _start_profile(self):
        import jax

        self._profile_dir = self.r.scratch("profile")
        jax.profiler.start_trace(str(self._profile_dir))
        self._annotation = jax.profiler.TraceAnnotation("chipbench.traced")
        self._annotation.__enter__()

    def _stop_profile(self):
        import jax

        from chipbench.lib import trace

        self._annotation.__exit__(None, None, None)
        self._annotation = None
        jax.profiler.stop_trace()
        compact = trace.compact(self._profile_dir)
        log("profile", device_lines=compact["lines"],
            host_phases=sorted({h[0] for h in compact["host"]}))
        self.reduced = trace.reduce(compact)
