"""The obs layer itself: disabled fast path, nesting, cross-thread
parent propagation (engine pool + async saver), Chrome export validity,
and counter/SaveResult agreement on a known delta save.

These pin the contracts DESIGN.md §9 promises: tracing off means one
global read + branch and a shared no-op singleton (no allocation, no
Tracer involvement); tracing on means every span lands on one monotonic
timebase with an explicit parent chain that survives thread handoffs.
"""

import glob
import json
import os
import statistics
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
import repro.obs as obs
import repro.obs.trace as trace_mod
from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.layout import MeshSpec
from repro.core.pytree import flatten_with_paths, unflatten_from_paths
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.saver import snapshot_state, write_distributed
from repro.dist.sharding import make_plan, vocab_multiple
from repro.models import build_model
from repro.train.optimizer import TrainState, init_state
from repro.train.trainer import Trainer

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _no_tracer_leak():
    """Every test starts and ends with tracing disabled — a leaked tracer
    would silently change the timing behaviour of every later test."""
    assert obs.active() is None, "a tracer leaked into this test"
    yield
    obs.disable()


@pytest.fixture(scope="module")
def model_setup():
    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    jmesh = make_mesh((1, 1), ("data", "model"))
    return cfg, plan, state, jmesh


def _bump(state: TrainState, idx: int) -> TrainState:
    flat = flatten_with_paths(jax.device_get(state.params))
    name = sorted(flat)[idx % len(flat)]
    flat[name] = np.asarray(flat[name]) + np.float32(1.0 + idx)
    return TrainState(
        unflatten_from_paths(flat), state.exp_avg, state.exp_avg_sq, state.step
    )


# ---------------------------------------------------------------------------
# Disabled fast path


def test_disabled_span_is_shared_singleton():
    a = obs.span("anything", step=1)
    b = obs.span("something_else")
    assert a is b is obs.NULL_SPAN  # no allocation: one shared no-op
    with a as s:
        assert s.set(x=1) is s  # set() chainable and inert
    assert obs.attach(None) is obs.NULL_SPAN
    assert obs.current() is None


def test_disabled_never_touches_tracer(monkeypatch):
    """No Tracer/Metrics machinery runs while disabled — the hot paths pay
    the global read + branch and nothing else."""
    calls = []
    monkeypatch.setattr(
        trace_mod.Tracer, "span",
        lambda self, *a, **k: calls.append(("span", a)),
    )
    monkeypatch.setattr(
        trace_mod.Tracer, "emit_event",
        lambda self, *a, **k: calls.append(("event", a)),
    )
    with obs.span("x"):
        obs.add("counter.name", 3)
        obs.event("event.name", detail="ignored")
    assert calls == []


DISABLED_CHILD = """
import sys
import repro.obs as obs
with obs.span("a"), obs.timed("b"):
    obs.add("c", 1)
    obs.event("d")
assert "jax" not in sys.modules, "repro.obs imported jax with tracing off"
import jax, jax.numpy as jnp, jax.profiler
from jax._src import monitoring
import repro.obs.trace as trace_mod

def touched(*a, **k):
    raise AssertionError("jax.profiler touched with tracing off")

jax.profiler.TraceAnnotation = touched
listeners = lambda: (len(monitoring.get_event_time_span_listeners()),
                     len(monitoring.get_event_listeners()))
before = listeners()
with obs.span("a"), obs.timed("b"):
    jax.jit(lambda x: x + 1)(jnp.ones(3)).block_until_ready()
assert listeners() == before, "a jax.monitoring listener was registered"
assert trace_mod._profiler_annotation is None
print("clean")
"""


def test_disabled_imports_no_jax_and_registers_nothing():
    """Tracing off: ``repro.obs`` imports no JAX, creates no profiler
    annotation and registers no ``jax.monitoring`` listener (a fresh
    process: a test worker may have enabled a tracer already)."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", DISABLED_CHILD], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "clean"


def test_disabled_timed_still_measures():
    with obs.timed("x") as sw:
        mid = sw.elapsed_s  # readable mid-flight (t1 unset)
        assert mid >= 0
    assert sw.elapsed_s >= mid
    assert sw.set(anything=1) is sw  # attrs silently dropped


# ---------------------------------------------------------------------------
# Nesting and parent propagation


def test_span_nesting_parent_chain():
    with obs.enabled() as tracer:
        with obs.span("outer", step=7) as outer:
            with obs.span("inner") as inner:
                assert obs.current() is inner
            assert obs.current() is outer
            obs.event("marker", reason="test")
        assert obs.current() is None
    recs = {r["name"]: r for r in tracer.span_records()}
    assert recs["outer"]["parent_id"] is None
    assert recs["inner"]["parent_id"] == recs["outer"]["span_id"]
    assert recs["outer"]["attrs"] == {"step": 7}
    # inner finished first and lies inside outer on the shared timebase
    assert recs["inner"]["ts_us"] >= recs["outer"]["ts_us"]
    (ev,) = tracer.event_records()
    assert ev["parent_id"] == recs["outer"]["span_id"]


def test_explicit_handoff_across_threads():
    """obs.attach(parent) is the only way a worker-thread span gets a
    parent — without it the span is a root (loud in the timeline)."""
    with obs.enabled() as tracer:
        with obs.span("submit") as parent:
            token = obs.current()

            def with_handoff():
                with obs.attach(token), obs.span("worker.attached"):
                    pass

            def without_handoff():
                with obs.span("worker.orphan"):
                    pass

            with ThreadPoolExecutor(max_workers=1) as pool:
                pool.submit(with_handoff).result()
                pool.submit(without_handoff).result()
    recs = {r["name"]: r for r in tracer.span_records()}
    assert recs["worker.attached"]["parent_id"] == recs["submit"]["span_id"]
    assert recs["worker.orphan"]["parent_id"] is None
    assert recs["worker.attached"]["tid"] != recs["submit"]["tid"]


def test_engine_pool_shard_spans_parented(model_setup, tmp_path):
    """A parallel save's per-shard spans (engine worker pool) parent to
    the ckpt.save span that submitted them."""
    cfg, plan, state, jmesh = model_setup
    with obs.enabled() as tracer:
        write_distributed(
            snapshot_state(state), plan, 10, tmp_path / "s10", workers=4
        )
    recs = tracer.span_records()
    (save_rec,) = [r for r in recs if r["name"] == "ckpt.save"]
    shards = [r for r in recs if r["name"] == "save.shard"]
    assert shards, "parallel save produced no save.shard spans"
    assert all(r["parent_id"] == save_rec["span_id"] for r in shards)
    assert {r["tid"] for r in shards} != {save_rec["tid"]}, (
        "expected at least one shard span on a pool worker thread"
    )


def test_async_saver_job_parented_to_submit(model_setup, tmp_path):
    """The AsyncSaver writer thread re-establishes the submitting span:
    save.async_job (and the ckpt.save under it) chain back to the
    manager.save that enqueued the snapshot."""
    cfg, plan, state, jmesh = model_setup
    with obs.enabled() as tracer:
        mgr = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
            async_save=True, save_interval=1))
        mgr.save(state, 10)
        mgr.wait()
        mgr.close()
    recs = tracer.span_records()
    by_id = {r["span_id"]: r for r in recs}
    (job,) = [r for r in recs if r["name"] == "save.async_job"]
    submit = by_id[job["parent_id"]]
    assert submit["name"] == "manager.save"
    assert job["tid"] != submit["tid"]  # really ran on the writer thread
    (save_rec,) = [r for r in recs if r["name"] == "ckpt.save"]
    assert save_rec["parent_id"] == job["span_id"]


# ---------------------------------------------------------------------------
# The profiler's clock and the jit compile path


def _host_events(profile_dir, prefix):
    """Host events named ``prefix…`` in a ``jax.profiler`` trace, per
    thread line: {line index: [(name, start_ns, duration_ns), ...]}."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(Path(profile_dir) / "**" / "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                       if e.name.startswith(prefix)]
                if evs:
                    out[i] = evs
    return out


def test_spans_mirrored_on_the_profiler_clock(tmp_path):
    """Each real span is also a profiler host event of its name on its own
    thread; mapped by the median offset of the matched pairs, every start
    lies within 100 us of the obs record and every duration within 50 us."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.enabled() as tracer:
            for _ in range(4):
                with obs.span("mirror.outer"):
                    time.sleep(0.002)
                    with obs.timed("mirror.inner"):
                        time.sleep(0.001)

            def worker():
                with obs.span("mirror.worker"):
                    time.sleep(0.001)

            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    lines = _host_events(tmp_path, "mirror.")
    recs = tracer.span_records()
    pairs = []
    for thread in {r["thread"] for r in recs}:
        mine = [r for r in recs if r["thread"] == thread]
        (line,) = [evs for evs in lines.values()
                   if {n for n, _, _ in evs} == {r["name"] for r in mine}]
        for name in {r["name"] for r in mine}:
            obs_side = sorted((r["ts_us"] * 1e3, r["dur_us"] * 1e3) for r in mine
                              if r["name"] == name)
            prof_side = sorted((s, d) for n, s, d in line if n == name)
            assert len(obs_side) == len(prof_side)
            pairs += list(zip(obs_side, prof_side))
    assert len(pairs) == 9
    offset = statistics.median(p[0] - o[0] for o, p in pairs)
    for (o_start, o_dur), (p_start, p_dur) in pairs:
        assert abs(p_start - offset - o_start) <= 100e3
        assert abs(p_dur - o_dur) <= 50e3


def scaled_sin(x):
    return jnp.sin(x) * 3.0 + 1.0


def test_jit_compile_path_recorded_as_spans():
    """A fresh jitted function traced, lowered and compiled under a tracer
    leaves ``jit.trace``/``jit.lower``/``jit.compile`` inside the enclosing
    span on the same thread; its cached second call leaves none."""
    f = jax.jit(scaled_sin)
    x = jnp.arange(5.0)
    with obs.enabled() as tracer:
        with obs.span("cold"):
            f(x).block_until_ready()
        with obs.span("warm"):
            f(x).block_until_ready()
    recs = tracer.span_records()
    by = {r["name"]: r for r in recs if r["name"] in ("cold", "warm")}
    jit = [r for r in recs if r["name"].startswith("jit.")]
    assert {r["name"] for r in jit} == {"jit.trace", "jit.lower", "jit.compile"}
    cold = by["cold"]
    for r in jit:
        assert r["parent_id"] == cold["span_id"] and r["tid"] == cold["tid"]
        assert r["ts_us"] >= cold["ts_us"] - 1
        assert r["ts_us"] + r["dur_us"] <= cold["ts_us"] + cold["dur_us"] + 1
    assert any(r["attrs"]["fun_name"] == "scaled_sin" for r in jit
               if r["name"] == "jit.trace")


CACHE_CHILD = """
import json
import jax, jax.numpy as jnp
import repro.obs as obs
with obs.enabled() as tracer:
    jax.jit(lambda x: jnp.cos(x) * 5.0)(jnp.arange(7.0)).block_until_ready()
print(json.dumps(tracer.counters()))
"""


def test_jit_cache_hits_and_misses_counted(tmp_path):
    """The persistent compilation cache's misses (first process) and hits
    (second process, same cache) become ``jit.cache_*`` counters."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    counts = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", CACHE_CHILD], capture_output=True,
                             text=True, env=env, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        counts.append(json.loads(out.stdout.strip().splitlines()[-1]))
    assert counts[0].get("jit.cache_misses", 0) >= 1
    assert counts[0].get("jit.cache_hits", 0) == 0
    assert counts[1].get("jit.cache_hits", 0) >= 1


def test_trainer_step_phases_in_order():
    """Each ``train.step`` holds ``train.batch``, ``train.dispatch`` and
    ``train.wait`` in that order; the first (cold) step's compile spans
    nest in its dispatch, and the warm step compiles nothing."""
    cfg = reduced(get_config("smollm-360m"))
    trainer = Trainer.create(
        cfg, ParallelismConfig(), TrainConfig(warmup_steps=2, total_steps=10),
        make_mesh((1, 1), ("data", "model")), batch_size=2, seq_len=16,
    )
    state = trainer.init_state()
    with obs.enabled() as tracer:
        trainer.run(state, 0, 2)
    recs = tracer.span_records()
    by_id = {r["span_id"]: r for r in recs}
    steps = sorted((r for r in recs if r["name"] == "train.step"),
                   key=lambda r: r["ts_us"])
    assert [r["attrs"]["step"] for r in steps] == [1, 2]
    for step in steps:
        kids = sorted((r for r in recs if r["parent_id"] == step["span_id"]),
                      key=lambda r: r["ts_us"])
        assert [r["name"] for r in kids] == ["train.batch", "train.dispatch", "train.wait"]
        for a, b in zip(kids, kids[1:]):
            assert a["ts_us"] + a["dur_us"] <= b["ts_us"]
        assert step["ts_us"] <= kids[0]["ts_us"]
        assert kids[-1]["ts_us"] + kids[-1]["dur_us"] <= step["ts_us"] + step["dur_us"]
    jit = [r for r in recs if r["name"].startswith("jit.")]
    assert jit, "the cold step compiled nothing"
    for r in jit:
        parent = by_id[r["parent_id"]]
        assert parent["name"] in ("train.batch", "train.dispatch")
        assert by_id[parent["parent_id"]] is steps[0]
    assert any(by_id[r["parent_id"]]["name"] == "train.dispatch" for r in jit)


# ---------------------------------------------------------------------------
# Chrome export


def test_chrome_export_valid_and_consistent(model_setup, tmp_path):
    cfg, plan, state, jmesh = model_setup
    with obs.enabled() as tracer:
        mgr = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
            async_save=False))
        mgr.save(state, 10)
        mgr.restore(jmesh, step=10)
        mgr.close()
        out = obs.write_chrome_trace(tmp_path / "trace.json", tracer)
    doc = json.loads(out.read_text())  # valid JSON on disk, not just dicts
    n = obs.validate_chrome_trace(doc)
    assert n >= 10
    assert doc["otherData"]["schema"] == "repro-trace/v1"
    assert doc["otherData"]["counters"].get("save.shards_written", 0) > 0
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert {"ckpt.save", "ckpt.restore", "ckpt.commit"} <= names
    # thread metadata present for every tid that emitted spans
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "X"}
    meta = {e["tid"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert tids <= meta


def test_validator_rejects_inconsistent_nesting():
    bad = {
        "traceEvents": [
            {"name": "parent", "ph": "X", "ts": 100, "dur": 10, "pid": 1,
             "tid": 1, "args": {"span_id": 1, "parent_id": None}},
            {"name": "child", "ph": "X", "ts": 50, "dur": 5, "pid": 1,
             "tid": 1, "args": {"span_id": 2, "parent_id": 1}},
        ]
    }
    with pytest.raises(AssertionError):
        obs.validate_chrome_trace(bad)  # child starts before its parent


# ---------------------------------------------------------------------------
# Counters vs SaveResult on a known delta save


def test_delta_counters_match_save_result(model_setup, tmp_path):
    cfg, plan, state, jmesh = model_setup
    with obs.enabled() as tracer:
        first = write_distributed(
            snapshot_state(state), plan, 10, tmp_path / "s10",
            save_mode="delta",
        )
        assert first.mode == "full"  # no base yet: forced rebase
        before = tracer.counters()
        result = write_distributed(
            snapshot_state(_bump(state, 0)), plan, 20, tmp_path / "s20",
            save_mode="delta", base=DistCheckpoint.open(tmp_path / "s10"),
        )
        after = tracer.counters()
    assert result.mode == "delta"
    assert result.shards_inherited > 0 and result.shards_written > 0
    delta = lambda k: after.get(k, 0) - before.get(k, 0)
    # exact agreement: the stats dataclass and the metric stream are two
    # views of one accumulation, not two counters that can drift
    assert delta("save.shards_written") == result.shards_written
    assert delta("save.shards_inherited") == result.shards_inherited
    assert delta("save.bytes_written") == result.bytes_written
    assert delta("save.delta") == 1
    assert delta("save.full") == 0
    # and the ckpt.save span carries the same numbers as attributes
    spans = [
        r for r in tracer.span_records()
        if r["name"] == "ckpt.save" and r["attrs"].get("step") == 20
    ]
    assert spans[0]["attrs"]["shards_written"] == result.shards_written
    assert spans[0]["attrs"]["shards_inherited"] == result.shards_inherited


# ---------------------------------------------------------------------------
# Summary / timeline plumbing


def test_summary_and_timeline_ordering():
    with obs.enabled() as tracer:
        with obs.span("a"):
            obs.event("mid")
            with obs.span("b"):
                pass
        obs.add("some.counter", 2)
    line = tracer.summary()
    assert "a" in line and "some.counter" in line
    tl = tracer.timeline()
    assert [r["ts_us"] for r in tl] == sorted(r["ts_us"] for r in tl)
    assert {r["kind"] for r in tl} == {"span", "event"}


def test_enable_is_process_exclusive():
    t = obs.enable()
    try:
        with pytest.raises(RuntimeError):
            obs.enable()
        # guarded disable: someone else's tracer stays installed
        obs.disable(trace_mod.Tracer())
        assert obs.active() is t
    finally:
        obs.disable(t)
    assert obs.active() is None
