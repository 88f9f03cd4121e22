"""The obs layer itself: disabled fast path, nesting, cross-thread
parent propagation (engine pool + async saver), Chrome export validity,
and counter/SaveResult agreement on a known delta save.

These pin the contracts DESIGN.md §9 promises: tracing off means one
global read + branch and a shared no-op singleton (no allocation, no
Tracer involvement); tracing on means every span lands on one monotonic
timebase with an explicit parent chain that survives thread handoffs.
"""

import json
import threading
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
import repro.obs as obs
import repro.obs.trace as trace_mod
from repro.configs import ParallelismConfig, get_config, reduced
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.layout import MeshSpec
from repro.core.pytree import flatten_with_paths, unflatten_from_paths
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.saver import snapshot_state, write_distributed
from repro.dist.sharding import make_plan, vocab_multiple
from repro.models import build_model
from repro.train.optimizer import TrainState, init_state


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
        obs.gauge("gauge.name", 1.5)
        obs.event("event.name", detail="ignored")
    assert calls == []


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
        mgr = CheckpointManager(
            tmp_path / "ck", plan, async_save=True, save_interval=1
        )
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
# Chrome export


def test_chrome_export_valid_and_consistent(model_setup, tmp_path):
    cfg, plan, state, jmesh = model_setup
    with obs.enabled() as tracer:
        mgr = CheckpointManager(tmp_path / "ck", plan, async_save=False)
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
