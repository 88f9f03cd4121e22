"""Delta (incremental) checkpointing on the fragment index.

Covers the delta-chain invariants the design promises (DESIGN.md §1):

* a delta step directory physically holds only the changed shards, the
  rest are flattened manifest references;
* restore from a K-deep chain — DIRECT, RESHARD_STREAM, and hot-promoted —
  is bit-identical to the equivalent full save;
* a crash mid-delta leaves the chain servable from the last commit;
* ``gc()`` never removes a base a live delta references, and a
  ``full_interval`` rebase makes the old chain collectable;
* an incompatible or missing base degrades to a full save (rebase),
  never an error.
"""

import dataclasses
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import ParallelismConfig, get_config, reduced
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.layout import MeshSpec
from repro.core.plan import ResumeMode
from repro.core.pytree import flatten_with_paths, unflatten_from_paths
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.saver import snapshot_state, write_distributed
from repro.dist.sharding import make_plan, vocab_multiple
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.optimizer import TrainState, init_state


@pytest.fixture()
def setup(tmp_path):
    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    jmesh = make_mesh((1, 1), ("data", "model"))
    return tmp_path, cfg, plan, state, jmesh


def _bump(state: TrainState, idx: int) -> TrainState:
    """Mutate one parameter leaf (sparse update: everything else unchanged)."""
    flat = flatten_with_paths(jax.device_get(state.params))
    name = sorted(flat)[idx % len(flat)]
    flat[name] = np.asarray(flat[name]) + np.float32(1.0 + idx)
    return TrainState(
        unflatten_from_paths(flat), state.exp_avg, state.exp_avg_sq, state.step
    )


def _params_equal(a, b):
    la, lb = jax.tree.leaves(a.params), jax.tree.leaves(b.params)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _reshard_plan(cfg):
    p2 = ParallelismConfig(zero=1, fsdp=False)
    mesh2 = MeshSpec.from_dict({"data": 1, "model": 1})
    lm2 = build_model(cfg, vocab_multiple=vocab_multiple(p2, mesh2))
    return make_plan(cfg, lm2.registry, p2, mesh2)


def test_delta_save_writes_only_changed_shards(setup):
    tmp, cfg, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        async_save=False, save_mode="delta", full_interval=100, keep_last=100))
    mgr.save(state, 10)  # seq 0: forced full rebase
    state2 = _bump(state, 0)
    mgr.save(state2, 20)
    ck = DistCheckpoint.open(mgr.step_dir(20))
    m = ck.manifest
    assert m.save_mode == "delta"
    assert m.base_step == 10
    assert m.shard_sources and set(m.shard_sources.values()) == {10}
    # the directory physically holds only the changed shards
    written = {
        str(p.relative_to(mgr.step_dir(20))) for p in mgr.step_dir(20).rglob("*.npy")
    }
    inherited = set(m.shard_sources)
    assert len(written) == len(m.shard_digests) - len(inherited)
    assert 0 < len(written) < len(m.shard_digests)
    # full digest table regardless: the next delta diffs this manifest alone
    assert set(m.shard_digests) == set(
        DistCheckpoint.open(mgr.step_dir(10)).manifest.shard_digests
    )
    # chain-resolved integrity check covers inherited shards too
    assert ck.validate() == []


@pytest.mark.parametrize("depth", [2, 5])
def test_chain_restore_bit_identical_to_full(setup, depth):
    """Restore from a K-deep chain — DIRECT and RESHARD_STREAM — matches a
    full save of the same final state, bit for bit."""
    tmp, cfg, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "delta", plan, policy=CheckpointPolicy(
        async_save=False, save_mode="delta", full_interval=100, keep_last=100))
    s = state
    mgr.save(s, 10)
    for i in range(depth):
        s = _bump(s, i)
        mgr.save(s, 20 + 10 * i)
    tip = 20 + 10 * (depth - 1)
    ck = DistCheckpoint.open(mgr.step_dir(tip))
    assert ck.manifest.base_step is not None  # really a delta
    # equivalent full save of the same final state
    full = CheckpointManager(tmp / "full", plan, policy=CheckpointPolicy(
        async_save=False))
    full.save(s, tip)

    r_delta, info = mgr.restore(jmesh, step=tip)
    r_full, _ = full.restore(jmesh, step=tip)
    assert info.mode == ResumeMode.DIRECT
    _params_equal(r_delta, r_full)
    _params_equal(r_delta, s)

    plan2 = _reshard_plan(cfg)
    r_delta2, info2 = mgr.restore(jmesh, step=tip, target_plan=plan2)
    r_full2, _ = full.restore(jmesh, step=tip, target_plan=plan2)
    assert info2.mode == ResumeMode.RESHARD_STREAM
    _params_equal(r_delta2, r_full2)
    _params_equal(r_delta2, s)
    # opt-in verification walks the chain
    r_v, _ = mgr.restore(jmesh, step=tip, verify=True)
    _params_equal(r_v, s)
    # VIA_UCP export consolidates through the chain too
    r_ucp, info_ucp = mgr.restore(
        jmesh, step=tip, target_plan=plan2, force_mode=ResumeMode.VIA_UCP
    )
    assert info_ucp.mode == ResumeMode.VIA_UCP
    _params_equal(r_ucp, s)


def test_hot_drainer_promotes_deltas(setup):
    """Hot-tier promotion follows the same delta policy: the drained disk
    steps form a chain and restore bit-identically."""
    tmp, cfg, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        save_mode="delta", full_interval=100, keep_last=100, hot_interval=1,
        disk_interval=1, hot_max_snapshots=2, async_save=False))
    s = state
    states = {}
    for i, step in enumerate((1, 2, 3)):
        s = _bump(s, i)
        states[step] = s
        mgr.save(s, step, block=True)
    mgr.wait()
    assert mgr.steps() == [1, 2, 3]
    ck3 = DistCheckpoint.open(mgr.step_dir(3))
    assert ck3.manifest.save_mode == "delta"
    assert ck3.manifest.base_step == 2
    assert ck3.manifest.shard_sources  # inherited the unchanged majority
    restored, info = mgr.restore(jmesh, step=3)
    _params_equal(restored, states[3])
    # hot-promoted delta also serves a reshard from the chain
    plan2 = _reshard_plan(cfg)
    r2, info2 = mgr.restore(jmesh, step=3, target_plan=plan2)
    assert info2.mode == ResumeMode.RESHARD_STREAM
    _params_equal(r2, states[3])
    mgr.close()


def test_crash_mid_delta_leaves_chain_servable(setup):
    tmp, cfg, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        async_save=False, save_mode="delta", full_interval=100, keep_last=100))
    mgr.save(state, 10)
    state2 = _bump(state, 0)
    mgr.save(state2, 20)
    # simulate a crash mid-delta for step 30: manifest written (delta-shaped,
    # referencing the chain), some shard missing, no COMMIT
    crashed = mgr.step_dir(30)
    ck20 = DistCheckpoint.open(mgr.step_dir(20))
    m = ck20.manifest.to_json()
    m["step"] = 30
    m["base_step"] = 20
    crashed.mkdir(parents=True)
    (crashed / "MANIFEST.json").write_text(json.dumps(m))
    # discovery skips it; the chain still serves the last commit
    assert mgr.latest_step() == 20
    restored, info = mgr.restore(jmesh)
    assert info.step == 20
    _params_equal(restored, state2)
    # the next save GCs the wreckage and keeps the chain intact
    state3 = _bump(state2, 1)
    mgr.save(state3, 40)
    assert not crashed.exists()
    restored3, _ = mgr.restore(jmesh, step=40)
    _params_equal(restored3, state3)


def test_gc_keeps_referenced_bases_until_rebase(setup):
    tmp, cfg, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        async_save=False, save_mode="delta", full_interval=100, keep_last=1))
    s = state
    mgr.save(s, 10)  # full base
    for i, step in enumerate((20, 30)):
        s = _bump(s, i)
        mgr.save(s, step)
    # keep_last=1 would keep only step 30, but 30's chain references 10
    # (and possibly 20): those bases must survive GC
    ck30 = DistCheckpoint.open(mgr.step_dir(30))
    refs = ck30.referenced_steps()
    assert 10 in refs
    for r in refs:
        assert mgr.step_dir(r).exists(), f"GC removed live base step {r}"
    restored, _ = mgr.restore(jmesh, step=30)
    _params_equal(restored, s)
    # a rebase (forced full save) makes the old chain collectable
    s = _bump(s, 2)
    mgr._disk_save_seq = 0  # next save hits the full_interval boundary
    mgr.save(s, 40)
    ck40 = DistCheckpoint.open(mgr.step_dir(40))
    assert ck40.manifest.base_step is None  # really a rebase
    assert mgr.steps() == [40]
    assert not mgr.step_dir(10).exists()
    assert not mgr.step_dir(30).exists()
    restored4, _ = mgr.restore(jmesh)
    _params_equal(restored4, s)


def test_gc_pins_inflight_delta_base(setup, monkeypatch):
    """Regression (TOCTOU): gc() must not collect a base that an in-flight
    delta already resolved but has not committed against yet — even when
    newer commits push the base out of the keep-last window."""
    import threading

    import repro.ckpt.saver as saver_mod

    tmp, cfg, plan, state, jmesh = setup
    real = saver_mod.write_distributed
    started, gate = threading.Event(), threading.Event()

    def stalled(snap, plan_, step, root, **kw):
        if step == 30:
            # resolve the base (registering the pin) exactly like the real
            # writer would, then stall before any bytes land
            kw["base"] = kw["base"]()
            started.set()
            assert gate.wait(20), "test gate never opened"
        return real(snap, plan_, step, root, **kw)

    monkeypatch.setattr(saver_mod, "write_distributed", stalled)
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        async_save=True, save_mode="delta", full_interval=2, keep_last=1))
    mgr.save(state, 10, block=True)  # seq 0: full (the future delta base)
    state2 = _bump(state, 0)
    mgr.save(state2, 30)  # seq 1: delta, queued, stalls post-resolution
    assert started.wait(20)
    # seq 2: a full rebase commits and gc() runs with keep={40} — without
    # the pin, step_10 is neither kept, in flight, nor referenced by any
    # committed manifest, and would be rmtree'd under the queued delta
    mgr.save(state2, 40, block=True)
    assert mgr.step_dir(10).exists(), "gc collected an in-flight delta's base"
    gate.set()
    mgr._async.wait()  # drain without re-running gc
    assert sorted(mgr.steps()) == [10, 30, 40]
    restored, _ = mgr.restore(jmesh, step=30)
    _params_equal(restored, state2)
    # the pin dies with the save: the next gc collects the dead chain
    mgr.gc()
    assert mgr.steps() == [40]
    assert not mgr.step_dir(10).exists()
    mgr.close()


def test_delta_falls_back_to_full_without_base(setup):
    tmp, cfg, plan, state, jmesh = setup
    snap = snapshot_state(state)
    # no base at all
    res = write_distributed(snap, plan, 1, tmp / "a" / "step_1", save_mode="delta")
    assert res.mode == "full" and res.fallback_reason
    m = DistCheckpoint.open(tmp / "a" / "step_1").manifest
    assert m.save_mode == "dedup" and m.base_step is None
    # incompatible base: different mesh geometry
    parallel = ParallelismConfig()
    mesh2 = MeshSpec.from_dict({"data": 1, "model": 1})
    lm2 = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh2))
    plan2 = make_plan(cfg, lm2.registry, parallel, mesh2)
    write_distributed(snapshot_state(state), plan2, 2, tmp / "a" / "step_2")
    base = DistCheckpoint.open(tmp / "a" / "step_2")
    res3 = write_distributed(
        snap, plan, 3, tmp / "a" / "step_3", save_mode="delta", base=base
    )
    assert res3.mode == "full" and "mesh changed" in res3.fallback_reason
    # compatible base: a real delta with zero changed shards writes nothing
    base1 = DistCheckpoint.open(tmp / "a" / "step_1")
    res4 = write_distributed(
        snap, plan, 4, tmp / "a" / "step_4", save_mode="delta", base=base1
    )
    assert res4.mode == "delta"
    assert res4.shards_written == 0
    assert not list((tmp / "a" / "step_4").rglob("*.npy"))
    r = DistCheckpoint.open(tmp / "a" / "step_4")
    assert r.validate() == []


def test_validate_reports_malformed_digest_as_problem(setup):
    """A corrupted recorded digest must surface as a validation problem,
    never as an unhandled exception (validation turns corruption into
    findings)."""
    tmp, cfg, plan, state, jmesh = setup
    write_distributed(snapshot_state(state), plan, 1, tmp / "ck" / "step_1")
    ck = DistCheckpoint.open(tmp / "ck" / "step_1")
    key = next(iter(ck.manifest.shard_digests))
    ck.manifest.shard_digests[key] = "bogus-algo:deadbeef"
    problems = ck.validate()
    assert any("unrecognized recorded digest" in p for p in problems)


def test_hot_promotion_honors_save_mode_all(setup):
    """save_mode='all' with the hot tier must capture and promote the full
    per-replica write set, not silently degrade to dedup."""
    tmp, cfg, plan, state, jmesh = setup
    mgr_all = CheckpointManager(tmp / "all", plan, policy=CheckpointPolicy(
        save_mode="all", hot_interval=1, disk_interval=1, async_save=False,
        keep_last=10))
    mgr_all.save(state, 1, block=True)
    mgr_all.wait()
    ck = DistCheckpoint.open(mgr_all.step_dir(1))
    assert ck.manifest.save_mode == "all"
    mgr_ded = CheckpointManager(tmp / "ded", plan, policy=CheckpointPolicy(
        async_save=False))
    mgr_ded.save(state, 1)
    n_all = len(list(mgr_all.step_dir(1).rglob("*.npy")))
    n_ded = len(list(mgr_ded.step_dir(1).rglob("*.npy")))
    assert n_all > n_ded  # replicas actually persisted per rank
    restored, _ = mgr_all.restore(jmesh, step=1)
    _params_equal(restored, state)
    mgr_all.close()
    mgr_ded.close()


def test_save_result_reports_delta_counts(setup):
    tmp, cfg, plan, state, jmesh = setup
    root = tmp / "ck"
    write_distributed(snapshot_state(state), plan, 1, root / "step_00000001")
    base = DistCheckpoint.open(root / "step_00000001")
    state2 = _bump(state, 0)
    res = write_distributed(
        snapshot_state(state2), plan, 2, root / "step_00000002",
        save_mode="delta", base=base,
    )
    assert res.mode == "delta"
    assert res.shards_written > 0
    assert res.shards_inherited > res.shards_written  # sparse update
    assert res.bytes_written < base.total_bytes()
