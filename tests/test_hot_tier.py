"""Hot in-memory tier: capture/restore bit-identity vs the disk path,
buddy replication (incl. the DP-dedup skip), rank-failure recovery through
HOT_DIRECT / HOT_RESHARD with fall-through to disk, ring-buffer budgets,
background drain, content-digest integrity, and crash-mid-save recovery."""

import numpy as np
import pytest

from repro.core import (
    DimSpec,
    DistCheckpoint,
    IntegrityError,
    MeshSpec,
    STATE_KINDS,
    StateKind,
    content_digest,
    uniform_param_spec,
)
from repro.ckpt.policy import CheckpointPolicy
from repro.core.plan import ResumeMode, TargetSpec
from repro.dist.sharding import ShardingPlan
from repro.hot import (
    HotDrainer,
    HotTier,
    ReplicationPolicy,
    persist_snapshot,
    place_holders,
    plan_hot_recovery,
    state_from_hot,
)
from repro.launch.mesh import make_mesh


def _plan(mesh, specs) -> ShardingPlan:
    return ShardingPlan(mesh=mesh, param_specs=dict(specs))


def _random_state(specs, seed=0):
    rng = np.random.default_rng(seed)
    return {
        n: {
            k: rng.normal(size=s.runtime_shape).astype(np.float32)
            for k in STATE_KINDS
        }
        for n, s in specs.items()
    }


def _specs_2x2():
    return {
        "w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec(("model",))]),
        "u": uniform_param_spec("u", (6, 4), [DimSpec(("model",)), DimSpec()]),
        "b": uniform_param_spec("b", (4,), [DimSpec()]),  # fully replicated
    }


MESH_2X2 = MeshSpec.from_dict({"data": 2, "model": 2})


def _tree_bytes(root):
    from pathlib import Path

    root = Path(root)
    return {
        str(p.relative_to(root)): p.read_bytes()
        for p in sorted(root.glob("ranks/**/*.npy"))
    }


# ---------------------------------------------------------------------------
# Replica placement
# ---------------------------------------------------------------------------


def test_buddy_placement_skips_natural_dp_replicas():
    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    pol = ReplicationPolicy(replication=1)
    # "b" is fully replicated: all 4 ranks naturally hold fragment 0 — no
    # buddy copies needed, every natural holder recorded.
    lb = specs["b"].layout_for(StateKind.FP32, MESH_2X2)
    assert set(place_holders(lb, 0, pol)) == {0, 1, 2, 3}
    # "w" is sharded over both axes: every rank a distinct fragment — one
    # buddy peer tops redundancy up to 2.
    lw = specs["w"].layout_for(StateKind.FP32, MESH_2X2)
    for owner in range(4):
        holders = place_holders(lw, owner, pol)
        assert holders[0] == owner and len(holders) == 2
    # capture-level accounting agrees: replicated fragments mirror nothing
    tier = HotTier(replication=1)
    _, stats = tier.capture(_random_state(specs), plan, 1)
    assert stats.natural_fragments > 0
    assert stats.mirrored_bytes > 0  # the sharded params did need mirrors
    assert stats.resident_bytes > stats.stored_bytes
    tier.clear()


def test_place_holders_ring_extension_and_average():
    # world=3, groups of 2 → tail group {2} alone; ring extension finds a peer
    spec = uniform_param_spec("w", (6,), [DimSpec(("data",))])
    mesh = MeshSpec.from_dict({"data": 3})
    layout = spec.layout_for(StateKind.FP32, mesh)
    holders = place_holders(layout, 2, ReplicationPolicy(replication=1))
    assert holders[0] == 2 and len(holders) == 2
    # natural_replication=False (average params): replicas diverge, so even
    # a fully-replicated layout gets buddy mirrors, not free holders.
    spec_r = uniform_param_spec("r", (4,), [DimSpec()])
    lr = spec_r.layout_for(StateKind.FP32, mesh)
    holders = place_holders(lr, 0, ReplicationPolicy(1), natural_replication=False)
    assert len(holders) == 2  # owner + one buddy, not all 3 naturals


# ---------------------------------------------------------------------------
# Capture → recover (bit-identity, failures, tier fall-through)
# ---------------------------------------------------------------------------


def test_hot_direct_and_reshard_bit_identical_after_rank_failure(tmp_path):
    import jax

    from repro.ckpt.restore import state_from_dist
    from repro.ckpt.saver import write_distributed

    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    snap = _random_state(specs, seed=3)
    write_distributed(snap, plan, 7, tmp_path / "disk", workers=4)
    disk = DistCheckpoint.open(tmp_path / "disk")

    tier = HotTier(replication=1)
    hs, _ = tier.capture(snap, plan, 7)

    # one failure per buddy group ({0,1} and {2,3}), chosen so no natural
    # replica pair ("u" is mirrored across {0,2}/{1,3}) dies whole: every
    # fragment keeps >= 1 holder.
    dead = tier.fail_ranks({0, 3})
    assert dead == {}, f"replication should cover single-buddy loss: {dead}"

    jmesh = make_mesh((1, 1), ("data", "model"))
    tgt_mesh = MeshSpec.from_dict({"data": 4, "model": 1})
    tgt_specs = {
        "w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec()]),
        "u": uniform_param_spec("u", (6, 4), [DimSpec(), DimSpec(("data",))]),
        "b": uniform_param_spec("b", (4,), [DimSpec()]),
    }
    for name, tplan in (("direct", plan), ("reshard", _plan(tgt_mesh, tgt_specs))):
        target = TargetSpec(tplan.mesh, tplan.param_specs)
        hp = plan_hot_recovery(tier, target)
        assert hp is not None and hp.step == 7
        assert hp.mode == (
            ResumeMode.HOT_DIRECT if name == "direct" else ResumeMode.HOT_RESHARD
        )
        s_hot = state_from_hot(hp.snapshot, tplan, jmesh, verify=True)
        s_disk = state_from_dist(disk, tplan, jmesh)
        lh, ld = jax.tree.leaves(s_hot), jax.tree.leaves(s_disk)
        assert len(lh) == len(ld) > 0
        for a, b in zip(lh, ld):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tier.clear()


def test_hot_recovery_falls_through_when_coverage_lost():
    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    tier = HotTier(replication=1)
    tier.capture(_random_state(specs), plan, 5)
    # whole buddy group {0,1} dies → rank-0-owned fragments of "w" are gone
    dead = tier.fail_ranks({0, 1})
    assert dead, "losing a full buddy group must lose fragments"
    assert plan_hot_recovery(tier, TargetSpec(plan.mesh, plan.param_specs)) is None
    # an *older complete* snapshot would still serve — capture order matters
    tier2 = HotTier(replication=1, max_snapshots=4)
    tier2.capture(_random_state(specs, 1), plan, 5)
    tier2.capture(_random_state(specs, 2), plan, 10)
    tier2._ring[-1].fail_ranks({0, 1})  # newest snapshot only loses coverage
    hp = plan_hot_recovery(tier2, TargetSpec(plan.mesh, plan.param_specs))
    assert hp is not None and hp.step == 5
    tier.clear(), tier2.clear()


def test_hot_reshard_rejects_structural_changes():
    specs = _specs_2x2()
    tier = HotTier(replication=3)  # everything survives any failure below
    tier.capture(_random_state(specs), _plan(MESH_2X2, specs), 5)
    changed = dict(specs)
    changed["w"] = uniform_param_spec(
        "w", (10, 6), [DimSpec(("data",)), DimSpec()]
    )  # different logical/runtime shape → needs UCP transformation
    assert plan_hot_recovery(tier, TargetSpec(MESH_2X2, changed)) is None
    tier.clear()


def test_min_step_prefers_newer_disk_checkpoint():
    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    tier = HotTier()
    tier.capture(_random_state(specs), plan, 5)
    target = TargetSpec(plan.mesh, plan.param_specs)
    assert plan_hot_recovery(tier, target, min_step=5) is not None
    assert plan_hot_recovery(tier, target, min_step=6) is None
    tier.clear()


# ---------------------------------------------------------------------------
# Ring buffer budget
# ---------------------------------------------------------------------------


def test_ring_buffer_count_and_byte_budget_eviction():
    specs = {"w": uniform_param_spec("w", (64, 64), [DimSpec(("data",)), DimSpec()])}
    plan = _plan(MeshSpec.from_dict({"data": 2}), specs)
    tier = HotTier(replication=1, max_snapshots=3)
    for step in (1, 2, 3, 4, 5):
        tier.capture(_random_state(specs, step), plan, step)
    assert [s.step for s in tier.snapshots()] == [3, 4, 5]
    assert tier.evictions == 2
    # byte budget: resident bytes of ~2 snapshots → keeps 2, evicts the rest
    one = tier.latest().resident_nbytes
    tier2 = HotTier(replication=1, max_snapshots=10, max_bytes=2 * one)
    for step in (1, 2, 3, 4):
        tier2.capture(_random_state(specs, step), plan, step)
    assert [s.step for s in tier2.snapshots()] == [3, 4]
    # ring never evicts the last snapshot, even over budget
    tier3 = HotTier(max_snapshots=10, max_bytes=1)
    tier3.capture(_random_state(specs), plan, 1)
    assert len(tier3.snapshots()) == 1
    tier.clear(), tier2.clear(), tier3.clear()


# ---------------------------------------------------------------------------
# Drain (background promotion to disk)
# ---------------------------------------------------------------------------


def test_drain_every_nth_snapshot_byte_identical(tmp_path):
    from repro.ckpt.saver import write_distributed

    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    tier = HotTier(replication=1)
    drainer = HotDrainer(every=2)
    states = {}
    for i, step in enumerate((5, 10, 15, 20), start=1):
        states[step] = _random_state(specs, seed=step)
        hs, _ = tier.capture(states[step], plan, step)
        queued = drainer.maybe_drain(hs, tmp_path / f"step_{step:08d}")
        assert queued == (i % 2 == 0)
    results = drainer.wait()
    assert sorted(r.step for r in results) == [10, 20]
    drainer.close()
    for step in (10, 20):
        root = tmp_path / f"step_{step:08d}"
        ck = DistCheckpoint.open(root)
        assert ck.is_committed and ck.validate() == []
        write_distributed(states[step], plan, step, tmp_path / "ref", workers=1)
        ref = _tree_bytes(tmp_path / "ref")
        got = _tree_bytes(root)
        assert got.keys() == ref.keys()
        for rel in ref:
            assert got[rel] == ref[rel], f"step {step} shard {rel} differs"
    assert not (tmp_path / "step_00000005" / "COMMIT").exists()
    tier.clear()


def test_drain_survives_ring_eviction_of_queued_snapshot(tmp_path):
    """A snapshot evicted (released) after its drain was enqueued must still
    be persisted complete — the drainer pins the fragment list at enqueue
    time — never committed as an empty checkpoint."""
    from repro.ckpt.saver import write_distributed

    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    tier = HotTier(replication=1)
    snap = _random_state(specs, seed=21)
    drainer = HotDrainer(every=1)
    hs, _ = tier.capture(snap, plan, 5)
    assert drainer.maybe_drain(hs, tmp_path / "step_00000005")
    hs.release(tier.engine)  # ring eviction before the background write ran
    assert [r.step for r in drainer.wait()] == [5]
    drainer.close()
    ck = DistCheckpoint.open(tmp_path / "step_00000005")
    assert ck.is_committed and ck.validate() == []
    write_distributed(snap, plan, 5, tmp_path / "ref", workers=1)
    ref, got = _tree_bytes(tmp_path / "ref"), _tree_bytes(tmp_path / "step_00000005")
    assert got.keys() == ref.keys() and got, "eviction must not empty the drain"
    for rel in ref:
        assert got[rel] == ref[rel], rel
    # and a direct persist of the now-released snapshot refuses loudly
    with pytest.raises(ValueError, match="empty hot snapshot"):
        persist_snapshot(hs, tmp_path / "again")
    tier.clear()


def test_post_failure_capture_places_replicas_on_survivors():
    """Captures taken after a rank failure must mirror onto live peers —
    dead buddies never count toward the replication guarantee."""
    specs = _specs_2x2()
    plan = _plan(MESH_2X2, specs)
    tier = HotTier(replication=1)
    tier.fail_ranks({1})  # rank 0's buddy is dead before the first capture
    hs, _ = tier.capture(_random_state(specs), plan, 5)
    for _, _, frag in hs.fragments():
        assert 1 not in frag.holders, frag
        assert len(frag.holders) >= 2, (
            f"fragment owned by {frag.owner} under-replicated: {frag.holders}"
        )
    # the guarantee holds going forward: losing one MORE rank keeps coverage
    dead = tier.fail_ranks({0})
    assert dead == {}, f"post-failure capture left single-holder fragments: {dead}"
    assert hs.is_complete()
    tier.clear()


def test_drain_refuses_incomplete_snapshot(tmp_path):
    specs = {"w": uniform_param_spec("w", (8,), [DimSpec(("data",))])}
    plan = _plan(MeshSpec.from_dict({"data": 2}), specs)
    tier = HotTier(replication=0)  # no redundancy: any loss is fatal
    hs, _ = tier.capture(_random_state(specs), plan, 1)
    tier.fail_ranks({0})
    with pytest.raises(ValueError, match="incomplete hot snapshot"):
        persist_snapshot(hs, tmp_path / "ck")
    tier.clear()


# ---------------------------------------------------------------------------
# Integrity digests (satellite)
# ---------------------------------------------------------------------------


def test_dist_digests_catch_silent_corruption(tmp_path):
    from repro.ckpt.saver import write_distributed

    specs = {"w": uniform_param_spec("w", (8, 4), [DimSpec(("data",)), DimSpec()])}
    plan = _plan(MeshSpec.from_dict({"data": 2}), specs)
    snap = _random_state(specs, seed=9)
    write_distributed(snap, plan, 1, tmp_path / "ck", workers=2)
    ck = DistCheckpoint.open(tmp_path / "ck")
    assert ck.manifest.shard_digests  # recorded at save time
    assert ck.validate() == []
    # flip bytes inside one shard file, past the .npy header
    victim = next(iter(sorted((tmp_path / "ck").glob("ranks/**/*.npy"))))
    raw = bytearray(victim.read_bytes())
    raw[-4] ^= 0xFF
    victim.write_bytes(bytes(raw))
    problems = ck.validate()
    assert problems and "digest" in problems[0]


def test_restore_verify_flag_raises_on_corruption(tmp_path):
    import jax

    from repro.configs import ParallelismConfig, get_config, reduced
    from repro.ckpt.manager import CheckpointManager
    from repro.dist.sharding import make_plan, vocab_multiple
    from repro.models import build_model
    from repro.train.optimizer import init_state

    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 1, "model": 1})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    jmesh = make_mesh((1, 1), ("data", "model"))

    mgr = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
        async_save=False))
    mgr.save(state, 10)
    mgr.restore(jmesh, verify=True)  # clean checkpoint verifies fine
    victim = next(iter(sorted((tmp_path / "ck").glob("step_*/ranks/**/*.npy"))))
    raw = bytearray(victim.read_bytes())
    raw[-1] ^= 0xFF
    victim.write_bytes(bytes(raw))
    mgr.engine.invalidate(mgr.step_dir(10))  # drop cached pre-corruption handles
    with pytest.raises(IntegrityError):
        mgr.restore(jmesh, verify=True)
    # without the flag, corruption still passes (documented opt-in)
    mgr.restore(jmesh)
    mgr.close()


def test_ucp_atom_digests_verified(tmp_path):
    from repro.core import convert_to_ucp
    from repro.ckpt.saver import write_distributed

    specs = {"w": uniform_param_spec("w", (6, 4), [DimSpec(("data",)), DimSpec()])}
    plan = _plan(MeshSpec.from_dict({"data": 2}), specs)
    write_distributed(_random_state(specs), plan, 1, tmp_path / "ck", workers=1)
    ucp, _ = convert_to_ucp(
        DistCheckpoint.open(tmp_path / "ck"), str(tmp_path / "ucp"), workers=1
    )
    assert all(a.digests for a in ucp.manifest.atoms.values())
    assert ucp.validate() == []
    atom = next(iter(sorted((tmp_path / "ucp").glob("atoms/**/*.npy"))))
    raw = bytearray(atom.read_bytes())
    raw[-2] ^= 0xFF
    atom.write_bytes(bytes(raw))
    problems = ucp.validate()
    assert problems and "digest" in problems[0]


def test_hot_snapshot_verify_catches_in_memory_rot():
    specs = {"w": uniform_param_spec("w", (8,), [DimSpec(("data",))])}
    plan = _plan(MeshSpec.from_dict({"data": 2}), specs)
    tier = HotTier(replication=1)
    hs, _ = tier.capture(_random_state(specs), plan, 1)
    assert hs.verify() == []
    frag = hs._frags[next(iter(hs._frags))]
    frag.data[0] += 1.0  # a replica rotting in host memory
    problems = hs.verify()
    assert problems and "digest" in problems[0]
    with pytest.raises(IntegrityError):
        import jax

        state_from_hot(hs, plan, make_mesh((1, 1), ("data", "model")), verify=True)
    tier.clear()


def test_content_digest_dtype_and_layout_stability():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    assert content_digest(a) == content_digest(np.ascontiguousarray(a.copy()))
    assert content_digest(a) != content_digest(a.T)  # different content order
    import ml_dtypes

    b = a.astype(ml_dtypes.bfloat16)  # extended dtype path
    assert content_digest(b).startswith("sha256:")


# ---------------------------------------------------------------------------
# Crash-mid-save recovery (satellite)
# ---------------------------------------------------------------------------


def test_crash_mid_save_discovery_hot_recovery_and_gc(tmp_path, monkeypatch):
    import jax

    from repro.configs import ParallelismConfig, get_config, reduced
    from repro.ckpt.manager import CheckpointManager
    from repro.dist.sharding import make_plan, vocab_multiple
    from repro.models import build_model
    from repro.train.optimizer import init_state
    import repro.hot.drain as drain_mod

    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 1, "model": 1})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    jmesh = make_mesh((1, 1), ("data", "model"))

    mgr = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
        hot_interval=5, save_interval=5, async_save=False))
    mgr.save(state, 5)  # committed disk checkpoint via drain
    mgr.wait()
    assert mgr.latest_step() == 5

    # kill the next promotion after a few shards hit disk
    real_write = DistCheckpoint.write_shard
    calls = {"n": 0}

    def dying_write(self, rank, name, kind, shard, **kw):
        calls["n"] += 1
        if calls["n"] > 3:
            raise OSError("simulated power loss mid-save")
        return real_write(self, rank, name, kind, shard, **kw)

    monkeypatch.setattr(DistCheckpoint, "write_shard", dying_write)
    mgr.save(state, 10)
    with pytest.raises(RuntimeError, match="drain failed"):
        mgr.wait()
    monkeypatch.setattr(DistCheckpoint, "write_shard", real_write)

    crashed = mgr.step_dir(10)
    assert crashed.exists() and not (crashed / "COMMIT").exists()
    assert 0 < len(list(crashed.glob("ranks/**/*.npy"))) < 10  # partial
    # discovery skips the uncommitted step…
    assert mgr.latest_step() == 5
    # …but the hot tier still has step 10 in memory: recovery uses it,
    # never touching the torn directory.
    restored, info = mgr.restore_latest(jmesh, verify=True)
    assert info.mode == ResumeMode.HOT_DIRECT and info.step == 10
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(restored.params)[0]),
        np.asarray(jax.tree.leaves(state.params)[0]),
    )
    # a later committed save triggers GC of the partial directory
    mgr.save(state, 15)
    mgr.wait()
    assert mgr.latest_step() == 15
    assert not crashed.exists(), "GC must remove the crashed partial save"
    mgr.close()
