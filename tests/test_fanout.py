"""Checkpoint fan-out (repro.serve): publish→subscribe, the peer fetch
ladder (binomial tree + digest verify + disk fallback), O(1) disk traffic
across a reader fleet, delta-aware in-place updates, the manager publish
hook, and the shared-engine concurrent-reader stress test."""

import threading

import jax
import numpy as np
import pytest

from repro.core import (
    DimSpec,
    DistCheckpoint,
    IntegrityError,
    MeshSpec,
    STATE_KINDS,
    StateKind,
    uniform_param_spec,
)
from repro.core.engine import CheckpointEngine
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.restore import (
    params_from_source,
    read_region_from_source,
    state_from_dist,
    state_from_source,
)
from repro.ckpt.saver import write_distributed
from repro.dist.sharding import ShardingPlan
from repro.hot import binomial_parent, fanout_ladder
from repro.launch.mesh import make_mesh
from repro.serve import (
    FanoutStats,
    FleetReplica,
    PeerFragmentSource,
    PublicationRegistry,
)

MESH_2X2 = MeshSpec.from_dict({"data": 2, "model": 2})
MESH_1X1 = MeshSpec.from_dict({"data": 1, "model": 1})


def _specs():
    return {
        "w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec(("model",))]),
        "u": uniform_param_spec("u", (6, 4), [DimSpec(("model",)), DimSpec()]),
        "b": uniform_param_spec("b", (4,), [DimSpec()]),  # fully replicated
    }


def _random_state(specs, seed=0):
    rng = np.random.default_rng(seed)
    return {
        n: {k: rng.normal(size=s.runtime_shape).astype(np.float32) for k in STATE_KINDS}
        for n, s in specs.items()
    }


def _params_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.fixture()
def published(tmp_path):
    """One committed 2x2 checkpoint, published; plus a 1x1 target plan."""
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    snap = _random_state(specs)
    write_distributed(snap, plan, 1, tmp_path / "step_1")
    ckpt = DistCheckpoint.open(tmp_path / "step_1")
    registry = PublicationRegistry()
    pub = registry.publish(ckpt)
    tgt_plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    jmesh = make_mesh((1, 1), ("data", "model"))
    return tmp_path, plan, snap, ckpt, registry, pub, tgt_plan, jmesh


# ---------------------------------------------------------------------------
# Binomial fan-out tree
# ---------------------------------------------------------------------------


def test_binomial_tree_shape():
    assert binomial_parent(0) is None
    assert binomial_parent(1) == 0
    assert binomial_parent(6) == 2
    assert fanout_ladder(0) == []
    assert fanout_ladder(11) == [3, 1, 0]
    for p in range(1, 200):
        ladder = fanout_ladder(p)
        # ladder = the ancestor chain: parent first, strictly decreasing,
        # ends at the tree root (node 0), O(log p) long.
        assert ladder[0] == binomial_parent(p)
        assert ladder[-1] == 0
        assert all(a > b for a, b in zip(ladder, ladder[1:]))
        assert len(ladder) == bin(p).count("1")
    # serving load is bounded: among N nodes, no parent serves more than
    # O(log N) children.
    children: dict[int, int] = {}
    for p in range(1, 256):
        children[binomial_parent(p)] = children.get(binomial_parent(p), 0) + 1
    assert max(children.values()) <= 8  # log2(256)
    with pytest.raises(ValueError):
        binomial_parent(-1)


# ---------------------------------------------------------------------------
# Registry: publish / subscribe / store GC
# ---------------------------------------------------------------------------


def test_registry_refuses_unsafe_publishes(tmp_path):
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    write_distributed(_random_state(specs), plan, 1, tmp_path / "step_1")
    ckpt = DistCheckpoint.open(tmp_path / "step_1")
    registry = PublicationRegistry()
    # uncommitted → refuse
    ckpt.commit_path.unlink()
    with pytest.raises(ValueError, match="uncommitted"):
        registry.publish(ckpt)
    ckpt.commit()
    # no digest table → refuse (peer fetches would be unverifiable)
    ckpt.manifest.shard_digests.clear()
    with pytest.raises(ValueError, match="digest"):
        registry.publish(ckpt)


def test_publish_diff_and_store_gc(published):
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    assert pub.kind == "full" and pub.seq == 1
    assert pub.changed == frozenset(pub.digests)
    # a subscriber joining now gets the current publication immediately
    sub = registry.subscribe("late")
    got = sub.poll()
    assert [p.seq for p in got] == [1]
    # second publish with only "u" weights changed → delta announcement
    snap2 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap.items()}
    snap2["u"][StateKind.FP32] += 1.0
    write_distributed(snap2, plan, 2, tmp / "step_2")
    pub2 = registry.publish(DistCheckpoint.open(tmp / "step_2"))
    assert pub2.kind == "delta"
    assert pub2.changed_params == frozenset({"u"})
    assert all("/u@" in k for k in pub2.changed)
    # a replica fetches under pub1, then the pub2 publish GCs the store
    # entries whose content pub2 no longer references
    r = FleetReplica("r0", registry, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    assert r.sync()  # drains both pubs → full rebuild at pub2
    assert r.seq == 2 and r.step == 2
    before = registry.stored_nbytes
    snap3 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap2.items()}
    snap3["u"][StateKind.FP32] += 1.0
    write_distributed(snap3, plan, 3, tmp / "step_3")
    registry.publish(DistCheckpoint.open(tmp / "step_3"))
    assert registry.store_evictions > 0
    assert registry.stored_nbytes <= before  # old "u" content dropped


# ---------------------------------------------------------------------------
# Fleet restore: bit-identity + O(1) disk traffic
# ---------------------------------------------------------------------------


def test_fleet_restore_bit_identical_and_o1_disk(published):
    """8 resharding readers with *private* engines (the peer tier does the
    distribution): every fp32 shard is read from disk exactly once fleet-
    wide, everything else comes from peers, and every replica's weights are
    bit-identical to a direct disk restore."""
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    reps = [
        FleetReplica(f"r{i}", registry, tgt_plan, jmesh,
                     engine=CheckpointEngine(workers=1))
        for i in range(8)
    ]
    for r in reps:
        assert r.sync()
    fp32_shards = [k for k in pub.digests if k.endswith("@fp32")]
    assert sum(r.stats.disk_fetches for r in reps) == len(fp32_shards)
    assert sum(r.stats.peer_fetches for r in reps) > 0
    assert sum(r.stats.digest_failures for r in reps) == 0
    ref = state_from_dist(ckpt, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    for r in reps:
        _params_equal(r.params, ref.params)
    # the fan-out tree registered every fetcher as a holder, in order
    for key in fp32_shards:
        skey = f"{key}@{pub.digests[key]}"
        assert len(registry.holders(skey)) == len(reps)


def test_fleet_shared_engine_serving_hot_set(published):
    """Replica threads sharing one engine pool their region reads: the
    shared_region cache assembles each target region once per fleet, so
    fragment reads (and hence disk fetches) don't scale with reader count."""
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    engine = CheckpointEngine(workers=2)
    reps = [
        FleetReplica(f"s{i}", registry, tgt_plan, jmesh, engine=engine)
        for i in range(6)
    ]
    errs: list[BaseException] = []

    def sync_one(r):
        try:
            assert r.sync()
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=sync_one, args=(r,)) for r in reps]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    fp32_shards = [k for k in pub.digests if k.endswith("@fp32")]
    # single-flight everywhere: each shard left disk exactly once, and the
    # shared regions mean no reader re-assembled another's region.
    total_fetches = sum(r.stats.disk_fetches + r.stats.peer_fetches for r in reps)
    assert sum(r.stats.disk_fetches for r in reps) == len(fp32_shards)
    assert total_fetches <= len(fp32_shards)  # regions built once, period
    ref = state_from_dist(ckpt, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    for r in reps:
        _params_equal(r.params, ref.params)


def test_fanout_consolidation_assembled_once_per_fleet(tmp_path):
    """A fused param under a TP change exercises the CONSOLIDATE stream
    path; the publication-keyed atom cache assembles it once per fleet."""
    from repro.core import SubFragment

    # A fused 2-subfragment param sharded over model, like fused QKV:
    # changing the TP degree repartitions the fused dim → CONSOLIDATE.
    fused = uniform_param_spec(
        "qkv", (8, 4),
        [DimSpec(("model",), (SubFragment("q", 4), SubFragment("k", 4))), DimSpec()],
        kind="fused_qkv",
    )
    specs = {"qkv": fused, "b": uniform_param_spec("b", (4,), [DimSpec()])}
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    write_distributed(_random_state(specs), plan, 1, tmp_path / "step_1")
    ckpt = DistCheckpoint.open(tmp_path / "step_1")
    registry = PublicationRegistry()
    registry.publish(ckpt)
    tgt_plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    jmesh = make_mesh((1, 1), ("data", "model"))
    engine = CheckpointEngine(workers=2)
    reps = [
        FleetReplica(f"c{i}", registry, tgt_plan, jmesh, engine=engine)
        for i in range(4)
    ]
    for r in reps:
        assert r.sync()
    ref = state_from_dist(ckpt, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    for r in reps:
        _params_equal(r.params, ref.params)
    # exactly one consolidated atom entry for the fused param, fleet-wide
    atom_keys = [
        k for k in engine.atoms._entries if "::atom::qkv@fp32" in k
    ]
    assert len(atom_keys) == 1


# ---------------------------------------------------------------------------
# Integrity: corrupt peer → evict + transparent refetch; corrupt disk → loud
# ---------------------------------------------------------------------------


def test_corrupt_peer_detected_evicted_and_refetched(published):
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    first = FleetReplica("first", registry, tgt_plan, jmesh,
                         engine=CheckpointEngine(workers=1))
    assert first.sync()
    # rot one of first's held shards; "first" is the only holder, so the
    # next reader's ladder hits it, detects the mismatch, evicts, and
    # transparently falls back to disk.
    key = next(k for k in pub.digests if "/w@fp32" in k)
    skey = f"{key}@{pub.digests[key]}"
    assert registry.holders(skey) == ["first"]
    registry.poison_holder("first", skey)
    victim = FleetReplica("victim", registry, tgt_plan, jmesh,
                          engine=CheckpointEngine(workers=1))
    assert victim.sync()
    assert victim.stats.digest_failures >= 1
    assert victim.stats.refetches >= 1
    assert "first" not in registry.holders(skey)  # corrupt holder evicted
    assert "victim" in registry.holders(skey)  # verified refetcher serves now
    ref = state_from_dist(ckpt, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    _params_equal(victim.params, ref.params)


def test_corrupt_disk_raises_integrity_error(published):
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    # disk is the last fetch tier: a corrupted shard *file* must raise, not
    # silently serve bad bytes.
    key = next(k for k in pub.digests if "/w@fp32" in k)
    rank = int(key.split("/")[0].split("_")[1])
    path = ckpt.shard_path(rank, "w", StateKind.FP32)
    raw = bytearray(path.read_bytes())
    raw[-1] ^= 0xFF
    path.write_bytes(bytes(raw))
    lone = FleetReplica("lone", registry, tgt_plan, jmesh,
                        engine=CheckpointEngine(workers=1))
    with pytest.raises(IntegrityError, match="disk copy"):
        lone.sync()


# ---------------------------------------------------------------------------
# Delta-aware publishes: in-place updates
# ---------------------------------------------------------------------------


def test_delta_publish_updates_replica_in_place(published):
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    r = FleetReplica("r", registry, tgt_plan, jmesh,
                     engine=CheckpointEngine(workers=1))
    assert r.sync()
    assert r.last_update == frozenset(_specs())  # first sync = full rebuild
    bytes_full = r.restore_stats.bytes_read
    # steady state: only "u" weights change → replica fetches only the diff
    snap2 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap.items()}
    snap2["u"][StateKind.FP32] += 1.0
    write_distributed(snap2, plan, 2, tmp / "step_2", save_mode="delta", base=ckpt)
    ck2 = DistCheckpoint.open(tmp / "step_2")
    pub2 = registry.publish(ck2)
    assert pub2.kind == "delta"
    assert r.sync()
    assert r.last_update == frozenset({"u"})
    assert r.restore_stats.bytes_read < 2 * bytes_full  # diff, not a rebuild
    ref = state_from_dist(ck2, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    _params_equal(r.params, ref.params)
    # an optimizer-only change is invisible to a weights-only replica
    snap3 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap2.items()}
    snap3["w"][StateKind.EXP_AVG] += 1.0
    write_distributed(snap3, plan, 3, tmp / "step_3", save_mode="delta", base=ck2)
    registry.publish(DistCheckpoint.open(tmp / "step_3"))
    assert r.sync()
    assert r.last_update == frozenset()
    assert r.step == 3


def test_gapped_feed_falls_back_to_full_rebuild(published):
    tmp, plan, snap, ckpt, registry, pub, tgt_plan, jmesh = published
    r = FleetReplica("r", registry, tgt_plan, jmesh,
                     engine=CheckpointEngine(workers=1))
    assert r.sync()
    # two publishes drained in one sync() are applied as one contiguous
    # window; but a replica that was *unsubscribed* across them (gap) must
    # rebuild.  Simulate the gap by forging the replica's seq cursor.
    snap2 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap.items()}
    snap2["u"][StateKind.FP32] += 1.0
    write_distributed(snap2, plan, 2, tmp / "step_2")
    ck2 = DistCheckpoint.open(tmp / "step_2")
    registry.publish(ck2)
    r.subscription.poll()  # lose the announcement (the gap)
    snap3 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap2.items()}
    snap3["b"][StateKind.FP32] += 1.0
    write_distributed(snap3, plan, 3, tmp / "step_3")
    ck3 = DistCheckpoint.open(tmp / "step_3")
    registry.publish(ck3)
    assert r.sync()
    assert r.last_update == frozenset(_specs())  # non-contiguous → rebuild
    ref = state_from_dist(ck3, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))
    _params_equal(r.params, ref.params)


# ---------------------------------------------------------------------------
# Manager publish hook
# ---------------------------------------------------------------------------


def test_manager_publishes_on_commit(tmp_path):
    from repro.ckpt.manager import CheckpointManager
    from repro.train.optimizer import TrainState
    import jax.numpy as jnp

    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    snap = _random_state(specs)
    state = TrainState(
        params={n: snap[n][StateKind.FP32] for n in specs},
        exp_avg={n: snap[n][StateKind.EXP_AVG] for n in specs},
        exp_avg_sq={n: snap[n][StateKind.EXP_AVG_SQ] for n in specs},
        step=jnp.asarray(0, jnp.int32),
    )
    registry = PublicationRegistry()
    sub = registry.subscribe("watcher")
    # sync saves publish immediately
    mgr = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
        async_save=False, registry=registry))
    mgr.save(state, 10)
    pubs = sub.poll()
    assert [p.step for p in pubs] == [10]
    # async saves publish once the commit is observed (at wait()).  A fresh
    # manager attached to an existing root first re-announces the step that
    # is already committed (its publish cursor starts empty) — an idempotent
    # empty-diff delta for any subscriber that already saw it.
    mgr2 = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
        async_save=True, registry=registry))
    mgr2.save(state, 20)
    mgr2.wait()
    mgr2.close()
    assert [p.step for p in sub.poll()] == [10, 20]
    assert registry.current().step == 20
    # explicit publish of an older step never moves the cursor backwards
    mgr2.publish(10)
    assert mgr2._published_step == 20
    mgr.close()


def test_crash_mid_publish_fleet_still_serves(tmp_path):
    """A crash between the publish-time store GC and delivery leaves the
    registry's cursor on the new step but no subscriber told.  Replicas on
    the old publication keep serving it, a fresh replica rebuilds the new
    step from disk (its peer-store entries were just GC'd), and the next
    successful publish heals the fleet — while manager GC keeps the
    currently-published step alive past keep_last throughout."""
    from repro.ckpt.manager import CheckpointManager
    from repro.chaos import ChaosController, FaultError, FaultSpec, Schedule
    from repro.train.optimizer import TrainState
    import jax.numpy as jnp

    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    tgt_plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    jmesh = make_mesh((1, 1), ("data", "model"))

    def state_at(seed):
        snap = _random_state(specs, seed=seed)
        return snap, TrainState(
            params={n: snap[n][StateKind.FP32] for n in specs},
            exp_avg={n: snap[n][StateKind.EXP_AVG] for n in specs},
            exp_avg_sq={n: snap[n][StateKind.EXP_AVG_SQ] for n in specs},
            step=jnp.asarray(0, jnp.int32),
        )

    registry = PublicationRegistry()
    mgr = CheckpointManager(tmp_path / "ck", plan, policy=CheckpointPolicy(
        keep_last=1, save_interval=10, async_save=False, io_workers=1,
        registry=registry))
    snap10, state10 = state_at(1)
    mgr.save(state10, 10)
    r1 = FleetReplica("r1", registry, tgt_plan, jmesh)
    assert r1.sync()

    snap20, state20 = state_at(2)
    sched = Schedule(0, (FaultSpec("registry.publish.deliver", hit=1),))
    with ChaosController(sched):
        with pytest.raises(FaultError):
            mgr.save(state20, 20)
    # The torn publish: cursor swapped + store GC'd, nothing delivered.
    assert registry.current().step == 20
    assert not r1.sync()  # never announced to r1: it stays consistent on 10
    for name, arr in r1.flat_params().items():
        np.testing.assert_array_equal(np.asarray(arr), snap10[name][StateKind.FP32])
    # A fresh replica rebuilds from the current publication: every shard
    # fetchable (peer copies are stale-or-gone, disk fallback serves).
    r2 = FleetReplica("r2", registry, tgt_plan, jmesh)
    assert r2.sync()
    for name, arr in r2.flat_params().items():
        np.testing.assert_array_equal(np.asarray(arr), snap20[name][StateKind.FP32])
    # Manager GC pins the published step: 20 outlives keep_last=1 even
    # after the next commit, until its successor is actually announced.
    snap30, state30 = state_at(3)
    mgr.save(state30, 30)
    assert registry.current().step == 30
    assert r1.sync() and r2.sync()
    for rep in (r1, r2):
        for name, arr in rep.flat_params().items():
            np.testing.assert_array_equal(
                np.asarray(arr), snap30[name][StateKind.FP32])
    mgr.close()


# ---------------------------------------------------------------------------
# Concurrent-reader stress: shared engine, shared caches, no races
# ---------------------------------------------------------------------------


def test_concurrent_readers_one_engine_stress(tmp_path):
    """Satellite: many threads restoring through ONE engine (shared
    HandleCache, BufferArena, atom single-flight, shared regions) must all
    produce bit-identical state with sane cache accounting."""
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    snap = _random_state(specs, seed=7)
    write_distributed(snap, plan, 5, tmp_path / "step_5")
    ckpt = DistCheckpoint.open(tmp_path / "step_5")
    tgt_plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    jmesh = make_mesh((1, 1), ("data", "model"))
    engine = CheckpointEngine(workers=4)
    ref = state_from_dist(ckpt, tgt_plan, jmesh, engine=CheckpointEngine(workers=1))

    registry = PublicationRegistry()
    pub = registry.publish(ckpt)
    results: list = [None] * 12
    errs: list[BaseException] = []

    def reader(i: int):
        try:
            if i % 3 == 0:
                # full-state restore straight from disk fragments
                st = state_from_source(ckpt, tgt_plan, jmesh, engine=engine)
                results[i] = (st.params, st.exp_avg)
            elif i % 3 == 1:
                # weights-only via the peer source (shared regions on)
                src = PeerFragmentSource(registry, pub, f"t{i}")
                results[i] = (params_from_source(
                    src, tgt_plan, jmesh, engine=engine), None)
            else:
                # raw region reads, the innermost shared path
                out = {
                    n: read_region_from_source(
                        ckpt, n, StateKind.FP32,
                        tuple(slice(0, d) for d in s.runtime_shape),
                        "float32", engine=engine,
                    ).copy()
                    for n, s in specs.items()
                }
                results[i] = (out, None)
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs
    for i, (params, moments) in enumerate(results):
        _params_equal(params, ref.params)
        if moments is not None:
            _params_equal(moments, ref.exp_avg)
    # cache accounting stayed sane under contention
    assert len(engine.handles) <= engine.handles.capacity
    assert engine.handles._bytes >= 0
    assert engine.atoms._bytes >= 0
    assert engine.arena._retained >= 0
    assert engine.arena._retained <= engine.arena.max_bytes
    engine.close()
