"""The chaos harness (repro.chaos): fault points, scheduled replay, the
ladder invariant, and the previously-fixed races re-expressed as explicit
chaos schedules.

The replay tests revert a specific fix via monkeypatch and assert the
exact schedule that found the bug fails again — proving the schedule
pins the race, not an accident of timing:

* GC-vs-in-flight-save: ``pending_roots()`` keeps a mid-write save out
  of wreckage removal;
* delta-base TOCTOU: the base loader pins the resolved chain under the
  same lock GC deletes under (plus ``check_chain_committed`` as the loud
  backstop);
* GC deletes newest-first, so a crash mid-GC never leaves a surviving
  committed delta referencing an already-collected ancestor;
* the currently-published step outlives ``keep_last`` (a crash between
  commit and announce leaves the fleet on the older publication).
"""

import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.saver import AsyncSaver, snapshot_state, write_distributed
from repro.core import (
    DimSpec,
    DistCheckpoint,
    MeshSpec,
    STATE_KINDS,
    StateKind,
    uniform_param_spec,
)
from repro.core import clock
from repro.dist.sharding import ShardingPlan
from repro.launch.mesh import make_mesh
from repro.serve import FleetReplica, PublicationRegistry
from repro.train.optimizer import TrainState

from repro.chaos import (
    CATALOG,
    ChaosController,
    FaultError,
    FaultSpec,
    Schedule,
    check_invariants,
    fault_point,
    generate_schedule,
)
from repro.chaos.harness import ChaosHarness, _is_fault
from repro.chaos.invariants import InvariantViolation, diff_snapshots
from repro.chaos.sweep import emit_regression_test, run_seed, shrink, sweep

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


def _train_state(snap, step):
    return TrainState(
        params={n: snap[n][StateKind.FP32] for n in snap},
        exp_avg={n: snap[n][StateKind.EXP_AVG] for n in snap},
        exp_avg_sq={n: snap[n][StateKind.EXP_AVG_SQ] for n in snap},
        step=np.int32(step),
    )


def _mutate(snap, seed):
    """Sparse update: one param's FP32 leaf changes, the rest stay put
    (so delta saves have both written and inherited shards)."""
    rng = np.random.default_rng(seed)
    name = sorted(snap)[seed % len(snap)]
    snap[name][StateKind.FP32] = snap[name][StateKind.FP32] + rng.normal(
        scale=0.01, size=snap[name][StateKind.FP32].shape
    ).astype(np.float32)


@pytest.fixture()
def setup(tmp_path):
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    tgt_plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    jmesh = make_mesh((1, 1), ("data", "model"))
    return tmp_path, plan, tgt_plan, jmesh


# ---------------------------------------------------------------------------
# fault points + schedules


def test_catalog_matches_callsites():
    """Every fault_point() call site in production code is in CATALOG and
    vice versa — the catalog cannot drift from the hooks silently.

    The authoritative (AST-based, multi-line-aware) version of this check
    is the `catalog` rule in repro.analysis, run by `scripts/ci.sh --lint`
    and tests/test_analysis.py; this regex pass stays as a cheap
    independent cross-check.  `analysis` is skipped like `chaos`: both
    mention fault points without being call sites.
    """
    src = Path(__file__).resolve().parent.parent / "src" / "repro"
    seen = set()
    for py in src.rglob("*.py"):
        if "chaos" in py.parts or "analysis" in py.parts:
            continue
        seen |= set(re.findall(r'fault_point\(\s*"([^"]+)"', py.read_text()))
    assert seen == set(CATALOG)


def test_fault_point_is_noop_when_inactive():
    fault_point("saver.shard", step=1)  # no controller: must not raise
    fault_point("manager.gc.begin")


def test_schedule_generation_is_deterministic():
    a = generate_schedule(42, n_faults=8)
    b = generate_schedule(42, n_faults=8)
    assert a == b
    assert generate_schedule(43, n_faults=8) != a


def test_schedule_json_roundtrip_and_prefix():
    s = generate_schedule(7, n_faults=5)
    assert Schedule.from_json(s.to_json()) == s
    assert s.prefix(2).faults == s.faults[:2]
    assert s.prefix(2).seed == s.seed


def test_schedule_rejects_unknown_points_and_actions():
    with pytest.raises(ValueError, match="unknown fault point"):
        FaultSpec(point="no.such.point")
    with pytest.raises(ValueError, match="unknown fault action"):
        FaultSpec(point="saver.shard", action="explode")
    with pytest.raises(ValueError, match="hit must be"):
        FaultSpec(point="saver.shard", hit=0)


def test_controller_requires_env_handlers():
    sched = Schedule(0, (FaultSpec("saver.shard", action="lose_ranks", args=(1,)),))
    with pytest.raises(ValueError, match="chaos_lose_ranks"):
        ChaosController(sched, env=object())


def test_controller_counts_hits_from_arming():
    """The second fault's hit counter restarts when it arms — the property
    that makes prefix replay (and therefore shrinking) sound."""
    sched = Schedule(0, (
        FaultSpec("manager.gc.begin", hit=2),
        FaultSpec("manager.gc.begin", hit=2),
    ))
    fired = []
    with ChaosController(sched) as ctrl:
        for i in range(4):
            try:
                fault_point("manager.gc.begin")
            except FaultError:
                fired.append(i)
    assert fired == [1, 3]
    assert ctrl.exhausted


# ---------------------------------------------------------------------------
# invariants


def test_invariants_clean_manager_passes(setup):
    tmp, plan, tgt_plan, jmesh = setup
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=2, save_interval=10, async_save=False, io_workers=1))
    snap = _random_state(plan.param_specs)
    mgr.save(_train_state(snap, 10), 10)
    assert check_invariants(mgr) == []
    mgr.close()


def test_invariants_flag_torn_checkpoint(setup):
    tmp, plan, tgt_plan, jmesh = setup
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=2, save_interval=10, async_save=False, io_workers=1))
    snap = _random_state(plan.param_specs)
    mgr.save(_train_state(snap, 10), 10)
    # Tear it: a shard file vanishes after commit.
    next(mgr.step_dir(10).glob("ranks/rank_*/*.npy")).unlink()
    viol = check_invariants(mgr)
    assert viol and all(v.check == "disk" for v in viol)
    with pytest.raises(InvariantViolation):
        check_invariants(mgr, strict=True)
    mgr.close()


def test_diff_snapshots_is_bit_exact():
    specs = _specs()
    a = _random_state(specs, seed=1)
    b = {n: {k: v.copy() for k, v in kv.items()} for n, kv in a.items()}
    assert diff_snapshots(a, b) == []
    b["w"][StateKind.FP32][0, 0] += np.float32(1e-7)
    diffs = diff_snapshots(a, b)
    assert diffs and "w" in diffs[0]


# ---------------------------------------------------------------------------
# background-error surfacing (async saver / hot drainer)


def test_async_save_crash_surfaces_on_wait(setup):
    tmp, plan, tgt_plan, jmesh = setup
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=2, save_interval=10, async_save=True, io_workers=1))
    snap = _random_state(plan.param_specs)
    sched = Schedule(0, (FaultSpec("saver.shard", action="crash", hit=1),))
    with ChaosController(sched):
        mgr.save(_train_state(snap, 10), 10)
        with pytest.raises(RuntimeError, match="async checkpoint save failed") as ei:
            mgr.wait()
    assert _is_fault(ei.value)  # the injected FaultError rides the chain
    assert mgr.wait() == []  # errors drained: the next wait is clean
    mgr.close()


def test_async_save_crash_surfaces_on_close(setup):
    tmp, plan, tgt_plan, jmesh = setup
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=2, save_interval=10, async_save=True, io_workers=1))
    snap = _random_state(plan.param_specs)
    sched = Schedule(0, (FaultSpec("saver.pre_commit", action="crash", hit=1),))
    with ChaosController(sched):
        mgr.save(_train_state(snap, 10), 10)
        with pytest.raises(RuntimeError, match="async checkpoint save failed"):
            mgr.close()
    assert mgr.steps() == []  # crash before COMMIT: discovery ignores it


def test_drain_crash_surfaces_on_wait(setup):
    tmp, plan, tgt_plan, jmesh = setup
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=2, save_interval=10, hot_interval=10, disk_interval=10,
        async_save=True, io_workers=1))
    snap = _random_state(plan.param_specs)
    sched = Schedule(0, (FaultSpec("drain.shard", action="crash", hit=1),))
    with ChaosController(sched):
        mgr.save(_train_state(snap, 10), 10)
        with pytest.raises(RuntimeError, match="hot snapshot drain failed") as ei:
            mgr.wait()
    assert _is_fault(ei.value)
    # the hot tier still serves: the crash only hit the disk promotion
    res = mgr.restore_latest(jmesh, target_plan=tgt_plan)
    assert res is not None and res[1].step == 10
    mgr.close()


# ---------------------------------------------------------------------------
# race replays: the previously-fixed races as explicit schedules.  Each
# test runs the schedule against current code (must pass) and against the
# fix reverted via monkeypatch (must fail) — the schedule pins the race.


def _paused_mid_save(tmp, plan, snap):
    """Start an async save of step 10 and park its writer thread mid-shards
    (pause gate), returning (mgr, controller ctx).  Caller drives the race
    while the writer is frozen between 'some shards written' and COMMIT."""
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=2, save_interval=10, async_save=True, io_workers=1))
    sched = Schedule(0, (FaultSpec("saver.shard", action="pause",
                                   hit=10, args=("mid-save",)),))
    ctrl = ChaosController(sched)
    return mgr, ctrl


def test_replay_gc_vs_inflight_save(setup):
    """GC runs while an older async save is mid-write: ``pending_roots``
    keeps its uncommitted directory out of wreckage removal."""
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    mgr, ctrl = _paused_mid_save(tmp, plan, snap)
    with ctrl:
        mgr.save(_train_state(snap, 10), 10)
        ctrl.wait_paused("mid-save")
        # A newer save commits and GCs while step 10 is frozen mid-write.
        mgr.save(_train_state(snap, 20), 20, block=True)
        assert mgr.steps() == [20]
        ctrl.release("mid-save")
        mgr.wait()
    assert mgr.steps() == [10, 20]
    assert check_invariants(mgr) == []
    mgr.close()


def test_replay_gc_vs_inflight_save_fails_without_fix(setup, monkeypatch):
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    mgr, ctrl = _paused_mid_save(tmp, plan, snap)
    # Revert the fix: GC no longer sees the async saver's in-flight roots.
    monkeypatch.setattr(AsyncSaver, "pending_roots", lambda self: set())
    with ctrl:
        mgr.save(_train_state(snap, 10), 10)
        ctrl.wait_paused("mid-save")
        mgr.save(_train_state(snap, 20), 20, block=True)
        ctrl.release("mid-save")
        err = None
        try:
            mgr.wait()
        except RuntimeError as e:
            err = e
    # The race reproduces: GC rmtree'd the mid-write directory, so the save
    # either dies loudly or commits a torn checkpoint the invariants flag.
    assert err is not None or check_invariants(mgr), (
        "reverting pending_roots() must reproduce the GC-vs-in-flight race"
    )
    try:
        mgr.close()
    except RuntimeError:
        pass


def _paused_mid_delta(tmp, plan, snap):
    """Commit a full step 10, then freeze an async *delta* save of step 20
    right after its base (step 10) was resolved but before any shard write."""
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=1, save_interval=10, async_save=True, io_workers=1, save_mode="delta",
        full_interval=8))
    mgr.save(_train_state(snap, 10), 10, block=True)  # seq 0: forced full
    _mutate(snap, 1)
    sched = Schedule(0, (FaultSpec("saver.shard", action="pause",
                                   hit=1, args=("mid-delta",)),))
    return mgr, ChaosController(sched)


def test_replay_delta_base_toctou(setup):
    """GC wants the base of a queued delta (keep_last pushed it out) while
    the delta is mid-write: the pinned chain survives until the commit."""
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    mgr, ctrl = _paused_mid_delta(tmp, plan, snap)
    ref20 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap.items()}
    with ctrl:
        mgr.save(_train_state(snap, 20), 20)  # delta over step 10
        ctrl.wait_paused("mid-delta")
        # A full step 30 commits out-of-band; with keep_last=1 GC now wants
        # every older step — including the frozen delta's base.
        write_distributed(_random_state(plan.param_specs, seed=9), plan, 30,
                          mgr.step_dir(30), engine=mgr.engine)
        mgr.gc()
        assert 10 in mgr.steps(), "pinned base must survive mid-delta GC"
        ctrl.release("mid-delta")
        mgr._async.wait()  # drain without GC: assert the committed chain
    assert set(mgr.steps()) == {10, 20, 30}
    assert check_invariants(mgr) == []
    res = mgr.restore(jmesh, step=20, target_plan=tgt_plan, verify=True)
    assert res is not None
    assert diff_snapshots(snapshot_state(res[0]), ref20) == []
    mgr.close()


def test_replay_delta_base_toctou_fails_without_fix(setup, monkeypatch):
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)

    def leaky_base_loader(self, step):
        # The pre-fix loader: resolves the newest committed base without
        # registering a pin (and outside GC's deletion lock).
        def load():
            older = [s for s in self.steps() if s < step]
            if not older:
                return None
            try:
                return DistCheckpoint.open(self.step_dir(older[-1]))
            except (OSError, ValueError, KeyError):
                return None
        return load

    monkeypatch.setattr(CheckpointManager, "_base_loader", leaky_base_loader)
    # ... and silence the loud pre-commit backstop so the race commits.
    monkeypatch.setattr("repro.ckpt.saver.check_chain_committed", lambda c: None)
    mgr, ctrl = _paused_mid_delta(tmp, plan, snap)
    with ctrl:
        mgr.save(_train_state(snap, 20), 20)
        ctrl.wait_paused("mid-delta")
        write_distributed(_random_state(plan.param_specs, seed=9), plan, 30,
                          mgr.step_dir(30), engine=mgr.engine)
        mgr.gc()
        assert 10 not in mgr.steps(), "unpinned base collected (fix reverted)"
        ctrl.release("mid-delta")
        mgr._async.wait()
    viol = check_invariants(mgr)
    assert any("live base collected" in str(v) for v in viol), (
        "reverting base pinning must commit a delta over a collected base"
    )
    mgr.close()


def test_gc_crash_mid_loop_deletes_newest_first(setup):
    """A crash between two GC deletions must never leave a surviving
    committed delta referencing an already-deleted ancestor — deletion
    order is newest-first (found by chaos seed 23)."""
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=1, save_interval=10, async_save=False, io_workers=1,
        save_mode="delta", full_interval=3))
    # seq 0 full(10) <- delta(20) <- delta(30); seq 3 full(40) rebases, so
    # GC then wants the whole old chain {10, 20, 30}.
    for step in (10, 20, 30):
        mgr.save(_train_state(snap, step), step)
        _mutate(snap, step)
    sched = Schedule(0, (FaultSpec("manager.gc.delete", action="crash", hit=2),))
    with ChaosController(sched):
        with pytest.raises(FaultError):
            mgr.save(_train_state(snap, 40), 40)  # crash after one deletion
    # Newest-first: 30 went, the crash hit before 20 — survivors 10 <- 20
    # still resolve.  (Oldest-first deleted 10 first, stranding 20 and 30.)
    assert set(mgr.steps()) == {10, 20, 40}
    assert check_invariants(mgr) == []
    res = mgr.restore(jmesh, step=20, target_plan=tgt_plan, verify=True)
    assert res is not None
    mgr.close()


def test_published_step_outlives_keep_last(setup):
    """A crash between commit and announce leaves the fleet reading the
    older publication — GC must keep that step alive past keep_last."""
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    registry = PublicationRegistry()
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=1, save_interval=10, async_save=False, io_workers=1,
        registry=registry))
    mgr.save(_train_state(snap, 10), 10)  # publishes step 10
    ref10 = {n: kv[StateKind.FP32].copy() for n, kv in snap.items()}
    assert registry.current().step == 10
    # Every subsequent publish attempt crashes: commits land, GC runs, the
    # announcement never goes out (publish order inside save: gc first).
    sched = Schedule(0, tuple(
        FaultSpec("registry.publish.begin", action="crash", hit=1)
        for _ in range(3)
    ))
    with ChaosController(sched):
        for step in (20, 30, 40):
            _mutate(snap, step)
            with pytest.raises(FaultError):
                mgr.save(_train_state(snap, step), step)
    assert registry.current().step == 10
    assert set(mgr.steps()) == {10, 40}, "published step must survive GC"
    replica = FleetReplica("r1", registry, tgt_plan, jmesh)
    assert replica.sync()
    for name, arr in replica.flat_params().items():
        np.testing.assert_array_equal(np.asarray(arr), ref10[name])
    mgr.close()


# ---------------------------------------------------------------------------
# clock injection (GC/commit wall-clock is not load-bearing)


def test_clock_is_injectable():
    try:
        clock.set_source(lambda: 1000.0)
        assert clock.now() == 1000.0
        clock.skew(-600)
        assert clock.now() == 400.0
    finally:
        clock.reset()


def test_commit_stamps_route_through_clock(setup):
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    try:
        clock.set_source(lambda: 12345.0)
        write_distributed(snap, plan, 1, tmp / "step_1")
        assert DistCheckpoint.open(tmp / "step_1").manifest.created_at == 12345.0
    finally:
        clock.reset()


def test_clock_skew_cannot_change_gc_newest(setup):
    """Discovery and GC order by step directory NAME: a checkpoint whose
    commit stamp says 'two hours ago' is still the newest if its step is."""
    tmp, plan, tgt_plan, jmesh = setup
    snap = _random_state(plan.param_specs)
    mgr = CheckpointManager(tmp / "ckpt", plan, policy=CheckpointPolicy(
        keep_last=1, save_interval=10, async_save=False, io_workers=1))
    try:
        mgr.save(_train_state(snap, 10), 10)
        clock.skew(-7200)  # step 20's stamps now predate step 10's
        _mutate(snap, 1)
        mgr.save(_train_state(snap, 20), 20)
        assert mgr.steps() == [20]
        assert mgr.latest_step() == 20
        assert check_invariants(mgr) == []
    finally:
        clock.reset()
        mgr.close()


# ---------------------------------------------------------------------------
# the harness end to end


def test_chaos_seed_smoke():
    """A few full seeded runs: real manager, real faults, ladder invariant
    checked after every event (the CI PR-lane smoke)."""
    result = sweep([0, 1, 2], events=6)
    assert result.ok, result.describe()


def test_chaos_seed_23_regression(tmp_path):
    """Shrunk from fallen sweep seed 23: a crash between two GC deletions
    of a doomed delta chain stranded committed deltas on a deleted base
    (fixed by newest-first deletion order)."""
    schedule = Schedule(seed=23, faults=(
        FaultSpec(point="drain.shard", action="skew_clock", hit=1, args=(-7200,)),
        FaultSpec(point="peer.fetch", action="crash", hit=4, args=()),
        FaultSpec(point="registry.publish.deliver", action="crash", hit=2, args=()),
        FaultSpec(point="manager.gc.delete", action="crash", hit=2, args=()),
    ))
    report = ChaosHarness(23, tmp_path / "run", events=12, schedule=schedule).run()
    assert report.ok, report.describe()


def test_shrink_returns_passing_report_unchanged():
    rep = run_seed(7, events=4)
    assert rep.ok, rep.describe()
    assert shrink(rep) is rep


def test_emitted_regression_test_is_valid_python():
    from repro.chaos.harness import ChaosReport

    rep = ChaosReport(
        ok=False, seed=5, config={}, events_completed=2,
        schedule=Schedule(5, (
            FaultSpec("saver.shard", action="crash", hit=2),
            FaultSpec("manager.gc.delete", action="lose_ranks", args=(1,)),
        )),
        violations=["[disk] step 20: torn"], error=None, log=[],
    )
    src = emit_regression_test(rep)
    compile(src, "<emitted>", "exec")  # syntactically valid pytest source
    assert "seed=5" in src and "saver.shard" in src and "tmp_path" in src
