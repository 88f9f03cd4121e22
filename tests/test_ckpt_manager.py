"""Checkpoint-manager behaviour: atomicity, async==sync, keep-k GC, crash
recovery, lazy UCP conversion caching, fast-path vs via-UCP restore."""

import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ParallelismConfig, get_config, reduced
from repro.core.layout import MeshSpec
from repro.core.plan import ResumeMode
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.saver import AsyncSaver, snapshot_state, write_distributed
from repro.dist.sharding import make_plan, vocab_multiple
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.optimizer import init_state


@pytest.fixture()
def setup(tmp_path):
    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 1, "model": 1})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    jmesh = make_mesh((1, 1), ("data", "model"))
    return tmp_path, cfg, lm, plan, state, jmesh


def _state_equal(a, b):
    fa, fb = jax.tree.leaves(a.params), jax.tree.leaves(b.params)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sync_save_restore_roundtrip(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(async_save=False))
    mgr.save(state, 10)
    assert mgr.latest_step() == 10
    restored, info = mgr.restore(jmesh)
    assert info.mode == ResumeMode.DIRECT
    assert int(restored.step) == 10
    _state_equal(state, restored)


def test_async_save_equals_sync(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    m1 = CheckpointManager(tmp / "sync", plan, policy=CheckpointPolicy(
        async_save=False))
    m1.save(state, 5)
    m2 = CheckpointManager(tmp / "async", plan, policy=CheckpointPolicy(
        async_save=True))
    m2.save(state, 5)
    results = m2.wait()
    assert results and results[0].step == 5
    # byte-identical shard trees
    s1 = sorted(p.relative_to(tmp / "sync") for p in (tmp / "sync").rglob("*.npy"))
    s2 = sorted(p.relative_to(tmp / "async") for p in (tmp / "async").rglob("*.npy"))
    assert s1 == s2
    for rel in s1:
        a = (tmp / "sync" / rel).read_bytes()
        b = (tmp / "async" / rel).read_bytes()
        assert a == b, rel
    m2.close()


def test_keep_last_gc(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        keep_last=2, async_save=False))
    for s in (10, 20, 30, 40):
        mgr.save(state, s)
    assert mgr.steps() == [30, 40]
    assert not (mgr.step_dir(10)).exists()


def test_uncommitted_checkpoints_ignored_and_cleaned(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(async_save=False))
    mgr.save(state, 10)
    # simulate crash-during-save: newer dir without COMMIT
    crashed = mgr.step_dir(20)
    crashed.mkdir(parents=True)
    (crashed / "MANIFEST.json").write_text("{}")
    assert mgr.latest_step() == 10
    restored, info = mgr.restore(jmesh)
    assert info.step == 10


def test_restore_prefers_requested_step(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        keep_last=10, async_save=False))
    mgr.save(state, 10)
    mgr.save(state, 20)
    _, info = mgr.restore(jmesh, step=10)
    assert info.step == 10


def test_reshard_stream_restore_writes_nothing(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(async_save=False))
    mgr.save(state, 10)
    # target: different parallelism flags → structurally different layouts
    parallel2 = ParallelismConfig(zero=1, fsdp=False)
    mesh2 = MeshSpec.from_dict({"data": 1, "model": 1})
    lm2 = build_model(cfg, vocab_multiple=vocab_multiple(parallel2, mesh2))
    plan2 = make_plan(cfg, lm2.registry, parallel2, mesh2)
    before = sorted(p for p in (tmp / "ck").rglob("*") if p.is_file())
    restored, info = mgr.restore(jmesh, target_plan=plan2)
    assert info.mode == ResumeMode.RESHARD_STREAM
    assert info.convert_stats is None  # nothing was converted
    # zero intermediate bytes: the checkpoint directory is untouched
    assert before == sorted(p for p in (tmp / "ck").rglob("*") if p.is_file())
    _state_equal(state, restored)


@pytest.mark.parametrize("zero1", [False, True], ids=["direct", "reshard_stream"])
def test_restore_params_reads_weights_only(setup, zero1):
    """The serving restore gives the full restore's params from a third of
    its reads, on the same and on a changed layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tmp, cfg, lm, plan, state, jmesh = setup
    CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        async_save=False)).save(state, 10)
    if zero1:
        parallel2 = ParallelismConfig(zero=1, fsdp=False)
        lm2 = build_model(cfg, vocab_multiple=vocab_multiple(parallel2, plan.mesh))
        plan = make_plan(cfg, lm2.registry, parallel2, plan.mesh)
    reader = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(
        async_save=False))
    params, info = reader.restore_params(jmesh)
    full, full_info = reader.restore(jmesh)
    want = ResumeMode.RESHARD_STREAM if zero1 else ResumeMode.DIRECT
    assert info.mode == full_info.mode == want and info.step == 10
    assert 3 * info.restore_stats.bytes_read == full_info.restore_stats.bytes_read
    for x, y in zip(jax.tree.leaves(params), jax.tree.leaves(full.params), strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # the restored step counter is placed on the mesh like the trainer's
    assert full.step.sharding.is_equivalent_to(NamedSharding(jmesh, P()), 0)


def test_via_ucp_restore_and_conversion_cache(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(async_save=False))
    mgr.save(state, 10)
    parallel2 = ParallelismConfig(zero=1, fsdp=False)
    mesh2 = MeshSpec.from_dict({"data": 1, "model": 1})
    lm2 = build_model(cfg, vocab_multiple=vocab_multiple(parallel2, mesh2))
    plan2 = make_plan(cfg, lm2.registry, parallel2, mesh2)
    # the paper's convert-then-Load workflow stays available when forced
    restored, info = mgr.restore(
        jmesh, target_plan=plan2, force_mode=ResumeMode.VIA_UCP
    )
    assert info.mode == ResumeMode.VIA_UCP
    assert info.convert_stats is not None  # converted this time
    _state_equal(state, restored)
    # second restore reuses the cached UCP directory (hub property)
    restored2, info2 = mgr.restore(
        jmesh, target_plan=plan2, force_mode=ResumeMode.VIA_UCP
    )
    assert info2.convert_stats is None
    _state_equal(state, restored2)


def test_export_ucp_is_explicit_and_cached(setup):
    tmp, cfg, lm, plan, state, jmesh = setup
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(async_save=False))
    mgr.save(state, 10)
    ucp, cstats = mgr.export_ucp()
    assert cstats is not None and cstats.params > 0
    assert (Path(str(mgr.step_dir(10)) + ".ucp") / "COMMIT").exists()
    ucp2, cstats2 = mgr.export_ucp(10)
    assert cstats2 is None  # cache hit
    # a forced-DIRECT restore onto a different layout must refuse
    parallel2 = ParallelismConfig(zero=1, fsdp=False)
    mesh2 = MeshSpec.from_dict({"data": 1, "model": 1})
    lm2 = build_model(cfg, vocab_multiple=vocab_multiple(parallel2, mesh2))
    plan2 = make_plan(cfg, lm2.registry, parallel2, mesh2)
    with pytest.raises(ValueError, match="cannot force DIRECT"):
        mgr.restore(jmesh, target_plan=plan2, force_mode=ResumeMode.DIRECT)


def test_gc_spares_inflight_save_dirs(setup, monkeypatch):
    """Regression: an older queued async save that commits after a newer
    synchronous one must not have its directory rmtree'd mid-write by
    ``gc()``'s uncommitted-wreckage removal."""
    import threading

    import repro.ckpt.saver as saver_mod

    tmp, cfg, lm, plan, state, jmesh = setup
    real_write = saver_mod.write_distributed
    started, gate = threading.Event(), threading.Event()

    def stalled_write(snap, plan_, step, root, **kw):
        if step == 10:  # the older save: stall mid-write, dir already created
            Path(root).mkdir(parents=True, exist_ok=True)
            (Path(root) / "MANIFEST.json").write_text("{}")
            started.set()
            assert gate.wait(20), "test gate never opened"
        return real_write(snap, plan_, step, root, **kw)

    monkeypatch.setattr(saver_mod, "write_distributed", stalled_write)
    mgr = CheckpointManager(tmp / "ck", plan, policy=CheckpointPolicy(async_save=True))
    mgr.save(state, 10)  # queued; stalls with its directory half-written
    assert started.wait(20)
    # a newer blocking save commits first, then gc() runs: step_10 is
    # uncommitted and older than the newest commit — the exact wreckage
    # signature — but it is in flight and must survive
    mgr.save(state, 20, block=True)
    assert mgr.steps() == [20]
    assert mgr.step_dir(10).exists(), "gc rmtree'd an in-flight save dir"
    gate.set()
    results = mgr.wait()
    assert any(r.step == 10 for r in results)
    assert sorted(mgr.steps()) == [10, 20]  # the stalled save still committed
    restored, info = mgr.restore(jmesh, step=10)
    _state_equal(state, restored)
    mgr.close()


def test_async_saver_surfaces_errors():
    saver = AsyncSaver()
    saver._q.put(lambda: (_ for _ in ()).throw(RuntimeError("disk full")))
    saver._q.join()
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        saver.check()
    saver.close()


def test_atomic_tensor_write_no_torn_files(setup, tmp_path):
    """Kill-during-write leaves either old or no file, never torn bytes —
    guaranteed by tmp+rename in save_tensor."""
    from repro.core.tensor_io import load_tensor, save_tensor

    p = tmp_path / "x.npy"
    a = np.arange(10, dtype=np.float32)
    save_tensor(p, a)
    b = np.arange(10, 20).astype(np.float32)
    save_tensor(p, b)  # overwrite is atomic (os.replace)
    np.testing.assert_array_equal(np.asarray(load_tensor(p, "float32")), b)
    assert not list(tmp_path.glob("*.tmp"))
