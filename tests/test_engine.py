"""Checkpoint I/O engine tests: the parallel save / indexed parallel restore
paths must be *bit-identical* to the serial ``workers=1`` paths across
randomized meshes and layouts (including the direct-reshard path), the
fragment index must agree with the brute-force rank scan, and the handle
cache must bound its population via LRU eviction."""

import threading

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    CheckpointEngine,
    DimSpec,
    DistCheckpoint,
    DistManifest,
    HandleCache,
    MeshSpec,
    ParamSpec,
    STATE_KINDS,
    StateKind,
    StateLayoutSpec,
    SubFragment,
    convert_to_ucp,
    uniform_param_spec,
)
from repro.dist.sharding import ShardingPlan
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


def _tree_bytes(root):
    """{relative path: bytes} for every shard file under a checkpoint dir."""
    from pathlib import Path

    root = Path(root)
    return {
        str(p.relative_to(root)): p.read_bytes()
        for p in sorted(root.glob("ranks/**/*.npy"))
    }


# ---------------------------------------------------------------------------
# Property: parallel save + indexed parallel restore == serial, bit for bit
# ---------------------------------------------------------------------------


@st.composite
def _case(draw):
    mesh = MeshSpec(
        (("data", draw(st.integers(1, 3))), ("model", draw(st.integers(1, 3))))
    )
    tgt = MeshSpec(
        (("data", draw(st.integers(1, 3))), ("model", draw(st.integers(1, 3))))
    )
    rows = draw(st.integers(1, 12))
    cols = draw(st.integers(1, 9))
    axis_choices = [(), ("data",), ("model",), ("data", "model")]
    sdims = (
        DimSpec(axes=draw(st.sampled_from(axis_choices))),
        DimSpec(axes=draw(st.sampled_from([(), ("model",)]))),
    )
    tdims = (
        DimSpec(axes=draw(st.sampled_from(axis_choices))),
        DimSpec(axes=draw(st.sampled_from([(), ("model",)]))),
    )
    if set(sdims[0].axes) & set(sdims[1].axes):
        sdims = (sdims[0], DimSpec())
    if set(tdims[0].axes) & set(tdims[1].axes):
        tdims = (tdims[0], DimSpec())
    save_mode = draw(st.sampled_from(["dedup", "all"]))
    return mesh, tgt, (rows, cols), sdims, tdims, save_mode


@settings(max_examples=25, deadline=None)
@given(_case())
def test_property_parallel_paths_bit_identical(tmp_path_factory, case):
    from repro.ckpt.restore import read_region_from_dist
    from repro.ckpt.saver import write_distributed

    mesh, tgt_mesh, shape, sdims, tdims, save_mode = case
    tmp = tmp_path_factory.mktemp("eng")
    specs = {
        "w": uniform_param_spec("w", shape, sdims),
        "b": uniform_param_spec("b", (shape[0],), sdims[:1]),
    }
    snap = _random_state(specs, seed=shape[0] * 31 + shape[1])
    plan = _plan(mesh, specs)

    write_distributed(snap, plan, 1, tmp / "ser", workers=1, save_mode=save_mode)
    write_distributed(snap, plan, 1, tmp / "par", workers=4, save_mode=save_mode)
    ser, par = _tree_bytes(tmp / "ser"), _tree_bytes(tmp / "par")
    assert ser.keys() == par.keys() and ser, "same shard files must exist"
    for rel in ser:
        assert ser[rel] == par[rel], f"shard {rel} differs serial vs parallel"

    # Direct-reshard restore: arbitrary Target-layout regions served from
    # the Source checkpoint, serial engine vs parallel engine vs the truth.
    ck = DistCheckpoint.open(tmp / "par")
    with CheckpointEngine(workers=1) as eng_ser, CheckpointEngine(workers=4) as eng_par:
        for name, spec in specs.items():
            tgt_layout = uniform_param_spec(
                name, spec.logical_shape, tdims[: len(spec.logical_shape)]
            ).layout_for(StateKind.FP32, tgt_mesh)
            regions = [e.atom_index() for r in tgt_mesh.ranks()
                       for e in tgt_layout.entries[r]]
            regions.append(tuple(slice(0, s) for s in spec.runtime_shape))
            for region in regions:
                got_ser = read_region_from_dist(
                    ck, name, StateKind.FP32, region, "float32", engine=eng_ser
                )
                got_par = read_region_from_dist(
                    ck, name, StateKind.FP32, region, "float32", engine=eng_par
                )
                want = snap[name][StateKind.FP32][region]
                np.testing.assert_array_equal(got_ser, want)
                np.testing.assert_array_equal(got_par, want)


def test_state_from_dist_parallel_equals_serial(tmp_path):
    """Full jax restore (direct-reshard: Source mesh != Target mesh) is
    bit-identical across engine worker counts."""
    import jax

    from repro.ckpt.restore import state_from_dist
    from repro.ckpt.saver import write_distributed

    src_mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    tgt_mesh = MeshSpec.from_dict({"data": 4, "model": 1})
    qkv = (SubFragment("q", 8), SubFragment("k", 2), SubFragment("v", 2))
    mk = lambda ax: {
        "wqkv": uniform_param_spec(
            "wqkv", (12, 6), [DimSpec(ax, qkv), DimSpec()], kind="fused_qkv"
        ),
        "emb": uniform_param_spec("emb", (10, 6), [DimSpec(ax), DimSpec()]),
        "bias": uniform_param_spec("bias", (6,), [DimSpec()]),
    }
    src_specs, tgt_specs = mk(("model",)), mk(("data",))
    snap = _random_state(src_specs, seed=7)
    write_distributed(snap, _plan(src_mesh, src_specs), 3, tmp_path / "ck", workers=4)
    ck = DistCheckpoint.open(tmp_path / "ck")

    jmesh = make_mesh((1, 1), ("data", "model"))
    tgt_plan = _plan(tgt_mesh, tgt_specs)
    with CheckpointEngine(workers=1) as e1, CheckpointEngine(workers=4) as e4:
        s1 = state_from_dist(ck, tgt_plan, jmesh, engine=e1)
        s4 = state_from_dist(ck, tgt_plan, jmesh, engine=e4)
    l1, l4 = jax.tree.leaves(s1), jax.tree.leaves(s4)
    assert len(l1) == len(l4) > 0
    for a, b in zip(l1, l4):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the restored values are the saved ones (tgt layout is unpadded)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(s1.params)[0]), snap["bias"][StateKind.FP32]
    )


# ---------------------------------------------------------------------------
# Fragment index
# ---------------------------------------------------------------------------


def test_fragment_index_matches_brute_force(tmp_path):
    from repro.ckpt.saver import write_distributed

    mesh = MeshSpec.from_dict({"data": 3, "model": 2})
    specs = {
        "w": uniform_param_spec("w", (13, 7), [DimSpec(("data",)), DimSpec(("model",))])
    }
    snap = _random_state(specs, seed=11)
    write_distributed(snap, _plan(mesh, specs), 1, tmp_path / "ck", workers=1)
    ck = DistCheckpoint.open(tmp_path / "ck")
    eng = CheckpointEngine(workers=1)
    idx = eng.index_for(ck, "w", StateKind.FP32)
    layout = idx.layout
    rng = np.random.default_rng(0)
    for _ in range(30):
        r0 = sorted(rng.integers(0, 14, size=2))
        r1 = sorted(rng.integers(0, 8, size=2))
        if r0[0] == r0[1] or r1[0] == r1[1]:
            continue
        region = (slice(r0[0], r0[1]), slice(r1[0], r1[1]))
        got = {(rank, e.atom_slice) for rank, e, _ in idx.overlapping(region)}
        want = set()
        seen_frags = set()
        for rank in ck.writing_ranks("w", StateKind.FP32):
            frag = layout.fragment_id[rank]
            if frag in seen_frags:
                continue
            seen_frags.add(frag)
            for e in layout.entries[rank]:
                if all(
                    max(a0, r.start) < min(a1, r.stop)
                    for (a0, a1), r in zip(e.atom_slice, region)
                ):
                    want.add((rank, e.atom_slice))
        assert got == want
    # the index is built once and cached per (checkpoint, param, kind)
    assert eng.index_for(ck, "w", StateKind.FP32) is idx


# ---------------------------------------------------------------------------
# Handle cache
# ---------------------------------------------------------------------------


def test_handle_cache_lru_eviction():
    cache = HandleCache(capacity=2)
    loads = []

    def loader(path):
        return lambda: loads.append(path) or f"handle:{path}"

    assert cache.get("/a", loader("/a")) == "handle:/a"
    assert cache.get("/b", loader("/b")) == "handle:/b"
    assert cache.get("/a", loader("/a")) == "handle:/a"  # hit, /a now MRU
    assert cache.get("/c", loader("/c")) == "handle:/c"  # evicts /b (LRU)
    assert len(cache) == 2
    assert "/b" not in cache and "/a" in cache and "/c" in cache
    assert cache.evictions == 1 and cache.hits == 1 and cache.misses == 3
    cache.get("/b", loader("/b"))  # /b must be re-loaded after eviction
    assert loads == ["/a", "/b", "/c", "/b"]
    with pytest.raises(ValueError):
        HandleCache(capacity=0)


def test_restore_opens_each_file_once(tmp_path):
    """N params x R regions touches each shard file exactly once."""
    from repro.ckpt.restore import read_region_from_dist

    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    specs = {"w": uniform_param_spec("w", (8, 4), [DimSpec(("data",)), DimSpec()])}
    snap = _random_state(specs, seed=3)
    from repro.ckpt.saver import write_distributed

    write_distributed(snap, _plan(mesh, specs), 1, tmp_path / "ck", workers=1)
    ck = DistCheckpoint.open(tmp_path / "ck")
    eng = CheckpointEngine(workers=1)
    for lo in range(0, 8, 2):  # 4 regions, 2 shard files
        read_region_from_dist(
            ck, "w", StateKind.FP32, (slice(lo, lo + 2), slice(None)), "float32",
            engine=eng,
        )
    assert eng.handles.misses == 2  # one open per shard file…
    assert eng.handles.hits >= 2  # …every later region reuses the handle


# ---------------------------------------------------------------------------
# Convert stats + AsyncSaver backpressure (satellites)
# ---------------------------------------------------------------------------


def test_resave_invalidates_default_engine_handles(tmp_path):
    """Re-saving into the same directory must not leave the process default
    engine serving the old checkpoint's bytes from cached handles."""
    from repro.ckpt.restore import read_region_from_dist
    from repro.ckpt.saver import write_distributed

    mesh = MeshSpec.from_dict({"data": 1, "model": 1})
    specs = {"w": uniform_param_spec("w", (4,), [DimSpec()])}
    region = (slice(0, 4),)
    plan = _plan(mesh, specs)
    snap1 = {"w": {k: np.full((4,), 1.0, np.float32) for k in STATE_KINDS}}
    snap2 = {"w": {k: np.full((4,), 2.0, np.float32) for k in STATE_KINDS}}

    write_distributed(snap1, plan, 1, tmp_path / "ck", workers=2)
    ck = DistCheckpoint.open(tmp_path / "ck")
    got = read_region_from_dist(ck, "w", StateKind.FP32, region, "float32")
    np.testing.assert_array_equal(got, snap1["w"][StateKind.FP32])
    # overwrite through a *private* pool (workers override) — the default
    # engine's cached handle for the old file must still be dropped
    write_distributed(snap2, plan, 1, tmp_path / "ck", workers=3)
    ck2 = DistCheckpoint.open(tmp_path / "ck")
    got = read_region_from_dist(ck2, "w", StateKind.FP32, region, "float32")
    np.testing.assert_array_equal(got, snap2["w"][StateKind.FP32])


def test_convert_stats_counts_atom_files(tmp_path):
    from repro.ckpt.saver import write_distributed

    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    specs = {
        "w": uniform_param_spec("w", (6, 4), [DimSpec(("data",)), DimSpec()]),
        "b": uniform_param_spec("b", (4,), [DimSpec()]),
    }
    snap = _random_state(specs, seed=5)
    write_distributed(snap, _plan(mesh, specs), 1, tmp_path / "ck", workers=1)
    _, stats = convert_to_ucp(
        DistCheckpoint.open(tmp_path / "ck"), str(tmp_path / "ucp"), workers=2
    )
    # one atom *file* per (param, state kind), not one per parameter
    assert stats.params == 2
    assert stats.atoms_written == 2 * len(STATE_KINDS)


def test_async_saver_bounds_pending_snapshots(monkeypatch):
    """submit() applies backpressure once max_pending jobs are queued."""
    import repro.ckpt.saver as saver_mod
    from repro.ckpt.saver import AsyncSaver, SaveResult

    release = threading.Event()
    started = threading.Event()

    def slow_write(snap, plan, step, root, **kw):
        started.set()
        release.wait(10)
        from pathlib import Path

        return SaveResult(step, Path(str(root)), 0, 0.0)

    monkeypatch.setattr(saver_mod, "write_distributed", slow_write)
    monkeypatch.setattr(saver_mod, "snapshot_state", lambda state: {})

    s = AsyncSaver(max_pending=1)
    s.submit(None, None, 1, "/tmp/x1")  # picked up by the worker, blocks
    assert started.wait(5)
    s.submit(None, None, 2, "/tmp/x2")  # fills the queue (depth 1)

    third_done = threading.Event()

    def third():
        s.submit(None, None, 3, "/tmp/x3")
        third_done.set()

    t = threading.Thread(target=third, daemon=True)
    t.start()
    assert not third_done.wait(0.3), "third submit should block on full queue"
    release.set()
    assert third_done.wait(5), "submit must unblock once the disk catches up"
    t.join(5)
    assert len(s.wait()) == 3
    s.close()
    with pytest.raises(ValueError):
        AsyncSaver(max_pending=0)


def test_invalidate_respects_path_boundaries():
    """invalidate(root) must drop root's own keys (incl. the delta-variant
    cache key and derived atom keys) but never a sibling's that merely
    shares the root as a string prefix (run1 vs run10)."""
    from repro.core.engine import _key_under_root

    root = "/ck/run1"
    assert _key_under_root("/ck/run1", root)
    assert _key_under_root("/ck/run1/ranks/r0/a.npy", root)
    assert _key_under_root("/ck/run1@delta:10", root)
    assert _key_under_root("/ck/run1::atom::w@fp32", root)
    assert not _key_under_root("/ck/run10", root)
    assert not _key_under_root("/ck/run10/ranks/r0/a.npy", root)
    assert not _key_under_root("/ck/run1.ucp/atoms/w/fp32.npy", root)

    eng = CheckpointEngine(workers=2)
    arr = np.zeros(4, np.float32)
    eng.handles.get("/ck/run1/ranks/r0/a.npy", lambda: arr)
    eng.handles.get("/ck/run10/ranks/r0/a.npy", lambda: arr)
    eng.invalidate("/ck/run1")
    assert "/ck/run1/ranks/r0/a.npy" not in eng.handles
    assert "/ck/run10/ranks/r0/a.npy" in eng.handles
    eng.close()
