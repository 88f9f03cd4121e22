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
    from repro.core.tensor_io import resolve_dtype

    rng = np.random.default_rng(seed)
    return {
        n: {
            k: rng.normal(size=s.runtime_shape).astype(resolve_dtype(s.states[k].dtype))
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


# ---------------------------------------------------------------------------
# Whole-fragment reads: a region served whole by one raw shard file is read
# straight into its destination buffer, in byte ranges over the pool
# ---------------------------------------------------------------------------

_SQUARE = MeshSpec.from_dict({"data": 1, "model": 1})


def _full(shape):
    return tuple(slice(0, n) for n in shape)


@pytest.mark.parametrize(
    "dtype,shape,range_bytes",
    [
        ("float32", (7, 13), 64),
        ("bfloat16", (5, 9), 16),
        ("int8", (3, 11), 8),
        ("float32", (), 64),
        ("float32", (0, 4), 64),
        ("float32", (10, 37), 96),  # 1480 bytes: 15 full ranges + 40 bytes
    ],
    ids=["float32", "bfloat16", "int8", "scalar", "zero_size", "ragged_ranges"],
)
def test_whole_fragment_read_matches_np_load(tmp_path, monkeypatch, dtype, shape, range_bytes):
    """The range reads into one destination buffer return exactly the bytes
    (and dtype) the ``np.load`` path serves, for every width the format
    stores, a 0-d and a zero-size tensor, and a payload that is not a whole
    number of ranges."""
    import repro.ckpt.restore as restore
    from repro.core.tensor_io import load_tensor, npy_payload_offset, resolve_dtype, save_tensor

    monkeypatch.setattr(restore, "READ_RANGE_BYTES", range_bytes)
    rng = np.random.default_rng(len(shape) * 7 + range_bytes)
    arr = (rng.normal(size=shape) * 50).astype(resolve_dtype(dtype))
    path = tmp_path / "t.npy"
    save_tensor(path, arr)
    offset = npy_payload_offset(path, shape, dtype)
    assert offset is not None

    def reader(*_):
        raise AssertionError("a whole-file region must not take the reader path")

    with CheckpointEngine(workers=3) as eng:
        (got,) = restore._prefetch(
            reader, lambda *_: (path, offset), StateKind.FP32,
            [("t", dtype, _full(shape))], eng,
        )
    want = load_tensor(path, dtype=dtype, mmap=False)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert got.tobytes() == want.tobytes()


def test_whole_fragment_truncated_shard_raises(tmp_path):
    """A shard file cut short raises out of the restore, as np.load does,
    instead of handing back a partly filled buffer."""
    from repro.ckpt.restore import state_from_dist
    from repro.ckpt.saver import write_distributed

    specs = {"w": uniform_param_spec("w", (64, 32), [DimSpec(), DimSpec()])}
    write_distributed(_random_state(specs), _plan(_SQUARE, specs), 1, tmp_path / "ck", workers=1)
    ck = DistCheckpoint.open(tmp_path / "ck")
    path = ck.shard_path(0, "w", StateKind.EXP_AVG)
    with open(path, "r+b") as f:
        f.truncate(path.stat().st_size - 100)
    jmesh = make_mesh((1, 1), ("data", "model"))
    with CheckpointEngine(workers=2) as eng:
        with pytest.raises(ValueError, match="short"):
            state_from_dist(ck, _plan(_SQUARE, specs), jmesh, engine=eng)


def _coded_source(tmp_path):
    from repro.ckpt.restore import state_from_dist
    from repro.ckpt.saver import write_distributed
    from repro.core.codec import CodecPolicy

    specs = {"w": uniform_param_spec("w", (8, 64), [DimSpec(), DimSpec()])}
    plan = _plan(_SQUARE, specs)
    codec = CodecPolicy("int8:b64", "int8:b64", "int8:b64", allow_lossy_params=True)
    write_distributed(_random_state(specs), plan, 1, tmp_path / "ck", workers=1, codec=codec)
    ck = DistCheckpoint.open(tmp_path / "ck")
    assert ck.manifest.shard_codecs, "every shard is coded"
    return ck, plan, lambda eng, jmesh: state_from_dist(ck, plan, jmesh, engine=eng)


def _stream_source(tmp_path):
    from repro.ckpt.restore import _whole_fragment_locator, state_from_stream
    from repro.ckpt.saver import write_distributed
    from repro.core.plan import TargetSpec, stream_transforms

    src = {"w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec()])}
    tgt = {"w": uniform_param_spec("w", (8, 6), [DimSpec(), DimSpec()])}
    src_mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    write_distributed(_random_state(src), _plan(src_mesh, src), 1, tmp_path / "ck", workers=1)
    ck = DistCheckpoint.open(tmp_path / "ck")
    plan = _plan(_SQUARE, tgt)
    transforms = stream_transforms(ck.manifest, TargetSpec(_SQUARE, tgt))
    with CheckpointEngine(workers=1) as eng:
        locate = _whole_fragment_locator(ck, eng, plan, transforms)
        # a region that covers part of one fragment, and one that unions two
        assert locate("w", StateKind.FP32, (slice(0, 2), slice(0, 6)), "float32") is None
        assert locate("w", StateKind.FP32, _full((8, 6)), "float32") is None
        # the same fragment's own whole region does qualify
        assert locate("w", StateKind.FP32, (slice(0, 4), slice(0, 6)), "float32") is not None
    return ck, plan, lambda eng, jmesh: state_from_stream(ck, plan, jmesh, transforms, engine=eng)


def _hot_source(tmp_path):
    from repro.ckpt.restore import state_from_source
    from repro.hot import HotTier

    specs = {"w": uniform_param_spec("w", (8, 6), [DimSpec(), DimSpec()])}
    plan = _plan(_SQUARE, specs)
    hs, _ = HotTier(replication=1).capture(_random_state(specs), plan, 3)
    return hs, plan, lambda eng, jmesh: state_from_source(hs, plan, jmesh, engine=eng)


@pytest.mark.parametrize("build", [_coded_source, _stream_source, _hot_source],
                         ids=["int8_codec", "reshard_stream_union", "hot_snapshot"])
def test_whole_fragment_read_does_not_engage(tmp_path, build):
    """Coded shards, regions that are not one whole fragment, and in-memory
    sources keep the fragment-union path: no whole-fragment read, and the
    state equals that path's region reads."""
    import jax

    import repro.obs as obs
    from repro.ckpt.restore import read_region_from_source

    source, plan, restore = build(tmp_path)
    jmesh = make_mesh((1, 1), ("data", "model"))
    with CheckpointEngine(workers=2) as eng, obs.enabled() as tracer:
        state = restore(eng, jmesh)
    counters = tracer.counters()
    assert counters.get("restore.whole_fragment_reads", 0) == 0
    assert counters.get("restore.whole_fragment_bytes", 0) == 0
    assert counters.get("restore.region_reads", 0) > 0
    with CheckpointEngine(workers=1) as eng:
        for field, kind in (("params", StateKind.FP32), ("exp_avg", StateKind.EXP_AVG),
                            ("exp_avg_sq", StateKind.EXP_AVG_SQ)):
            leaf = jax.tree.leaves(getattr(state, field))[0]
            spec = plan.param_specs["w"]
            want = read_region_from_source(
                source, "w", kind, _full(spec.runtime_shape), spec.states[kind].dtype,
                engine=eng,
            )
            np.testing.assert_array_equal(np.asarray(leaf), want)


def test_direct_restore_reads_every_region_whole(tmp_path):
    """A DIRECT restore of a small model (float32, bfloat16, int8, 0-d and
    fused-QKV parameters; the last is one file of three sub-fragment
    entries) reads every region straight from its shard file: one
    whole-fragment read per region, every payload byte counted, no
    fragment-union read, nothing through the handle cache; bit for bit."""
    import jax

    import repro.obs as obs
    from repro.ckpt.restore import state_from_dist
    from repro.ckpt.saver import write_distributed
    from repro.core.tensor_io import npy_payload_offset

    specs = {
        "emb": uniform_param_spec("emb", (24, 16), [DimSpec(("model",)), DimSpec()]),
        "w": uniform_param_spec("w", (3, 16, 40), [DimSpec(), DimSpec(), DimSpec(("model",))]),
        "w_bf16": uniform_param_spec("w_bf16", (16, 8), [DimSpec(), DimSpec()],
                                     dtype="bfloat16"),
        "w_int8": uniform_param_spec("w_int8", (5, 7), [DimSpec(), DimSpec()], dtype="int8"),
        "scale": uniform_param_spec("scale", (), []),
        "wqkv": uniform_param_spec(
            "wqkv", (12, 6),
            [DimSpec(("model",), (SubFragment("q", 8), SubFragment("k", 2), SubFragment("v", 2))),
             DimSpec()],
            kind="fused_qkv",
        ),
    }
    plan = _plan(_SQUARE, specs)
    snap = _random_state(specs, seed=21)
    write_distributed(snap, plan, 4, tmp_path / "ck", workers=2)
    ck = DistCheckpoint.open(tmp_path / "ck")
    payload = 0
    for name, spec in specs.items():
        for kind in STATE_KINDS:
            path = ck.shard_path(0, name, kind)
            shape = spec.layout_for(kind, _SQUARE).local_shape
            payload += path.stat().st_size - npy_payload_offset(path, shape, spec.states[kind].dtype)

    jmesh = make_mesh((1, 1), ("data", "model"))
    with CheckpointEngine(workers=4) as eng, obs.enabled() as tracer:
        state = state_from_dist(ck, plan, jmesh, engine=eng)
        assert eng.handles.misses == 0 and len(eng.handles) == 0
    counters = tracer.counters()
    assert counters["restore.whole_fragment_reads"] == len(specs) * len(STATE_KINDS)
    assert counters["restore.whole_fragment_bytes"] == payload
    assert counters.get("restore.region_reads", 0) == 0
    for field, kind in (("params", StateKind.FP32), ("exp_avg", StateKind.EXP_AVG),
                        ("exp_avg_sq", StateKind.EXP_AVG_SQ)):
        tree = getattr(state, field)
        for name in specs:
            got = np.asarray(tree[name])
            assert got.dtype == snap[name][kind].dtype
            assert got.tobytes() == snap[name][kind].tobytes()


def test_whole_fragment_ranges_outnumber_workers(tmp_path, monkeypatch):
    """Many more range jobs than pool threads still complete: range jobs
    are enumerated up front and never wait on the pool they run in."""
    import jax

    import repro.ckpt.restore as restore
    from repro.ckpt.saver import write_distributed

    monkeypatch.setattr(restore, "READ_RANGE_BYTES", 256)
    specs = {
        "a": uniform_param_spec("a", (32, 40), [DimSpec(), DimSpec()]),
        "b": uniform_param_spec("b", (16, 24), [DimSpec(), DimSpec()]),
    }
    plan = _plan(_SQUARE, specs)
    snap = _random_state(specs, seed=5)
    write_distributed(snap, plan, 1, tmp_path / "ck", workers=1)
    ck = DistCheckpoint.open(tmp_path / "ck")
    jmesh = make_mesh((1, 1), ("data", "model"))
    out: dict = {}
    eng = CheckpointEngine(workers=2)
    t = threading.Thread(
        target=lambda: out.setdefault("s", restore.state_from_dist(ck, plan, jmesh, engine=eng)),
        daemon=True,
    )
    t.start()
    t.join(120)
    # a deadlocked pool is left to the daemon threads: closing it would hang
    assert not t.is_alive(), "whole-fragment range jobs deadlocked the pool"
    eng.close()
    for name in specs:
        got = np.asarray(jax.tree.leaves(out["s"].exp_avg_sq)[sorted(specs).index(name)])
        np.testing.assert_array_equal(got, snap[name][StateKind.EXP_AVG_SQ])


# ---------------------------------------------------------------------------
# Restore-scoped arena hold: one state kind's staging storage backs the next
# ---------------------------------------------------------------------------

_KIND_MAX = 128 << 10  # arena bound: two of a kind's four 64 KiB buffers


def test_arena_hold_retains_then_trims():
    """Inside a hold the arena keeps recycled storage beyond ``max_bytes``;
    on exit it trims back to the bound.  ``hold(0)`` holds nothing, and
    what was recycled before a hold is reaped under the plain bound."""
    from repro.core.engine import BufferArena

    arena = BufferArena(max_bytes=8192)
    page = 4096

    def cycle(n, settle=True):
        """Stage ``n`` one-page buffers, recycle them, and reap."""
        bufs = [arena.alloc((page,), np.uint8) for _ in range(n)]
        for buf in bufs:
            arena.recycle(buf)
        del bufs, buf
        if settle:
            with arena._lock:
                arena._reap_locked()
        return arena._retained

    assert cycle(4) == 2 * page
    with arena.hold(0):
        assert cycle(4) == 2 * page
    cycle(4, settle=False)  # recycled before the hold, reaped at its entry
    with arena.hold(4 * page):
        assert arena._retained == 2 * page
        assert cycle(6) == 6 * page
        with arena.hold(page):
            assert cycle(7) == 7 * page
        assert arena._retained == 6 * page  # the inner exit trims to the outer bound
    assert arena._retained == 2 * page
    assert arena.reuse_bytes == (2 + 2 + 2 + 6) * page
    assert arena.alloc_bytes == (4 + 2 + 2 + 4 + 1) * page


def test_arena_concurrent_holds_keep_the_books():
    """Threads (more than cores, short switch interval) open overlapping
    holds while they stage and recycle buffers: when every hold has ended
    the arena's bound is back to ``max_bytes``, its free lists hold each
    buffer once, and its retained count is what the lists hold."""
    import os
    import sys

    from repro.core.engine import BufferArena

    arena = BufferArena(max_bytes=4 * 4096)
    errors: list[BaseException] = []

    def work(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(40):
                with arena.hold(int(rng.integers(0, 8)) * 4096):
                    bufs = [arena.alloc((int(rng.integers(1, 3)) * 4096,), np.uint8)
                            for _ in range(int(rng.integers(1, 6)))]
                    for buf in bufs:
                        buf[:] = seed
                        arena.recycle(buf)
                    del bufs, buf
        except BaseException as e:  # reported by the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,), daemon=True)
                   for i in range(2 * (os.cpu_count() or 2) + 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads), "arena holds deadlocked"
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors
    with arena._lock:
        arena._reap_locked()
        free = [raw for stack in arena._free.values() for raw in stack]
        assert arena._held == 0
        assert len({id(raw) for raw in free}) == len(free)
        assert arena._retained == sum(raw.nbytes for raw in free) <= arena.max_bytes


def _kinds_checkpoint(tmp_path):
    from repro.ckpt.saver import write_distributed

    specs = {f"w{i}": uniform_param_spec(f"w{i}", (64, 256), [DimSpec(), DimSpec()])
             for i in range(4)}  # 64 KiB a region, 256 KiB a kind
    plan = _plan(_SQUARE, specs)
    snap = _random_state(specs, seed=16)
    write_distributed(snap, plan, 2, tmp_path / "ck", workers=1)
    return DistCheckpoint.open(tmp_path / "ck"), plan, snap


@pytest.mark.parametrize("case", ["held", "hold_disabled", "consumer_aliases", "jax_consumer"])
def test_restore_hands_staging_kind_to_kind(tmp_path, monkeypatch, case):
    """A three-kind DIRECT restore with ``arena_max_bytes`` below one kind:
    each kind stages into the storage of the kind before it (two thirds
    of the staging bytes warm), where without the hold a kind gets at
    most ``max_bytes`` warm.  After the restore the arena retains at most
    ``max_bytes``, so the next restore's first kind reuses what it would
    without the hold.  A staging buffer that a consumer still references
    (jax's CPU backend may alias one) is never handed to the next kind:
    its bytes stay what was handed over.  Every restore equals a serial
    ``workers=1`` restore bit for bit.

    On the chip the runtime copies each staging buffer to the device; the
    consumer here copies too, so whether the CPU backend aliases a buffer
    (by its alignment) does not decide the counts.  ``jax_consumer`` hands
    jax the staging buffers themselves: a kind then reuses every buffer of
    the kind before but those the restored arrays alias, which jax's CPU
    copies release only through its deferred reference list."""
    import contextlib

    import jax

    import repro.obs as obs
    from repro.ckpt.restore import state_from_dist
    from repro.core.engine import BufferArena

    ck, plan, snap = _kinds_checkpoint(tmp_path)
    jmesh = make_mesh((1, 1), ("data", "model"))
    kind_bytes = 4 * (64 << 10)
    with CheckpointEngine(workers=1) as eng:
        serial = state_from_dist(ck, plan, jmesh, engine=eng)
    for field, kind in (("params", StateKind.FP32), ("exp_avg", StateKind.EXP_AVG),
                        ("exp_avg_sq", StateKind.EXP_AVG_SQ)):
        for name, leaf in zip(sorted(snap), jax.tree.leaves(getattr(serial, field))):
            assert np.asarray(leaf).tobytes() == snap[name][kind].tobytes()
    want = jax.tree.leaves(serial)
    if case == "hold_disabled":
        monkeypatch.setattr(BufferArena, "hold", lambda self, nbytes: contextlib.nullcontext())
    held: list[tuple[np.ndarray, np.ndarray]] = []
    aliased: list[int] = []  # jax_consumer: regions a restored array aliases, per kind
    real = jax.make_array_from_callback

    def consumer(shape, sharding, cb):
        if case == "jax_consumer":
            staged = []
            arr = real(shape, sharding, lambda index: staged.append(cb(index)) or staged[-1])
            ptr = arr.unsafe_buffer_pointer()  # one device: one buffer
            aliased.append(sum(x.__array_interface__["data"][0] == ptr for x in staged))
            return arr

        def copying(index):
            staged = cb(index)
            if case == "consumer_aliases":
                held.append((staged.view(), staged.copy()))
            return np.array(staged, copy=True)
        return real(shape, sharding, copying)

    monkeypatch.setattr(jax, "make_array_from_callback", consumer)
    with CheckpointEngine(workers=4, arena_max_bytes=_KIND_MAX) as eng:
        warm, counters = [], []
        for _ in range(2):
            with obs.enabled() as tracer:
                got = jax.tree.leaves(state_from_dist(ck, plan, jmesh, engine=eng))
            assert eng.arena._retained <= _KIND_MAX
            spans = [s for s in tracer.span_records() if s["name"] == "restore.prefetch"]
            assert [s["attrs"]["field"] for s in spans] == ["params", "exp_avg", "exp_avg_sq"]
            for s in spans:
                assert s["attrs"]["warm_bytes"] + s["attrs"]["fresh_bytes"] == kind_bytes
            warm.append([s["attrs"]["warm_bytes"] for s in spans])
            counters.append(tracer.counters())
            assert len(got) == len(want) > 0
            for a, b in zip(got, want):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            del got

    first = counters[0]
    total = first.get("engine.arena.reuse_bytes", 0) + first.get("engine.arena.alloc_bytes", 0)
    assert total == 3 * kind_bytes
    assert warm[0][0] == 0  # a new engine: the first kind faults fresh pages
    if case == "jax_consumer":
        n = len(plan.param_specs)
        per_kind = [sum(aliased[k * n:(k + 1) * n]) for k in range(6)]
        for r in range(2):  # the second also finds what the first's arrays held
            for k in (1, 2):
                want_warm = kind_bytes - per_kind[3 * r + k - 1] * (64 << 10)
                assert warm[r][k] == want_warm if r == 0 else warm[r][k] >= want_warm
        return
    # the next restore's first kind: max_bytes of the last kind's storage, first fit
    assert warm[1][0] == (_KIND_MAX if case != "consumer_aliases" else 0)
    if case == "held":
        assert warm[0][1:] == [kind_bytes, kind_bytes]
        assert first["engine.arena.reuse_bytes"] >= 2 * total / 3
    elif case == "hold_disabled":
        assert warm[0][1:] == [_KIND_MAX, _KIND_MAX]
    else:
        assert warm == [[0, 0, 0], [0, 0, 0]]
        assert len(held) == 2 * 3 * len(plan.param_specs)
        for view, handed in held:
            assert view.tobytes() == handed.tobytes()


def test_release_staging_frees_placed_buffers():
    """Once ``_release_staging`` returns, jax holds none of the host buffers
    the arrays were placed from, so the arena hands their storage to the
    next allocation.  A 64 MiB buffer is copied to the device after the
    placement call returns and released through jax's deferred list."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.ckpt.restore import _release_staging
    from repro.core.engine import BufferArena

    arena = BufferArena()
    n = 16 << 20
    staged = arena.alloc((n,), np.float32, zero=False)
    staged[...] = 1.5
    # one element in: never 64-byte aligned, so the CPU backend copies it
    src = staged[1:]
    arr = jax.make_array_from_callback(
        src.shape, SingleDeviceSharding(jax.devices()[0]), lambda index: src[index]
    )
    arena.recycle(staged)
    del staged, src
    _release_staging([arr])
    arena.alloc((n,), np.float32, zero=False)
    assert arena.reuses == 1
    assert float(arr[-1]) == 1.5
