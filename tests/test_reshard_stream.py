"""RESHARD_STREAM: streaming pattern-based resharding.

Covers the per-param transform classifier, the reshard-matrix smoke
(planner picks the expected mode for dp/tp/pp/zero mesh pairs and the
restored state is bit-identical to the VIA_UCP path with zero intermediate
bytes on disk), a property test that stream restore equals VIA_UCP restore
for every param class (plain, fused-QKV, vocab-padded, MoE expert,
params_to_average), and the crash-mid-stream fallback.
"""

import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ParallelismConfig, get_config, reduced
from repro.core import (
    DimSpec,
    DistCheckpoint,
    MeshSpec,
    STATE_KINDS,
    StateKind,
    StateLayoutSpec,
    SubFragment,
    TransformClass,
    classify_transform,
    convert_to_ucp,
    plan_resume,
    stream_transforms,
    uniform_param_spec,
)
from repro.core.patterns import ParamSpec
from repro.core.plan import ResumeMode, TargetSpec
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.restore import state_from_stream, state_from_ucp
from repro.ckpt.saver import write_distributed
from repro.dist.sharding import ShardingPlan, make_plan, vocab_multiple
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.optimizer import init_state


def _random_state(specs, seed=0):
    rng = np.random.default_rng(seed)
    return {
        n: {
            k: rng.normal(size=s.runtime_shape).astype(np.float32)
            for k in STATE_KINDS
        }
        for n, s in specs.items()
    }


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb) > 0
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _stream_vs_ucp(tmp, src_mesh, tgt_mesh, src_specs, tgt_specs, seed=0):
    """Save under the Source layout, restore via stream and via UCP atoms;
    both must be bit-identical.  Returns the plan table."""
    plan_src = ShardingPlan(mesh=src_mesh, param_specs=dict(src_specs))
    plan_tgt = ShardingPlan(mesh=tgt_mesh, param_specs=dict(tgt_specs))
    snap = _random_state(src_specs, seed=seed)
    write_distributed(snap, plan_src, 1, tmp / "ck", workers=2)
    ck = DistCheckpoint.open(tmp / "ck")
    transforms = stream_transforms(
        ck.manifest, TargetSpec(tgt_mesh, dict(tgt_specs))
    )
    jmesh = make_mesh((1, 1), ("data", "model"))
    s_stream = state_from_stream(ck, plan_tgt, jmesh, transforms)
    ucp, _ = convert_to_ucp(ck, str(tmp / "ucp"), workers=1)
    s_ucp = state_from_ucp(ucp, plan_tgt, jmesh)
    _leaves_equal(s_stream, s_ucp)
    return transforms


# ---------------------------------------------------------------------------
# Transform classification (the per-param plan table)
# ---------------------------------------------------------------------------


def test_classify_plain_reslice_and_identity():
    mesh_a = MeshSpec.from_dict({"data": 2, "model": 2})
    mesh_b = MeshSpec.from_dict({"data": 4, "model": 1})
    spec = uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec(("model",))])
    spec_b = uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec()])
    assert classify_transform(spec, spec, mesh_a, mesh_a).cls is TransformClass.IDENTITY
    assert classify_transform(spec, spec_b, mesh_a, mesh_b).cls is TransformClass.RESLICE
    # same specs on a different mesh: re-slicing, not identity
    assert classify_transform(spec, spec, mesh_a, mesh_b).cls is TransformClass.RESLICE


def test_classify_fused_repartition_consolidates():
    qkv = (SubFragment("q", 12), SubFragment("k", 3), SubFragment("v", 3))
    mk = lambda: uniform_param_spec(
        "wqkv", (18, 5), [DimSpec(("model",), qkv), DimSpec()], kind="fused_qkv"
    )
    m4 = MeshSpec.from_dict({"data": 1, "model": 4})
    m2 = MeshSpec.from_dict({"data": 1, "model": 2})
    t = classify_transform(mk(), mk(), m4, m2)
    assert t.cls is TransformClass.CONSOLIDATE and "repartitioned" in t.reason
    # unchanged TP degree: the fused geometry is untouched → re-slice is fine
    assert classify_transform(mk(), mk(), m2, m2).cls is TransformClass.IDENTITY


def test_classify_padding_change_and_average_consolidate():
    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    padded = lambda rt: ParamSpec(
        name="emb",
        logical_shape=(10, 4),
        runtime_shape=rt,
        states={k: StateLayoutSpec((DimSpec(("data",)), DimSpec())) for k in STATE_KINDS},
    )
    t = classify_transform(padded((12, 4)), padded((16, 4)), mesh, mesh)
    assert t.cls is TransformClass.CONSOLIDATE and "padding" in t.reason
    # same padding multiple → pure re-slicing (padding re-zeroed on the fly)
    assert classify_transform(padded((12, 4)), padded((12, 4)),
                              mesh, MeshSpec.from_dict({"data": 1, "model": 2})
                              ).cls is TransformClass.RESLICE
    avg = ParamSpec(
        name="a", logical_shape=(6,), runtime_shape=(2, 6),
        states={k: StateLayoutSpec((DimSpec(("data",)), DimSpec())) for k in STATE_KINDS},
        average=True,
    )
    assert classify_transform(avg, avg, mesh, mesh).cls is TransformClass.CONSOLIDATE


def test_classify_moe_regroup_consolidates():
    ep = uniform_param_spec(
        "moe.w", (4, 6, 8), [DimSpec(("model",)), DimSpec(), DimSpec()],
        kind="moe_expert",
    )
    tp = uniform_param_spec(
        "moe.w", (4, 6, 8), [DimSpec(), DimSpec(("model",)), DimSpec()],
        kind="moe_expert",
    )
    mesh = MeshSpec.from_dict({"data": 1, "model": 2})
    t = classify_transform(ep, tp, mesh, mesh)
    assert t.cls is TransformClass.CONSOLIDATE and "re-grouping" in t.reason
    # EP degree change without re-grouping: expert dim stays the sharded one
    m4 = MeshSpec.from_dict({"data": 1, "model": 4})
    assert classify_transform(ep, ep, m4, mesh).cls is TransformClass.RESLICE


def test_plan_resume_modes():
    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    spec = uniform_param_spec("w", (8, 4), [DimSpec(("data",)), DimSpec()])
    snap = _random_state({"w": spec})
    import tempfile
    from pathlib import Path

    with tempfile.TemporaryDirectory() as tmp:
        write_distributed(snap, ShardingPlan(mesh, {"w": spec}), 1,
                          Path(tmp) / "ck", workers=1)
        ck = DistCheckpoint.open(Path(tmp) / "ck")
        assert plan_resume(ck.manifest, TargetSpec(mesh, {"w": spec})).mode \
            is ResumeMode.DIRECT
        tgt = uniform_param_spec("w", (8, 4), [DimSpec(), DimSpec(("data",))])
        rp = plan_resume(ck.manifest, TargetSpec(mesh, {"w": tgt}))
        assert rp.mode is ResumeMode.RESHARD_STREAM
        assert rp.transforms is not None and not rp.consolidate_params
        # different param set is not streamable → VIA_UCP
        rp2 = plan_resume(
            ck.manifest, TargetSpec(mesh, {"w": tgt, "extra": spec})
        )
        assert rp2.mode is ResumeMode.VIA_UCP
        # the paper's workflow stays selectable
        assert plan_resume(ck.manifest, TargetSpec(mesh, {"w": tgt}),
                           allow_stream=False).mode is ResumeMode.VIA_UCP


# ---------------------------------------------------------------------------
# Stream == VIA_UCP bit-identity, one test per param class
# ---------------------------------------------------------------------------


def test_stream_plain_param_matches_ucp(tmp_path):
    src_mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    tgt_mesh = MeshSpec.from_dict({"data": 4, "model": 1})
    src = {"w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec(("model",))])}
    tgt = {"w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec()])}
    tr = _stream_vs_ucp(tmp_path, src_mesh, tgt_mesh, src, tgt)
    assert tr["w"].cls is TransformClass.RESLICE


def test_stream_fused_qkv_matches_ucp(tmp_path):
    qkv = (SubFragment("q", 12), SubFragment("k", 3), SubFragment("v", 3))
    mk = lambda: uniform_param_spec(
        "wqkv", (18, 5), [DimSpec(("model",), qkv), DimSpec()], kind="fused_qkv"
    )
    src_mesh = MeshSpec.from_dict({"data": 1, "model": 4})
    tgt_mesh = MeshSpec.from_dict({"data": 1, "model": 2})
    tr = _stream_vs_ucp(tmp_path, src_mesh, tgt_mesh, {"wqkv": mk()}, {"wqkv": mk()})
    assert tr["wqkv"].cls is TransformClass.CONSOLIDATE


def test_stream_vocab_padded_matches_ucp(tmp_path):
    """Padded runtime rows carry garbage at save time; both paths must
    canonicalize them to zero — same multiple (reslice) and changed
    multiple (consolidate)."""
    mk = lambda rt, dims: ParamSpec(
        name="emb", logical_shape=(10, 4), runtime_shape=rt,
        states={k: StateLayoutSpec(tuple(dims)) for k in STATE_KINDS},
    )
    src_mesh = MeshSpec.from_dict({"data": 4, "model": 1})
    # same padding multiple, resharded → streams, padding re-zeroed
    tgt_mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    tr = _stream_vs_ucp(
        tmp_path / "a", src_mesh, tgt_mesh,
        {"emb": mk((12, 4), [DimSpec(("data",)), DimSpec()])},
        {"emb": mk((12, 4), [DimSpec(("model",)), DimSpec(("data",))])},
    )
    assert tr["emb"].cls is TransformClass.RESLICE
    # padding multiple changed → StripPadding + re-pad through the atom
    tr = _stream_vs_ucp(
        tmp_path / "b", src_mesh, tgt_mesh,
        {"emb": mk((12, 4), [DimSpec(("data",)), DimSpec()])},
        {"emb": mk((16, 4), [DimSpec(("data", "model"),), DimSpec()])},
    )
    assert tr["emb"].cls is TransformClass.CONSOLIDATE


def test_stream_moe_expert_matches_ucp(tmp_path):
    ep = uniform_param_spec(
        "moe.w", (4, 6, 8), [DimSpec(("model",)), DimSpec(), DimSpec()],
        kind="moe_expert",
    )
    tp = uniform_param_spec(
        "moe.w", (4, 6, 8), [DimSpec(), DimSpec(("model",)), DimSpec()],
        kind="moe_expert",
    )
    mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    tr = _stream_vs_ucp(tmp_path, mesh, mesh, {"moe.w": ep}, {"moe.w": tp})
    assert tr["moe.w"].cls is TransformClass.CONSOLIDATE


def test_stream_average_param_matches_ucp(tmp_path):
    """params_to_average: divergent replicas are averaged then re-broadcast."""
    mk = lambda dims: ParamSpec(
        name="a", logical_shape=(6, 4), runtime_shape=(2, 6, 4),
        states={k: StateLayoutSpec(tuple(dims)) for k in STATE_KINDS},
        average=True,
    )
    src_mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    tgt_mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    tr = _stream_vs_ucp(
        tmp_path, src_mesh, tgt_mesh,
        {"a": mk([DimSpec(("data",)), DimSpec(), DimSpec()])},
        {"a": mk([DimSpec(("data",)), DimSpec(("model",)), DimSpec()])},
    )
    assert tr["a"].cls is TransformClass.CONSOLIDATE


@st.composite
def _random_reshard_case(draw):
    axis_choices = [(), ("data",), ("model",), ("data", "model")]
    src_mesh = MeshSpec.from_dict(
        {"data": draw(st.integers(1, 3)), "model": draw(st.integers(1, 3))}
    )
    tgt_mesh = MeshSpec.from_dict(
        {"data": draw(st.integers(1, 3)), "model": draw(st.integers(1, 3))}
    )
    rows = draw(st.integers(4, 12))
    pad = draw(st.integers(0, 3))

    def dims():
        d = [
            DimSpec(draw(st.sampled_from(axis_choices))),
            DimSpec(draw(st.sampled_from([(), ("model",)]))),
        ]
        if set(d[0].axes) & set(d[1].axes):
            d = [d[0], DimSpec()]
        return tuple(d)

    return src_mesh, tgt_mesh, rows, pad, dims(), dims()


@settings(max_examples=15, deadline=None)
@given(_random_reshard_case())
def test_property_stream_equals_ucp_random_layouts(tmp_path_factory, case):
    """Random source/target shardings (incl. dedup'd replicas and padding):
    stream restore is always bit-identical to the VIA_UCP restore."""
    src_mesh, tgt_mesh, rows, pad, sd, td = case
    mk = lambda d: ParamSpec(
        name="w", logical_shape=(rows, 5), runtime_shape=(rows + pad, 5),
        states={k: StateLayoutSpec(tuple(d)) for k in STATE_KINDS},
    )
    tmp = tmp_path_factory.mktemp("prop")
    _stream_vs_ucp(tmp, src_mesh, tgt_mesh, {"w": mk(sd)}, {"w": mk(td)},
                   seed=rows * 7 + pad)


# ---------------------------------------------------------------------------
# Reshard-matrix smoke: real model, manager-level, ~6 mesh pairs
# ---------------------------------------------------------------------------

# (source mesh, source parallel kw, target mesh, target parallel kw, mode)
MATRIX = [
    ({"data": 2, "model": 2}, {}, {"data": 2, "model": 2}, {}, "direct"),
    ({"data": 2, "model": 2}, {}, {"data": 4, "model": 1}, {}, "reshard_stream"),
    ({"data": 4, "model": 1}, {}, {"data": 2, "model": 2}, {}, "reshard_stream"),
    ({"data": 2, "model": 2}, {}, {"pipe": 2, "data": 1, "model": 2},
     {"pipe_axis": "pipe"}, "reshard_stream"),
    ({"data": 2, "model": 2}, {}, {"data": 2, "model": 2},
     {"zero": 1, "fsdp": False}, "reshard_stream"),
    ({"data": 1, "model": 4}, {}, {"data": 4, "model": 1}, {}, "reshard_stream"),
]


@pytest.fixture(scope="module")
def matrix_cfg():
    return reduced(get_config("smollm-360m"))


@pytest.fixture(scope="module")
def matrix_sources(matrix_cfg, tmp_path_factory):
    """One saved source checkpoint (+ its init state) per distinct source."""
    cache = {}

    def get(src_mesh_d, src_kw):
        key = (tuple(sorted(src_mesh_d.items())), tuple(sorted(src_kw.items())))
        if key not in cache:
            mesh = MeshSpec.from_dict(src_mesh_d)
            parallel = ParallelismConfig(**src_kw)
            lm = build_model(matrix_cfg, vocab_multiple=vocab_multiple(parallel, mesh))
            plan = make_plan(matrix_cfg, lm.registry, parallel, mesh)
            state = init_state(lm.init(jax.random.PRNGKey(0)))
            root = tmp_path_factory.mktemp("src")
            mgr = CheckpointManager(root / "ck", plan, policy=CheckpointPolicy(
                async_save=False))
            mgr.save(state, 10)
            cache[key] = (root / "ck", plan, state)
        return cache[key]

    return get


@pytest.mark.parametrize("src_mesh,src_kw,tgt_mesh,tgt_kw,expect", MATRIX)
def test_reshard_matrix(matrix_cfg, matrix_sources, tmp_path,
                        src_mesh, src_kw, tgt_mesh, tgt_kw, expect):
    ck_dir, src_plan, state = matrix_sources(src_mesh, src_kw)
    mesh = MeshSpec.from_dict(tgt_mesh)
    parallel = ParallelismConfig(**tgt_kw)
    lm = build_model(matrix_cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    tgt_plan = make_plan(matrix_cfg, lm.registry, parallel, mesh)
    axes = tuple(tgt_mesh)
    jmesh = make_mesh((1,) * len(axes), axes)

    mgr = CheckpointManager(ck_dir, src_plan, policy=CheckpointPolicy(async_save=False))
    before = sorted(p for p in ck_dir.rglob("*") if p.is_file())
    restored, info = mgr.restore(jmesh, target_plan=tgt_plan)
    assert info.mode.value == expect, info.reason
    # streaming must leave the checkpoint directory untouched — zero
    # intermediate bytes (the VIA_UCP cache below is written deliberately)
    assert before == sorted(p for p in ck_dir.rglob("*") if p.is_file())
    if expect == "direct":
        _leaves_equal(
            (restored.params, restored.exp_avg, restored.exp_avg_sq),
            (state.params, state.exp_avg, state.exp_avg_sq),
        )
    else:
        via, info2 = mgr.restore(
            jmesh, target_plan=tgt_plan, force_mode=ResumeMode.VIA_UCP
        )
        assert info2.mode is ResumeMode.VIA_UCP
        _leaves_equal(restored, via)


def test_logical_shape_change_is_not_streamable(tmp_path):
    """A logical-shape change hiding inside unchanged runtime padding must
    route VIA_UCP (which rejects it loudly), never RESLICE — streaming it
    would serve Source padding bytes as data."""
    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    mk = lambda logical: ParamSpec(
        name="emb", logical_shape=logical, runtime_shape=(12, 4),
        states={k: StateLayoutSpec((DimSpec(("data",)), DimSpec())) for k in STATE_KINDS},
    )
    snap = _random_state({"emb": mk((10, 4))})
    write_distributed(snap, ShardingPlan(mesh, {"emb": mk((10, 4))}), 1,
                      tmp_path / "ck", workers=1)
    ck = DistCheckpoint.open(tmp_path / "ck")
    rp = plan_resume(ck.manifest, TargetSpec(mesh, {"emb": mk((12, 4))}))
    assert rp.mode is ResumeMode.VIA_UCP
    assert "not streamable" in rp.reason and "logical shape" in rp.reason
    with pytest.raises(ValueError, match="not streamable"):
        stream_transforms(ck.manifest, TargetSpec(mesh, {"emb": mk((12, 4))}))


def test_hot_direct_preserves_divergent_average_replicas():
    """Identical-layout hot recovery of a params_to_average parameter must
    restore each replica's own divergent copy bit-exactly — averaging is a
    reconfiguration semantic, not a restart semantic."""
    from repro.hot import HotTier, state_from_hot

    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    spec = ParamSpec(
        name="a", logical_shape=(6, 4), runtime_shape=(2, 6, 4),
        states={
            k: StateLayoutSpec((DimSpec(("data",)), DimSpec(), DimSpec()))
            for k in STATE_KINDS
        },
        average=True,
    )
    plan = ShardingPlan(mesh, {"a": spec})
    snap = _random_state({"a": spec}, seed=5)
    tier = HotTier(replication=1)
    hs, _ = tier.capture(snap, plan, 3)
    jmesh = make_mesh((1, 1), ("data", "model"))
    restored = state_from_hot(hs, plan, jmesh)
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(restored.params)[0]),
        snap["a"][StateKind.FP32],
    )
    tier.clear()


# ---------------------------------------------------------------------------
# Crash-mid-stream: fall back cleanly to VIA_UCP
# ---------------------------------------------------------------------------


def test_crash_mid_stream_falls_back_to_via_ucp(tmp_path, monkeypatch):
    mesh = MeshSpec.from_dict({"data": 2, "model": 1})
    specs = {
        "w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec()]),
        "u": uniform_param_spec("u", (6, 4), [DimSpec(), DimSpec(("data",))]),
    }
    plan_src = ShardingPlan(mesh, dict(specs))
    snap = _random_state(specs, seed=11)
    mgr = CheckpointManager(tmp_path / "ck", plan_src, policy=CheckpointPolicy(
        async_save=False))
    write_distributed(snap, plan_src, 10, mgr.step_dir(10), engine=mgr.engine)
    tgt = {
        "w": uniform_param_spec("w", (8, 6), [DimSpec(), DimSpec(("data",))]),
        "u": uniform_param_spec("u", (6, 4), [DimSpec(("data",)), DimSpec()]),
    }
    plan_tgt = ShardingPlan(mesh, dict(tgt))
    jmesh = make_mesh((1, 1), ("data", "model"))

    import repro.ckpt.restore as R

    real = R.read_region_from_source
    calls = {"n": 0}

    def flaky(*a, **kw):
        calls["n"] += 1
        if calls["n"] >= 3:
            raise OSError("simulated I/O loss mid-stream")
        return real(*a, **kw)

    monkeypatch.setattr(R, "read_region_from_source", flaky)
    restored, info = mgr.restore(jmesh, target_plan=plan_tgt)
    assert calls["n"] >= 3, "stream path was never exercised"
    assert info.mode is ResumeMode.VIA_UCP
    assert "stream failed" in info.reason and "via_ucp" in info.reason
    monkeypatch.setattr(R, "read_region_from_source", real)
    want = mgr.restore(jmesh, target_plan=plan_tgt,
                       force_mode=ResumeMode.VIA_UCP)[0]
    _leaves_equal(restored, want)

    # forcing the stream disables the silent fallback: errors surface
    monkeypatch.setattr(R, "read_region_from_source", flaky)
    calls["n"] = 0
    mgr.engine.invalidate()
    with pytest.raises(OSError, match="mid-stream"):
        mgr.restore(jmesh, target_plan=plan_tgt,
                    force_mode=ResumeMode.RESHARD_STREAM)
