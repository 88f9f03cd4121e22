"""Quantized shard codec (repro.core.codec + repro.kernels.block_quant).

Covers the DESIGN.md §10 contract:

* the shared block-quant core: error bounds, the explicit-count padding
  contract, zero blocks, and the Pallas kernels bit-identical to the
  jitted reference (the property that lets encode/decode trust either);
* the ``RQS1`` payload: encode→decode round-trips, header cross-checks
  against the manifest (mismatch is an ``IntegrityError``, never a silent
  misread), ``int8ef`` bit-exactness *by construction* (verify-or-fallback),
  and re-encode drift of the lossy families bounded by one quantization
  step;
* the two digest tables: served digests keep validate/peer verification
  working on coded checkpoints, pre-encode digests keep the delta diff
  working (a coded save still inherits unchanged shards);
* every consumer above the single decode point serves coded checkpoints
  unchanged: DIRECT restore, streaming reshard, the delta chain, the hot
  drain's promoted steps, and the peer fan-out;
* a resume from block-quantized optimizer moments tracks the uninterrupted
  loss curve within the paper's reconfiguration tolerance (0.02).
"""

import jax
import numpy as np
import pytest

from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
from repro.core import (
    DimSpec,
    DistCheckpoint,
    IntegrityError,
    MeshSpec,
    STATE_KINDS,
    StateKind,
    uniform_param_spec,
)
from repro.core.codec import (
    CODEC_RAW,
    CodecPolicy,
    _dequantize_np,
    decode_payload,
    encode_shard,
    parse_codec,
)
from repro.core.dist_ckpt import DistManifest, shard_digest_key
from repro.ckpt.manager import CheckpointManager
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.restore import params_from_source, state_from_dist
from repro.ckpt.saver import write_distributed
from repro.dist.sharding import ShardingPlan, make_plan, vocab_multiple
from repro.kernels.block_quant import (
    FMAX,
    block_dequantize,
    block_quantize,
    blocked,
    dequantize_blocks,
    quantize_blocks,
)
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.serve import PeerFragmentSource, PublicationRegistry
from repro.train.optimizer import TrainState, init_state
from repro.train.trainer import Trainer

MESH_2X2 = MeshSpec.from_dict({"data": 2, "model": 2})
MESH_1X1 = MeshSpec.from_dict({"data": 1, "model": 1})

QDTYPES = ["int8", "float8_e4m3fn", "float8_e5m2"]


def _rand(n, seed=0, scale=3.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=n) * scale).astype(np.float32)


# ---------------------------------------------------------------------------
# Block-quant core: reference semantics
# ---------------------------------------------------------------------------


def test_blocked_pads_to_block_multiple():
    x = np.arange(10, dtype=np.float32)
    b = np.asarray(blocked(x, block=4))
    assert b.shape == (3, 4)
    np.testing.assert_array_equal(b.reshape(-1)[:10], x)
    np.testing.assert_array_equal(b.reshape(-1)[10:], 0.0)


def test_explicit_count_contract():
    # padding never leaks: dequantize returns exactly `count` elements
    x = _rand(1000)
    q, s = block_quantize(x, block=256)
    out = np.asarray(block_dequantize(q, s, count=1000))
    assert out.shape == (1000,)


def test_int8_error_bounded_by_half_step():
    x = _rand(4096, seed=1)
    q, s = block_quantize(x, block=128)
    out = np.asarray(block_dequantize(q, s, count=x.size))
    step = np.repeat(np.asarray(s), 128)[: x.size]  # per-element block scale
    assert np.all(np.abs(out - x) <= 0.51 * step + 1e-7)


@pytest.mark.parametrize("qdtype,rel", [("float8_e4m3fn", 0.08), ("float8_e5m2", 0.2)])
def test_fp8_relative_error_sane(qdtype, rel):
    x = _rand(4096, seed=2)
    q, s = block_quantize(x, block=128, dtype=qdtype)
    out = np.asarray(block_dequantize(q, s, count=x.size))
    assert np.linalg.norm(out - x) / np.linalg.norm(x) < rel


def test_zero_blocks_are_lossless_and_safe():
    x = np.zeros(300, dtype=np.float32)
    q, s = block_quantize(x, block=128)
    out = np.asarray(block_dequantize(q, s, count=300))
    np.testing.assert_array_equal(out, x)
    assert np.all(np.asarray(s) == 0.0)


def test_large_values_clip_not_nan():
    # fp8 cast has no saturation; the core must clip before casting
    x = np.float32([1e30, -1e30, 0.5, 0.0])
    for qd in QDTYPES:
        q, s = block_quantize(x, block=4, dtype=qd)
        out = np.asarray(block_dequantize(q, s, count=4))
        assert np.all(np.isfinite(out)), qd


@pytest.mark.parametrize("qdtype", QDTYPES)
@pytest.mark.parametrize("n", [1, 7, 256, 1000])
def test_pallas_kernel_bit_identical_to_reference(qdtype, n):
    """The property the codec relies on: either implementation may encode."""
    x = _rand(n, seed=n)
    q_ref, s_ref = block_quantize(x, block=128, dtype=qdtype)
    q_k, s_k = block_quantize(
        x, block=128, dtype=qdtype, use_kernel=True, interpret=True
    )
    assert np.asarray(q_ref).view(np.uint8).tobytes() == \
        np.asarray(q_k).view(np.uint8).tobytes()
    assert np.asarray(s_ref).tobytes() == np.asarray(s_k).tobytes()
    d_ref = np.asarray(block_dequantize(q_ref, s_ref, count=n))
    d_k = np.asarray(
        block_dequantize(q_ref, s_ref, count=n, use_kernel=True, interpret=True)
    )
    assert d_ref.tobytes() == d_k.tobytes()


@pytest.mark.parametrize("qdtype", QDTYPES)
def test_numpy_decode_pinned_to_jax_dequantize(qdtype):
    """The codec's pure-numpy decode mirror must match the jax core bit for
    bit — the manifest digest of a served shard depends on it."""
    x = _rand(777, seed=3)
    q, s = block_quantize(x, block=64, dtype=qdtype)
    ref = np.asarray(block_dequantize(q, s, count=777))
    mine = _dequantize_np(np.asarray(q), np.asarray(s), 777)
    assert ref.tobytes() == mine.tobytes()


# ---------------------------------------------------------------------------
# Tags, specs, policy
# ---------------------------------------------------------------------------


def test_parse_codec_roundtrip():
    for tag in ["raw", "int8:b256", "int8ef:b64", "fp8:e4m3:b128", "fp8:e5m2:b32"]:
        assert parse_codec(tag).tag == tag
    assert parse_codec("int8ef:b64").lossless
    assert not parse_codec("int8:b64").lossless


@pytest.mark.parametrize("junk", ["int8", "int8:b0", "int8:bx", "fp8:b64", "zstd"])
def test_parse_codec_rejects_junk(junk):
    with pytest.raises(ValueError):
        parse_codec(junk)


def test_codec_policy_guard_and_tag_for():
    with pytest.raises(ValueError):
        CodecPolicy(params="int8:b256")  # lossy params need the opt-in
    p = CodecPolicy.moments("fp8:e4m3:b128")
    assert p.tag_for(StateKind.FP32) == "raw"
    assert p.tag_for(StateKind.EXP_AVG) == "fp8:e4m3:b128"
    assert p.tag_for(StateKind.EXP_AVG_SQ) == "fp8:e4m3:b128"
    assert CodecPolicy().is_raw and not p.is_raw
    assert CodecPolicy(params="int8ef:b256").tag_for(StateKind.FP32) == "int8ef:b256"


# ---------------------------------------------------------------------------
# RQS1 payload: encode / decode
# ---------------------------------------------------------------------------


def test_raw_tag_is_a_passthrough():
    x = _rand(32)
    es = encode_shard(x, CODEC_RAW)
    assert es.tag == CODEC_RAW and es.payload is None and es.decoded is x


@pytest.mark.parametrize("tag", ["int8:b64", "fp8:e4m3:b64", "fp8:e5m2:b64"])
@pytest.mark.parametrize("shape", [(5,), (33, 7), (4, 3, 5)])
def test_lossy_payload_roundtrip(tag, shape):
    x = _rand(int(np.prod(shape)), seed=5).reshape(shape)
    es = encode_shard(x, tag)
    assert es.tag == tag
    out = decode_payload(es.payload, expect_tag=tag, expect_dtype="float32")
    # what a reader serves is exactly what the encoder reported serving
    assert out.tobytes() == es.decoded.tobytes()
    assert out.shape == shape and out.dtype == np.float32


def test_int8ef_bit_exact_fp32():
    for shape in [(1,), (257,), (33, 9)]:
        x = _rand(int(np.prod(shape)), seed=7).reshape(shape)
        es = encode_shard(x, "int8ef:b64")
        assert es.tag == "int8ef:b64"  # fp32 inputs must not need the fallback
        assert es.decoded.tobytes() == x.tobytes()
        out = decode_payload(es.payload, expect_tag="int8ef:b64")
        assert out.tobytes() == x.tobytes()


def test_int8ef_exact_or_fallback_other_dtypes():
    # the invariant is bit-exact OR raw — never silently lossy
    import ml_dtypes

    for dt in [np.float16, ml_dtypes.bfloat16]:
        x = _rand(300, seed=8).astype(dt)
        es = encode_shard(x, "int8ef:b64")
        if es.tag == "int8ef:b64":
            assert es.decoded.tobytes() == x.tobytes()
        else:
            assert es.tag == CODEC_RAW and es.payload is None


def test_int8ef_idempotent_and_lossy_drift_bounded():
    x = _rand(2048, seed=9)
    ef = encode_shard(x, "int8ef:b128")
    assert encode_shard(ef.decoded, "int8ef:b128").decoded.tobytes() == x.tobytes()
    # lossy: re-encoding the decoded view drifts at most one quantization
    # step (fp32 scale arithmetic is not exactly idempotent)
    es = encode_shard(x, "int8:b128")
    es2 = encode_shard(es.decoded, "int8:b128")
    step = np.abs(x).max() / FMAX["int8"]
    assert np.abs(es2.decoded - es.decoded).max() <= step + 1e-7


def test_decode_crosschecks_raise():
    x = _rand(128)
    es = encode_shard(x, "int8:b64")
    with pytest.raises(IntegrityError, match="manifest recorded"):
        decode_payload(es.payload, expect_tag="int8:b32")
    with pytest.raises(IntegrityError, match="dtype"):
        decode_payload(es.payload, expect_dtype="float16")
    with pytest.raises(IntegrityError, match="magic"):
        decode_payload(np.zeros(64, dtype=np.uint8), expect_tag="int8:b64")


def test_compression_ratio():
    x = _rand(1 << 16, seed=10)
    es = encode_shard(x, "int8:b256")
    assert es.payload.nbytes < 0.30 * x.nbytes  # ~1B/elt + scales + header


# ---------------------------------------------------------------------------
# Manifest: the two digest tables
# ---------------------------------------------------------------------------


def test_manifest_codec_tables_sparse_json_roundtrip(tmp_path):
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    snap = _random_state(specs)
    write_distributed(snap, plan, 1, tmp_path / "raw_save")
    raw_man = DistCheckpoint.open(tmp_path / "raw_save").manifest
    # all-raw manifests carry neither table (byte-compatible with pre-codec)
    j = raw_man.to_json()
    assert "shard_codecs" not in j and "shard_pre_digests" not in j
    assert raw_man.codec_tag("rank_00000/w@fp32") == "raw"
    assert raw_man.pre_encode_digests() == raw_man.shard_digests

    write_distributed(
        snap, plan, 1, tmp_path / "coded_save",
        codec=CodecPolicy.moments("int8:b64"),
    )
    man = DistCheckpoint.open(tmp_path / "coded_save").manifest
    j2 = man.to_json()
    assert j2["shard_codecs"] and j2["shard_pre_digests"]
    man2 = DistManifest.from_json(j2)
    assert man2.shard_codecs == man.shard_codecs
    assert man2.shard_pre_digests == man.shard_pre_digests
    # pre-encode view overlays only where encode was lossy
    pre = man2.pre_encode_digests()
    for key, d in man2.shard_pre_digests.items():
        assert pre[key] == d and man2.shard_digests[key] != d


# ---------------------------------------------------------------------------
# Save/restore integration (synthetic plans)
# ---------------------------------------------------------------------------


def _specs():
    return {
        "w": uniform_param_spec("w", (8, 6), [DimSpec(("data",)), DimSpec(("model",))]),
        "u": uniform_param_spec("u", (6, 4), [DimSpec(("model",)), DimSpec()]),
        "b": uniform_param_spec("b", (4,), [DimSpec()]),
    }


def _random_state(specs, seed=0):
    rng = np.random.default_rng(seed)
    return {
        n: {k: rng.normal(size=s.runtime_shape).astype(np.float32) for k in STATE_KINDS}
        for n, s in specs.items()
    }


def test_coded_full_save_direct_restore(tmp_path):
    """1x1 source (shard == param): moments decode to exactly the values the
    encoder reported, params stay bit-identical, validate() passes."""
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    snap = _random_state(specs, seed=11)
    tag = "int8:b64"
    write_distributed(
        snap, plan, 1, tmp_path / "step_1", codec=CodecPolicy.moments(tag)
    )
    ckpt = DistCheckpoint.open(tmp_path / "step_1")
    # only moment shards are tagged
    for key, t in ckpt.manifest.shard_codecs.items():
        assert t == tag and "@fp32" not in key
    assert ckpt.validate() == []  # served digests verify coded shards
    jmesh = make_mesh((1, 1), ("data", "model"))
    st = state_from_dist(ckpt, plan, jmesh)
    for n in specs:
        np.testing.assert_array_equal(
            np.asarray(st.params[n]), snap[n][StateKind.FP32]
        )
        expect = encode_shard(snap[n][StateKind.EXP_AVG], tag).decoded
        assert np.asarray(st.exp_avg[n]).tobytes() == expect.tobytes()
    # read_shard is the decode point: it returns the served array directly
    served = ckpt.read_shard(0, "w", StateKind.EXP_AVG)
    assert served.tobytes() == encode_shard(
        snap["w"][StateKind.EXP_AVG], tag
    ).decoded.tobytes()
    # on-disk shrink needs shards big enough to amortize the container
    # header (the tiny fixture shards are header-dominated): one big param
    big = {"m": uniform_param_spec("m", (256, 64), [DimSpec(), DimSpec()])}
    bplan = ShardingPlan(mesh=MESH_1X1, param_specs=big)
    bsnap = _random_state(big, seed=99)
    write_distributed(
        bsnap, bplan, 1, tmp_path / "big", codec=CodecPolicy.moments(tag)
    )
    bck = DistCheckpoint.open(tmp_path / "big")
    coded = bck.shard_path(0, "m", StateKind.EXP_AVG).stat().st_size
    assert coded < 0.35 * bsnap["m"][StateKind.EXP_AVG].nbytes


def test_int8ef_params_bit_identical(tmp_path):
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    snap = _random_state(specs, seed=12)
    write_distributed(
        snap, plan, 1, tmp_path / "step_1",
        codec=CodecPolicy(params="int8ef:b64", exp_avg="int8:b64",
                          exp_avg_sq="int8:b64"),
    )
    ckpt = DistCheckpoint.open(tmp_path / "step_1")
    assert ckpt.validate() == []
    jmesh = make_mesh((1, 1), ("data", "model"))
    st = state_from_dist(ckpt, plan, jmesh)
    for n in specs:
        np.testing.assert_array_equal(
            np.asarray(st.params[n]), snap[n][StateKind.FP32]
        )
    # error-feedback params are lossless, so no pre-digest overlay for them
    for key in ckpt.manifest.shard_pre_digests:
        assert "@fp32" not in key


def test_coded_reshard_and_peer_fanout(tmp_path):
    """A 2x2-sharded coded checkpoint consolidates to a 1x1 target through
    the stream path and serves the peer fan-out unchanged."""
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_2X2, param_specs=specs)
    snap = _random_state(specs, seed=13)
    write_distributed(
        snap, plan, 1, tmp_path / "step_1", codec=CodecPolicy.moments("int8:b32")
    )
    ckpt = DistCheckpoint.open(tmp_path / "step_1")
    tgt_plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    jmesh = make_mesh((1, 1), ("data", "model"))
    st = state_from_dist(ckpt, tgt_plan, jmesh)
    for n in specs:
        np.testing.assert_array_equal(
            np.asarray(st.params[n]), snap[n][StateKind.FP32]
        )
        # consolidated moments: within one quantization step of the raw ones
        raw = snap[n][StateKind.EXP_AVG]
        atol = np.abs(raw).max() / 120  # >= blockmax/127 half-step + fuzz
        np.testing.assert_allclose(np.asarray(st.exp_avg[n]), raw, atol=atol)
    # peer fan-out: publication digests are served digests → verification
    # passes on coded shards without the source knowing about codecs
    registry = PublicationRegistry()
    pub = registry.publish(ckpt)
    src = PeerFragmentSource(registry, pub, "reader")
    params = params_from_source(src, tgt_plan, jmesh)
    for n in specs:
        np.testing.assert_array_equal(
            np.asarray(params[n]), snap[n][StateKind.FP32]
        )


def test_coded_delta_chain_inherits_and_diffs_on_pre_digests(tmp_path):
    """The diff keys on pre-encode digests: unchanged raw content inherits
    the base's *coded* shard; changed content re-encodes.  The chain then
    restores identically to a coded full save of the same state."""
    specs = _specs()
    plan = ShardingPlan(mesh=MESH_1X1, param_specs=specs)
    snap = _random_state(specs, seed=14)
    codec = CodecPolicy.moments("int8:b64")
    write_distributed(snap, plan, 1, tmp_path / "step_1", codec=codec)
    base = DistCheckpoint.open(tmp_path / "step_1")
    snap2 = {n: {k: v.copy() for k, v in kv.items()} for n, kv in snap.items()}
    snap2["w"][StateKind.EXP_AVG] += 1.0  # one lossy-coded shard changes
    snap2["u"][StateKind.FP32] += 1.0     # one raw shard changes
    write_distributed(
        snap2, plan, 2, tmp_path / "step_2",
        save_mode="delta", base=base, codec=codec,
    )
    ck2 = DistCheckpoint.open(tmp_path / "step_2")
    m = ck2.manifest
    assert m.base_step == 1
    key_w_ea = shard_digest_key(0, "w", StateKind.EXP_AVG)
    key_u_p = shard_digest_key(0, "u", StateKind.FP32)
    # changed shards written fresh, everything else inherited from step 1
    assert key_w_ea not in m.shard_sources
    assert key_u_p not in m.shard_sources
    inherited = set(m.shard_sources)
    assert inherited, "codec must not defeat the delta diff"
    # inherited coded shards keep their base codec tag and both digests
    for key in inherited:
        assert m.codec_tag(key) == base.manifest.codec_tag(key)
        assert m.shard_digests[key] == base.manifest.shard_digests[key]
    assert ck2.validate() == []
    # chain restore == coded full save of the same final state
    write_distributed(snap2, plan, 2, tmp_path / "full_2", codec=codec)
    full = DistCheckpoint.open(tmp_path / "full_2")
    jmesh = make_mesh((1, 1), ("data", "model"))
    st_chain = state_from_dist(ck2, plan, jmesh)
    st_full = state_from_dist(full, plan, jmesh)
    for a, b in zip(jax.tree.leaves(st_chain.exp_avg), jax.tree.leaves(st_full.exp_avg)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    for a, b in zip(jax.tree.leaves(st_chain.params), jax.tree.leaves(st_full.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# The full ladder through the manager (model-based)
# ---------------------------------------------------------------------------


@pytest.fixture()
def model_setup(tmp_path):
    cfg = reduced(get_config("smollm-360m"))
    mesh = MeshSpec.from_dict({"data": 2, "model": 2})
    parallel = ParallelismConfig()
    lm = build_model(cfg, vocab_multiple=vocab_multiple(parallel, mesh))
    plan = make_plan(cfg, lm.registry, parallel, mesh)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    # init moments are zeros (which quantize losslessly); randomize them so
    # the lossy path and the pre-digest table are actually exercised
    rng = np.random.default_rng(42)
    rand = lambda t: jax.tree.map(
        lambda x: rng.normal(size=np.shape(x)).astype(np.float32) * 0.1, t
    )
    state = TrainState(state.params, rand(state.exp_avg),
                       rand(state.exp_avg_sq), state.step)
    jmesh = make_mesh((1, 1), ("data", "model"))
    return tmp_path, cfg, plan, state, jmesh


def _bump_params(state, idx):
    from repro.core.pytree import flatten_with_paths, unflatten_from_paths

    flat = flatten_with_paths(jax.device_get(state.params))
    name = sorted(flat)[idx % len(flat)]
    flat[name] = np.asarray(flat[name]) + np.float32(1.0 + idx)
    return TrainState(
        unflatten_from_paths(flat), state.exp_avg, state.exp_avg_sq, state.step
    )


def test_hot_ladder_promotes_coded_deltas(model_setup):
    """Hot tier stays raw in memory; the background promotion encodes under
    the manager's policy, the chain inherits, and every restore tier decodes."""
    tmp, cfg, plan, state, jmesh = model_setup
    pol = CheckpointPolicy(
        save_mode="delta", full_interval=100, keep_last=100,
        hot_interval=1, disk_interval=1, hot_max_snapshots=2,
        async_save=False, codec="int8:b256",
    )
    mgr = CheckpointManager(tmp / "ck", plan, policy=pol)
    s = state
    states = {}
    for i, step in enumerate((1, 2, 3)):
        s = _bump_params(s, i)
        states[step] = s
        mgr.save(s, step, block=True)
    mgr.wait()
    assert mgr.steps() == [1, 2, 3]
    ck1 = DistCheckpoint.open(mgr.step_dir(1))
    ck3 = DistCheckpoint.open(mgr.step_dir(3))
    assert ck1.manifest.shard_codecs and ck1.manifest.shard_pre_digests
    assert ck3.manifest.base_step == 2
    assert ck3.manifest.shard_sources  # moments unchanged → inherited coded
    assert ck3.validate() == []
    # params restore bit-identical through DIRECT from the coded chain
    restored, info = mgr.restore(jmesh, step=3)
    for a, b in zip(jax.tree.leaves(restored.params),
                    jax.tree.leaves(states[3].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and through the streaming reshard tier
    p2 = ParallelismConfig(zero=1, fsdp=False)
    mesh2 = MeshSpec.from_dict({"data": 1, "model": 1})
    lm2 = build_model(cfg, vocab_multiple=vocab_multiple(p2, mesh2))
    plan2 = make_plan(cfg, lm2.registry, p2, mesh2)
    r2, info2 = mgr.restore(jmesh, step=3, target_plan=plan2, verify=True)
    for a, b in zip(jax.tree.leaves(r2.params),
                    jax.tree.leaves(states[3].params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mgr.close()


# ---------------------------------------------------------------------------
# Loss-curve equivalence of a resume from coded moments
# ---------------------------------------------------------------------------

EQUIV_TOL = 0.02  # max |dloss|, the paper's reconfiguration tolerance


def _equiv_trainer(ckpt_dir, *, save_interval=8, codec=None):
    return Trainer.create(
        reduced(get_config("smollm-360m")), ParallelismConfig(),
        TrainConfig(warmup_steps=2, total_steps=100),
        make_mesh((1, 1), ("data", "model")), batch_size=4, seq_len=24,
        ckpt_dir=str(ckpt_dir),
        policy=CheckpointPolicy(
            save_interval=save_interval, async_save=False, codec=codec,
        ),
    )


@pytest.fixture(scope="module")
def baseline_losses(tmp_path_factory):
    """The uninterrupted 16-step run: step -> loss."""
    t = _equiv_trainer(tmp_path_factory.mktemp("base"))
    state, _ = t.init_or_restore()
    _, hist = t.run(state, 0, 16)
    t.manager.close()
    return {h["step"]: h["loss"] for h in hist}


@pytest.mark.parametrize(
    "codec",
    [None, "int8:b256", "fp8:e4m3:b256"],
    ids=["lossless", "int8_moments", "fp8_moments"],
)
def test_coded_moments_resume_tracks_baseline(baseline_losses, tmp_path, codec):
    """Train 8 steps and save with the moments coded, resume in a fresh
    trainer and train 8 more: every resumed loss stays within
    ``EQUIV_TOL`` of the uninterrupted run's."""
    t1 = _equiv_trainer(tmp_path, codec=codec)
    s1, _ = t1.init_or_restore()
    t1.run(s1, 0, 8)
    t1.manager.close()
    # the resumed run must not save, or it would resume from its own step
    t2 = _equiv_trainer(tmp_path, save_interval=10**6, codec=codec)
    s2, info = t2.init_or_restore()
    assert info is not None and info.step == 8
    _, hist = t2.run(s2, 8, 8)
    t2.manager.close()
    delta = max(abs(h["loss"] - baseline_losses[h["step"]]) for h in hist)
    assert delta <= EQUIV_TOL, f"codec {codec}: resumed loss off by {delta:.4f}"
