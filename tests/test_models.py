"""Model-layer correctness: attention variants, MoE, SSM, decode parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import MoEConfig, get_config, reduced
from repro.models import build_model
from repro.models import decode as D
from repro.models.attention import chunked_attention, decode_attention, full_attention
from repro.models.moe import capacity_per_group, moe_block
from repro.models.ssm import ssd_chunked, ssd_recurrent

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [0, 8, 32])
@pytest.mark.parametrize("groups", [1, 3])
def test_chunked_matches_full(window, groups):
    b, s, hkv, d = 2, 64, 2, 16
    h = hkv * groups
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    o1 = full_attention(q, k, v, causal=True, window=window)
    o2 = chunked_attention(q, k, v, causal=True, window=window, q_block=16, kv_block=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_gqa_equals_mha_when_kv_repeated():
    b, s, h, d = 1, 32, 4, 8
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    kv = jax.random.normal(ks[1], (b, s, 1, d))
    v = jax.random.normal(ks[2], (b, s, 1, d))
    o_gqa = full_attention(q, kv, v)
    o_mha = full_attention(q, jnp.repeat(kv, h, 2), jnp.repeat(v, h, 2))
    np.testing.assert_allclose(np.asarray(o_gqa), np.asarray(o_mha), atol=1e-6)


def test_sliding_window_masks_old_tokens():
    """With window=1 each position attends only to itself → output = v."""
    b, s, h, d = 1, 16, 2, 4
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    o = full_attention(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(v), atol=1e-5)


def test_decode_attention_matches_full_with_ring_buffer():
    """Ring-buffered decode == full attention at the same position."""
    b, s, h, d, win = 2, 24, 2, 8, 8
    ks = jax.random.split(KEY, 3)
    q_all = jax.random.normal(ks[0], (b, s, h, d))
    k_all = jax.random.normal(ks[1], (b, s, h, d))
    v_all = jax.random.normal(ks[2], (b, s, h, d))
    ref = full_attention(q_all, k_all, v_all, causal=True, window=win)

    cache_k = jnp.zeros((b, win, h, d))
    cache_v = jnp.zeros((b, win, h, d))
    slot_pos = jnp.full((b, win), -1, jnp.int32)
    for t in range(s):
        slot = t % win
        cache_k = cache_k.at[:, slot].set(k_all[:, t])
        cache_v = cache_v.at[:, slot].set(v_all[:, t])
        slot_pos = slot_pos.at[:, slot].set(t)
        o = decode_attention(
            q_all[:, t : t + 1], cache_k, cache_v,
            cache_positions=slot_pos, cur_pos=jnp.full((b,), t), window=win,
        )
        np.testing.assert_allclose(
            np.asarray(o[:, 0]), np.asarray(ref[:, t]), atol=2e-5,
            err_msg=f"t={t}",
        )


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------


def _moe_params(d, cfg, key):
    ks = jax.random.split(key, 4)
    e, f = cfg.num_experts, cfg.d_ff_expert
    return (
        jax.random.normal(ks[0], (d, e)) * 0.1,
        jax.random.normal(ks[1], (e, d, f)) * 0.1,
        jax.random.normal(ks[2], (e, d, f)) * 0.1,
        jax.random.normal(ks[3], (e, f, d)) * 0.1,
    )


def test_moe_big_capacity_matches_dense_topk():
    """With capacity ≥ tokens, routed output == explicit dense top-k mix."""
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8, capacity_factor=8.0)
    b, s, d = 2, 8, 6
    router, wg, wu, wd = _moe_params(d, cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d))
    out, aux = moe_block(x, router, wg, wu, wd, cfg, groups=b)

    # dense reference
    logits = jnp.einsum("bsd,de->bse", x, router)
    probs = jax.nn.softmax(logits, -1)
    gk, ik = jax.lax.top_k(probs, 2)
    gk = gk / gk.sum(-1, keepdims=True)
    ref = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, wg[e])) * jnp.einsum(
            "bsd,df->bsf", x, wu[e]
        )
        y = jnp.einsum("bsf,fd->bsd", h, wd[e])
        w = ((ik == e) * gk).sum(-1)
        ref = ref + y * w[..., None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens_not_correctness():
    cfg = MoEConfig(num_experts=2, top_k=1, d_ff_expert=4, capacity_factor=0.5)
    b, s, d = 1, 16, 4
    router, wg, wu, wd = _moe_params(d, cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, d))
    out, _ = moe_block(x, router, wg, wu, wd, cfg, groups=b)
    assert out.shape == x.shape
    assert not bool(jnp.isnan(out).any())
    # capacity formula
    assert capacity_per_group(16, cfg) == 4


def test_moe_group_invariance():
    """Same tokens, different group partitioning, big capacity → same out."""
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8, capacity_factor=16.0)
    b, s, d = 4, 4, 6
    router, wg, wu, wd = _moe_params(d, cfg, KEY)
    x = jax.random.normal(jax.random.PRNGKey(3), (b, s, d))
    o1, _ = moe_block(x, router, wg, wu, wd, cfg, groups=1)
    o2, _ = moe_block(x, router, wg, wu, wd, cfg, groups=4)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-4)


# ---------------------------------------------------------------------------
# SSM (property: chunked == recurrent for any chunking)
# ---------------------------------------------------------------------------


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(
    st.sampled_from([1, 2, 4, 8, 16, 32]),
    st.integers(1, 3),
    st.sampled_from([1, 2]),
)
def test_property_ssd_chunked_equals_recurrent(chunk, heads_per_group, g):
    b, s, p, n = 1, 32, 4, 8
    h = heads_per_group * g
    ks = jax.random.split(jax.random.PRNGKey(chunk * 7 + h), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    y1, h1 = ssd_recurrent(x, dt, a, bm, cm)
    y2, h2 = ssd_chunked(x, dt, a, bm, cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=5e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=5e-4)


def test_ssd_chunked_with_intra_kernel_equals_recurrent(monkeypatch):
    """The kernel path of ``ssd_chunked`` (as on a TPU, the kernel run in
    interpret mode) against the recurrence: two chunks of 128, one group
    of 128 state over two heads of 64.

    Tolerance: both sides float32 over 256 tokens; they differ by
    summation order alone, 1e-5 of the largest entry."""
    import functools

    from repro.kernels.ssd_scan.ops import ssd_intra
    from repro.models import ssm

    monkeypatch.setattr(ssm, "_one_tpu", lambda: True)
    monkeypatch.setattr(ssm, "ssd_intra", functools.partial(ssd_intra, interpret=True))
    b, s, h, p, g, n = 1, 256, 2, 64, 1, 128
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = 0.1 * jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    y1, h1 = ssd_recurrent(x, dt, a, bm, cm)
    y2, h2 = ssd_chunked(x, dt, a, bm, cm, chunk=128)
    for got, want in ((y2, y1), (h2, h1)):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())


def _published_ssd_inputs(b, s, p, n, key):
    """Four heads at the corners of Mamba-2's published init: A in [1, 16],
    dt = softplus(proj + dt_bias) with dt_bias placing dt in [1e-3, 1e-1],
    and a small per-token term, as the projection adds."""
    ks = jax.random.split(key, 4)
    a = -jnp.array([1.0, 4.0, 16.0, 16.0])
    dt_head = jnp.array([1e-3, 1e-2, 1e-1, 5e-2])
    dt = dt_head * jnp.exp(0.1 * jax.random.normal(ks[0], (b, s, 4)))
    x = jax.random.normal(ks[1], (b, s, 4, p))
    bm = jax.random.normal(ks[2], (b, s, 1, n))
    cm = jax.random.normal(ks[3], (b, s, 1, n))
    return x, dt, a, bm, cm


def test_ssd_chunked_gradient_finite_at_published_chunk():
    """At chunk 256 a head with dt·A = -1.6 sums to ≈ 408 above the
    diagonal; the exponential of that is inf in float32, so the mask must
    come before it.  The gradient is then finite and the recurrence's.

    Tolerance: both sides are float32 over 512 tokens; they differ by
    summation order alone, 1e-4 of the largest gradient entry."""
    x, dt, a, bm, cm = _published_ssd_inputs(1, 512, 8, 16, jax.random.PRNGKey(11))

    def scalar(f):
        return lambda *t: jnp.sum(jnp.sin(f(*t)[0]))

    args = (x, dt, a, bm, cm)
    g_chunk = jax.grad(scalar(lambda *t: ssd_chunked(*t, chunk=256)), argnums=range(5))(*args)
    g_rec = jax.grad(scalar(ssd_recurrent), argnums=range(5))(*args)
    for name, got, want in zip(("x", "dt", "a", "B", "C"), g_chunk, g_rec):
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all(), name
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-4 * np.abs(want).max(),
                                   err_msg=name)


@pytest.fixture(scope="module")
def mamba_trainer():
    """A reduced mamba2-130m at the published chunk (256) over 512-token
    rows: two chunks a row, so the boundary scan runs too."""
    import dataclasses as _dc

    from repro.configs import ParallelismConfig, TrainConfig
    from repro.launch.mesh import make_mesh
    from repro.train.trainer import Trainer

    cfg = reduced(get_config("mamba2-130m"))
    cfg = _dc.replace(cfg, ssm=_dc.replace(cfg.ssm, chunk=256))
    t = Trainer.create(cfg, ParallelismConfig(), TrainConfig(), make_mesh((1, 1), ("data", "model")),
                       batch_size=2, seq_len=512)
    return t, t.init_state()


def test_mamba_trainer_step_finite_at_published_chunk(mamba_trainer):
    t, state = mamba_trainer
    assert t.cfg.ssm.chunk == 256 and t.seq_len == 512
    state, hist = t.run(jax.tree.map(jnp.copy, state), 0, 1)
    assert np.isfinite(hist[0]["loss"]) and np.isfinite(hist[0]["grad_norm"])
    for leaf in jax.tree.leaves((state.params, state.exp_avg, state.exp_avg_sq)):
        assert np.isfinite(np.asarray(leaf)).all()


def test_mamba_step_hlo_carries_the_mixer_scopes(mamba_trainer):
    """The device trace can name the mixer's parts only if the compiled
    step's op metadata carries their scopes, in the forward pass and in
    the backward pass of the rematerialised layers."""
    import re

    t, state = mamba_trainer
    with t.jmesh:
        hlo = t.step_fn.lower(state, t.batch(0)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("mamba.in_proj", "mamba.conv", "ssd.intra", "ssd.states", "ssd.scan",
                  "ssd.inter", "mamba.gate_norm", "mamba.out_proj"):
        under = [n for n in names if f"/{scope}/" in n]
        assert any(n.startswith("jit(train_step)/jvp(") for n in under), scope
        assert any(n.startswith("jit(train_step)/transpose(") for n in under), scope


# ---------------------------------------------------------------------------
# prefill/decode parity (end-to-end, per family)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "arch", ["smollm-360m", "mamba2-130m", "gemma3-27b", "mixtral-8x22b",
             "deepseek-v2-236b", "whisper-tiny", "llama-3.2-vision-11b"]
)
def test_prefill_then_decode_matches_forward(arch):
    """prefill(t[:n]) + decode steps == forward(t) logits, per family.

    MoE capacity is raised so no tokens drop: capacity dropping is a
    train-time approximation that legitimately differs between a 12-token
    prefill group and a 1-token decode group."""
    import dataclasses as _dc

    cfg = reduced(get_config(arch))
    if cfg.moe is not None:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, capacity_factor=16.0))
    lm = build_model(cfg, attn_impl="full", remat="none", compute_dtype=jnp.float32)
    params = lm.init(KEY)
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(9), (b, s), 0, cfg.vocab_size)
    extra = {}
    if cfg.cross_attn:
        extra["source_embeds"] = jax.random.normal(
            jax.random.PRNGKey(10), (b, cfg.cross_attn.source_len, cfg.cross_attn.source_dim)
        )
    if cfg.encoder:
        extra["source_embeds"] = jax.random.normal(
            jax.random.PRNGKey(10), (b, cfg.encoder.source_len, cfg.d_model)
        )
    logits_full, _ = lm.forward(params, toks, source_embeds=extra.get("source_embeds"))
    logits_full = logits_full[..., : cfg.vocab_size]

    n = 8
    cache = D.init_cache(lm, b, s + 4)
    lp, cache = D.prefill(lm, params, cache, toks[:, :n], **extra)
    np.testing.assert_allclose(
        np.asarray(lp), np.asarray(logits_full[:, n - 1]), atol=0.05, rtol=0.05
    )
    for t in range(n, s):
        ld, cache = D.decode_step(lm, params, cache, toks[:, t : t + 1])
        np.testing.assert_allclose(
            np.asarray(ld[:, 0]), np.asarray(logits_full[:, t]),
            atol=0.05, rtol=0.05, err_msg=f"{arch} step {t}",
        )
