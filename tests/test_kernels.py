"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Kernels execute under ``interpret=True`` (CPU container); the same calls
compile to Mosaic on a TPU runtime."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ops import ssd_intra
from repro.models import ssm

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,window,causal",
    [
        (1, 64, 2, 2, 16, 0, True),
        (2, 128, 4, 2, 32, 0, True),     # GQA 2:1
        (1, 128, 6, 3, 16, 32, True),    # GQA + sliding window
        (2, 64, 2, 1, 64, 16, True),     # MQA + window
        (1, 64, 2, 2, 32, 0, False),     # bidirectional (encoder/cross)
        (1, 256, 8, 8, 8, 128, True),
    ],
)
def test_flash_attention_sweep(b, s, hq, hkv, d, window, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d)).astype(dtype)
    out = flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=32, block_k=32, interpret=True,
    )
    ref = attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal, window=window,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_flash_attention_block_shape_invariance():
    b, s, h, d = 1, 128, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    outs = [
        np.asarray(
            flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        )
        for bq, bk in [(16, 16), (32, 64), (128, 128), (64, 32)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=2e-5)


def test_flash_attention_matches_model_reference():
    """Kernel == the model-layer chunked path (same math, different impl)."""
    from repro.models.attention import chunked_attention

    b, s, h, d = 2, 128, 4, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, d))
    o1 = flash_attention(q, k, v, window=32, block_q=32, block_k=32, interpret=True)
    o2 = chunked_attention(q, k, v, causal=True, window=32, q_block=32, kv_block=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def _intra_inputs(b, nc, q, h, g, n, p, dtype, large_da, key):
    """cum, C, B, dt·x in the layouts ``ssd_chunked`` hands the kernel.
    ``large_da`` takes dt·A from the corners of Mamba-2's published init
    (A up to 16, dt up to 0.1): over a chunk of 256 the decay exponent
    then spans ≈400, whose exponential overflows float32 above the
    diagonal unless the mask goes in first."""
    ks = jax.random.split(key, 6)
    if large_da:
        a = -jnp.linspace(1.0, 16.0, h)
        dt = jnp.geomspace(1e-3, 1e-1, h) * jnp.exp(0.1 * jax.random.normal(ks[0], (b, nc, q, h)))
    else:
        a = -jnp.exp(jax.random.normal(ks[0], (h,)))
        dt = 0.05 * jax.nn.softplus(jax.random.normal(ks[1], (b, nc, q, h)))
    cum = jnp.cumsum(dt * a, axis=2)
    c = jax.random.normal(ks[2], (b, nc, q, g, n)).astype(dtype)
    bm = jax.random.normal(ks[3], (b, nc, q, g, n)).astype(dtype)
    x = jax.random.normal(ks[4], (b, nc, q, h, p)).astype(dtype)
    xdt = x.astype(jnp.float32) * dt[..., None]
    w = jax.random.normal(ks[5], xdt.shape)
    return (cum, c, bm, xdt), w


def _rel(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize(
    "b,nc,q,h,g,n,p,dtype,large_da",
    [
        (1, 2, 128, 4, 1, 128, 64, jnp.float32, False),   # G = 1, two heads a lane window
        (1, 2, 128, 4, 1, 128, 64, jnp.bfloat16, False),
        (1, 1, 128, 4, 2, 128, 64, jnp.float32, False),   # G = 2
        (1, 1, 128, 4, 2, 128, 64, jnp.bfloat16, False),
        (2, 1, 256, 2, 1, 128, 64, jnp.float32, False),   # the published chunk
        (1, 1, 256, 4, 2, 128, 128, jnp.bfloat16, False),  # one head a lane window
        (1, 1, 128, 8, 2, 32, 32, jnp.float32, False),    # four heads a lane window
        (1, 2, 256, 4, 1, 64, 64, jnp.float32, True),     # |dt·A| of the published init
        (1, 1, 256, 4, 2, 128, 64, jnp.bfloat16, True),
        (1, 1, 256, 24, 1, 128, 64, jnp.bfloat16, False),  # mamba2-130m's widths
    ],
)
def test_ssd_intra_kernel_matches_jnp_term(b, nc, q, h, g, n, p, dtype, large_da):
    """The kernel pair against ``ssd_chunked``'s jnp intra term: y and the
    gradient of every input through the custom VJP.

    Tolerance, as a share of the largest entry: float32 runs the same
    arithmetic in another order (1e-5).  With bfloat16 C and B the kernel
    feeds the MXU bfloat16 operands, as a TPU's DEFAULT precision does for
    the jnp term's float32 einsums, while the jnp term on the CPU keeps
    float32 (2e-2)."""
    args, w = _intra_inputs(b, nc, q, h, g, n, p, dtype, large_da, jax.random.PRNGKey(q + h + n))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5

    def loss(f):
        return lambda *t: jnp.sum(f(*t) * w)

    kernel = lambda *t: ssd_intra(*t, interpret=True)
    assert _rel(kernel(*args), ssm._intra_jnp(*args)) < tol
    got = jax.grad(loss(kernel), argnums=range(4))(*args)
    want = jax.grad(loss(ssm._intra_jnp), argnums=range(4))(*args)
    for name, u, v, arg in zip(("cum", "C", "B", "dt·x"), got, want, args):
        assert u.dtype == arg.dtype, name
        assert _rel(u, v) < tol, name


LEGAL = dict(chunk=256, h=24, g=1, p=64, n=128)


@pytest.mark.parametrize(
    "one_tpu,shape,path",
    [
        (True, {}, "kernel"),
        (True, dict(chunk=64), "fallback"),           # chunk not a multiple of 128
        (True, dict(p=48), "fallback"),               # a head straddles lane windows
        (True, dict(g=2, n=64), "fallback"),          # C and B not split on 128 lanes
        (True, dict(h=6, g=2, p=32), "fallback"),     # no legal head block
        (False, {}, "fallback"),                      # not one TPU chip
    ],
)
def test_ssd_chunked_dispatches_intra_kernel(monkeypatch, one_tpu, shape, path):
    """``ssd_chunked`` takes the kernel exactly on one TPU chip at widths
    that meet its tiling rule, and counts the path it traced."""
    import repro.obs as obs

    d = {**LEGAL, **shape}
    monkeypatch.setattr(ssm, "_one_tpu", lambda: one_tpu)
    x = jax.ShapeDtypeStruct((1, 2 * d["chunk"], d["h"], d["p"]), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((1, 2 * d["chunk"], d["h"]), jnp.float32)
    a = jax.ShapeDtypeStruct((d["h"],), jnp.float32)
    bc = jax.ShapeDtypeStruct((1, 2 * d["chunk"], d["g"], d["n"]), jnp.bfloat16)
    with obs.enabled() as tracer:
        jaxpr = jax.make_jaxpr(
            lambda *t: ssm.ssd_chunked(*t, chunk=d["chunk"]))(x, dt, a, bc, bc)
    counters = tracer.counters()
    other = "fallback" if path == "kernel" else "kernel"
    assert counters.get(f"ssd.intra_{path}") == 1
    assert f"ssd.intra_{other}" not in counters
    assert ("pallas_call" in str(jaxpr)) == (path == "kernel")


@pytest.mark.parametrize(
    "platform,count,want", [("tpu", 1, True), ("tpu", 4, False), ("cpu", 1, False)]
)
def test_intra_kernel_needs_one_tpu_chip(monkeypatch, platform, count, want):
    """Over several chips XLA would have to partition the Mosaic kernel,
    which it cannot: the step then keeps the jnp term."""
    import types

    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(platform=platform)] * count)
    assert ssm._one_tpu() is want
