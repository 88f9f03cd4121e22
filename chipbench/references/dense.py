"""Plain reference of the dense decoder (Llama / SmolLM), one layer:
pre-RMSNorm, fused QKV with grouped KV heads, rotary embedding on the two
halves of each head, causal softmax attention, SwiGLU MLP.  Found by the
configuration's ``model.family`` (``"dense"``)."""

from __future__ import annotations

import math

import numpy as np

from chipbench.lib.reference import rms, silu


def _rope(x, pos, theta):
    """Rotate the two halves of each head: [x1 c - x2 s, x2 c + x1 s]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    s, c = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def layer(m, x, p, m_cfg):
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    hq, hkv, hd = m_cfg["num_heads"], m_cfg["num_kv_heads"], m_cfg["head_dim"]
    eps, theta = m_cfg["norm_eps"], m_cfg["rope_theta"]
    pos = jnp.arange(s)
    h = rms(x, p["attn_norm"], eps)
    qkv = m("bsd,df->bsf", h, p["wqkv"])
    q = qkv[..., : hq * hd].reshape(b, s, hq, hd)
    k = qkv[..., hq * hd : (hq + hkv) * hd].reshape(b, s, hkv, hd)
    v = qkv[..., (hq + hkv) * hd :].reshape(b, s, hkv, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    q = q.reshape(b, s, hkv, hq // hkv, hd)  # query head j reads kv head j // groups
    scores = m("bqkgd,btkd->bkgqt", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, -1)
    o = m("bkgqt,btkd->bqkgd", probs, v).reshape(b, s, hq * hd)
    x = x + m("bsf,fd->bsd", o, p["wo"])
    h = rms(x, p["mlp_norm"], eps)
    a = m("bsd,df->bsf", h, p["w_gate"])
    u = m("bsd,df->bsf", h, p["w_up"])
    return x + m("bsf,fd->bsd", silu(a) * u, p["w_down"])
