"""Plain reference of the Mamba-2 block (SSD, arXiv:2405.21060), one layer,
as the published ``Mamba2`` module describes it: pre-RMSNorm, ``in_proj``
split into z, xBC and dt, depthwise causal conv with bias then SiLU over
xBC, ``dt = softplus(dt + dt_bias)``, ``A = -exp(a_log)``, the selective
state-space recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_tᵀ,    y_t = C_t h_t,

plus ``D · x``, the gate ``y · silu(z)`` and an RMSNorm after it
(``norm_before_gate=False``), ``out_proj`` and the residual.  Found by the
configuration's ``model.family`` (``"ssm"``).

The recurrence is computed in its quadratic (attention-like) form over the
whole sequence, from the zero state:

    y_t = Σ_{s ≤ t} (C_t · B_s) exp(Σ_{s < r ≤ t} dt_r A) dt_s x_s,

with the causal mask applied to the exponent, before the exponential, so
that no entry above the diagonal overflows.  Departures from the published
module, none of which changes the mathematics:

* float32 throughout (the residual too, as ``residual_in_fp32``), every
  matmul through ``m`` at the highest precision;
* the decay exponent ``Σ_{s < r ≤ t} dt_r A`` is the difference of two
  prefix sums taken in two levels — within blocks of at most 64 tokens,
  and the blocks' totals summed exactly between blocks — so that it is
  not the difference of two prefix sums of the whole sequence, whose
  rounding grows with their size;
* no ``dt_limit`` clamp (the published default is (0, inf)), no initial
  state, no chunking: the sequence is one block.
"""

from __future__ import annotations

import math

from chipbench.lib.reference import rms, silu


def _decay_exponent(da):
    """``da [b, S, H]`` (``dt·A``) → ``[b, H, S, S]``: for ``t >= s``,
    ``Σ_{s < r <= t} da_r``; ``-inf`` above the diagonal."""
    import jax
    import jax.numpy as jnp

    b, s, h = da.shape
    blk = math.gcd(s, 64)
    nb = s // blk
    local = jnp.cumsum(da.reshape(b, nb, blk, h), axis=2)           # within a block
    tot = local[:, :, -1, :]                                         # [b, nb, H]
    k = jnp.arange(nb)
    # between[i, j] = Σ_{j <= k < i} tot_k: a 0/1 weight, summed exactly
    w = ((k[None, None, :] >= k[None, :, None]) & (k[None, None, :] < k[:, None, None]))
    between = jnp.einsum("ijk,bkh->bhij", w.astype(da.dtype), tot,
                         precision=jax.lax.Precision.HIGHEST)
    local = local.transpose(0, 3, 1, 2)                              # [b, H, nb, blk]
    seg = (between[:, :, :, None, :, None] + local[:, :, :, :, None, None]
           - local[:, :, None, None, :, :]).reshape(b, h, s, s)
    causal = jnp.tril(jnp.ones((s, s), bool))
    return jnp.where(causal, seg, -jnp.inf)


def ssm(m, x, dt, a, bmat, cmat):
    """``x [b, S, H, P]``, ``dt [b, S, H]``, ``a [H]``, ``bmat``/``cmat``
    ``[b, S, G, N]`` → ``y [b, S, H, P]``; head ``h`` reads group
    ``h // (H / G)``."""
    import jax.numpy as jnp

    b, s, h, p = x.shape
    g = bmat.shape[2]
    decay = jnp.exp(_decay_exponent(dt * a))                         # [b, H, T, S]
    cb = m("btgn,bsgn->bgts", cmat, bmat)                            # [b, G, T, S]
    scores = cb[:, :, None] * decay.reshape(b, g, h // g, s, s)      # [b, G, R, T, S]
    xdt = (x * dt[..., None]).reshape(b, s, g, h // g, p)
    y = m("bgrts,bsgrp->btgrp", scores, xdt)
    return y.reshape(b, s, h, p)


def layer(m, x, p, m_cfg):
    import jax
    import jax.numpy as jnp

    b, s, d = x.shape
    c = m_cfg["ssm"]
    di = c["expand"] * d
    hp, g, n, k = c["head_dim"], c["n_groups"], c["d_state"], c["d_conv"]
    heads = di // hp
    eps = m_cfg["norm_eps"]

    h = rms(x, p["norm"], eps)
    zxbcdt = m("bsd,df->bsf", h, p["in_proj"])
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di : 2 * di + 2 * g * n]
    dt = zxbcdt[..., 2 * di + 2 * g * n :]

    # depthwise causal conv: tap j of channel c weighs x[t - (k - 1) + j]
    xp = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(xp[:, j : j + s, :] * p["conv_w"][:, j] for j in range(k)) + p["conv_b"]
    xbc = silu(conv)
    xs = xbc[..., :di].reshape(b, s, heads, hp)
    bmat = xbc[..., di : di + g * n].reshape(b, s, g, n)
    cmat = xbc[..., di + g * n :].reshape(b, s, g, n)

    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    y = ssm(m, xs, dt, a, bmat, cmat) + xs * p["d_skip"][:, None]
    y = y.reshape(b, s, di) * silu(z)
    y = rms(y, p["ssm_norm"], eps)
    return x + m("bsf,fd->bsd", y, p["out_proj"])
