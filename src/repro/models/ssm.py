"""Mamba-2 (SSD — state-space duality) blocks: chunked scan + decode step.

The SSD formulation (arXiv:2405.21060) is natively TPU-friendly: within a
chunk the recurrence is expressed as masked matmuls (MXU work), and only a
short ``lax.scan`` over chunk boundary states remains sequential.  This is
the adaptation story for this architecture — no CUDA-style selective-scan
kernel is needed; the matmul-rich form *is* the hardware-appropriate
algorithm.  The chunk's quadratic term, ``(C·Bᵀ ⊙ L) · (dt·x)``, is one
Pallas kernel pair on one TPU chip (``repro.kernels.ssd_scan``: forward
and backward, every per-head ``[Q, Q]`` tile kept in VMEM, C·Bᵀ formed once
per group) wherever the widths meet its tiling rule; elsewhere (CPU, a
step partitioned over several chips, chunks not a multiple of 128, odd
widths) :func:`ssd_chunked` computes it as whole-tensor jnp.  :func:`ssd_recurrent` is the O(S) oracle used by tests.

Shapes follow the paper: x [B,S,H,P] (P = head dim), dt [B,S,H],
A [H] (negative), B/C [B,S,G,N] (G groups broadcast over heads, N = state).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import repro.obs as obs
from repro.kernels.ssd_scan.ops import fits as intra_kernel_fits
from repro.kernels.ssd_scan.ops import ssd_intra

__all__ = ["ssd_chunked", "ssd_recurrent", "ssm_decode_step", "causal_conv1d", "conv_decode_step"]


def _broadcast_groups(bc: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] → [..., H, N] by repeating groups."""
    *lead, g, n = bc.shape
    rep = heads // g
    return jnp.broadcast_to(bc[..., :, None, :], (*lead, g, rep, n)).reshape(
        *lead, heads, n
    )


def _one_tpu() -> bool:
    """The process drives one TPU chip.  Over more devices the step is
    partitioned, and XLA cannot partition a Mosaic kernel by itself."""
    devices = jax.devices()
    return len(devices) == 1 and devices[0].platform == "tpu"


def _intra_jnp(cum, cq, bq, xdt):
    """The intra-chunk term as whole-tensor jnp, for shapes and backends
    the kernel does not take.  Layouts as :func:`ssd_intra`'s.

    L[i,j] = exp(cum_i - cum_j) for i >= j (segment decay), else 0.  The
    mask goes in before the exponential: above the diagonal cum_i - cum_j
    is positive and overflows float32 at the published chunk (256) and
    A/dt init, and a masked inf turns the gradient into 0 · inf = NaN."""
    h, chunk = cum.shape[-1], cum.shape[2]
    cq = _broadcast_groups(cq, h).astype(jnp.float32)
    bq = _broadcast_groups(bq, h).astype(jnp.float32)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # [B,nc,Q,Q,H]
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    l_mask = jnp.exp(jnp.where(tri[None, None, :, :, None], seg, -jnp.inf))
    cb = jnp.einsum("bcqhn,bckhn->bcqkh", cq, bq)
    return jnp.einsum("bcqkh,bckhp->bcqhp", cb * l_mask, xdt)


def ssd_recurrent(x, dt, a, bmat, cmat, *, h0=None):
    """Sequential oracle: h_t = exp(dt·A)·h_{t-1} + dt·B_t ⊗ x_t; y = C·h."""
    bsz, s, h, p = x.shape
    n = bmat.shape[-1]
    bmat = _broadcast_groups(bmat, h)
    cmat = _broadcast_groups(cmat, h)
    da = dt * a[None, None, :]  # [B,S,H]
    h_state = jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None else h0

    def step(hs, inp):
        xt, dtt, dat, bt, ct = inp
        decay = jnp.exp(dat)[..., None, None]
        upd = (dtt[..., None, None] * xt[..., :, None]) * bt[..., None, :]
        hs = hs * decay + upd.astype(jnp.float32)
        y = jnp.einsum("bhpn,bhn->bhp", hs, ct.astype(jnp.float32))
        return hs, y

    xs = (
        x.transpose(1, 0, 2, 3),
        dt.transpose(1, 0, 2),
        da.transpose(1, 0, 2),
        bmat.transpose(1, 0, 2, 3),
        cmat.transpose(1, 0, 2, 3),
    )
    h_state, ys = jax.lax.scan(step, h_state, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), h_state


def ssd_chunked(x, dt, a, bmat, cmat, *, chunk: int, h0=None):
    """Chunked SSD: intra-chunk masked matmuls + inter-chunk state scan.

    Matches :func:`ssd_recurrent` (property-tested).  Returns (y, h_final).
    Finite forward and backward for any chunk length and any ``dt·A <= 0``.
    Its parts carry the named scopes ``ssd.intra``, ``ssd.states``,
    ``ssd.scan`` and ``ssd.inter``, so a device trace can attribute their
    time.  The intra-chunk term takes the Pallas kernel in a process that
    drives one TPU chip, when the widths meet its tiling rule, else
    whole-tensor jnp; each traced call bumps the counter
    ``ssd.intra_kernel`` or ``ssd.intra_fallback``.
    """
    bsz, s, h, p = x.shape
    g, n = bmat.shape[-2:]
    if s % chunk:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    nc = s // chunk

    # reshape to chunks: [B, nc, Q, ...]; B and C keep their G groups
    xq = x.reshape(bsz, nc, chunk, h, p)
    dtq = dt.reshape(bsz, nc, chunk, h)
    bq = bmat.reshape(bsz, nc, chunk, g, n)
    cq = cmat.reshape(bsz, nc, chunk, g, n)
    da = (dtq * a[None, None, None, :]).astype(jnp.float32)  # [B,nc,Q,H]

    cum = jnp.cumsum(da, axis=2)                      # inclusive cumsum
    total = cum[:, :, -1, :]                          # [B,nc,H]

    # ---- intra-chunk (quadratic in chunk length; pure matmul) -------------
    with jax.named_scope("ssd.intra"):
        xdt = xq.astype(jnp.float32) * dtq[..., None]
        if _one_tpu() and intra_kernel_fits(chunk, h, g, p, n):
            obs.add("ssd.intra_kernel")
            y_intra = ssd_intra(cum, cq, bq, xdt)
        else:
            obs.add("ssd.intra_fallback")
            y_intra = _intra_jnp(cum, cq, bq, xdt)
    bq = _broadcast_groups(bq, h)
    cq = _broadcast_groups(cq, h)

    # ---- chunk boundary states --------------------------------------------
    # state contribution of chunk c: sum_j exp(total - cum_j) dt_j x_j B_j^T
    # (total - cum_j <= 0, as is cum below: no exponential here can overflow)
    with jax.named_scope("ssd.states"):
        decay_to_end = jnp.exp(total[:, :, None, :] - cum)       # [B,nc,Q,H]
        s_chunk = jnp.einsum(
            "bcqhp,bcqhn->bchpn", xdt * decay_to_end[..., None], bq.astype(jnp.float32)
        )

    h_init = jnp.zeros((bsz, h, p, n), jnp.float32) if h0 is None else h0

    def boundary(hprev, inp):
        s_c, tot_c = inp  # [B,H,P,N], [B,H]
        hnew = hprev * jnp.exp(tot_c)[:, :, None, None] + s_c
        return hnew, hprev

    with jax.named_scope("ssd.scan"):
        (h_final, h_prevs) = jax.lax.scan(
            boundary,
            h_init,
            (s_chunk.transpose(1, 0, 2, 3, 4), total.transpose(1, 0, 2)),
        )
        h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)               # [B,nc,H,P,N]

    # ---- inter-chunk: y += C_t · exp(cum_t) · h_prev ----------------------
    with jax.named_scope("ssd.inter"):
        y_inter = jnp.einsum(
            "bcqhn,bchpn->bcqhp", cq.astype(jnp.float32) * jnp.exp(cum)[..., None], h_prevs
        )

    y = (y_intra + y_inter).reshape(bsz, s, h, p).astype(x.dtype)
    return y, h_final


def ssm_decode_step(h, xt, dtt, a, bt, ct):
    """Single-token state update.  h: [B,H,P,N]; xt: [B,H,P]; bt/ct: [B,G,N]."""
    heads = xt.shape[1]
    bt = _broadcast_groups(bt[:, None], heads)[:, 0]
    ct = _broadcast_groups(ct[:, None], heads)[:, 0]
    da = dtt * a[None, :]
    decay = jnp.exp(da)[..., None, None]
    upd = (dtt[..., None, None] * xt[..., :, None]) * bt[:, :, None, :]
    h = h * decay + upd.astype(jnp.float32)
    y = jnp.einsum("bhpn,bhn->bhp", h, ct.astype(jnp.float32))
    return h, y.astype(xt.dtype)


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv.  x: [B,S,D]; w: [D,K]; b: [D]."""
    k = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    # window sum: Σ_j x[t-k+1+j] * w[:, j]
    out = jnp.zeros_like(x)
    for j in range(k):  # K is 4: unrolled window taps
        out = out + xp[:, j : j + x.shape[1], :] * w[None, None, :, j]
    return jax.nn.silu(out + b[None, None, :])


def conv_decode_step(conv_state: jax.Array, xt: jax.Array, w: jax.Array, b: jax.Array):
    """conv_state: [B,K-1,D] last inputs; xt: [B,D] → (new_state, out [B,D])."""
    k = w.shape[-1]
    window = jnp.concatenate([conv_state, xt[:, None, :]], axis=1)  # [B,K,D]
    out = jnp.einsum("bkd,dk->bd", window, w) + b[None, :]
    return window[:, 1:], jax.nn.silu(out)
