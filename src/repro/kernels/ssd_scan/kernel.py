"""Mamba-2 SSD intra-chunk term: a Pallas TPU kernel pair, forward and backward.

Within a chunk of ``Q`` tokens the SSD output has a quadratic part

    y_intra = (C·Bᵀ ⊙ L) · (dt·x),    L[i, j] = exp(cum_i − cum_j) if i ≥ j else 0,

with ``cum`` the inclusive cumulative sum of dt·A over the chunk.  Written
as whole-tensor jnp it makes several float32 ``[Q, Q]``-per-head tensors in
HBM.  Here one grid step takes one chunk of one batch row for a block of
heads that lies within one group.  It forms C·Bᵀ once in VMEM, builds each
head's decay tile L there from ``cum`` (masked before the exponential, so
no inf is ever formed), and writes only y.  The backward kernel recomputes
C·Bᵀ and L and gives d(dt·x), dC and dB (summed over the block's heads) and
the row and column sums of ``dM ⊙ M`` that make d cum, again without
writing a ``[Q, Q]`` tile.

Every operand keeps the model's layout, reshaped for free: ``cum``
``[B, nc, Q, H]`` (plus two tiny reoriented copies, one row of Q per head
and one column), C and B ``[B, nc, Q, G·N]``, dt·x and y ``[B, nc, Q, H·P]``.
A block is ``(Q, N)`` or ``(Q, heads·P)``, so its last dim is lane-dense.
Each head's ``P`` lanes are read through the 128-lane window that holds
them, and a window that holds several heads is split by a lane mask.

Precision: the MXU gets its operands in the compute dtype of C and B
(bfloat16 in training) and accumulates in float32, as XLA's DEFAULT
precision does for the float32 einsums of the jnp formula on a TPU; ``cum``,
the decay, the exponential and every elementwise product and sum stay in
float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["head_block", "intra_fwd", "intra_bwd"]

LANES = 128
_BLOCK_BYTES = 2 << 20  # bound on one block of dt·x (float32) in VMEM


def head_block(heads: int, groups: int, head_dim: int, chunk: int) -> tuple[int, bool]:
    """The heads of one grid step, and whether that block obeys the TPU's
    tiling rule (its ``heads·P`` lanes a multiple of 128, or every head).

    The block divides the heads of a group, so it lies within one group;
    it is the largest legal one whose dt·x fits ``_BLOCK_BYTES``, else the
    smallest legal one, else (never on a TPU) the whole group."""
    per_group = heads // groups
    divisors = [d for d in range(per_group, 0, -1) if per_group % d == 0]
    legal = [d for d in divisors if (d * head_dim) % LANES == 0 or d == heads]
    fitting = [d for d in legal if chunk * d * head_dim * 4 <= _BLOCK_BYTES]
    if fitting:
        return fitting[0], True
    if legal:
        return legal[-1], True
    return per_group, False


def _windows(hb: int, p: int):
    """Lane windows over a block of ``hb`` heads of ``p`` lanes: each is
    ``(lo, hi, [(head, lo_in_window, hi_in_window), ...])``, 128-aligned
    and never splitting a head (``p`` divides 128 or 128 divides ``p``)."""
    width = hb * p
    if p >= LANES:
        return [(h * p, (h + 1) * p, [(h, 0, p)]) for h in range(hb)]
    out = []
    for lo in range(0, width, LANES):
        hi = min(lo + LANES, width)
        out.append((lo, hi, [(h, h * p - lo, (h + 1) * p - lo)
                             for h in range(lo // p, hi // p)]))
    return out


def _dot(a, b, contract, mxu):
    return jax.lax.dot_general(
        a.astype(mxu), b.astype(mxu), (contract, ((), ())),
        preferred_element_type=jnp.float32,
    )


def _decay(cum_col, cum_row, causal):
    """L = exp(cum_i − cum_j) on and below the diagonal, 0 above it; the
    mask goes in before the exponential (above the diagonal the difference
    is positive and can overflow)."""
    return jnp.exp(jnp.where(causal, cum_col - cum_row, -jnp.inf))


def _fwd_kernel(cr_ref, cc_ref, c_ref, b_ref, x_ref, y_ref, *, hb, p):
    q = x_ref.shape[0]
    mxu = c_ref.dtype
    s = _dot(c_ref[...], b_ref[...], ((1,), (1,)), mxu)           # C·Bᵀ [Q, Q]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    cum_r, cum_c = cr_ref[...], cc_ref[...]                       # [hb, Q], [Q, hb]
    for lo, hi, heads in _windows(hb, p):
        xw = x_ref[:, lo:hi]
        lane = jax.lax.broadcasted_iota(jnp.int32, (q, hi - lo), 1)
        out = None
        for h, a, z in heads:
            m = s * _decay(cum_c[:, h:h + 1], cum_r[h:h + 1, :], causal)
            yh = _dot(m, xw, ((1,), (0,)), mxu)                    # [Q, window]
            if len(heads) > 1:
                yh = jnp.where((lane >= a) & (lane < z), yh, 0.0 if out is None else out)
            out = yh
        y_ref[:, lo:hi] = out


def _bwd_kernel(cr_ref, cc_ref, c_ref, b_ref, x_ref, dy_ref,
                dx_ref, dc_ref, db_ref, dr_ref, dcol_ref, *, hb, p):
    q = x_ref.shape[0]
    mxu = c_ref.dtype
    c, b = c_ref[...], b_ref[...]
    s = _dot(c, b, ((1,), (1,)), mxu)
    causal = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    cum_r, cum_c = cr_ref[...], cc_ref[...]
    head_row = jax.lax.broadcasted_iota(jnp.int32, (hb, q), 0)
    head_col = jax.lax.broadcasted_iota(jnp.int32, (q, hb), 1)
    ds_sum = jnp.zeros((q, q), jnp.float32)
    rows = jnp.zeros((hb, q), jnp.float32)   # head h: column sums of dM ⊙ M
    cols = jnp.zeros((q, hb), jnp.float32)   # head h: row sums of dM ⊙ M
    for lo, hi, heads in _windows(hb, p):
        xw, dyw = x_ref[:, lo:hi], dy_ref[:, lo:hi]
        lane = jax.lax.broadcasted_iota(jnp.int32, (q, hi - lo), 1)
        out = None
        for h, a, z in heads:
            l_h = _decay(cum_c[:, h:h + 1], cum_r[h:h + 1, :], causal)
            m = s * l_h
            dxh = _dot(m, dyw, ((0,), (0,)), mxu)                  # Mᵀ·dY [Q, window]
            dyh = dyw
            if len(heads) > 1:
                own = (lane >= a) & (lane < z)
                dxh = jnp.where(own, dxh, 0.0 if out is None else out)
                dyh = jnp.where(own, dyw, 0.0)
            out = dxh
            dm = _dot(dyh, xw, ((1,), (1,)), mxu)                  # dY·xᵀ [Q, Q]
            ds_sum = ds_sum + dm * l_h
            dseg = dm * m
            cols = jnp.where(head_col == h, jnp.sum(dseg, axis=1, keepdims=True), cols)
            rows = jnp.where(head_row == h, jnp.sum(dseg, axis=0, keepdims=True), rows)
        dx_ref[:, lo:hi] = out
    dc_ref[...] = _dot(ds_sum, b, ((1,), (0,)), mxu)               # dS·B
    db_ref[...] = _dot(ds_sum, c, ((0,), (0,)), mxu)               # dSᵀ·C
    dr_ref[...] = rows
    dcol_ref[...] = cols


class _Layout:
    """The kernel's view of the model's tensors: block sizes, grid, and
    the free reshapes and two small reorientations of ``cum``."""

    def __init__(self, cum, c, xdt):
        self.bsz, self.nc, self.q, self.h = cum.shape
        self.g, self.n = c.shape[-2:]
        self.p = xdt.shape[-1]
        self.hb, _ = head_block(self.h, self.g, self.p, self.q)
        self.nhb = self.h // self.hb
        self.per_group = self.nhb // self.g

    def cum_views(self, cum):
        c5 = cum.reshape(self.bsz, self.nc, self.q, self.nhb, self.hb)
        return c5.transpose(0, 1, 3, 4, 2), c5.transpose(0, 1, 3, 2, 4)

    def flat(self, t):
        """[B, nc, Q, X, Y] → [B, nc, Q, X·Y] (free)."""
        return t.reshape(self.bsz, self.nc, self.q, -1)

    def specs(self):
        q, hb, n, p, per_group = self.q, self.hb, self.n, self.p, self.per_group
        sq = pl.Squeezed()
        return {
            "cum_r": pl.BlockSpec((sq, sq, sq, hb, q), lambda i, j, k: (i, j, k, 0, 0)),
            "cum_c": pl.BlockSpec((sq, sq, sq, q, hb), lambda i, j, k: (i, j, k, 0, 0)),
            "cb": pl.BlockSpec((sq, sq, q, n), lambda i, j, k: (i, j, 0, k // per_group)),
            "x": pl.BlockSpec((sq, sq, q, hb * p), lambda i, j, k: (i, j, 0, k)),
            "part": pl.BlockSpec((sq, sq, sq, q, n), lambda i, j, k: (i, j, k, 0, 0)),
        }

    @property
    def grid(self):
        return (self.bsz, self.nc, self.nhb)

    def params(self, blocks: int):
        """Parallel grid; scoped VMEM for ``blocks`` double-buffered
        ``(Q, heads·P)`` float32 blocks and a dozen ``[Q, Q]`` tiles."""
        tile = self.q * max(self.hb * self.p, LANES) * 4
        need = 2 * blocks * tile + 12 * self.q * self.q * 4 + (4 << 20)
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=max(32 << 20, min(need, 100 << 20)),
        )

    def cost(self, passes: int, bytes_accessed: int):
        mm = 2 * self.q * self.q
        flops = self.bsz * self.nc * (self.nhb * mm * self.n + self.h * mm * self.p) * passes
        return pl.CostEstimate(flops=flops, bytes_accessed=bytes_accessed,
                               transcendentals=self.bsz * self.nc * self.h * self.q * self.q)


def _nbytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays)


def intra_fwd(cum, c, b, xdt, *, interpret: bool = False):
    """y_intra ``[B, nc, Q, H, P]`` float32 from ``cum`` ``[B, nc, Q, H]``
    float32, C and B ``[B, nc, Q, G, N]`` and dt·x ``[B, nc, Q, H, P]``."""
    lay = _Layout(cum, c, xdt)
    cum_r, cum_c = lay.cum_views(cum)
    sp = lay.specs()
    x = lay.flat(xdt)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=lay.hb, p=lay.p),
        grid=lay.grid,
        in_specs=[sp["cum_r"], sp["cum_c"], sp["cb"], sp["cb"], sp["x"]],
        out_specs=sp["x"],
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=lay.params(blocks=2),
        cost_estimate=lay.cost(1, _nbytes(cum, c, b, x) + x.size * 4),
        interpret=interpret,
    )(cum_r, cum_c, lay.flat(c), lay.flat(b), x)
    return y.reshape(xdt.shape)


def intra_bwd(cum, c, b, xdt, dy, *, interpret: bool = False):
    """Cotangents ``(d cum, dC, dB, d(dt·x))`` of :func:`intra_fwd` for
    ``dy``; dC and dB in float32."""
    lay = _Layout(cum, c, xdt)
    cum_r, cum_c = lay.cum_views(cum)
    sp = lay.specs()
    x, dyf = lay.flat(xdt), lay.flat(dy.astype(jnp.float32))
    bsz, nc, q, nhb, hb = lay.bsz, lay.nc, lay.q, lay.nhb, lay.hb
    part = jax.ShapeDtypeStruct((bsz, nc, nhb, q, lay.n), jnp.float32)
    dx, dc, db, d_rows, d_cols = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=lay.p),
        grid=lay.grid,
        in_specs=[sp["cum_r"], sp["cum_c"], sp["cb"], sp["cb"], sp["x"], sp["x"]],
        out_specs=[sp["x"], sp["part"], sp["part"], sp["cum_r"], sp["cum_c"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, jnp.float32), part, part,
            jax.ShapeDtypeStruct(cum_r.shape, jnp.float32),
            jax.ShapeDtypeStruct(cum_c.shape, jnp.float32),
        ],
        compiler_params=lay.params(blocks=3),
        cost_estimate=lay.cost(3, _nbytes(cum, c, b, x, dyf) * 2),
        interpret=interpret,
    )(cum_r, cum_c, lay.flat(c), lay.flat(b), x, dyf)
    # d cum_i = Σ_j dseg[i, j] − Σ_k dseg[k, i]: row sums less column sums
    dcum = d_cols - d_rows.transpose(0, 1, 2, 4, 3)                 # [B, nc, NHB, Q, hb]
    dcum = dcum.transpose(0, 1, 3, 2, 4).reshape(cum.shape)

    def per_group(t):  # [B, nc, NHB, Q, N] → [B, nc, Q, G, N], summed over a group's blocks
        t = t.reshape(bsz, nc, lay.g, lay.per_group, q, lay.n).sum(axis=3)
        return t.transpose(0, 1, 3, 2, 4)

    return dcum, per_group(dc), per_group(db), dx.reshape(xdt.shape)
