"""Public wrapper of the SSD intra-chunk kernel pair: one differentiable
call in the model's layouts, and the tiling rule that says where the TPU
can run it."""

from __future__ import annotations

import functools

import jax

from .kernel import LANES, head_block, intra_bwd, intra_fwd

__all__ = ["ssd_intra", "fits"]


def fits(chunk: int, heads: int, groups: int, head_dim: int, state: int) -> bool:
    """Whether the kernel's blocks obey the TPU's tiling rule at these
    widths: the chunk a multiple of 128 (it is the lane dim of every
    ``[Q, Q]`` tile), each head's lanes inside one 128-lane window, C and B
    split per group along 128-lane blocks, and a legal head block."""
    return (
        chunk % LANES == 0
        and (head_dim % LANES == 0 or LANES % head_dim == 0)
        and (groups == 1 or state % LANES == 0)
        and head_block(heads, groups, head_dim, chunk)[1]
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _intra(cum, c, b, xdt, interpret):
    return intra_fwd(cum, c, b, xdt, interpret=interpret)


def _intra_fwd(cum, c, b, xdt, interpret):
    return intra_fwd(cum, c, b, xdt, interpret=interpret), (cum, c, b, xdt)


def _intra_bwd(interpret, res, dy):
    cum, c, b, xdt = res
    dcum, dc, db, dx = intra_bwd(cum, c, b, xdt, dy, interpret=interpret)
    return dcum, dc.astype(c.dtype), db.astype(b.dtype), dx


_intra.defvjp(_intra_fwd, _intra_bwd)


def ssd_intra(cum, c, b, xdt, *, interpret: bool = False) -> jax.Array:
    """``(C·Bᵀ ⊙ L) · (dt·x)`` per chunk, differentiable in every input.

    ``cum`` ``[B, nc, Q, H]`` float32 (inclusive cumsum of dt·A within each
    chunk), C and B ``[B, nc, Q, G, N]`` in the compute dtype (groups not
    repeated over heads), dt·x ``[B, nc, Q, H, P]`` float32.  Returns
    ``[B, nc, Q, H, P]`` float32."""
    return _intra(cum, c, b, xdt, interpret)
