"""How ``correct`` is decided: the numbers compared and their arithmetic.

Training (the program's first steps against the plain reference):

* ``loss_gap`` — the largest relative gap of a step's loss;
* ``grad_gap`` — the worst leaf's gap between the norms of the first
  gradient as the optimizer gets it (the program's ``exp_avg / (1 - b1)``
  after one step), over the larger of that leaf's reference norm and the
  median leaf's;
* ``change_gap`` — the same for the norm of each leaf's change after the
  set-up's steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone).

State read back (checkpoint round trips) is compared exactly through
:func:`checksum_fn`: two position-weighted 32-bit sums of each leaf's bit
pattern.
"""

from __future__ import annotations

import numpy as np

STILL = 1e-3  # leaves whose reference gradient is under this share of the median leaf's


def checksum_fn():
    """Jitted ``tree -> uint32 [n_leaves, 2]``, independent of sharding."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        x = x.reshape(-1)
        if x.dtype.itemsize == 2:
            bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        else:
            bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        i = jax.lax.iota(jnp.uint32, bits.shape[0])
        return jnp.stack([
            jnp.sum(bits * (2 * i + 1), dtype=jnp.uint32),
            jnp.sum(bits ^ (i * jnp.uint32(0x9E3779B1)), dtype=jnp.uint32),
        ])

    return jax.jit(lambda tree: jnp.stack([leaf(x) for x in jax.tree.leaves(tree)]))


def mismatched_leaves(got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.shape[0], want.shape[0])
    return int(np.any(got != want, axis=1).sum())


def leaf_norms_fn():
    """Jitted per-leaf L2 norms of a nested tree, keyed by path."""
    import jax
    import jax.numpy as jnp

    from repro.core.pytree import flatten_with_paths

    def norms(tree):
        return {k: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                for k, x in flatten_with_paths(tree).items()}

    return jax.jit(norms)


def change_norms_fn(shapes: dict):
    """Jitted ``(params, hi, lo) -> {path: ||params - init(seed)||}``."""
    import jax
    import jax.numpy as jnp

    from repro.core.pytree import flatten_with_paths

    from .model import gen_params

    def change(params, hi, lo):
        init = gen_params(hi, lo, shapes)
        flat = flatten_with_paths(params)
        return {k: jnp.sqrt(jnp.sum(jnp.square(flat[k].astype(jnp.float32) - init[k])))
                for k in init}

    return jax.jit(change)


def _worst_leaf(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """The largest gap over the leaves; NaN as soon as either side has one."""
    keys = [k for k in want if keep is None or keep(k)]
    med = float(np.median([want[k] for k in want]))
    worst, name = 0.0, ""
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], med, 1e-30)
        if not gap <= worst:  # NaN compares False both ways
            worst, name = gap, k
            if gap != gap:
                break
    return worst, name


def training_gaps(prog: dict, ref: dict) -> dict:
    """The three training numbers of ``prog`` against ``ref`` (each a dict
    of ``losses``, ``grad`` and ``change``; ``ref`` also ``grad_raw``)."""
    loss = float(np.max([abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])]))
    grad, grad_leaf = _worst_leaf(prog["grad"], ref["grad"])
    med = float(np.median(list(ref["grad_raw"].values())))
    moving = lambda k: ref["grad_raw"][k] >= STILL * med
    change, change_leaf = _worst_leaf(prog["change"], ref["change"], moving)
    return {
        "loss_gap": loss, "grad_gap": grad, "change_gap": change,
        "grad_gap_leaf": grad_leaf, "change_gap_leaf": change_leaf,
        "still_leaves": sorted(k for k in ref["grad_raw"] if not moving(k)),
    }
