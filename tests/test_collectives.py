"""Gradient-compression tests: quantization round-trip + error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist.collectives import (
    compressed_psum,
    dequantize_int8,
    quantize_int8,
)
from repro.launch.mesh import make_mesh


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 3.0
    q, s = quantize_int8(x, block=128)
    y = dequantize_int8(q, s, x.shape)
    # per-block max-scaled int8: error ≤ scale/2 = max|block|/254
    err = np.abs(np.asarray(x - y))
    assert err.max() <= float(jnp.abs(x).max()) / 254 + 1e-6


def test_quantize_handles_zeros_and_padding():
    x = jnp.zeros((130,))
    q, s = quantize_int8(x, block=64)
    y = dequantize_int8(q, s, x.shape)
    np.testing.assert_array_equal(np.asarray(y), 0.0)


def test_error_feedback_unbiased_over_steps():
    """With error feedback, the *accumulated* synced gradient converges to
    the accumulated true gradient (compression noise does not build up)."""
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((1,), ("pod",))

    true_total = jnp.zeros((64,))
    sync_total = jnp.zeros((64,))
    err = jnp.zeros((64,))
    key = jax.random.PRNGKey(1)

    @jax.jit
    def step(g, err):
        f = jax.shard_map(
            lambda gg, ee: compressed_psum(gg, ee, axis_name="pod"),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
        )
        return f(g, err)

    for i in range(30):
        key, k = jax.random.split(key)
        g = jax.random.normal(k, (64,))
        synced, err = step(g, err)
        true_total = true_total + g
        sync_total = sync_total + synced

    # residual is bounded by one step's quantization error, so the
    # accumulated difference stays small relative to the accumulated norm
    diff = float(jnp.linalg.norm(sync_total - true_total))
    assert diff <= float(jnp.abs(err).sum()) + 1e-3
    rel = diff / float(jnp.linalg.norm(true_total))
    assert rel < 0.05


def test_wire_bytes_are_4x_smaller():
    x = jnp.zeros((1024,), jnp.float32)
    q, s = quantize_int8(x, block=256)
    wire = q.nbytes + s.nbytes
    assert wire * 3.5 < x.nbytes * 1.01
