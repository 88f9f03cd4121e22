"""Ahead-of-time compiles for a described TPU v5e, no chip attached.

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached.  What it refuses here (a kernel tile the
chip cannot hold, a step that does not fit in device memory, a sharding it
cannot partition) it would refuse on the chip, so these tests guard the
main path's programs at no chip time.  Nothing runs: they say nothing about
results or speed.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and a test worker
that loaded it keeps it until it exits.  Every sharding and mesh built
from the topology is built in a fixture or test too.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ParallelismConfig, TrainConfig, get_config
from repro.launch.mesh import make_mesh
from repro.train.optimizer import init_state
from repro.train.trainer import Trainer

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A persistent cache entry compiled for a described chip cannot be read
    # back without one; keep these compiles out of any cache.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # repro: allow[except-discipline] -- any failure to describe the topology means no TPU compiler here: skip
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])


@pytest.fixture(scope="module")
def four_chips(topo):
    return make_mesh((2, 2), ("data", "model"), devices=topo.devices)


def compile_train_step(cfg, jmesh, *, batch: int, seq: int):
    """Lower and compile the Trainer's jitted step over ``jmesh`` from
    shapes alone (a described device holds no arrays)."""
    t = Trainer.create(
        cfg, ParallelismConfig(), TrainConfig(), jmesh, batch_size=batch, seq_len=seq
    )
    shapes = jax.eval_shape(
        lambda: init_state(t.lm.init(jax.random.PRNGKey(0)), moment_dtype=jnp.float32)
    )
    state_sh = Trainer._state_shardings(t.plan, jmesh)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, state_sh,
    )
    batch_sh = Trainer._batch_shardings(cfg, t.parallel, jmesh)
    tokens = t.batch(0)["tokens"]
    batch_sds = {
        "tokens": jax.ShapeDtypeStruct(tokens.shape, tokens.dtype, sharding=batch_sh["tokens"])
    }
    return t.step_fn.lower(state, batch_sds).compile()


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


@pytest.fixture(scope="module")
def smollm_2_layers():
    """smollm-360m at its published widths, depth cut to 2 layers."""
    return dataclasses.replace(get_config("smollm-360m"), num_layers=2)


def test_train_step_compiles_on_one_chip(smollm_2_layers, one_chip):
    compiled = compile_train_step(smollm_2_layers, one_chip, batch=8, seq=1024)
    assert 0 < device_bytes(compiled) < V5E_HBM


def test_train_step_compiles_on_2x2(smollm_2_layers, four_chips):
    compiled = compile_train_step(smollm_2_layers, four_chips, batch=8, seq=1024)
    assert 0 < device_bytes(compiled) < V5E_HBM
    hlo = compiled.as_text()
    assert "all-gather" in hlo or "all-reduce" in hlo  # FSDP/TP collectives


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_kernel_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention

    sh = NamedSharding(one_chip, P())
    q = _sds((1, 2048, 8, 64), jnp.bfloat16, sh)
    compiled = flash_attention.lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.float8_e4m3fn])
def test_block_quant_kernel_compiles(one_chip, dtype):
    from repro.kernels.block_quant.ops import block_quantize

    shard = _sds((960, 2560), jnp.float32, NamedSharding(one_chip, P()))
    compiled = block_quantize.lower(
        shard, block=256, dtype=dtype, use_kernel=True
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_dequant_kernel_compiles(one_chip):
    from repro.kernels.block_quant.ops import block_dequantize

    sh = NamedSharding(one_chip, P())
    n = 960 * 2560
    compiled = block_dequantize.lower(
        _sds((n // 256, 256), jnp.int8, sh), _sds((n // 256,), jnp.float32, sh),
        count=n, use_kernel=True,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def ssd_intra_shapes(one_chip):
    """The SSD intra-chunk kernel's operands at mamba2-130m's widths and
    training shape: 16 rows of 2048 tokens in chunks of 256, 24 heads of 64
    in one group, state 128, bfloat16 compute."""
    sh = NamedSharding(one_chip, P())
    b, nc, q, h, g, n, p = 16, 8, 256, 24, 1, 128, 64
    return (
        _sds((b, nc, q, h), jnp.float32, sh),
        _sds((b, nc, q, g, n), jnp.bfloat16, sh),
        _sds((b, nc, q, g, n), jnp.bfloat16, sh),
        _sds((b, nc, q, h, p), jnp.float32, sh),
    )


def test_ssd_intra_kernel_compiles(ssd_intra_shapes):
    from repro.kernels.ssd_scan.ops import fits, ssd_intra

    assert fits(256, 24, 1, 64, 128)
    compiled = jax.jit(ssd_intra).lower(*ssd_intra_shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_intra_backward_kernel_compiles(ssd_intra_shapes):
    from repro.kernels.ssd_scan.ops import ssd_intra

    def loss(cum, c, b, xdt):
        return jnp.sum(ssd_intra(cum, c, b, xdt))

    compiled = jax.jit(jax.grad(loss, argnums=range(4))).lower(*ssd_intra_shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
