"""Per-architecture smoke tests (assignment deliverable f).

Each assigned arch instantiates a REDUCED same-family config and runs one
forward/train step on CPU, asserting output shapes and the absence of
NaNs.  Full configs are exercised only through the dry-run
(ShapeDtypeStruct, no allocation)."""

import jax
import jax.numpy as jnp
import pytest

# Multi-second subprocess/e2e tests: excluded from `scripts/ci.sh --fast`.
pytestmark = pytest.mark.slow

from repro.configs import (
    SHAPES,
    ParallelismConfig,
    TrainConfig,
    get_config,
    list_configs,
    reduced,
)
from repro.models import build_model, input_specs
from repro.models import decode as D
from repro.train.optimizer import init_state
from repro.train.steps import make_train_step

ARCHS = list_configs()


def _batch(cfg, b, s, key):
    out = {"tokens": jax.random.randint(key, (b, s + 1), 0, cfg.vocab_size)}
    if cfg.cross_attn:
        out["source_embeds"] = jax.random.normal(
            key, (b, cfg.cross_attn.source_len, cfg.cross_attn.source_dim),
            jnp.bfloat16,
        )
    if cfg.encoder:
        out["source_embeds"] = jax.random.normal(
            key, (b, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16
        )
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_matches_assignment(arch):
    """The full (non-reduced) configs carry the assigned hyperparameters."""
    cfg = get_config(arch)
    table = {
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
        "mamba2-130m": (24, 768, None, None, 0, 50288),
        "deepseek-v2-236b": (60, 5120, 128, 128, None, 102400),
        "mixtral-8x22b": (56, 6144, 48, 8, 16384, 32768),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "gemma3-27b": (62, 5376, 32, 16, 21504, 262144),
        "gemma3-12b": (48, 3840, 16, 8, 15360, 262144),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "minitron-8b": (32, 4096, 32, 8, 16384, 256000),
        "gpt3-350m": (24, 1024, 16, 16, 4096, 51200),
    }
    L, d, h, kv, ff, v = table[arch]
    assert cfg.num_layers == L and cfg.d_model == d and cfg.vocab_size == v
    if h is not None:
        assert cfg.num_heads == h and cfg.num_kv_heads == kv
    if ff is not None:
        assert cfg.d_ff == ff
    # structural features present
    if arch == "deepseek-v2-236b":
        assert cfg.mla and cfg.mla.kv_lora_rank == 512
        assert cfg.moe and cfg.moe.num_experts == 160 and cfg.moe.top_k == 6
    if arch == "jamba-1.5-large-398b":
        assert cfg.hybrid_pattern and cfg.moe.num_experts == 16
        assert cfg.ssm is not None
    if arch.startswith("gemma3"):
        assert cfg.layer_pattern.count("local") == 5
    if arch == "mamba2-130m":
        assert cfg.ssm and cfg.ssm.d_state == 128


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step_no_nans(arch):
    cfg = reduced(get_config(arch))
    lm = build_model(cfg, remat="full")
    parallel = ParallelismConfig(grad_accum=2)
    step_fn = make_train_step(lm, TrainConfig(warmup_steps=1), parallel)
    state = init_state(lm.init(jax.random.PRNGKey(0)))
    batch = _batch(cfg, 4, 16, jax.random.PRNGKey(1))
    new_state, metrics = jax.jit(step_fn)(state, batch)
    assert new_state.step == 1
    loss = float(metrics["loss"])
    assert 0.0 < loss < 20.0 and loss == loss  # finite, sane
    for leaf in jax.tree.leaves(new_state.params):
        assert not bool(jnp.isnan(leaf).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_decode_step_shapes(arch):
    cfg = reduced(get_config(arch))
    lm = build_model(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    cache = D.init_cache(lm, 2, 16)
    toks = jnp.zeros((2, 1), jnp.int32)
    logits, cache2 = D.decode_step(lm, params, cache, toks)
    assert logits.shape == (2, 1, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert int(cache2["pos"][0]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_shapes(arch):
    cfg = get_config(arch)
    for shape in SHAPES.values():
        specs = input_specs(cfg, shape)
        assert "tokens" in specs
        t = specs["tokens"]
        if shape.kind == "decode":
            assert t.shape == (shape.global_batch, 1)
        elif shape.kind == "train":
            assert t.shape == (shape.global_batch, shape.seq_len + 1)
        if cfg.family in ("vlm", "encdec") and shape.kind != "decode":
            assert "source_embeds" in specs
