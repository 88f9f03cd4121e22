"""What the benchmark feeds the system: its configuration, its weights and
its token stream, all made from the run's seed and nothing the program made.

* :func:`model_config` builds the program's ``ModelConfig`` from a file in
  ``chipbench/configs/`` — the file is the configuration as it is run;
* :func:`gen_params` draws every parameter from the seed by its name and
  shape (one jitted call); the plain reference draws the same weights
  through the same function;
* :class:`Feed` gives step ``i`` its ``[batch, seq + 1]`` token rows, every
  row drawn afresh from ``(seed, i)``.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


def seed_words(seed: int) -> tuple[np.uint32, np.uint32]:
    """A seed of any size as two 32-bit words (``PRNGKey`` keeps 32 bits)."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"--seed must be in [0, 2**64), got {seed}")
    return np.uint32(seed >> 32), np.uint32(seed & 0xFFFFFFFF)


def model_config(raw: dict):
    """``ModelConfig`` of a configuration file (``raw["model"]``)."""
    from repro.configs.base import ModelConfig, SSMConfig

    m = dict(raw["model"])
    if "ssm" in m:
        m["ssm"] = SSMConfig(**m["ssm"])
    return ModelConfig(name=raw["name"], source=raw["source"], **m)


def parallel_config(raw: dict):
    from repro.configs.base import ParallelismConfig

    p = raw["precision"]
    return ParallelismConfig(
        param_dtype=p["param_dtype"],
        compute_dtype=p["compute_dtype"],
        moment_dtype=p["moment_dtype"],
    )


def train_config(raw: dict, seed: int):
    from repro.configs.base import TrainConfig

    o = raw["optimizer"]
    return TrainConfig(
        learning_rate=o["learning_rate"], min_lr_ratio=o["min_lr_ratio"],
        warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
        weight_decay=o["weight_decay"], adam_b1=o["adam_b1"],
        adam_b2=o["adam_b2"], grad_clip=o["grad_clip"],
        seed=seed & 0x7FFFFFFF,
    )


# ---------------------------------------------------------------- weights
def _leaf(key, name: str, shape: tuple[int, ...]):
    """One parameter by its name: gains 1, conv bias 0, Mamba-2's A and dt
    in their published init ranges, matrices N(0, 1/fan_in)."""
    import jax
    import jax.numpy as jnp

    last = name.rsplit(".", 1)[-1]
    if last.endswith("norm") or last == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if last == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    if last == "a_log":  # A in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if last == "dt_bias":  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dt + jnp.log(-jnp.expm1(-dt))
    fan_in = shape[-1] if last in ("embed", "conv_w") else shape[-2]
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def gen_params(hi, lo, shapes: dict[str, tuple[int, ...]]) -> dict:
    """Flat ``{path: float32 array}``; leaf ``i`` of the sorted paths draws
    from ``fold_in(key(seed), i)``.  Traceable: call it under ``jit``."""
    import jax

    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), hi), lo)
    return {
        name: _leaf(jax.random.fold_in(key, i), name, shapes[name])
        for i, name in enumerate(sorted(shapes))
    }


def param_shapes(trainer) -> dict[str, tuple[int, ...]]:
    return {d.path: tuple(d.shape) for d in trainer.lm.registry}


def make_state_fn(trainer):
    """One jitted call ``(hi, lo) -> TrainState`` on the trainer's state
    shardings: weights from the seed, zero moments, step 0."""
    import jax
    import jax.numpy as jnp

    from repro.core.pytree import unflatten_from_paths
    from repro.train.optimizer import init_state

    shapes = param_shapes(trainer)
    moment = jnp.dtype(trainer.parallel.moment_dtype)
    param = jnp.dtype(trainer.parallel.param_dtype)
    sh = trainer._state_shardings(trainer.plan, trainer.jmesh)

    def make(hi, lo):
        flat = gen_params(hi, lo, shapes)
        params = unflatten_from_paths({k: v.astype(param) for k, v in flat.items()})
        return init_state(params, moment_dtype=moment)

    return jax.jit(make, out_shardings=sh)


# ----------------------------------------------------------------- tokens
@dataclasses.dataclass
class Feed:
    """Token rows for step index ``i`` (0-based): ``[batch, seq+1]`` int32,
    uniform over the vocabulary, drawn from ``(seed, i)``.  Installed as
    ``Trainer.batch`` so the window's own call takes the benchmark's rows."""

    seed: int
    vocab: int
    batch: int
    seq: int
    half: bool = False  # a planted fault: the step sees half of its rows

    def tokens(self, i: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 7919, int(i)]))
        return rng.integers(0, self.vocab, (self.batch, self.seq + 1), dtype=np.int32)

    def __call__(self, i: int) -> dict:
        t = self.tokens(i)
        return {"tokens": t[: self.batch // 2] if self.half else t}


# ------------------------------------------------------------------ FLOPs
def train_flops_per_token(cfg, seq: int) -> float:
    """Model FLOPs of one trained token: forward + backward, no remat.

    Dense (PaLM, App. B): ``6 N + 12 L H Q T`` — ``N`` every parameter that
    multiplies a token (a tied embedding counts once, as the unembedding
    matmul), ``L`` layers, ``H`` query heads of size ``Q``, ``T`` the
    sequence.  Mamba-2: ``6 N`` plus three times the SSD chunk terms per
    layer, chunk ``Q``, state ``N_s``, heads ``H`` of size ``P``, groups
    ``G``: ``2 G Q N_s`` (C·Bᵀ) + ``2 H Q P`` (masked scores · x) +
    ``2 H N_s P`` (chunk states) + ``2 H N_s P`` (state read-out).
    """
    n = _n_params(cfg)
    if cfg.ssm is None:
        hd = cfg.head_dim or cfg.d_model // cfg.num_heads
        return 6.0 * n + 12.0 * cfg.num_layers * cfg.num_heads * hd * seq
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.head_dim
    q = min(s.chunk, seq)
    ssd = (2 * s.n_groups * q * s.d_state + 2 * heads * q * s.head_dim
           + 4 * heads * s.d_state * s.head_dim)
    return 6.0 * n + 3.0 * cfg.num_layers * ssd


def _n_params(cfg) -> int:
    """Parameter count from the configuration's shapes alone."""
    d, L, v = cfg.d_model, cfg.num_layers, cfg.vocab_size
    n = v * d + d  # embedding (tied: also the unembedding) + final norm
    if not cfg.tie_embeddings:
        n += d * v
    if cfg.ssm is None:
        hd = cfg.head_dim or d // cfg.num_heads
        qkv = d * (cfg.num_heads + 2 * cfg.num_kv_heads) * hd
        per = d + qkv + cfg.num_heads * hd * d + d + 3 * d * cfg.d_ff
        return n + L * per
    s = cfg.ssm
    di = s.expand * d
    heads = di // s.head_dim
    conv = di + 2 * s.n_groups * s.d_state
    per = (d + d * (2 * di + 2 * s.n_groups * s.d_state + heads)
           + conv * s.d_conv + conv + 3 * heads + di + di * d)
    return n + L * per
