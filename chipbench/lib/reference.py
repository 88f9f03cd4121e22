"""Plain references of the benchmark's models: loss, gradient and AdamW
in ``jax.numpy`` at float32, written from the published descriptions.

Imports nothing of the program.  Weights come from
:func:`chipbench.lib.model.gen_params` (the benchmark's own draw from the
seed) under the program's parameter paths, which name each tensor.  One
layer of each model family is a file of its own,
``chipbench/references/<family>.py`` (``layer(m, x, p, m_cfg)``), found by
the configuration's ``model.family``; this module holds what they share:
the embedding, the layer stack, the final norm, the tied unembedding, the
mean next-token cross-entropy and the optimizer.

``mode`` sets every matmul's arithmetic: ``"f32"`` at the highest
precision (the reference), ``"int8"`` with each operand scaled by its
absolute maximum and rounded to int8 (the control: the precision below the
configuration's bfloat16 compute).

The optimizer follows the configuration's ``optimizer`` block: AdamW with
global-norm clipping, bias correction, linear warm-up then cosine decay,
and decoupled weight decay on every tensor stored with rank 2 or more.
"""

from __future__ import annotations

from typing import Callable

import numpy as np

ROW_BLOCK_TOKENS = 2048  # rows per gradient block: row_block * seq <= this


def matmul(mode: str) -> Callable:
    import jax
    import jax.numpy as jnp

    if mode == "f32":
        return lambda eq, a, b: jnp.einsum(
            eq, a, b, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    if mode == "int8":
        return _int8_matmul
    raise ValueError(f"unknown reference mode {mode!r}")


def _int8(x):
    """``x`` on the symmetric int8 grid of its absolute maximum: the
    integers (exact in bfloat16) and the scale."""
    import jax.numpy as jnp

    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.bfloat16), s


def _int8_matmul(eq, a, b):
    """The control's matmul: both operands rounded to int8 with a
    per-tensor absmax scale, multiplied exactly (integers in bfloat16,
    float32 accumulation) and scaled back.  The backward pass is the
    straight-through one of int8 training: the float32 gradients of the
    matmul of the rounded operands."""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def mm(a, b):
        (qa, sa), (qb, sb) = _int8(a), _int8(b)
        return jnp.einsum(eq, qa, qb, preferred_element_type=jnp.float32) * (sa * sb)

    def fwd(a, b):
        (qa, sa), (qb, sb) = _int8(a), _int8(b)
        da, db = qa.astype(jnp.float32) * sa, qb.astype(jnp.float32) * sb
        out = jnp.einsum(eq, qa, qb, preferred_element_type=jnp.float32) * (sa * sb)
        return out, (da, db)

    def bwd(res, g):
        da, db = res
        _, vjp = jax.vjp(lambda x, y: jnp.einsum(
            eq, x, y, precision=jax.lax.Precision.HIGHEST), da, db)
        return vjp(g)

    mm.defvjp(fwd, bwd)
    return mm(a, b)


def rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def nll_sum(params: dict, tokens, m_cfg: dict, mode: str):
    """Summed next-token negative log-likelihood of ``tokens [b, s+1]``."""
    import jax
    import jax.numpy as jnp

    from chipbench.lib.harness import load_module

    m = matmul(mode)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]
    prefix = "layers.blk."
    stack = {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}
    layer = load_module("references", m_cfg["family"]).layer
    body = jax.checkpoint(lambda h, p: (layer(m, h, p, m_cfg), None))
    x, _ = jax.lax.scan(body, x, stack)
    x = rms(x, params["final_norm"], m_cfg["norm_eps"])
    logits = m("bsd,vd->bsv", x, params["embed"])
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


class Reference:
    """Loss, gradient and AdamW of one configuration in one precision mode,
    each program jitted once and reused across seeds."""

    def __init__(self, raw_cfg: dict, mode: str = "f32"):
        import jax

        self.m_cfg = dict(raw_cfg["model"])
        self.opt = raw_cfg["optimizer"]
        self.mode = mode

        def vg(params, tokens):
            with jax.default_matmul_precision("highest"):
                return jax.value_and_grad(nll_sum)(params, tokens, self.m_cfg, mode)

        self._vg = jax.jit(vg)
        self._acc = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x + y, a, b))
        self._adam = jax.jit(self._adamw)

    def loss_and_grad(self, params, tokens: np.ndarray, half: bool = False):
        """Mean loss and gradient over the batch, computed in row blocks."""
        if half:  # a planted fault: half of the rows, the mean over the rest
            tokens = tokens[: tokens.shape[0] // 2]
        b, s1 = tokens.shape
        rows = max(1, min(b, ROW_BLOCK_TOKENS // (s1 - 1)))
        total, gsum = 0.0, None
        for i in range(0, b, rows):
            l, g = self._vg(params, tokens[i : i + rows])
            total = total + l
            gsum = g if gsum is None else self._acc(gsum, g)
        n = b * (s1 - 1)
        return total / n, {k: v / n for k, v in gsum.items()}

    def _adamw(self, params, m, v, g, t):
        import jax.numpy as jnp

        o = self.opt
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()))
        clip = jnp.minimum(1.0, o["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        warm = jnp.minimum(t / max(o["warmup_steps"], 1), 1.0)
        frac = jnp.clip((t - o["warmup_steps"])
                        / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0)
        scale = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
        lr = o["learning_rate"] * warm * scale
        b1, b2 = o["adam_b1"], o["adam_b2"]
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        new_p, new_m, new_v = {}, {}, {}
        for k in params:
            gk = g[k] * clip
            new_m[k] = b1 * m[k] + (1 - b1) * gk
            new_v[k] = b2 * v[k] + (1 - b2) * gk * gk
            u = (new_m[k] / c1) / (jnp.sqrt(new_v[k] / c2) + 1e-8)
            if params[k].ndim >= 2:
                u = u + o["weight_decay"] * params[k]
            new_p[k] = params[k] - lr * u
        return new_p, new_m, new_v, clip

    def train(self, params0: dict, batches: list[np.ndarray], half: bool = False) -> dict:
        """Train ``len(batches)`` steps from ``params0``; return the readings
        the comparison takes: losses, per-leaf norms of the first gradient as
        the optimizer gets it (clipped) and of the raw one, and per-leaf
        norms of the parameters' change."""
        import jax
        import jax.numpy as jnp

        norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x)) for k, x in t.items()})
        diff = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum((a[k] - b[k]) ** 2)) for k in a})
        p = params0
        m = {k: jnp.zeros_like(x) for k, x in p.items()}
        v = {k: jnp.zeros_like(x) for k, x in p.items()}
        losses, first = [], None
        for t, tok in enumerate(batches, start=1):
            loss, g = self.loss_and_grad(p, tok, half=half)
            losses.append(float(loss))
            p, m, v, clip = self._adam(p, m, v, g, jnp.float32(t))
            if t == 1:
                raw = {k: float(x) for k, x in norms(g).items()}
                c = float(clip)
                first = {"grad": {k: x * c for k, x in raw.items()}, "grad_raw": raw}
            del g
        change = {k: float(x) for k, x in diff(p, params0).items()}
        return {"losses": losses, **first, "change": change}
