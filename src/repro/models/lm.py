"""Unified language-model implementation for all assigned architectures.

One code path serves six families (dense / moe / ssm / hybrid / vlm /
encdec) by compiling a config into a *stage plan*:

* a **stage** is a ``lax.scan`` over ``count`` repetitions of a **body**;
* a body is a short, statically-unrolled list of **layer positions**
  (1 for homogeneous stacks; 5 for Llama-Vision's 4-self+1-cross period;
  8 for Jamba's 7-mamba+1-attn period);
* per-layer *metadata* that varies inside a homogeneous scan (Gemma-3's
  5:1 local:global window schedule) rides along as scanned arrays, so a
  single traced body serves every layer.

Parameters live in nested dicts with leading stack dims ``[count, ...]``;
the same tables drive initialization, sharding (via logical axis names) and
the UCP checkpoint layer — one source of truth.

Decode uses per-position ring-buffer KV caches (window layers keep
``window`` slots), compressed-latent caches for MLA (DeepSeek), and
(conv, ssm-state) caches for Mamba blocks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from .attention import (
    chunked_attention,
    decode_attention,
    full_attention,
)
from .common import (
    ParamDef,
    ParamRegistry,
    apply_rope,
    gelu_mlp,
    rms_norm,
    rotary_embedding,
    swiglu,
)
from .moe import capacity_per_group, moe_block
from .ssm import (
    causal_conv1d,
    conv_decode_step,
    ssd_chunked,
    ssm_decode_step,
)

__all__ = ["LayerDef", "StageDef", "LM", "build_lm"]


# ---------------------------------------------------------------------------
# Stage planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LayerDef:
    name: str               # body-position name (param subtree key)
    kind: str               # "attn" | "mamba" | "cross"
    window: int = 0         # 0=full; -1=per-layer scanned metadata
    moe: bool = False
    with_mlp: bool = True
    with_cross: bool = False  # whisper-style: self-attn THEN cross-attn
    causal: bool = True


@dataclasses.dataclass(frozen=True)
class StageDef:
    name: str
    count: int
    body: tuple[LayerDef, ...]
    windows: tuple[int, ...] = ()  # len == count when any body window == -1


def plan_stages(cfg: ModelConfig) -> list[StageDef]:
    """Compile a config's layer schedule into scan stages."""
    if cfg.family == "ssm":
        return [
            StageDef(
                "layers",
                cfg.num_layers,
                (LayerDef("blk", "mamba", with_mlp=False),),
            )
        ]

    if cfg.family == "hybrid":
        kinds = cfg.hybrid_pattern
        moe_mask = cfg.moe_layer_mask()
        period = len(kinds)
        body = tuple(
            LayerDef(f"p{i}_{k}", k, moe=moe_mask[i]) for i, k in enumerate(kinds)
        )
        return [StageDef("periods", cfg.num_layers // period, body)]

    if cfg.family == "vlm":
        k = cfg.cross_attn.every_k_layers
        assert cfg.num_layers % k == 0
        body = tuple(
            [LayerDef(f"self{i}", "attn") for i in range(k - 1)]
            + [LayerDef("cross", "cross", causal=False)]
        )
        return [StageDef("periods", cfg.num_layers // k, body)]

    if cfg.family == "encdec":
        return [
            StageDef(
                "dec_layers",
                cfg.num_layers,
                (LayerDef("blk", "attn", with_cross=True),),
            )
        ]

    # dense / moe decoders: one homogeneous scan (+ optional dense head for
    # DeepSeek-style leading dense layers).
    windows = tuple(cfg.window_for_layer(i) for i in range(cfg.num_layers))
    uniform_window = len(set(windows)) == 1
    moe_mask = cfg.moe_layer_mask()
    stages: list[StageDef] = []
    start = 0
    if cfg.moe and cfg.moe.first_dense_layers:
        nd = cfg.moe.first_dense_layers
        stages.append(
            StageDef(
                "head",
                nd,
                (LayerDef("blk", "attn", window=windows[0], moe=False),),
            )
        )
        start = nd
    assert all(moe_mask[start:]) or not any(moe_mask[start:]), (
        "non-uniform MoE cadence requires the hybrid/period planner"
    )
    w = windows[start] if uniform_window else -1
    stages.append(
        StageDef(
            "layers",
            cfg.num_layers - start,
            (LayerDef("blk", "attn", window=w, moe=bool(moe_mask[start] if cfg.moe else False)),),
            windows=() if uniform_window else windows[start:],
        )
    )
    return stages


# ---------------------------------------------------------------------------
# Parameter tables
# ---------------------------------------------------------------------------


def _attn_defs(cfg: ModelConfig, prefix: str, stack: tuple[int, ...]) -> list[ParamDef]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    stacked = len(stack) > 0
    defs: list[ParamDef] = []

    def P(name, shape, axes, **kw):
        defs.append(
            ParamDef(
                f"{prefix}.{name}",
                stack + tuple(shape),
                ("layers",) * len(stack) + tuple(axes),
                stacked=stacked,
                **kw,
            )
        )

    P("attn_norm", (d,), ("embed",), init="ones")
    if cfg.mla is not None:
        m = cfg.mla
        qk = m.qk_nope_head_dim + m.qk_rope_head_dim
        P("wq_a", (d, m.q_lora_rank), ("embed", "lora"), fan_in_dim=len(stack))
        P("q_norm", (m.q_lora_rank,), ("lora",), init="ones")
        P("wq_b", (m.q_lora_rank, hq * qk), ("lora", "heads"), fan_in_dim=len(stack))
        P(
            "wkv_a",
            (d, m.kv_lora_rank + m.qk_rope_head_dim),
            ("embed", "lora"),
            fan_in_dim=len(stack),
        )
        P("kv_norm", (m.kv_lora_rank,), ("lora",), init="ones")
        P(
            "wkv_b",
            (m.kv_lora_rank, hq * (m.qk_nope_head_dim + m.v_head_dim)),
            ("lora", "heads"),
            fan_in_dim=len(stack),
        )
        P("wo", (hq * m.v_head_dim, d), ("heads", "embed"), fan_in_dim=len(stack))
    else:
        P(
            "wqkv",
            (d, (hq + 2 * hkv) * hd),
            ("embed", "qkv_fused"),
            parts=(("q", hq * hd), ("k", hkv * hd), ("v", hkv * hd)),
            parts_dim=len(stack) + 1,
            kind="fused_qkv",
            fan_in_dim=len(stack),
        )
        P("wo", (hq * hd, d), ("heads", "embed"), fan_in_dim=len(stack))
    return defs


def _cross_defs(cfg: ModelConfig, prefix: str, stack, *, gated: bool) -> list[ParamDef]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    hq, hkv = cfg.num_heads, cfg.num_kv_heads
    src = cfg.cross_attn.source_dim if cfg.cross_attn else d
    stacked = len(stack) > 0
    defs = []

    def P(name, shape, axes, **kw):
        defs.append(
            ParamDef(
                f"{prefix}.{name}",
                stack + tuple(shape),
                ("layers",) * len(stack) + tuple(axes),
                stacked=stacked,
                **kw,
            )
        )

    P("cross_norm", (d,), ("embed",), init="ones")
    P("cross_wq", (d, hq * hd), ("embed", "heads"), fan_in_dim=len(stack))
    P(
        "cross_wkv",
        (src, 2 * hkv * hd),
        ("embed", "qkv_fused"),
        parts=(("k", hkv * hd), ("v", hkv * hd)),
        parts_dim=len(stack) + 1,
        kind="fused_qkv",
        fan_in_dim=len(stack),
    )
    P("cross_wo", (hq * hd, d), ("heads", "embed"), fan_in_dim=len(stack))
    if gated:
        P("cross_gate", (1,), ("scalar",), init="zeros")
    return defs


def _mlp_defs(cfg: ModelConfig, prefix: str, stack, *, moe: bool) -> list[ParamDef]:
    d = cfg.d_model
    stacked = len(stack) > 0
    defs = []

    def P(name, shape, axes, **kw):
        defs.append(
            ParamDef(
                f"{prefix}.{name}",
                stack + tuple(shape),
                ("layers",) * len(stack) + tuple(axes),
                stacked=stacked,
                **kw,
            )
        )

    P("mlp_norm", (d,), ("embed",), init="ones")
    if moe:
        assert cfg.moe is not None
        e, f = cfg.moe.num_experts, cfg.moe.d_ff_expert
        P("router", (d, e), ("embed", "expert_router"), fan_in_dim=len(stack))
        P("we_gate", (e, d, f), ("expert", "embed", "expert_mlp"),
          kind="moe_expert", fan_in_dim=len(stack) + 1)
        P("we_up", (e, d, f), ("expert", "embed", "expert_mlp"),
          kind="moe_expert", fan_in_dim=len(stack) + 1)
        P("we_down", (e, f, d), ("expert", "expert_mlp", "embed"),
          kind="moe_expert", fan_in_dim=len(stack) + 1)
        if cfg.moe.num_shared:
            sf = cfg.moe.num_shared * f
            P("ws_gate", (d, sf), ("embed", "mlp"), fan_in_dim=len(stack))
            P("ws_up", (d, sf), ("embed", "mlp"), fan_in_dim=len(stack))
            P("ws_down", (sf, d), ("mlp", "embed"), fan_in_dim=len(stack))
    else:
        ff = cfg.d_ff
        if cfg.family == "encdec" or cfg.name.startswith("gpt3"):
            P("w1", (d, ff), ("embed", "mlp"), fan_in_dim=len(stack))
            P("w2", (ff, d), ("mlp", "embed"), fan_in_dim=len(stack))
        else:
            P("w_gate", (d, ff), ("embed", "mlp"), fan_in_dim=len(stack))
            P("w_up", (d, ff), ("embed", "mlp"), fan_in_dim=len(stack))
            P("w_down", (ff, d), ("mlp", "embed"), fan_in_dim=len(stack))
    return defs


def _mamba_defs(cfg: ModelConfig, prefix: str, stack) -> list[ParamDef]:
    s = cfg.ssm
    assert s is not None
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    g, n = s.n_groups, s.d_state
    conv_dim = di + 2 * g * n
    stacked = len(stack) > 0
    defs = []

    def P(name, shape, axes, **kw):
        defs.append(
            ParamDef(
                f"{prefix}.{name}",
                stack + tuple(shape),
                ("layers",) * len(stack) + tuple(axes),
                stacked=stacked,
                **kw,
            )
        )

    P("norm", (d,), ("embed",), init="ones")
    P(
        "in_proj",
        (d, 2 * di + 2 * g * n + nh),
        ("embed", "ssm_fused"),
        parts=(("z", di), ("x", di), ("B", g * n), ("C", g * n), ("dt", nh)),
        parts_dim=len(stack) + 1,
        kind="fused_qkv",
        fan_in_dim=len(stack),
    )
    P("conv_w", (conv_dim, s.d_conv), ("ssm_conv", "conv"))
    P("conv_b", (conv_dim,), ("ssm_conv",), init="zeros")
    P("a_log", (nh,), ("ssm_heads",), init="ssm_alog")
    P("d_skip", (nh,), ("ssm_heads",), init="ones")
    P("dt_bias", (nh,), ("ssm_heads",), init="ssm_dt")
    P("ssm_norm", (di,), ("ssm_inner",), init="ones")
    P("out_proj", (di, d), ("ssm_inner", "embed"), fan_in_dim=len(stack))
    return defs


def build_param_defs(cfg: ModelConfig, vocab_padded: int) -> ParamRegistry:
    defs: list[ParamDef] = [
        ParamDef("embed", (vocab_padded, cfg.d_model), ("vocab", "embed"),
                 fan_in_dim=1),
        ParamDef("final_norm", (cfg.d_model,), ("embed",), init="ones"),
    ]
    if not cfg.tie_embeddings:
        defs.append(
            ParamDef("unembed", (cfg.d_model, vocab_padded), ("embed", "vocab"),
                     fan_in_dim=0)
        )
    if cfg.encoder is not None:
        stack = (cfg.encoder.num_layers,)
        defs += _attn_defs(cfg, "encoder.blk", stack)
        defs += _mlp_defs(cfg, "encoder.blk", stack, moe=False)
        defs.append(ParamDef("encoder.norm", (cfg.d_model,), ("embed",), init="ones"))

    for stage in plan_stages(cfg):
        stack = (stage.count,)
        for ld in stage.body:
            prefix = f"{stage.name}.{ld.name}"
            if ld.kind == "mamba":
                defs += _mamba_defs(cfg, prefix, stack)
                if ld.with_mlp:
                    defs += _mlp_defs(cfg, prefix, stack, moe=ld.moe)
            elif ld.kind == "cross":
                defs += _cross_defs(cfg, prefix, stack, gated=True)
                defs += _mlp_defs(cfg, prefix, stack, moe=ld.moe)
            else:
                defs += _attn_defs(cfg, prefix, stack)
                if ld.with_cross:
                    defs += _cross_defs(cfg, prefix, stack, gated=False)
                defs += _mlp_defs(cfg, prefix, stack, moe=ld.moe)
    return ParamRegistry(defs)


# ---------------------------------------------------------------------------
# The model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LM:
    """Functional model: parameters in, tensors out.

    ``shard``: callback ``(x, logical_axes) -> x`` installed by the
    distribution layer (identity by default) — used for activation
    sharding constraints at stage boundaries.
    """

    cfg: ModelConfig
    vocab_padded: int
    registry: ParamRegistry
    stages: list[StageDef]
    compute_dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"  # "auto" | "full" | "chunked"
    moe_groups: int | None = None
    remat: str = "full"
    shard: Callable[[jax.Array, tuple[str, ...]], jax.Array] = lambda x, axes: x

    # ------------------------------------------------------------------ util
    def init(self, key: jax.Array) -> dict:
        return self.registry.init(key)

    def _attention(self, q, k, v, *, causal, window, q_offset=0):
        sq, skv = q.shape[1], k.shape[1]
        use_full = self.attn_impl == "full" or (
            self.attn_impl == "auto" and max(sq, skv) <= 2048
        )
        if use_full:
            return full_attention(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
        kv_block = max(b for b in (1024, 512, 500, 400, 256, 128, 100, 64, 32, 16, 8, 4, 2, 1)
                       if skv % b == 0)
        q_block = max(b for b in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
                      if sq % b == 0)
        return chunked_attention(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset, q_block=q_block,
                                 kv_block=kv_block)

    # ------------------------------------------------------- layer forwards
    def _self_attn(self, p, x, *, window, positions, causal=True, kv_out=None):
        cfg = self.cfg
        b, s, d = x.shape
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if cfg.mla is not None:
            out, kv = self._mla_attn(p, h, positions=positions, window=window)
        else:
            hd = cfg.resolved_head_dim
            hq, hkv = cfg.num_heads, cfg.num_kv_heads
            qkv = jnp.einsum("bsd,df->bsf", h, p["wqkv"].astype(h.dtype))
            q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
            q = q.reshape(b, s, hq, hd)
            k = k.reshape(b, s, hkv, hd)
            v = v.reshape(b, s, hkv, hd)
            sin, cos = rotary_embedding(positions, hd, cfg.rope_theta)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
            q = self.shard(q, ("batch", "seq", "heads", "head_dim"))
            o = self._attention(q, k, v, causal=causal, window=window)
            out = jnp.einsum(
                "bsf,fd->bsd", o.reshape(b, s, hq * hd), p["wo"].astype(h.dtype)
            )
            kv = (k, v)
        if kv_out is not None:
            kv_out.append(kv)
        return x + self.shard(out, ("batch", "seq", "embed")), kv

    def _mla_attn(self, p, h, *, positions, window):
        cfg, m = self.cfg, self.cfg.mla
        b, s, d = h.shape
        hq = cfg.num_heads
        nope, rope, vhd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        qk = nope + rope
        qa = jnp.einsum("bsd,dr->bsr", h, p["wq_a"].astype(h.dtype))
        qa = rms_norm(qa, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rf->bsf", qa, p["wq_b"].astype(h.dtype)).reshape(
            b, s, hq, qk
        )
        kva = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"].astype(h.dtype))
        c_kv, k_rope = kva[..., : m.kv_lora_rank], kva[..., m.kv_lora_rank :]
        c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
        kvb = jnp.einsum("bsr,rf->bsf", c_kv, p["wkv_b"].astype(h.dtype)).reshape(
            b, s, hq, nope + vhd
        )
        k_nope, v = kvb[..., :nope], kvb[..., nope:]
        sin, cos = rotary_embedding(positions, rope, cfg.rope_theta)
        q_nope, q_rope = q[..., :nope], q[..., nope:]
        q_rope = apply_rope(q_rope, sin, cos)
        k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)  # 1 shared head
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (b, s, hq, rope))], axis=-1
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        o = self._attention(q, k, v, causal=True, window=window)
        out = jnp.einsum("bsf,fd->bsd", o.reshape(b, s, hq * vhd),
                         p["wo"].astype(h.dtype))
        return out, (c_kv, k_rope[:, :, 0, :])

    def _cross_attn(self, p, x, source, *, gated):
        cfg = self.cfg
        b, s, d = x.shape
        hd = cfg.resolved_head_dim
        hq, hkv = cfg.num_heads, cfg.num_kv_heads
        h = rms_norm(x, p["cross_norm"], cfg.norm_eps)
        q = jnp.einsum("bsd,df->bsf", h, p["cross_wq"].astype(h.dtype)).reshape(
            b, s, hq, hd
        )
        kv = jnp.einsum(
            "bxe,ef->bxf", source.astype(h.dtype), p["cross_wkv"].astype(h.dtype)
        )
        k, v = jnp.split(kv, 2, axis=-1)
        k = k.reshape(b, -1, hkv, hd)
        v = v.reshape(b, -1, hkv, hd)
        o = self._attention(q, k, v, causal=False, window=0)
        out = jnp.einsum("bsf,fd->bsd", o.reshape(b, s, hq * hd),
                         p["cross_wo"].astype(h.dtype))
        if gated:
            out = out * jnp.tanh(p["cross_gate"].astype(out.dtype))
        return x + out, (k, v)

    def _mlp(self, p, x, *, moe: bool):
        cfg = self.cfg
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        aux = jnp.zeros((), jnp.float32)
        if moe:
            out, aux = moe_block(
                h, p["router"], p["we_gate"], p["we_up"], p["we_down"], cfg.moe,
                groups=self.moe_groups,
            )
            if cfg.moe.num_shared:
                out = out + swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"])
        elif "w1" in p:
            out = gelu_mlp(h, p["w1"], p["w2"])
        else:
            out = swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        return x + self.shard(out, ("batch", "seq", "embed")), aux

    def _mamba(self, p, x, *, h0=None, conv0=None, return_state=False):
        cfg, s = self.cfg, self.cfg.ssm
        b, sl, d = x.shape
        di = s.d_inner(d)
        nh = s.n_heads(d)
        g, n = s.n_groups, s.d_state
        with jax.named_scope("mamba.in_proj"):
            h = rms_norm(x, p["norm"], cfg.norm_eps)
            zxbcdt = jnp.einsum("bsd,df->bsf", h, p["in_proj"].astype(h.dtype))
            z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * g * n], axis=-1)
        conv_tail = xbc[:, -(s.d_conv - 1):, :] if return_state else None
        with jax.named_scope("mamba.conv"):
            cw = p["conv_w"].astype(h.dtype)
            cb = p["conv_b"].astype(h.dtype)
            if conv0 is not None:
                xbc_ext = jnp.concatenate([conv0, xbc], axis=1)
                xbc = causal_conv1d(xbc_ext, cw, cb)[:, s.d_conv - 1:]
            else:
                xbc = causal_conv1d(xbc, cw, cb)
        xin, bmat, cmat = jnp.split(xbc, [di, di + g * n], axis=-1)
        xin = xin.reshape(b, sl, nh, s.head_dim)
        bmat = bmat.reshape(b, sl, g, n)
        cmat = cmat.reshape(b, sl, g, n)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        a = -jnp.exp(p["a_log"].astype(jnp.float32))
        chunk = min(s.chunk, sl)
        while sl % chunk:
            chunk //= 2
        y, h_final = ssd_chunked(xin, dt, a, bmat, cmat, chunk=chunk, h0=h0)
        y = y + xin * p["d_skip"].astype(y.dtype)[None, None, :, None]
        with jax.named_scope("mamba.gate_norm"):
            y = y.reshape(b, sl, di) * jax.nn.silu(z)
            y = rms_norm(y, p["ssm_norm"], cfg.norm_eps)
        with jax.named_scope("mamba.out_proj"):
            out = jnp.einsum("bsf,fd->bsd", y, p["out_proj"].astype(y.dtype))
        state = (h_final, conv_tail) if return_state else None
        return x + self.shard(out, ("batch", "seq", "embed")), state

    # ------------------------------------------------------------- forward
    def _run_layer(self, ld: LayerDef, p, x, *, window, positions, source):
        aux = jnp.zeros((), jnp.float32)
        if ld.kind == "mamba":
            x, _ = self._mamba(p, x)
        elif ld.kind == "cross":
            x, _ = self._cross_attn(p, x, source, gated=True)
        else:
            x, _ = self._self_attn(
                p, x, window=window, positions=positions, causal=ld.causal
            )
            if ld.with_cross:
                x, _ = self._cross_attn(p, x, source, gated=False)
        if ld.with_mlp:
            x, aux = self._mlp(p, x, moe=ld.moe)
        return x, aux

    def _stage_forward(self, stage: StageDef, params, x, *, positions, source):
        def body(carry, step):
            h, aux = carry
            sp, win = step
            for ld in stage.body:
                w = win if ld.window == -1 else jnp.asarray(ld.window)
                h, a = self._run_layer(
                    ld, sp[ld.name], h, window=w, positions=positions, source=source
                )
                aux = aux + a
            return (h, aux), None

        if self.remat != "none":
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if self.remat == "dots"
                else jax.checkpoint_policies.nothing_saveable
            )
            body = jax.checkpoint(body, policy=policy)

        wins = (
            jnp.asarray(stage.windows, jnp.int32)
            if stage.windows
            else jnp.zeros((stage.count,), jnp.int32)
        )
        (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                   (params, wins))
        return x, aux

    def encode(self, params, source_embeds):
        """Whisper encoder: bidirectional stack over frame embeddings."""
        cfg = self.cfg
        x = source_embeds.astype(self.compute_dtype)
        p = params["encoder"]["blk"]
        positions = jnp.arange(x.shape[1])

        def body(h, sp):
            h, _ = self._self_attn(
                sp, h, window=0, positions=positions, causal=False
            )
            h, _ = self._mlp(sp, h, moe=False)
            return h, None

        if self.remat != "none":
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, p)
        return rms_norm(x, params["encoder"]["norm"], cfg.norm_eps)

    def forward(self, params, tokens, *, source_embeds=None, positions=None):
        """tokens [B,S] → logits [B,S,vocab_padded] (+ aux loss scalar)."""
        cfg = self.cfg
        x = params["embed"].astype(self.compute_dtype)[tokens]
        x = self.shard(x, ("batch", "seq", "embed"))
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        source = None
        if cfg.encoder is not None:
            source = self.encode(params, source_embeds)
        elif cfg.cross_attn is not None:
            source = source_embeds
        aux = jnp.zeros((), jnp.float32)
        for stage in self.stages:
            x, a = self._stage_forward(
                stage, params[stage.name], x, positions=positions, source=source
            )
            aux = aux + a
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        unembed = (
            params["embed"].T if cfg.tie_embeddings else params["unembed"]
        )
        logits = jnp.einsum(
            "bsd,dv->bsv", x, unembed.astype(self.compute_dtype),
            preferred_element_type=jnp.float32,
        )
        return self.shard(logits, ("batch", "seq", "vocab")), aux

    def loss_fn(self, params, batch):
        """Next-token cross-entropy over the logical vocabulary."""
        tokens = batch["tokens"]
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        logits, aux = self.forward(
            params, inputs, source_embeds=batch.get("source_embeds")
        )
        logits = logits[..., : self.cfg.vocab_size]  # mask alignment padding
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        loss = nll.mean()
        total = loss
        if self.cfg.moe is not None:
            total = total + self.cfg.moe.router_aux_weight * aux
        return total, {"loss": loss, "aux": aux}


def build_lm(
    cfg: ModelConfig,
    *,
    vocab_multiple: int = 1,
    compute_dtype=jnp.bfloat16,
    attn_impl: str = "auto",
    remat: str = "full",
    moe_groups: int | None = None,
    shard: Callable[[jax.Array, tuple[str, ...]], jax.Array] | None = None,
) -> LM:
    """Construct the model for a config.

    ``vocab_multiple``: alignment multiple for the embedding/unembedding
    vocab dim (product of the mesh-axis sizes that shard it).  The padded
    region is runtime-only — UCP atoms store the logical vocab and
    ``StripPadding``/re-pad handle Source/Target multiple changes.
    """
    vp = -(-cfg.vocab_size // vocab_multiple) * vocab_multiple
    return LM(
        cfg=cfg,
        vocab_padded=vp,
        registry=build_param_defs(cfg, vp),
        stages=plan_stages(cfg),
        compute_dtype=compute_dtype,
        attn_impl=attn_impl,
        remat=remat,
        moe_groups=moe_groups,
        shard=shard or (lambda x, axes: x),
    )
