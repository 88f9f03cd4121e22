"""Serving launcher: load a checkpoint (any Source layout) and decode.

Demonstrates the weights-only restore path: serving needs the ``fp32``
parameter state (cast to the serving dtype) and skips the optimizer
moments entirely — one third of the checkpoint bytes.

::

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --reduced \
        --ckpt-dir /tmp/run1 --batch 4 --prompt-len 16 --gen 32

:func:`build_server`, :func:`load_params` and :func:`generate` are the
steps of ``main``; ``chip_smoke.py`` drives them directly.
"""

from __future__ import annotations

import argparse
import sys


def build_server(cfg, jmesh):
    """The serving model on ``jmesh`` and its sharding plan."""
    from repro.configs import ParallelismConfig
    from repro.core.layout import MeshSpec
    from repro.dist.sharding import make_plan, make_sharder, vocab_multiple
    from repro.models import build_model

    mspec = MeshSpec.from_mesh(jmesh)
    parallel = ParallelismConfig(
        data_axes=tuple(a for a in ("pod", "data") if mspec.has_axis(a)) or ("data",),
    )
    lm = build_model(
        cfg,
        vocab_multiple=vocab_multiple(parallel, mspec),
        remat="none",
        shard=make_sharder(parallel, jmesh),
    )
    return lm, make_plan(cfg, lm.registry, parallel, mspec)


def load_params(ckpt_dir, plan, jmesh):
    """Weights-only restore of the newest committed step:
    ``(params, RestoreInfo)``, or None when nothing is committed."""
    from repro.ckpt.manager import CheckpointManager
    from repro.ckpt.policy import CheckpointPolicy

    mgr = CheckpointManager(ckpt_dir, plan, policy=CheckpointPolicy(async_save=False))
    try:
        return mgr.restore_params(jmesh)
    finally:
        mgr.close()


def generate(lm, params, tokens, gen: int, *, cache_len: int = 0, extra=None):
    """Greedy decode: prefill ``tokens`` [B,S], then ``gen - 1`` steps.

    Returns ``(generated [B, gen], prefill_logits [B, V], prefill_s,
    decode_s)``; both timings end when the device has produced the result.
    """
    import jax
    import jax.numpy as jnp

    import repro.obs as obs
    from repro.models import decode as D

    b, s = tokens.shape
    cache = D.init_cache(lm, b, cache_len or (s + gen))
    with obs.timed("serve.prefill", batch=b, prompt_len=s) as sw:
        logits, cache = D.prefill(lm, params, cache, tokens, **(extra or {}))
        jax.block_until_ready((logits, cache))
    prefill_s = sw.elapsed_s
    step = jax.jit(lambda pp, cc, tt: D.decode_step(lm, pp, cc, tt))
    cur = jnp.argmax(logits, -1)[:, None]
    outs = [cur]
    with obs.timed("serve.decode", batch=b, steps=gen - 1) as sw:
        for _ in range(gen - 1):
            lg, cache = step(params, cache, cur)
            cur = jnp.argmax(lg[:, -1], -1)[:, None]
            outs.append(cur)
        jax.block_until_ready(cur)
    return jnp.concatenate(outs, 1), logits, prefill_s, sw.elapsed_s


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--host-devices", type=int, default=0,
                   help="simulate N devices on the CPU (a CPU run on every "
                        "machine; see repro.launch.train)")
    p.add_argument("--mesh", default="data=1,model=1")
    p.add_argument("--ckpt-dir", default=None, help="resume weights from here")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=32)
    p.add_argument("--cache-len", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.host_devices:
        from repro.launch.train import simulate_host_devices

        simulate_host_devices(args.host_devices)

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_from_string

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    jmesh = make_mesh_from_string(args.mesh)
    lm, plan = build_server(cfg, jmesh)

    res = load_params(args.ckpt_dir, plan, jmesh) if args.ckpt_dir else None
    if res is None:
        if args.ckpt_dir:
            print("no checkpoint found; serving from random init")
        params = lm.init(jax.random.PRNGKey(args.seed))
    else:
        params, info = res
        print(f"restored step {info.step} via {info.mode.value} "
              f"in {info.wall_time_s:.2f}s")

    b = args.batch
    key = jax.random.PRNGKey(args.seed)
    toks = jax.random.randint(key, (b, args.prompt_len), 0, cfg.vocab_size)
    extra = {}
    if cfg.cross_attn is not None:
        extra["source_embeds"] = jax.random.normal(
            key, (b, cfg.cross_attn.source_len, cfg.cross_attn.source_dim),
            jnp.bfloat16)
    if cfg.encoder is not None:
        extra["source_embeds"] = jax.random.normal(
            key, (b, cfg.encoder.source_len, cfg.d_model), jnp.bfloat16)

    with jmesh:
        seq, _, prefill_s, gen_s = generate(
            lm, params, toks, args.gen, cache_len=args.cache_len, extra=extra
        )
    print(f"prefill {args.prompt_len} toks × {b} reqs: {prefill_s*1e3:.0f} ms")
    print(f"decode  {args.gen - 1} steps × {b} reqs: {gen_s*1e3:.0f} ms "
          f"({b*(args.gen-1)/max(gen_s,1e-9):.0f} tok/s)")
    print("sample:", seq[0, :16].tolist())
    return 0


if __name__ == "__main__":
    sys.exit(main())
