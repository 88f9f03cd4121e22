"""Training launcher CLI.

Examples::

    # fresh run on a 2x2 host-device mesh (CPU simulation)
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --host-devices 4 --mesh data=2,model=2 --steps 20 --batch 8 --seq 64 \
        --ckpt-dir /tmp/run1

    # elastic resume of the same run on a DIFFERENT mesh/parallelism —
    # the trainer detects the layout change and goes through UCP atoms
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --host-devices 8 --mesh data=8,model=1 --steps 20 --batch 8 --seq 64 \
        --ckpt-dir /tmp/run1

``--host-devices N`` is a CPU simulation on every machine: it selects the
CPU backend and gives it N devices.  Both must be applied before jax
initializes, hence the environment mutation at the very top of ``main``
and all deferred imports.
``--log-json`` emits one JSON object per step on stdout (consumed by the
e2e reconfiguration tests and the correctness benchmark).
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="repro trainer")
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true", help="tiny same-family config")
    p.add_argument("--host-devices", type=int, default=0,
                   help="simulate N devices on the CPU (sets JAX_PLATFORMS=cpu "
                        "and XLA_FLAGS before jax init), also on a machine "
                        "with an accelerator")
    p.add_argument("--mesh", default="data=1,model=1")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--save-interval", type=int, default=10)
    p.add_argument("--hot-interval", type=int, default=None,
                   help="capture an in-memory peer-replicated snapshot every "
                   "N steps (repro.hot); every save-interval/hot-interval-th "
                   "snapshot is drained to disk in the background")
    p.add_argument("--hot-replication", type=int, default=1)
    p.add_argument("--save-mode", default="dedup",
                   choices=("dedup", "all", "delta"),
                   help="'delta': steady-state disk saves write only the "
                   "shards whose content changed since the previous commit")
    p.add_argument("--full-interval", type=int, default=8,
                   help="with --save-mode delta: every Nth disk save is a "
                   "full rebase, bounding the delta chain length")
    p.add_argument("--keep-last", type=int, default=10)
    p.add_argument("--codec", default=None, metavar="TAG",
                   help="code optimizer-moment shards with this block-quant "
                   "tag (e.g. int8:b256, fp8:e4m3:b256); params stay raw "
                   "(bit-exact).  See repro.core.codec")
    p.add_argument("--codec-params", default=None, metavar="TAG",
                   help="code parameter shards too; lossless tags only "
                   "(raw, int8ef:bN) unless you know what you are doing")
    p.add_argument("--sync-save", action="store_true")
    p.add_argument("--zero", type=int, default=3, choices=(1, 2, 3))
    p.add_argument("--no-fsdp", action="store_true")
    p.add_argument("--no-tp", action="store_true")
    p.add_argument("--no-sp", action="store_true")
    p.add_argument("--no-ep", action="store_true")
    p.add_argument("--pipe-axis", default=None)
    p.add_argument("--grad-accum", type=int, default=1)
    p.add_argument("--remat", default="full", choices=("none", "full", "dots"))
    p.add_argument("--moment-dtype", default="float32")
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--warmup", type=int, default=10)
    p.add_argument("--total-steps", type=int, default=200)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-json", action="store_true")
    p.add_argument("--trace", default=None, metavar="PATH",
                   help="record an obs trace of the run and export it as a "
                   "Chrome trace-event JSON (Perfetto-loadable) at PATH")
    return p


def simulate_host_devices(n: int) -> None:
    """Run on ``n`` simulated CPU devices, even where an accelerator is the
    default backend.  Must run before jax initializes."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n} "
        + os.environ.get("XLA_FLAGS", "")
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.host_devices:
        simulate_host_devices(args.host_devices)

    # obs is jax-free, safe to import before XLA_FLAGS matters
    import repro.obs as obs

    tracer = obs.enable() if args.trace else None
    try:
        return _run(args)
    finally:
        if tracer is not None:
            obs.write_chrome_trace(args.trace, tracer)
            obs.disable(tracer)


def _run(args) -> int:
    # jax-dependent imports only after JAX_PLATFORMS/XLA_FLAGS are final
    import jax

    from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
    from repro.ckpt.policy import CheckpointPolicy
    from repro.core.codec import CodecPolicy
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_from_string
    from repro.train.trainer import Trainer

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    jmesh = make_mesh_from_string(args.mesh)
    names = jmesh.axis_names
    data_axes = tuple(a for a in ("pod", "data") if a in names)
    parallel = ParallelismConfig(
        data_axes=data_axes or ("data",),
        model_axis="model",
        pipe_axis=args.pipe_axis if (args.pipe_axis in names if args.pipe_axis else False) else ("pipe" if "pipe" in names else None),
        fsdp=not args.no_fsdp,
        zero=args.zero,
        tensor_parallel=not args.no_tp,
        expert_parallel=not args.no_ep,
        sequence_parallel=not args.no_sp,
        moment_dtype=args.moment_dtype,
        remat=args.remat,
        grad_accum=args.grad_accum,
    )
    tcfg = TrainConfig(
        learning_rate=args.lr,
        warmup_steps=args.warmup,
        total_steps=args.total_steps,
        seed=args.seed,
    )

    codec = None
    if args.codec is not None or args.codec_params is not None:
        moments = args.codec or "raw"
        codec = CodecPolicy(
            params=args.codec_params or "raw",
            exp_avg=moments,
            exp_avg_sq=moments,
            allow_lossy_params=args.codec_params is not None,
        )
    policy = CheckpointPolicy(
        keep_last=args.keep_last,
        save_interval=args.save_interval,
        hot_interval=args.hot_interval,
        hot_replication=args.hot_replication,
        async_save=not args.sync_save,
        save_mode=args.save_mode,
        full_interval=args.full_interval,
        codec=codec,
    )
    trainer = Trainer.create(
        cfg, parallel, tcfg, jmesh,
        batch_size=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        policy=policy,
    )
    state, info = trainer.init_or_restore()
    start = int(jax.device_get(state.step))
    if info is not None:
        print(
            json.dumps(
                {
                    "event": "restored",
                    "step": info.step,
                    "mode": info.mode.value,
                    "reason": info.reason,
                    "load_s": round(info.wall_time_s, 3),
                }
            ),
            flush=True,
        )

    def log(rec):
        if args.log_json:
            print(json.dumps({"event": "step", **rec}), flush=True)
        else:
            print(
                f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                f"gnorm {rec['grad_norm']:.3f} ({rec['dt']*1e3:.0f} ms)",
                flush=True,
            )

    remaining = args.steps - start
    if remaining > 0:
        state, _ = trainer.run(state, start, remaining, log=log)
    if trainer.manager is not None:
        trainer.manager.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
