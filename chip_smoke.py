#!/usr/bin/env python3
"""Chip smoke test: the main path on a TPU at full ``smollm-360m`` width.

::

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # a 2x2 host: layout-change resume only

One chip, in one process, through the entry points a user calls:

1. train: ``Trainer.create`` → ``init_or_restore`` → ``run`` on mesh
   ``data=1,model=1`` with the default async ``CheckpointManager``, saving
   at the last step of the first segment, then the uninterrupted
   continuation as the reference;
2. resume: a fresh ``Trainer`` over the same checkpoint directory must
   resume ``DIRECT``, its state bit-identical to a host snapshot of the
   saved state and its next losses equal to the reference bit for bit;
3. serve: a weights-only restore of the same checkpoint
   (``repro.launch.serve``), checked bit-identical to the saved weights,
   then a prefill and greedy decode checked against a full forward pass.

``--chips 4`` trains on ``data=2,model=2``, saves, and resumes under
``data=4,model=1`` (``RESHARD_STREAM``): the resumed state is
bit-identical to the saved one, every array spans all four devices, and
the next losses stay within 2e-2 of the uninterrupted 2x2 run.

The script exits non-zero, printing no result, when JAX finds no TPU.
Its last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "smollm-360m"
BATCH, SEQ = 8, 1024        # one step's activations + 12 B/param state fit 16 GB
SAVE_AT, CONTINUE = 3, 2    # steps before the save, steps compared after it
SERVE_BATCH, PROMPT, GEN = 4, 128, 8
LOSS_BAND = 2e-2            # cross-layout loss band of tests/test_reconfig_e2e.py
LOGIT_RTOL = 2e-2           # relative L2, prefill vs full forward (both bf16)


def log(phase: str, **rec) -> None:
    print(json.dumps({"phase": phase, **rec}), flush=True)


def dir_bytes(root: Path) -> int:
    return sum(p.stat().st_size for p in root.rglob("*") if p.is_file())


def make_trainer(cfg, mesh: str, ckpt_dir: str, *, batch: int, seq: int):
    from repro.ckpt.policy import CheckpointPolicy
    from repro.configs import ParallelismConfig, TrainConfig
    from repro.launch.mesh import make_mesh_from_string
    from repro.train.trainer import Trainer

    return Trainer.create(
        cfg, ParallelismConfig(), TrainConfig(), make_mesh_from_string(mesh),
        batch_size=batch, seq_len=seq, ckpt_dir=ckpt_dir,
        policy=CheckpointPolicy(save_interval=SAVE_AT),
    )


def assert_same_state(got: dict, want: dict, what: str) -> int:
    """Bit-for-bit comparison of two host snapshots; returns bytes compared."""
    import numpy as np

    if got.keys() != want.keys():
        raise AssertionError(f"{what}: param sets differ")
    n = 0
    for name, kinds in want.items():
        for kind, w in kinds.items():
            g = got[name][kind]
            if g.shape != w.shape or g.dtype != w.dtype:
                raise AssertionError(
                    f"{what}: {name}@{kind.value} is {g.dtype}{g.shape}, "
                    f"saved {w.dtype}{w.shape}"
                )
            bits = lambda a: np.ascontiguousarray(a).view(np.uint8)
            if not np.array_equal(bits(g), bits(w)):
                raise AssertionError(f"{what}: {name}@{kind.value} differs")
            n += w.nbytes
    return n


def train_and_save(cfg, mesh: str, ckpt_dir: str, *, batch: int, seq: int):
    """Train SAVE_AT steps (saving at the last), snapshot the saved state,
    then continue CONTINUE steps uninterrupted.  Returns the snapshot and
    the reference losses."""
    from repro.ckpt.saver import snapshot_state

    t = make_trainer(cfg, mesh, ckpt_dir, batch=batch, seq=seq)
    state, info = t.init_or_restore()
    if info is not None:
        raise AssertionError(f"fresh directory restored step {info.step}")
    state, hist = t.run(state, 0, SAVE_AT)
    saved = snapshot_state(state)
    state, ref = t.run(state, SAVE_AT, CONTINUE)
    del state
    t.manager.close()
    step_dir = t.manager.step_dir(SAVE_AT)
    if t.manager.steps() != [SAVE_AT]:
        raise AssertionError(f"committed steps {t.manager.steps()}, want [{SAVE_AT}]")
    log(
        "train", mesh=mesh, batch=batch, seq=seq,
        first_step_s=hist[0]["dt"],  # includes the step's compilation
        step_s=[r["dt"] for r in hist[1:] + ref],
        losses=[r["loss"] for r in hist + ref],
        saved_step=SAVE_AT, saved_bytes=dir_bytes(step_dir),
    )
    return saved, [r["loss"] for r in ref]


def resume(cfg, mesh: str, ckpt_dir: str, saved: dict, *, batch: int, seq: int,
           want_mode):
    """Resume a fresh Trainer; check mode, state and sharding; return the
    next CONTINUE losses."""
    import jax

    from repro.ckpt.saver import snapshot_state

    t = make_trainer(cfg, mesh, ckpt_dir, batch=batch, seq=seq)
    state, info = t.init_or_restore()
    if info is None or info.mode is not want_mode or info.step != SAVE_AT:
        raise AssertionError(
            f"resume gave {info and (info.mode, info.step)}, "
            f"want ({want_mode}, {SAVE_AT})"
        )
    devices = set(t.jmesh.devices.flat)
    for leaf in jax.tree.leaves(state):
        if {s.device for s in leaf.addressable_shards} != devices:
            raise AssertionError(f"a restored array misses devices of {mesh}")
    nbytes = assert_same_state(snapshot_state(state), saved, "resumed state")
    state, hist = t.run(state, SAVE_AT, CONTINUE)
    del state
    t.manager.close()
    log(
        "resume", mesh=mesh, mode=info.mode.value, restore_s=info.wall_time_s,
        bytes_read=info.restore_stats.bytes_read, identical_bytes=nbytes,
        step_s=[r["dt"] for r in hist], losses=[r["loss"] for r in hist],
    )
    return [r["loss"] for r in hist]


def serve(cfg, mesh: str, ckpt_dir: str, saved: dict, *, seed: int = 0):
    """Weights-only restore + prefill + greedy decode through
    ``repro.launch.serve``, checked against the saved weights and a full
    forward pass of the same model."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.patterns import StateKind
    from repro.core.plan import ResumeMode
    from repro.core.pytree import flatten_with_paths
    from repro.launch import serve as S
    from repro.launch.mesh import make_mesh_from_string

    jmesh = make_mesh_from_string(mesh)
    lm, plan = S.build_server(cfg, jmesh)
    params, info = S.load_params(ckpt_dir, plan, jmesh)
    if info.mode is not ResumeMode.DIRECT or info.step != SAVE_AT:
        raise AssertionError(f"weights-only restore gave {info.mode}, {info.step}")
    host = flatten_with_paths(jax.device_get(params))
    nbytes = assert_same_state(
        {n: {StateKind.FP32: np.asarray(a)} for n, a in host.items()},
        {n: {StateKind.FP32: k[StateKind.FP32]} for n, k in saved.items()},
        "served weights",
    )
    prompt = jax.random.randint(
        jax.random.PRNGKey(seed), (SERVE_BATCH, PROMPT), 0, cfg.vocab_size
    )
    with jmesh:
        seq, logits, prefill_s, decode_s = S.generate(lm, params, prompt, GEN)
        full, _ = jax.jit(lm.forward)(params, jnp.concatenate([prompt, seq[:, :-1]], 1))
    seq, logits = np.asarray(seq), np.asarray(logits, np.float32)
    full = np.asarray(full[..., : cfg.vocab_size], np.float32)
    if seq.shape != (SERVE_BATCH, GEN) or logits.shape != (SERVE_BATCH, cfg.vocab_size):
        raise AssertionError(f"shapes {seq.shape}, {logits.shape}")
    if not np.isfinite(logits).all() or not np.isfinite(full).all():
        raise AssertionError("non-finite logits")
    ref = full[:, PROMPT - 1]
    rel = float(np.linalg.norm(logits - ref) / np.linalg.norm(ref))
    if rel > LOGIT_RTOL:
        raise AssertionError(f"prefill logits off the forward pass by {rel:.3g}")
    # Greedy tokens must be the forward pass's argmax wherever its top-2
    # margin is clear of bf16 noise.
    pos = full[:, PROMPT - 1 : PROMPT - 1 + GEN]
    top2 = np.sort(pos, -1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]) > 0.1
    agree = seq == pos.argmax(-1)
    if not agree[clear].all():
        raise AssertionError(f"greedy tokens disagree at {int((~agree & clear).sum())} clear positions")
    log(
        "serve", mesh=mesh, mode=info.mode.value, restore_s=info.wall_time_s,
        bytes_read=info.restore_stats.bytes_read, identical_bytes=nbytes,
        prefill_s=prefill_s, decode_s=decode_s, decode_steps=GEN - 1,
        prefill_rel_l2=rel, tokens_checked=int(clear.sum()),
        tokens_total=int(clear.size),
    )


def one_chip(cfg, ckpt_dir: str, *, batch: int = BATCH, seq: int = SEQ) -> None:
    from repro.core.plan import ResumeMode

    mesh = "data=1,model=1"
    saved, ref = train_and_save(cfg, mesh, ckpt_dir, batch=batch, seq=seq)
    got = resume(cfg, mesh, ckpt_dir, saved, batch=batch, seq=seq,
                 want_mode=ResumeMode.DIRECT)
    if got != ref:  # same program, same bytes: bit for bit
        raise AssertionError(f"resumed losses {got} != uninterrupted {ref}")
    serve(cfg, mesh, ckpt_dir, saved)


def four_chips(cfg, ckpt_dir: str, *, batch: int = BATCH, seq: int = SEQ) -> None:
    from repro.core.plan import ResumeMode

    saved, ref = train_and_save(cfg, "data=2,model=2", ckpt_dir, batch=batch, seq=seq)
    got = resume(cfg, "data=4,model=1", ckpt_dir, saved, batch=batch, seq=seq,
                 want_mode=ResumeMode.RESHARD_STREAM)
    gap = max(abs(a - b) for a, b in zip(got, ref))
    log("reshard", loss_gap=gap, band=LOSS_BAND)
    if gap >= LOSS_BAND:
        raise AssertionError(f"resharded losses {got} vs uninterrupted {ref}")


def device_summary() -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = p.parse_args(argv)

    enable_compile_cache()
    import jax

    from repro.configs import get_config

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    log("device", **device_summary())

    cfg = get_config(ARCH)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        (four_chips if args.chips == 4 else one_chip)(cfg, f"{tmp}/ckpt")
    summary = device_summary()
    log("memory", peak_bytes_in_use=summary.pop("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
