"""Quickstart: train → distributed checkpoint → UCP atoms → inspect.

Runs on a single CPU device in ~a minute::

    PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
from repro.ckpt.policy import CheckpointPolicy
from repro.core.atoms import UcpCheckpoint
from repro.core.convert import convert_to_ucp
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.patterns import StateKind
from repro.launch.mesh import make_mesh
from repro.train.trainer import Trainer


def main() -> None:
    cfg = reduced(get_config("smollm-360m"))
    print(f"model: {cfg.name}  layers={cfg.num_layers} d={cfg.d_model}")

    with tempfile.TemporaryDirectory() as tmp:
        jmesh = make_mesh((1, 1), ("data", "model"))
        trainer = Trainer.create(
            cfg, ParallelismConfig(), TrainConfig(warmup_steps=2),
            jmesh, batch_size=4, seq_len=32,
            ckpt_dir=f"{tmp}/run",
            policy=CheckpointPolicy(save_interval=5, async_save=False),
        )
        state, _ = trainer.init_or_restore()
        state, hist = trainer.run(state, 0, 10, log=lambda r: print(
            f"  step {r['step']:3d}  loss {r['loss']:.4f}"))

        step = trainer.manager.latest_step()
        ckpt = DistCheckpoint.open(trainer.manager.step_dir(step))
        print(f"\ndistributed checkpoint @ step {step}: "
              f"{ckpt.total_bytes()/1e6:.1f} MB across "
              f"{len(list(ckpt.root.glob('ranks/*')))} rank dirs")

        ucp, stats = convert_to_ucp(ckpt, f"{tmp}/ucp", workers=2)
        print(f"converted to UCP: {stats.atoms_written} atoms, "
              f"{stats.bytes_written/1e6:.1f} MB "
              f"({stats.throughput_mb_s():.0f} MB/s)")

        # inspect one atom: the consolidated embedding + its Adam moments
        name = "embed"
        info = ucp.manifest.atoms[name]
        print(f"\natom {name!r}: logical shape {info.logical_shape}")
        for kind in StateKind:
            arr = ucp.read_atom(name, kind)
            print(f"  {kind.value:12s} dtype={arr.dtype} "
                  f"|x|max={abs(arr[:8]).max():.4f} (lazy mmap read)")
        problems = ucp.validate()
        print(f"\nvalidate(): {'OK' if not problems else problems}")


if __name__ == "__main__":
    main()
