"""Publish → fleet: one training job feeds eight serving replicas.

The fan-out story (DESIGN.md §7): a trainer on a ``data=2,model=2`` mesh
publishes every committed step to a :class:`PublicationRegistry`; eight
decode-layout replicas (TP degree 1, weights only) subscribe and restore
through the peer tier — the checkpoint leaves disk roughly once for the
whole fleet, every peer fetch is digest-verified, and a later *delta*
publication updates the live replicas in place.  Both generations are
asserted bit-identical to a direct disk restore.

Runs on a single CPU (4 simulated chips) in ~a minute::

    PYTHONPATH=src python examples/publish_fleet.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import numpy as np

from repro.configs import ParallelismConfig, TrainConfig, get_config, reduced
from repro.ckpt.policy import CheckpointPolicy
from repro.ckpt.restore import state_from_dist
from repro.core import DistCheckpoint, MeshSpec
from repro.core.engine import CheckpointEngine
from repro.core.pytree import flatten_with_paths
from repro.dist.sharding import ShardingPlan
from repro.launch.mesh import make_mesh
from repro.serve import FanoutStats, FleetReplica, PublicationRegistry
from repro.train.trainer import Trainer

N_REPLICAS = 8


def check_bit_identical(replicas, ckpt, plan, jmesh) -> None:
    ref = state_from_dist(ckpt, plan, jmesh, engine=CheckpointEngine(workers=1))
    want = {k: np.asarray(v) for k, v in flatten_with_paths(ref.params).items()}
    for r in replicas:
        got = r.flat_params()
        assert set(got) == set(want)
        for name, arr in got.items():
            assert np.array_equal(np.asarray(arr), want[name]), (r.name, name)
    print(f"  ✓ all {len(replicas)} replicas bit-identical to the disk restore")


def main() -> None:
    cfg = reduced(get_config("smollm-360m"))
    registry = PublicationRegistry(name="demo")

    with tempfile.TemporaryDirectory() as tmp:
        train_mesh = make_mesh((2, 2), ("data", "model"))
        trainer = Trainer.create(
            cfg, ParallelismConfig(), TrainConfig(warmup_steps=2),
            train_mesh, batch_size=8, seq_len=32,
            ckpt_dir=f"{tmp}/job",
            policy=CheckpointPolicy(
                save_interval=5, async_save=False, registry=registry,
            ),
        )
        print("training: data=2,model=2 — every committed save is published")
        state, _ = trainer.init_or_restore()
        state, _ = trainer.run(state, 0, 5, log=lambda r: print(
            f"  step {r['step']:3d}  loss {r['loss']:.4f}"))

        pub = registry.current()
        print(f"\npublication seq {pub.seq} ({pub.kind}): step {pub.step}, "
              f"{len(pub.digests)} shard digests")

        # The serving fleet: decode layout (TP 2→1), weights only, one
        # shared engine per host — the serving hot set assembles each
        # target region once for all eight replicas.
        decode_plan = ShardingPlan(
            mesh=MeshSpec.from_dict({"data": 1, "model": 1}),
            param_specs=trainer.plan.param_specs,
        )
        decode_jmesh = make_mesh((1, 1), ("data", "model"))
        engine = CheckpointEngine(workers=4)
        stats = FanoutStats()
        replicas = [
            FleetReplica(f"replica{i}", registry, decode_plan, decode_jmesh,
                         engine=engine, stats=stats)
            for i in range(N_REPLICAS)
        ]
        print(f"\nfleet restore: {N_REPLICAS} replicas subscribe and sync")
        for r in replicas:
            r.sync()
        fp32_bytes = sum(
            int(np.prod(s.runtime_shape)) * 4
            for s in trainer.plan.param_specs.values()
        )
        print(f"  fp32 payload on disk     {fp32_bytes / 1e6:9.1f} MB")
        print(f"  disk bytes read (fleet)  {stats.disk_bytes_read / 1e6:9.1f} MB "
              f"({stats.disk_fetches} fetches)")
        print(f"  peer fetches             {stats.peer_fetches:6d}  "
              f"local hits {stats.local_hits}")
        ckpt = DistCheckpoint.open(trainer.manager.step_dir(pub.step))
        check_bit_identical(replicas, ckpt, decode_plan, decode_jmesh)

        print("\ncontinuing training to step 10 — the next publish is a delta")
        state, _ = trainer.run(state, 5, 5, log=lambda r: print(
            f"  step {r['step']:3d}  loss {r['loss']:.4f}"))
        pub2 = registry.current()
        print(f"\npublication seq {pub2.seq} ({pub2.kind}): step {pub2.step}, "
              f"{len(pub2.changed)}/{len(pub2.digests)} shards changed")
        for r in replicas:
            r.sync()
        n_updated = len(replicas[0].last_update)
        n_params = len(replicas[0].flat_params())
        print(f"  in-place update: {n_updated}/{n_params} params rebuilt "
              f"per replica (unchanged arrays kept)")
        ckpt2 = DistCheckpoint.open(trainer.manager.step_dir(pub2.step))
        check_bit_identical(replicas, ckpt2, decode_plan, decode_jmesh)
        trainer.manager.close()


if __name__ == "__main__":
    main()
