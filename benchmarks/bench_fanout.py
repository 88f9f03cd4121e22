"""Fan-out fleet restore benchmarks (DESIGN.md §7).

``bench_fanout`` — N concurrent resharding readers (decode layout, weights
only) restoring one published checkpoint:

* ``fanout_independent_{1,32}`` — the baseline everyone runs today: each
  reader restores straight from disk with a private engine, so work and
  disk traffic scale linearly with N;
* ``fanout_readers_{1,8,32}`` — the same readers as a subscribed fleet on
  one registry + shared engine: the peer store and serving hot set make
  disk traffic O(1) in N and the restore work single-flight, so
  *aggregate* restore bandwidth scales with N instead of dividing by it.

Derived columns record aggregate bandwidth (N × fp32 payload / wall) and
the disk-bytes-read census.  At ``medium`` the acceptance bar is asserted:
32 fan-out readers ≥ 8× the aggregate bandwidth of 32 independent
readers, fleet disk bytes ≤ 2× a single reader's, and every replica
bit-identical to a direct disk restore.
"""

from __future__ import annotations

import threading

import jax
import numpy as np

from .bench_checkpointing import PARALLEL_WORKERS, SAVE_WORKERS, _timeit
from .common import bench_tmpdir, build_sized, default_mesh, state_nbytes

from repro.ckpt.engine import CheckpointEngine
from repro.ckpt.restore import build_param_arrays, state_from_dist
from repro.ckpt.saver import snapshot_state, write_distributed
from repro.configs import ParallelismConfig
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.layout import MeshSpec
from repro.core.pytree import flatten_with_paths
from repro.dist.sharding import ShardingPlan
from repro.launch.mesh import make_mesh
from repro.serve import FanoutStats, FleetReplica, PublicationRegistry

READER_COUNTS = (1, 8, 32)


def _run_threads(n, fn):
    errs: list[BaseException] = []

    def wrap(i):
        try:
            fn(i)
        except BaseException as e:  # pragma: no cover - re-raised below
            errs.append(e)

    threads = [threading.Thread(target=wrap, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]


def bench_fanout(sizes=("small", "medium")) -> list[tuple[str, float, str]]:
    rows = []
    mesh = default_mesh()
    parallel = ParallelismConfig()
    decode_mesh = MeshSpec.from_dict({"data": 1, "model": 1})
    jmesh = make_mesh((1, 1), ("data", "model"))
    for size in sizes:
        cfg, lm, plan, state = build_sized(size, mesh, parallel)
        snap = snapshot_state(state)
        fp32_bytes = state_nbytes(state) // 3  # weights-only payload
        with bench_tmpdir() as tmp:
            write_distributed(snap, plan, 1, f"{tmp}/step_1", workers=SAVE_WORKERS)
            ckpt = DistCheckpoint.open(f"{tmp}/step_1")
            decode_plan = ShardingPlan(
                mesh=decode_mesh, param_specs=plan.param_specs
            )
            ref = {
                k: np.asarray(v) for k, v in flatten_with_paths(
                    state_from_dist(
                        ckpt, decode_plan, jmesh,
                        engine=CheckpointEngine(workers=1),
                    ).params
                ).items()
            }

            def independent(n, out):
                def run():
                    def one(i):
                        arrs = build_param_arrays(
                            ckpt, decode_plan, jmesh,
                            engine=CheckpointEngine(workers=1),
                        )
                        if i == 0:
                            out["flat"] = arrs

                    _run_threads(n, one)

                # every private reader pulls the full payload from disk
                out["disk"] = n * fp32_bytes
                return _timeit(run, n=3 if n == 1 else 2)

            def fleet(n, out):
                def run():
                    registry = PublicationRegistry()
                    registry.publish(ckpt)
                    engine = CheckpointEngine(workers=PARALLEL_WORKERS)
                    stats = FanoutStats()
                    reps = [
                        FleetReplica(f"r{i}", registry, decode_plan, jmesh,
                                     engine=engine, stats=stats)
                        for i in range(n)
                    ]
                    _run_threads(n, lambda i: reps[i].sync())
                    out["disk"] = stats.disk_bytes_read
                    out["flats"] = [r.flat_params() for r in reps]

                return _timeit(run)

            ind: dict[int, dict] = {}
            for n in (1, 32):
                out: dict = {}
                t = independent(n, out)
                ind[n] = {"t": t, **out}
                bw = n * fp32_bytes / t / 1e9
                rows.append((
                    f"fanout_independent_{n}_{size}", t * 1e6,
                    f"{bw:.2f}GB/s_agg disk={out['disk'] / 1e6:.0f}MB",
                ))
            fleets: dict[int, dict] = {}
            for n in READER_COUNTS:
                out = {}
                t = fleet(n, out)
                fleets[n] = {"t": t, **out}
                bw = n * fp32_bytes / t / 1e9
                rows.append((
                    f"fanout_readers_{n}_{size}", t * 1e6,
                    f"{bw:.2f}GB/s_agg disk={out['disk'] / 1e6:.0f}MB",
                ))
                for flat in out["flats"]:
                    assert set(flat) == set(ref)
                    assert all(
                        np.array_equal(np.asarray(flat[k]), ref[k]) for k in ref
                    ), f"fanout replica diverged from disk restore ({size}, n={n})"
            if size == "medium":
                # The acceptance bar: fleet bandwidth scales, disk doesn't.
                bw_fan = 32 * fp32_bytes / fleets[32]["t"]
                bw_ind = 32 * fp32_bytes / ind[32]["t"]
                assert bw_fan >= 8 * bw_ind, (
                    f"32-reader fan-out {bw_fan / 1e9:.2f} GB/s < 8x "
                    f"independent {bw_ind / 1e9:.2f} GB/s"
                )
                assert fleets[32]["disk"] <= 2 * fleets[1]["disk"], (
                    f"fleet disk census {fleets[32]['disk']} > 2x single "
                    f"reader {fleets[1]['disk']}"
                )
    return rows
