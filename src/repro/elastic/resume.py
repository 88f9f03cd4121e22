"""Elastic resume orchestration: failure → plan → recover → continue.

This is the glue a cluster controller would call after detecting node
failures (or receiving opportunistic capacity):

    new_mesh_spec = propose_mesh(cfg, healthy_device_count)
    trainer = rebuild_trainer(..., new_mesh)
    state, info = trainer.init_or_restore()   # tiered, automatic

Two recovery regimes:

* **process survived** (a peer rank died, this job reconfigures in place):
  :func:`hot_recover` marks the dead ranks' host memory lost and takes the
  tiered ladder — HOT_DIRECT / HOT_RESHARD from the surviving in-memory
  replicas when they still cover the state, disk otherwise.  No disk read
  in the common case (the paper's negligible-cost resume, one tier up).
* **process restarted** (job rescheduled from scratch): host memory is
  gone, so ``init_or_restore`` lands on the disk ladder — DIRECT when the
  layout matches, otherwise RESHARD_STREAM (source fragments streamed
  straight into the new layout, zero intermediate bytes on disk), with
  VIA_UCP (the paper's convert-then-Load workflow) as the fallback.

On real hardware, failure detection comes from the platform (missing
heartbeats / NCCL-equivalent timeouts / preemption notices); in this
repository it is driven explicitly by the examples and tests
(``examples/elastic_resume.py`` kills a run and resumes on a different
simulated device count, then simulates in-process rank loss against the
hot tier).
"""

from __future__ import annotations

import dataclasses

import jax

from repro.configs.base import ModelConfig, ParallelismConfig, TrainConfig
from repro.launch.mesh import make_mesh
from repro.train.trainer import Trainer
from .planner import propose_mesh

__all__ = ["rebuild_on", "hot_recover", "ElasticEvent"]


@dataclasses.dataclass(frozen=True)
class ElasticEvent:
    """A capacity change the controller reacts to.

    ``failed_ranks``: logical ranks whose host memory died with them —
    the hot tier loses exactly those replicas (empty for scale events and
    whole-process restarts, where the tier is gone entirely).
    """

    healthy_devices: int
    reason: str  # "failure" | "scale_up" | "scale_down"
    failed_ranks: tuple[int, ...] = ()


def rebuild_on(
    event: ElasticEvent,
    cfg: ModelConfig,
    parallel: ParallelismConfig,
    tcfg: TrainConfig,
    *,
    batch_size: int,
    seq_len: int,
    ckpt_dir: str,
) -> Trainer:
    """Build a trainer for the post-event topology.

    The returned trainer's ``init_or_restore`` transparently reconfigures
    the latest checkpoint through UCP if the layout changed.
    """
    mesh_spec = propose_mesh(cfg, event.healthy_devices,
                             moment_dtype=parallel.moment_dtype)
    jmesh = make_mesh(mesh_spec.shape, mesh_spec.axis_names)
    return Trainer.create(
        cfg, parallel, tcfg, jmesh,
        batch_size=batch_size, seq_len=seq_len, ckpt_dir=ckpt_dir,
    )


def hot_recover(
    manager,
    event: ElasticEvent,
    jmesh: jax.sharding.Mesh,
    *,
    target_plan=None,
    verify: bool = False,
):
    """In-process recovery after peer-rank loss, preferring the hot tier.

    Marks ``event.failed_ranks``' host memory as lost in the manager's hot
    tier (each affected snapshot drops those replicas and re-keys its
    fragment indexes), then resumes through the tiered ladder: surviving
    in-memory replicas when they cover the state, disk otherwise.  Returns
    ``(state, RestoreInfo)`` or None when nothing committed exists.
    """
    if manager.hot is not None and event.failed_ranks:
        manager.hot.fail_ranks(event.failed_ranks)
    return manager.restore_latest(jmesh, target_plan=target_plan, verify=verify)
