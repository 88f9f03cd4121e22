"""Trainer: mesh + model + optimizer + data + checkpointing, end to end.

The resume path realizes the paper's workflow: on start-up the trainer asks
the CheckpointManager for the latest committed checkpoint; if the current
(mesh, parallelism, precision) equals the Source's, state streams back via
DIRECT per-rank reads; otherwise the manager streams Source fragments
straight into the new Target layout (RESHARD_STREAM, with converting to UCP
atoms and Loading them as the fallback) — training continues at the
checkpointed step with the same global data order (reshard-invariant
pipeline).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.obs as obs

from repro.configs.base import (
    ModelConfig,
    ParallelismConfig,
    TrainConfig,
)
from repro.core.layout import MeshSpec
from repro.ckpt.manager import CheckpointManager, RestoreInfo
from repro.ckpt.policy import CheckpointPolicy
from repro.dist.sharding import ShardingPlan, make_plan, make_sharder, vocab_multiple
from repro.models import build_model
from repro.models.lm import LM
from .data import batch_for_step
from .optimizer import TrainState, init_state
from .steps import make_train_step

__all__ = ["Trainer"]


@dataclasses.dataclass
class Trainer:
    cfg: ModelConfig
    parallel: ParallelismConfig
    tcfg: TrainConfig
    jmesh: jax.sharding.Mesh
    lm: LM
    plan: ShardingPlan
    manager: CheckpointManager | None
    step_fn: Callable
    batch_size: int
    seq_len: int
    data_seed: int

    # ------------------------------------------------------------- factory
    @classmethod
    def create(
        cls,
        cfg: ModelConfig,
        parallel: ParallelismConfig,
        tcfg: TrainConfig,
        jmesh: jax.sharding.Mesh,
        *,
        batch_size: int,
        seq_len: int,
        ckpt_dir: str | None = None,
        policy: CheckpointPolicy | None = None,
        grad_transform=None,
    ) -> "Trainer":
        """Checkpointing is configured by one
        :class:`~repro.ckpt.policy.CheckpointPolicy` (``policy=``; None
        means the policy's defaults)."""
        mesh_spec = MeshSpec.from_mesh(jmesh)
        lm = build_model(
            cfg,
            vocab_multiple=vocab_multiple(parallel, mesh_spec),
            remat=parallel.remat,
            shard=make_sharder(parallel, jmesh),
        )
        plan = make_plan(cfg, lm.registry, parallel, mesh_spec)
        manager = (
            CheckpointManager(
                ckpt_dir,
                plan,
                policy=policy,
                config_fingerprint={
                    "model": cfg.fingerprint(),
                    "parallel": parallel.fingerprint(),
                },
            )
            if ckpt_dir
            else None
        )
        raw_step = make_train_step(lm, tcfg, parallel, grad_transform=grad_transform)
        state_sh = cls._state_shardings(plan, jmesh)
        batch_sh = cls._batch_shardings(cfg, parallel, jmesh)
        step_fn = jax.jit(
            raw_step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        return cls(
            cfg=cfg,
            parallel=parallel,
            tcfg=tcfg,
            jmesh=jmesh,
            lm=lm,
            plan=plan,
            manager=manager,
            step_fn=step_fn,
            batch_size=batch_size,
            seq_len=seq_len,
            data_seed=tcfg.seed,
        )

    # ---------------------------------------------------------- shardings
    @staticmethod
    def _state_shardings(plan: ShardingPlan, jmesh) -> TrainState:
        from repro.core.pytree import unflatten_from_paths

        ps = plan.state_pspecs()
        mk = lambda specs: unflatten_from_paths(
            {n: NamedSharding(jmesh, s) for n, s in specs.items()}
        )
        return TrainState(
            params=mk(ps["params"]),
            exp_avg=mk(ps["exp_avg"]),
            exp_avg_sq=mk(ps["exp_avg_sq"]),
            step=NamedSharding(jmesh, P()),
        )

    @staticmethod
    def _batch_shardings(cfg, parallel, jmesh) -> dict:
        data = tuple(a for a in parallel.data_axes if a in jmesh.axis_names)
        bspec = data if len(data) != 1 else data[0]
        sh = {"tokens": NamedSharding(jmesh, P(bspec, None))}
        if cfg.cross_attn is not None or cfg.encoder is not None:
            sh["source_embeds"] = NamedSharding(jmesh, P(bspec, None, None))
        return sh

    # ------------------------------------------------------------ lifecycle
    def init_state(self) -> TrainState:
        import jax.numpy as jnp

        key = jax.random.PRNGKey(self.tcfg.seed)
        state_sh = self._state_shardings(self.plan, self.jmesh)

        def init_fn():
            params = self.lm.init(key)
            return init_state(
                params, moment_dtype=jnp.dtype(self.parallel.moment_dtype)
            )

        with self.jmesh:
            return jax.jit(init_fn, out_shardings=state_sh)()

    def init_or_restore(self) -> tuple[TrainState, RestoreInfo | None]:
        if self.manager is not None:
            # Tiered: surviving in-memory snapshots first (HOT_DIRECT /
            # HOT_RESHARD), then the disk ladder; identical to restore()
            # when the hot tier is off.
            res = self.manager.restore_latest(self.jmesh)
            if res is not None:
                return res
        return self.init_state(), None

    def batch(self, step: int) -> dict:
        from repro.configs.base import ShapeSpec

        shape = ShapeSpec("train", self.seq_len, self.batch_size, "train")
        full = batch_for_step(
            self.cfg, shape, step, seed=self.data_seed,
            batch_override=self.batch_size, seq_override=self.seq_len,
        )
        # The jitted step's in_shardings pytree is (tokens[, source_embeds]);
        # drop the per-branch keys batch_for_step also exposes.
        return {k: v for k, v in full.items() if k in ("tokens", "source_embeds")}

    def run(
        self,
        state: TrainState,
        start_step: int,
        num_steps: int,
        *,
        log: Callable[[dict], None] | None = None,
    ) -> tuple[TrainState, list[dict[str, Any]]]:
        history: list[dict[str, Any]] = []
        with self.jmesh:
            for step in range(start_step, start_step + num_steps):
                with obs.timed("train.step", step=step + 1) as sw:
                    with obs.span("train.batch"):
                        batch = self.batch(step)
                    with obs.span("train.dispatch"):
                        state, metrics = self.step_fn(state, batch)
                    # jit returns at dispatch: the step ends when the
                    # device has produced its outputs.
                    with obs.span("train.wait"):
                        jax.block_until_ready((state, metrics))
                rec = {
                    "step": step + 1,
                    "loss": float(metrics["loss"]),
                    "grad_norm": float(metrics["grad_norm"]),
                    "lr": float(metrics["lr"]),
                    "dt": sw.elapsed_s,
                }
                history.append(rec)
                if log:
                    log(rec)
                if self.manager is not None and self.manager.should_save(step + 1):
                    self.manager.save(state, step + 1)
        if self.manager is not None:
            self.manager.wait()
        return state, history
