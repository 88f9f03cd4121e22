"""CheckpointManager: the policy layer tying saving, discovery and resume.

Responsibilities:

* periodic saves (sync or async/overlapped), atomic commit, keep-last-k GC
  (delta-aware: never collects a base a live delta references; in-flight
  save directories are never treated as wreckage);
* incremental saves (``save_mode="delta"``): steady-state disk saves write
  only the shards whose content digest changed since the previous commit,
  with every ``full_interval``-th save a full rebase bounding chain depth
  (the hot drainer promotes snapshots through the same diff);
* the hot in-memory tier (``hot_interval``): per-``hot_interval``-step
  peer-replicated host snapshots with every Nth promoted to disk in the
  background (``disk_interval``), see :mod:`repro.hot`;
* discovery that skips uncommitted (crashed) checkpoint directories;
* tiered resume (``restore_latest``): the ladder is
  HOT_DIRECT → HOT_RESHARD → DIRECT → RESHARD_STREAM → VIA_UCP —
  surviving in-memory replicas first, then the disk tiers;
* disk resume beyond the paper's lazy conversion: DIRECT per-rank reads
  when the Target layout equals the Source; otherwise RESHARD_STREAM
  streams Source fragments straight into the Target layout (consolidating
  the few params that need it in memory) with **zero intermediate bytes
  written to disk**.  VIA_UCP — convert to a cached UCP atom directory
  (``<step dir>.ucp``), then Load — remains the fallback when streaming
  fails mid-flight or the parameter set changed, and the explicit export
  path (``export_ucp``);
* the UCP cache is shared: five different Targets resuming from the same
  Source convert once (hub-format property, paper §3.1);
* opt-in integrity verification (``verify=True``) against the content
  digests recorded at save/capture/convert time.
"""

from __future__ import annotations

import dataclasses
import shutil
import threading
from pathlib import Path
from typing import Any, Mapping

import jax

import repro.obs as obs
from repro.chaos.points import fault_point
from repro.core.atoms import UcpCheckpoint
from repro.core.convert import ConvertStats, convert_to_ucp
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.engine import CheckpointEngine, default_engine
from repro.core.plan import ResumeMode, TargetSpec, plan_resume, stream_transforms
from repro.core.tensor_io import IntegrityError
from repro.dist.sharding import ShardingPlan
from repro.train.optimizer import TrainState
from .policy import CheckpointPolicy
from .restore import (
    RestoreStats,
    params_from_source,
    state_from_dist,
    state_from_stream,
    state_from_ucp,
)
from .saver import AsyncSaver, SaveResult, snapshot_state, write_distributed

__all__ = ["CheckpointManager", "RestoreInfo"]


def _dir_bytes(root: Path) -> int:
    """Recursive file-size sum of one step directory (GC accounting;
    only walked while a tracer is enabled)."""
    total = 0
    try:
        for p in root.rglob("*"):
            try:
                if p.is_file():
                    total += p.stat().st_size
            except OSError:
                continue
    except OSError:
        pass
    return total


@dataclasses.dataclass
class RestoreInfo:
    step: int
    mode: ResumeMode
    reason: str
    scalars: dict[str, Any]
    convert_stats: ConvertStats | None
    restore_stats: RestoreStats
    wall_time_s: float


class CheckpointManager:
    def __init__(
        self,
        root: str | Path,
        plan: ShardingPlan,
        *,
        policy: CheckpointPolicy | None = None,
        config_fingerprint: Mapping[str, Any] | None = None,
    ):
        """All checkpointing knobs live on one validated
        :class:`~repro.ckpt.policy.CheckpointPolicy` — cadence, retention,
        hot tiering, delta policy, the shard codec and the fan-out
        registry; see its docstring for the field-by-field reference.
        ``config_fingerprint`` stays a separate argument: it is this
        *run's* identity (model/parallelism fingerprints recorded into
        every manifest), not checkpointing policy.

        Hot-tier policy: ``hot_interval`` (None = disabled) captures a
        peer-replicated in-memory snapshot every N steps; every
        ``disk_interval // hot_interval``-th snapshot is promoted to a
        durable disk checkpoint in the background (``disk_interval``
        defaults to ``save_interval``, which stays the disk cadence when
        the hot tier is off).  ``hot_replication`` extra copies per
        fragment, ``hot_max_snapshots`` / ``hot_max_bytes`` bound the ring.

        Delta policy: ``save_mode="delta"`` makes the steady-state disk
        save (direct or hot-promoted) an incremental one — only shards
        whose content digest changed since the previous committed step are
        written; the rest are manifest references into the chain.  Every
        ``full_interval``-th disk save is forced full (a *rebase*), which
        bounds chain length and lets GC collect old chains.  ``gc()`` never
        removes a step that a live delta references.  ``"dedup"`` /
        ``"all"`` keep their previous meaning (every save full).

        Codec policy: ``codec`` opts shards into block-quantized payloads
        (per StateKind — see :class:`~repro.core.codec.CodecPolicy`); both
        the direct save path and the hot drainer's promotions encode under
        the same policy, and every restore tier decodes transparently.

        Fan-out: ``registry`` (a
        :class:`~repro.serve.registry.PublicationRegistry`) subscribes a
        serving fleet to this run — every newly committed step is
        published automatically (``_maybe_publish`` runs after ``save()``
        and ``wait()``, so async saves announce as soon as their commit is
        observed).  The newest committed step is always within
        ``keep_last``, so a publication's disk fallback tier outlives GC.
        """
        self.policy = policy if policy is not None else CheckpointPolicy()
        policy = self.policy
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.plan = plan
        self.keep_last = policy.keep_last
        self.save_interval = policy.save_interval
        self.disk_interval = policy.effective_disk_interval
        self.hot_interval = policy.hot_interval
        self.save_mode = policy.save_mode
        self.full_interval = policy.full_interval
        self.codec = policy.codec
        self._disk_save_seq = 0  # disk-save counter driving the rebase cadence
        # Chain pins: save root -> the base chain directories an in-flight
        # delta resolved (registered by the base loader on the writer
        # thread, pruned by gc() once the save leaves the pending set).
        # Closes the window where gc() could collect a base between a
        # queued delta's base resolution and its commit.
        self._pin_lock = threading.Lock()
        self._pinned_chains: dict[Path, set[Path]] = {}  #: guarded by self._pin_lock
        # Committed manifests are immutable: memoize referenced_steps per
        # step so gc() doesn't re-parse keep_last manifests on every save.
        self._refs_cache: dict[int, set[int]] = {}
        self.registry = policy.registry
        self._published_step: int | None = None
        self.config_fingerprint = dict(config_fingerprint or {})
        self.engine = (
            CheckpointEngine(workers=policy.io_workers)
            if policy.io_workers is not None
            else default_engine()
        )
        self._async = (
            AsyncSaver(max_pending=policy.max_pending_saves)
            if policy.async_save
            else None
        )
        self.hot = None
        self._drainer = None
        if policy.hot_interval is not None:
            from repro.hot import HotDrainer, HotTier

            self.hot = HotTier(
                replication=policy.hot_replication,
                max_snapshots=policy.hot_max_snapshots,
                max_bytes=policy.hot_max_bytes,
                engine=self.engine,
                # "all" must capture the full per-replica write set or the
                # promoted disk checkpoints would silently be dedup'd;
                # "delta" captures the dedup set (deltas require it).
                save_mode="all" if policy.save_mode == "all" else "dedup",
            )
            self._drainer = HotDrainer(
                every=max(1, self.disk_interval // policy.hot_interval),
                engine=self.engine,
                max_pending=policy.max_pending_saves,
            )

    # ------------------------------------------------------------------ save
    def step_dir(self, step: int) -> Path:
        return self.root / f"step_{step:08d}"

    def should_save(self, step: int) -> bool:
        if step <= 0:
            return False
        if self.hot is not None:
            # hot cadence subsumes the disk cadence: every Nth snapshot is
            # promoted to disk by the background drainer.
            return step % self.hot_interval == 0
        return step % self.save_interval == 0

    def _base_loader(self, step: int):
        """A callable resolving the delta base for a save of ``step`` —
        evaluated on the *writing* thread, so a queued delta always diffs
        against the newest step that actually committed before it runs.

        The resolved base's chain is *pinned* (``_pinned_chains``) before
        the loader returns, and ``gc()`` refuses to collect pinned
        directories until the save leaves the in-flight set.  Resolution
        runs entirely under ``_pin_lock`` — the same lock gc() holds
        around each committed-step deletion — so the loader either pins
        the base before gc can consider it (deletion skipped) or observes
        the already-deleted state and rebases; there is no window where a
        half-deleted base can be resolved (the saver's pre-commit chain
        check remains the loud last-resort backstop)."""
        save_root = self.step_dir(step)

        def load() -> DistCheckpoint | None:
            with self._pin_lock:
                older = [s for s in self.steps() if s < step]
                if not older:
                    return None
                try:
                    base = DistCheckpoint.open(self.step_dir(older[-1]))
                except (OSError, ValueError, KeyError):
                    return None  # unreadable base: rebase to a full save
                self._pinned_chains[save_root] = set(base.chain_roots())
            return base

        return load

    def _next_save_kw(self, step: int) -> dict[str, Any]:
        """Per-save delta policy: ``save_mode``/``base`` kwargs for the
        next disk save, advancing the rebase cadence (every
        ``full_interval``-th disk save is full)."""
        if self.save_mode == "all":
            return {"save_mode": "all"}
        if self.save_mode != "delta":
            return {}
        seq = self._disk_save_seq
        self._disk_save_seq += 1
        if seq % self.full_interval == 0:
            return {}  # forced rebase: a plain full save
        return {"save_mode": "delta", "base": self._base_loader(step)}

    def save(
        self, state: TrainState, step: int, *, scalars: Mapping[str, Any] | None = None,
        block: bool = False,
    ) -> None:
        fault_point("manager.save.begin", step=step, block=block)
        with obs.span("manager.save", step=step):
            self._save(state, step, scalars=scalars, block=block)

    def _save(
        self, state: TrainState, step: int, *, scalars: Mapping[str, Any] | None,
        block: bool,
    ) -> None:
        # A re-save into an existing step replaces its manifest: the memoized
        # reference set is stale the moment the save starts.
        self._refs_cache.pop(step, None)
        if self.hot is not None and step % self.hot_interval == 0:
            snap = snapshot_state(state)
            hs, _ = self.hot.capture(
                snap, self.plan, step,
                scalars=dict(scalars or {}),
                config_fingerprint=self.config_fingerprint,
            )
            drain_kw = self._next_save_kw(step) if self._drainer.next_drains else {}
            self._drainer.maybe_drain(
                hs, self.step_dir(step), codec=self.codec, **drain_kw
            )
            if block:
                self._drainer.wait()
            self.gc()
            self._maybe_publish()
            return
        kw = dict(
            scalars=dict(scalars or {}),
            config_fingerprint=self.config_fingerprint,
            engine=self.engine,
            codec=self.codec,
        )
        kw.update(self._next_save_kw(step))
        if self._async is not None and not block:
            self._async.submit(state, self.plan, step, self.step_dir(step), **kw)
        else:
            snap = snapshot_state(state)
            write_distributed(snap, self.plan, step, self.step_dir(step), **kw)
        self.gc()
        self._maybe_publish()

    def wait(self) -> list[SaveResult]:
        # try/finally ladder: a drainer failure must not leave async-saver
        # errors undrained (or vice versa), and GC/publish still observe
        # whatever *did* commit before the error surfaced.
        res: list[SaveResult] = []
        try:
            if self._drainer is not None:
                res.extend(self._drainer.wait())
        finally:
            try:
                if self._async is not None:
                    res.extend(self._async.wait())
            finally:
                if self._async is not None or self._drainer is not None:
                    self.gc()
                self._maybe_publish()
        return res

    # ----------------------------------------------------------- publishing
    def publish(self, step: int | None = None):
        """Announce one committed step (default: newest) to the fan-out
        registry — see :mod:`repro.serve`.  Returns the
        :class:`~repro.serve.registry.Publication`, or None when there is
        nothing committed yet."""
        if self.registry is None:
            raise ValueError("CheckpointManager has no publication registry")
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        pub = self.registry.publish(DistCheckpoint.open(self.step_dir(step)))
        self._published_step = max(step, self._published_step or step)
        return pub

    def _maybe_publish(self) -> None:
        """Publish the newest committed step not yet announced.  Runs after
        every ``save()``/``wait()``: a synchronous save publishes
        immediately, an async/drained save on the next call that observes
        its commit."""
        if self.registry is None:
            return
        step = self.latest_step()
        if step is None or (
            self._published_step is not None and step <= self._published_step
        ):
            return
        self.publish(step)

    def close(self) -> None:
        # Same discipline as wait(): every component closes (and surfaces
        # its background errors) even when an earlier one raises.
        try:
            if self._drainer is not None:
                self._drainer.close()
        finally:
            try:
                if self._async is not None:
                    self._async.close()
            finally:
                if self.hot is not None:
                    self.hot.clear()

    # ----------------------------------------------------------------- lookup
    def steps(self) -> list[int]:
        out = []
        for p in sorted(self.root.glob("step_*")):
            if p.is_dir() and not p.name.endswith(".ucp") and (p / "COMMIT").exists():
                try:
                    out.append(int(p.name.split("_")[1]))
                except (IndexError, ValueError):
                    continue
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def _inflight_roots(self) -> set[Path]:
        """Step directories with a save queued or mid-write right now."""
        out: set[Path] = set()
        if self._async is not None:
            out |= self._async.pending_roots()
        if self._drainer is not None:
            out |= self._drainer.pending_roots()
        return out

    def gc(self) -> None:
        """Keep the newest ``keep_last`` committed checkpoints (+their UCP
        caches); remove uncommitted wreckage older than the newest commit.

        Delta-aware: a kept delta's whole ancestor chain stays alive — a
        base is only collectable once no surviving manifest references it
        (a ``full_interval`` rebase is what eventually frees old chains) —
        and chains pinned by an in-flight delta's base resolution are held
        until that save completes.  In-flight-aware: directories the async
        saver / hot drainer are still writing are never wreckage, even
        when a newer save already committed — an older queued save may
        legitimately commit *after* a newer synchronous one.
        """
        with obs.span("ckpt.gc"):
            self._gc()

    def _gc(self) -> None:
        fault_point("manager.gc.begin")
        # Read order matters: in-flight BEFORE committed.  A background save
        # commits and *then* leaves the pending set; reading pending first
        # means any save gone from `inflight` is already visible in `steps`
        # (pending_roots() and the discard share a lock).  The reverse order
        # has a window — commit + discard between the two reads — where a
        # just-committed delta is in neither set, its base pin gets pruned
        # below, and the base is collected under a live manifest.  Found by
        # the chaos harness (crash schedules on drain.pre_commit).
        inflight = self._inflight_roots()
        steps = self.steps()
        keep: set[int] = set(steps[-self.keep_last:]) if self.keep_last else set(steps)
        if self.registry is not None:
            # The fleet's disk-fallback tier: the currently-published step
            # must outlive GC even when newer commits have pushed it past
            # keep_last (a crash between commit and announce leaves the
            # fleet reading the older publication indefinitely).
            pub = self.registry.current()
            if pub is not None and pub.step in steps:
                keep.add(pub.step)
        # Expand with every step a kept chain references.  Provenance is
        # flattened in each manifest, but walk to a fixpoint anyway so a
        # kept base that is itself a delta keeps *its* ancestors too.
        frontier = list(keep)
        while frontier:
            s = frontier.pop()
            refs = self._refs_cache.get(s)
            if refs is None:
                try:
                    refs = DistCheckpoint.open(self.step_dir(s)).referenced_steps()
                except (OSError, ValueError, KeyError):
                    continue  # unreadable manifest: nothing to pin
                if self.step_dir(s) not in inflight:
                    # cache only settled steps: an in-flight re-save may be
                    # about to replace this manifest
                    self._refs_cache[s] = refs
            for r in refs:
                if r not in keep:
                    keep.add(r)
                    frontier.append(r)
        with self._pin_lock:
            # pins die with their save: drop entries whose save finished
            self._pinned_chains = {
                r: c for r, c in self._pinned_chains.items() if r in inflight
            }
        # Delete newest-first: delta references only point backwards, so a
        # GC interrupted mid-loop (crash) then leaves no surviving committed
        # manifest referencing an already-deleted ancestor — oldest-first had
        # exactly that window (found by the chaos harness: crash on
        # manager.gc.delete while a doomed chain was being collected).
        for s in sorted(steps, reverse=True):
            step_dir = self.step_dir(s)
            if s in keep or step_dir in inflight:
                continue
            # Outside the pin lock (a paused thread here must not block the
            # base loader); the pin set is still re-read under the lock below.
            fault_point("manager.gc.delete", step=s)
            # Per-deletion critical section, shared with the delta base
            # loader: the pin set is re-read right before the rmtree, so a
            # base resolved concurrently is either already pinned (skip) or
            # resolves strictly after the deletion (loader rebases).
            with self._pin_lock:
                pinned: set[Path] = set().union(
                    set(), *self._pinned_chains.values()
                )
                if step_dir in pinned:
                    obs.add("gc.pinned_steps")
                    continue
                self._refs_cache.pop(s, None)
                if obs.active() is not None:  # sizing walk only when traced
                    obs.add("gc.collected_bytes", _dir_bytes(step_dir))
                obs.add("gc.collected_steps")
                shutil.rmtree(step_dir, ignore_errors=True)
                shutil.rmtree(Path(str(step_dir) + ".ucp"), ignore_errors=True)
            self.engine.invalidate(step_dir)
            self.engine.invalidate(str(step_dir) + ".ucp")
        if steps:
            newest = self.step_dir(steps[-1])
            for p in self.root.glob("step_*"):
                if (
                    p.is_dir()
                    and not p.name.endswith(".ucp")
                    and not (p / "COMMIT").exists()
                    and p not in inflight
                    and p.name < newest.name
                ):
                    fault_point("manager.gc.wreckage", path=p.name)
                    obs.add("gc.wreckage_removed")
                    shutil.rmtree(p, ignore_errors=True)

    # ---------------------------------------------------------------- restore
    def restore(
        self,
        jmesh: jax.sharding.Mesh,
        *,
        step: int | None = None,
        target_plan: ShardingPlan | None = None,
        convert_workers: int | None = None,
        verify: bool = False,
        force_mode: ResumeMode | None = None,
    ) -> tuple[TrainState, RestoreInfo] | None:
        """Resume onto ``jmesh`` under ``target_plan`` (default: own plan)
        from the *disk* tiers (DIRECT → RESHARD_STREAM → VIA_UCP).

        A layout change streams Source fragments directly into the Target
        layout (``RESHARD_STREAM``, zero intermediate bytes on disk); a
        stream failure mid-flight (e.g. a shard file lost after planning)
        falls back cleanly to the VIA_UCP convert+Load path.  ``force_mode``
        pins a specific mode instead — RESHARD_STREAM / VIA_UCP for
        benchmarking one path against the other (no silent fallback when
        forced), DIRECT only when the layouts are actually equal.

        ``convert_workers`` overrides the conversion pool width for this
        call (None = the manager's own engine/pool).  ``verify=True``
        checks the checkpoint's content digests before building state and
        raises :class:`~repro.core.tensor_io.IntegrityError` on mismatch.
        Returns None when no committed checkpoint exists (fresh start).
        """
        plan = target_plan or self.plan
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        fault_point("manager.restore.begin", step=step)
        with obs.timed("ckpt.restore", step=step) as sw:
            return self._restore_traced(
                sw, plan, jmesh, step, convert_workers, verify, force_mode
            )

    def restore_params(
        self, jmesh: jax.sharding.Mesh, *, step: int | None = None
    ) -> tuple[Any, RestoreInfo] | None:
        """Weights-only restore for serving: the params pytree of ``step``
        (default: newest) on ``jmesh`` under the manager's plan.

        DIRECT and RESHARD_STREAM layouts read only the FP32 state — a
        third of a training restore's bytes.  Any other plan (a changed
        parameter set, an unstreamable change) takes the full
        :meth:`restore` ladder and keeps its params.  Returns None when no
        committed checkpoint exists."""
        step = step if step is not None else self.latest_step()
        if step is None:
            return None
        ckpt = DistCheckpoint.open(self.step_dir(step))
        target = TargetSpec(self.plan.mesh, self.plan.param_specs)
        rp = plan_resume(ckpt.manifest, target)
        if rp.mode not in (ResumeMode.DIRECT, ResumeMode.RESHARD_STREAM):
            state, info = self.restore(jmesh, step=step)
            return state.params, info
        transforms = (
            None
            if rp.mode is ResumeMode.DIRECT
            else rp.transforms or stream_transforms(ckpt.manifest, target)
        )
        with obs.timed(
            "ckpt.restore", step=step, mode=rp.mode.value, reason=rp.reason
        ) as sw:
            stats = RestoreStats()
            with obs.span("restore.tier", tier=rp.mode.value):
                params = params_from_source(
                    ckpt, self.plan, jmesh, stats,
                    transforms=transforms, engine=self.engine,
                )
            obs.add("restore.count")
        info = RestoreInfo(
            step=step,
            mode=rp.mode,
            reason=rp.reason,
            scalars=dict(ckpt.manifest.scalars),
            convert_stats=None,
            restore_stats=stats,
            wall_time_s=sw.elapsed_s,
        )
        return params, info

    def _restore_traced(
        self, sw, plan, jmesh, step, convert_workers, verify, force_mode
    ) -> tuple[TrainState, RestoreInfo]:
        # Body of restore(), run inside its ``ckpt.restore`` span; ``sw``
        # supplies wall time and carries the plan decision attributes.
        ckpt = DistCheckpoint.open(self.step_dir(step))
        if verify:
            problems = ckpt.validate()
            if problems:
                raise IntegrityError(
                    f"checkpoint step {step} failed verification: "
                    + "; ".join(problems[:5])
                )
        target = TargetSpec(plan.mesh, plan.param_specs)
        with obs.span("restore.plan"):
            rp = plan_resume(ckpt.manifest, target)
        mode = rp.mode
        reason = rp.reason
        if force_mode is not None:
            force = ResumeMode(force_mode)
            if force is ResumeMode.DIRECT and rp.mode is not ResumeMode.DIRECT:
                raise ValueError(
                    f"cannot force DIRECT resume: layouts differ ({rp.reason})"
                )
            if force not in (
                ResumeMode.DIRECT, ResumeMode.RESHARD_STREAM, ResumeMode.VIA_UCP
            ):
                raise ValueError(f"cannot force disk resume mode {force}")
            mode = force
            reason = f"forced {force.value}; planner said {rp.mode.value}"
        stats = RestoreStats()
        cstats: ConvertStats | None = None
        state: TrainState | None = None
        if mode == ResumeMode.DIRECT:
            with obs.span("restore.tier", tier="direct"):
                state = state_from_dist(ckpt, plan, jmesh, stats, engine=self.engine)
        elif mode == ResumeMode.RESHARD_STREAM:
            transforms = rp.transforms or stream_transforms(ckpt.manifest, target)
            try:
                with obs.span("restore.tier", tier="reshard_stream"):
                    state = state_from_stream(
                        ckpt, plan, jmesh, transforms, stats, engine=self.engine
                    )
            except (OSError, KeyError, IntegrityError) as e:
                # Expected stream-time failures: a shard file lost/corrupt
                # after planning, a manifest entry gone.  Programming errors
                # propagate — silently degrading every resume to VIA_UCP
                # would negate the zero-intermediate-bytes property.
                if force_mode is not None:
                    raise
                # Fall back cleanly: drop any cached handles/indexes of the
                # (possibly damaged) source — for a delta, of its whole
                # ancestor chain — and take the convert+Load path.
                self.engine.invalidate_chain(ckpt)
                obs.event(
                    "restore.fallback", step=step,
                    tier="reshard_stream", to="via_ucp",
                    error=f"{type(e).__name__}: {e}",
                )
                mode = ResumeMode.VIA_UCP
                reason = (
                    f"{reason}; stream failed ({type(e).__name__}: {e}), "
                    "falling back to via_ucp"
                )
                stats = RestoreStats()
        if mode == ResumeMode.VIA_UCP and state is None:
            with obs.span("restore.tier", tier="via_ucp"):
                ucp, cstats = self._cached_ucp(
                    ckpt, step, convert_workers=convert_workers, verify=verify
                )
                state = state_from_ucp(ucp, plan, jmesh, stats, engine=self.engine)
        sw.set(mode=mode.value, reason=reason)
        obs.add("restore.count")
        info = RestoreInfo(
            step=step,
            mode=mode,
            reason=reason,
            scalars=dict(ckpt.manifest.scalars),
            convert_stats=cstats,
            restore_stats=stats,
            wall_time_s=sw.elapsed_s,
        )
        return state, info

    def _cached_ucp(
        self,
        ckpt: DistCheckpoint,
        step: int,
        *,
        convert_workers: int | None = None,
        verify: bool = False,
    ) -> tuple[UcpCheckpoint, ConvertStats | None]:
        """The step's UCP atom checkpoint: reuse the committed cache beside
        the step directory, else convert once (hub-format property)."""
        cstats: ConvertStats | None = None
        ucp_dir = Path(str(self.step_dir(step)) + ".ucp")
        if (ucp_dir / "COMMIT").exists():
            ucp = UcpCheckpoint.open(ucp_dir)
        else:
            shutil.rmtree(ucp_dir, ignore_errors=True)  # partial convert
            ucp, cstats = convert_to_ucp(
                ckpt, str(ucp_dir), workers=convert_workers, engine=self.engine
            )  # explicit convert_workers wins over the manager engine
        if verify and cstats is None:
            # cached UCP directory: its atoms were not just produced
            # from the (already-verified) shards — check their digests.
            problems = ucp.validate()
            if problems:
                raise IntegrityError(
                    f"cached UCP for step {step} failed verification: "
                    + "; ".join(problems[:5])
                )
        return ucp, cstats

    def export_ucp(
        self,
        step: int | None = None,
        *,
        convert_workers: int | None = None,
        verify: bool = False,
    ) -> tuple[UcpCheckpoint, ConvertStats | None]:
        """Explicitly export one step as a UCP atom checkpoint.

        Since resume streams (``RESHARD_STREAM``), conversion is no longer
        on the resume hot path — this is the deliberate export tool for
        producing the portable hub format (publishing a checkpoint, feeding
        external consumers).  Reuses the committed ``<step dir>.ucp`` cache
        when present (``ConvertStats`` is then None).
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise ValueError(f"no committed checkpoint under {self.root} to export")
        ckpt = DistCheckpoint.open(self.step_dir(step))
        return self._cached_ucp(
            ckpt, step, convert_workers=convert_workers, verify=verify
        )

    def restore_latest(
        self,
        jmesh: jax.sharding.Mesh,
        *,
        target_plan: ShardingPlan | None = None,
        convert_workers: int | None = None,
        verify: bool = False,
    ) -> tuple[TrainState, RestoreInfo] | None:
        """Tiered resume: HOT_DIRECT → HOT_RESHARD → DIRECT →
        RESHARD_STREAM → VIA_UCP.

        Prefers the newest surviving in-memory snapshot when it is at
        least as fresh as the best committed disk checkpoint and its
        replicas still cover the full state (after any ``hot.fail_ranks``
        events); otherwise falls through to :meth:`restore`.  With the hot
        tier disabled this *is* :meth:`restore`.
        """
        plan = target_plan or self.plan
        if self.hot is not None:
            from repro.hot import plan_hot_recovery, state_from_hot

            target = TargetSpec(plan.mesh, plan.param_specs)
            with obs.span("restore.plan"):
                hp = plan_hot_recovery(self.hot, target, min_step=self.latest_step())
            if hp is not None:
                with obs.timed(
                    "ckpt.restore", step=hp.step,
                    mode=hp.mode.value, reason=hp.reason,
                ) as sw:
                    stats = RestoreStats()
                    with obs.span("restore.tier", tier=hp.mode.value):
                        state = state_from_hot(
                            hp.snapshot, plan, jmesh, stats,
                            engine=self.engine, verify=verify,
                        )
                    obs.add("restore.count")
                    info = RestoreInfo(
                        step=hp.step,
                        mode=hp.mode,
                        reason=hp.reason,
                        scalars=dict(hp.snapshot.manifest.scalars),
                        convert_stats=None,
                        restore_stats=stats,
                        wall_time_s=sw.elapsed_s,
                    )
                return state, info
        return self.restore(
            jmesh, target_plan=target_plan,
            convert_workers=convert_workers, verify=verify,
        )
