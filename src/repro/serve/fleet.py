"""FleetReplica: a subscribed serving reader that stays current in place.

The consumer side of the fan-out subsystem: one replica owns a target
sharding plan (typically a decode/inference layout different from the
training layout), subscribes to a :class:`PublicationRegistry`, and on
``sync()``:

* first publication (or a gap in the feed) → a *full* weights-only
  restore through :class:`~repro.serve.peer.PeerFragmentSource` —
  identical region reads to a disk restore, bytes from peers;
* contiguous delta publication(s) → an *in-place* update: only the
  parameters with a changed shard are rebuilt and swapped into the live
  tree; every unchanged parameter keeps its array (the digests prove the
  bytes are identical, so the result is bit-for-bit the same as a full
  restore of the new step).

Replicas restore *weights only* (:func:`repro.ckpt.restore.params_from_source`
semantics): a serving fleet has no use for optimizer moments, so each
replica pays a third of a training restore's I/O and memory.

Replicas that share an engine additionally share the *built arrays*:
``jax.Array`` is immutable, so the flat param set for one (publication,
target layout) pair is built single-flight in the engine's atom cache and
every co-hosted replica's tree references the same arrays — N replica
threads on one serving host cost one restore's work plus N cheap tree
constructions, which is what makes fleet restore bandwidth scale with N
instead of dividing by it (``tests/test_fanout.py`` pins the O(1) disk
reads).
"""

from __future__ import annotations

import hashlib
import json

import jax

import repro.obs as obs
from repro.ckpt.restore import RestoreStats, build_param_arrays
from repro.core.engine import CheckpointEngine, default_engine
from repro.core.plan import TargetSpec, layouts_equal, stream_transforms
from repro.core.pytree import unflatten_from_paths
from repro.dist.sharding import ShardingPlan

from .peer import FanoutStats, PeerFragmentSource
from .registry import Publication, PublicationRegistry

__all__ = ["FleetReplica"]


class FleetReplica:
    """One serving replica: subscribe → restore → stay current in place."""

    def __init__(
        self,
        name: str,
        registry: PublicationRegistry,
        plan: ShardingPlan,
        jmesh: jax.sharding.Mesh,
        *,
        engine: CheckpointEngine | None = None,
        stats: FanoutStats | None = None,
    ):
        self.name = str(name)
        self.registry = registry
        self.plan = plan
        self.jmesh = jmesh
        self.engine = engine or default_engine()
        self.stats = stats or FanoutStats()
        self.restore_stats = RestoreStats()
        self.subscription = registry.subscribe(self.name)
        self.step: int | None = None
        self.seq: int | None = None
        self.last_update: frozenset[str] = frozenset()  # params rebuilt by last sync
        self._flat: dict[str, jax.Array] | None = None
        self._plan_key = _plan_fingerprint(plan)

    @property
    def params(self):
        """The live weights pytree (None before the first sync)."""
        return None if self._flat is None else unflatten_from_paths(self._flat)

    def flat_params(self) -> dict[str, jax.Array]:
        if self._flat is None:
            raise RuntimeError(f"replica {self.name} has not synced yet")
        return dict(self._flat)

    # -------------------------------------------------------------- syncing
    def sync(self) -> bool:
        """Apply pending publications; True if the replica updated.

        Incremental only when the feed is contiguous from this replica's
        current publication (all-delta announcements, no gap) — anything
        else, including the first sync, is a full rebuild.  Either way the
        resulting weights are bit-identical to a direct disk restore of
        the newest published step.
        """
        pubs = self.subscription.poll()
        if not pubs:
            return False
        with obs.span("serve.sync", replica=self.name) as sp:
            self._sync(pubs, sp)
        obs.add("serve.syncs")
        return True

    def _sync(self, pubs: list[Publication], sp) -> None:
        pub = pubs[-1]
        contiguous = (
            self._flat is not None
            and self.seq is not None
            and pubs[0].seq == self.seq + 1
            and all(p.kind == "delta" for p in pubs)
        )
        source = PeerFragmentSource(
            self.registry, pub, self.name, stats=self.stats
        )
        target = TargetSpec(self.plan.mesh, self.plan.param_specs)
        transforms = (
            None
            if layouts_equal(pub.manifest, target)
            else stream_transforms(pub.manifest, target)
        )
        if not contiguous:
            sp.set(mode="full", step=pub.step, params=len(pub.manifest.params))
            self._flat = dict(self._build_shared(source, transforms, None))
            self.last_update = frozenset(self._flat)
        else:
            # In-place delta: rebuild exactly the params with a changed
            # FP32 shard anywhere in the drained window.  (Changes limited
            # to optimizer-state shards are invisible to a weights-only
            # replica and are skipped.)
            changed = frozenset(
                name
                for p in pubs
                for name in _changed_fp32_params(p)
            )
            sp.set(mode="delta", step=pub.step, params=len(changed))
            if changed:
                self._flat.update(self._build_shared(source, transforms, changed))
            self.last_update = changed
        self.seq = pub.seq
        self.step = pub.step

    def _build_shared(
        self,
        source: PeerFragmentSource,
        transforms,
        names: frozenset[str] | None,
    ) -> dict[str, jax.Array]:
        """Build the requested param arrays once per (publication, target
        layout) *per engine* — co-hosted replicas get the same immutable
        arrays back from the atom cache instead of re-assembling and
        re-staging identical bytes."""
        sel = "all" if names is None else hashlib.sha256(
            "\0".join(sorted(names)).encode()
        ).hexdigest()[:16]
        key = f"{source.cache_key}::fleet::{self._plan_key}::{sel}"
        return self.engine.memo(
            key,
            lambda: build_param_arrays(
                source, self.plan, self.jmesh,
                transforms=transforms,
                names=None if names is None else set(names),
                stats=self.restore_stats, engine=self.engine,
            ),
        )


def _plan_fingerprint(plan: ShardingPlan) -> str:
    """Deterministic digest of a target layout (mesh + every param's spec)
    — two plans with equal fingerprints produce bit-identical arrays, so
    the fingerprint is a safe sharing key for the fleet param cache."""
    blob = json.dumps(
        {
            "mesh": plan.mesh.to_json(),
            "params": {n: s.to_json() for n, s in plan.param_specs.items()},
        },
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _changed_fp32_params(pub: Publication) -> frozenset[str]:
    """Parameter names whose *weight* shards changed in one publication."""
    out = set()
    for key in pub.changed:
        # key = "rank_NNNNN/<name>@<kind>"; names never contain "@".
        name, kind = key.split("/", 1)[1].rsplit("@", 1)
        if kind == "fp32":
            out.add(name)
    return frozenset(out)
