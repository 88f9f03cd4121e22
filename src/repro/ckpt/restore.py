"""Restore: turn checkpoints (distributed or UCP) back into a sharded
TrainState on an arbitrary Target mesh.

Both paths build arrays with ``jax.make_array_from_callback``: JAX asks for
each device's *index* into the runtime-shaped global array and we serve
exactly those bytes —

* DIRECT (layouts equal): from the rank's own shard file (the paper's
  zero-transformation resume),
* VIA_UCP: from the consolidated atom via mmap slice reads
  (``GenUcpMetadata`` + ``Load``), with padding zero-filled, the replica
  dim broadcast, and dtype cast to the Target precision policy.

``read_region_from_source`` additionally supports serving an arbitrary
region from any *fragment source* by unioning overlapping fragments on the
fly — a distributed checkpoint on disk (the beyond-paper "direct reshard"
fast path, skipping atom materialization when the Source can stream
straight into the Target)
or an in-memory hot snapshot (``repro.hot``: the ``HOT_RESHARD`` recovery
tier unions surviving peer replicas without touching disk).  The two share
one code path because the engine's index and fragment reads are generic
over :class:`~repro.core.engine.FragmentSource`.

All file I/O routes through a :class:`~repro.core.engine.CheckpointEngine`:
fragment lookups hit the engine's sorted interval index (built once per
``(checkpoint, param, kind)``), shard/atom files are opened once through its
handle cache, and ``_build_state`` prefetches every device region
concurrently over the engine's worker pool.  A region that one raw shard
file on disk holds whole skips the handle cache: its payload is read
straight into the region's staging buffer in byte ranges, jobs of the same
pool (``_prefetch``).  The state kinds stage one after another with the
same shapes, so for the length of a restore the engine's arena keeps one
kind's staging storage (``BufferArena.hold``) and each kind stages into
the pages of the kind before it.  ``CheckpointEngine(workers=1)``
degrades to the exact serial order, byte-identical by construction.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Mapping

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.obs as obs
from repro.core.atoms import UcpCheckpoint
from repro.core.convert import assemble_atom
from repro.core.dist_ckpt import DistCheckpoint
from repro.core.engine import BufferArena, CheckpointEngine, default_engine
from repro.core.ops import clip_region_to_logical, read_runtime_region
from repro.core.patterns import ParamTransform, StateKind, TransformClass
from repro.core.pytree import unflatten_from_paths
from repro.core.tensor_io import READ_RANGE_BYTES, read_into, resolve_dtype
from repro.dist.sharding import ShardingPlan
from repro.train.optimizer import TrainState

__all__ = [
    "build_param_arrays",
    "params_from_source",
    "read_region_from_source",
    "read_region_from_dist",
    "state_from_source",
    "state_from_stream",
    "state_from_ucp",
    "state_from_dist",
    "RestoreStats",
]


def _canon_region(
    region: tuple[slice, ...], shape: tuple[int, ...]
) -> tuple[slice, ...]:
    """Normalize a device index to concrete unit-step slices over ``shape``."""
    return tuple(slice(*r.indices(s)) for r, s in zip(region, shape))


def read_region_from_source(
    source,
    name: str,
    kind: StateKind,
    region: tuple[slice, ...],
    dtype,
    *,
    engine: CheckpointEngine | None = None,
) -> np.ndarray:
    """Serve a runtime-coordinate region by unioning source fragments.

    ``source`` is any :class:`~repro.core.engine.FragmentSource`: a
    :class:`DistCheckpoint` (fragments are shard files) or a hot snapshot
    (fragments are surviving in-memory replicas).  When Source and Target
    layouts are identical, each Target device's region coincides with
    exactly one fragment → one fragment read (DIRECT / HOT_DIRECT).
    Otherwise this is on-the-fly resharding (no atoms materialized).

    The engine's :class:`~repro.core.engine.FragmentIndex` pre-selects the
    fragments overlapping the region (distinct fragments are pairwise
    disjoint, so every hit contributes unique elements), and its handle
    cache keeps each disk-backed fragment open across regions and params.
    """
    engine = engine or default_engine()
    idx = engine.index_for(source, name, kind)
    region = _canon_region(region, idx.spec.runtime_shape)

    def build() -> np.ndarray:
        shape = tuple(r.stop - r.start for r in region)
        hits = idx.overlapping(region)
        # Zero-fill only when the fragments don't tile the whole region (the
        # remainder is alignment padding); fragments are pairwise disjoint so
        # coverage is a plain sum.
        total = math.prod(shape)
        covered = sum(math.prod(hi - lo for lo, hi in ovs) for _, _, ovs in hits)
        obs.add("restore.region_reads")
        obs.add("restore.region_fragments", len(hits))
        out = engine.alloc(shape, resolve_dtype(dtype), zero=covered < total)
        for rank, e, ovs in hits:
            shard = engine.read_fragment(source, rank, name, kind)
            src_idx = tuple(
                slice(s0 + (lo - a0), s0 + (hi - a0))
                for (a0, _), (s0, _), (lo, hi) in zip(e.atom_slice, e.shard_slice, ovs)
            )
            dst_idx = tuple(
                slice(lo - r.start, hi - r.start) for (lo, hi), r in zip(ovs, region)
            )
            # Direct assignment: one copy straight into the output, casting in
            # place when dtypes differ — never an intermediate materialization.
            out[dst_idx] = shard[src_idx]
        return out

    # Fan-out sources (share_regions, e.g. serve.PeerFragmentSource) pool
    # identical region reads across a whole reader fleet: assembled once
    # into the engine's byte-bounded cache, served to every reader.
    if getattr(source, "share_regions", False):
        return engine.shared_region(source, name, kind, region, dtype, build)
    return build()


# Historical name (the path predates the fragment-source generalization);
# disk checkpoints are just one kind of source.
read_region_from_dist = read_region_from_source


class RestoreStats:
    def __init__(self):
        self.bytes_read = 0
        self.arrays = 0


_FIELDS: tuple[tuple[str, StateKind], ...] = (
    ("params", StateKind.FP32),
    ("exp_avg", StateKind.EXP_AVG),
    ("exp_avg_sq", StateKind.EXP_AVG_SQ),
)


def _build_trees(
    reader,  # (name, kind, region, dtype) -> np.ndarray
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    fields: tuple[tuple[str, StateKind], ...],
    stats: RestoreStats | None = None,
    engine: CheckpointEngine | None = None,
    *,
    names: set[str] | None = None,
    locate: Callable | None = None,
) -> dict[str, dict[str, jax.Array]]:
    """Build the requested state trees as flat ``{field: {name: array}}``.

    The engine of every full restore path: enumerates the device regions,
    prefetches them concurrently, then materializes sharded jax arrays.
    ``fields`` selects which state kinds to build (the full ladder for a
    training resume, params-only for a serving reader) and ``names``
    restricts to a parameter subset (delta-subscription in-place updates).
    ``locate`` (see :func:`_whole_fragment_locator`) names the regions that
    one raw shard file serves whole; those skip ``reader``.
    """
    engine = engine or default_engine()
    pspecs = plan.state_pspecs()
    param_items = [
        (n, s) for n, s in plan.param_specs.items() if names is None or n in names
    ]

    # Enumerate every (param, device-region) each state kind will request,
    # so a kind's reads can all start concurrently up front: the
    # make_array callbacks then serve from the prefetch table instead of
    # reading serially one device region at a time.  Batching per kind
    # bounds peak prefetch memory to one state copy.
    plans: list[tuple[dict[str, NamedSharding], list]] = []
    for field, kind in fields:
        shardings: dict[str, NamedSharding] = {}
        jobs: list[tuple[str, str, tuple[slice, ...]]] = []
        seen: set[tuple] = set()
        for name, spec in param_items:
            sharding = NamedSharding(jmesh, pspecs[field][name])
            shardings[name] = sharding
            shape = tuple(spec.runtime_shape)
            for index in sharding.addressable_devices_indices_map(shape).values():
                canon = _canon_region(index, shape)
                key = (name, tuple((r.start, r.stop) for r in canon))
                if key not in seen:
                    seen.add(key)
                    jobs.append((name, spec.states[kind].dtype, canon))
        plans.append((shardings, jobs))
    # Every kind but the last hands its staging storage to the next (same
    # shapes), so the arena keeps the largest such kind's worth for the
    # length of the restore instead of faulting fresh pages for each kind.
    handoff = max(
        (_staging_bytes(jobs) for _, jobs in plans[:-1]), default=0
    ) if engine.use_arena else 0

    trees: dict[str, dict[str, jax.Array]] = {}
    with engine.arena.hold(handoff):
        for i, ((field, kind), (shardings, jobs)) in enumerate(zip(fields, plans)):
            warm0, fresh0 = engine.arena.reuse_bytes, engine.arena.alloc_bytes
            with obs.span("restore.prefetch", field=field, regions=len(jobs)) as sp:
                results = _prefetch(reader, locate, kind, jobs, engine)
                sp.set(warm_bytes=engine.arena.reuse_bytes - warm0,
                       fresh_bytes=engine.arena.alloc_bytes - fresh0)
            table = {
                (n, tuple((r.start, r.stop) for r in canon)): arr
                for (n, _, canon), arr in zip(jobs, results)
            }
            del results  # the table holds the only references now

            flat: dict[str, jax.Array] = {}
            with obs.span("restore.materialize", field=field):
                for name, spec in param_items:
                    dtype = spec.states[kind].dtype
                    shape = tuple(spec.runtime_shape)

                    def cb(index, _n=name, _k=kind, _d=dtype, _s=shape):
                        canon = _canon_region(index, _s)
                        arr = table.get((_n, tuple((r.start, r.stop) for r in canon)))
                        if arr is None:  # region jax didn't pre-announce: read now
                            arr = reader(_n, _k, canon, _d)
                        if stats is not None:
                            stats.bytes_read += arr.nbytes
                        obs.add("restore.bytes_read", arr.nbytes)
                        return arr

                    flat[name] = jax.make_array_from_callback(
                        shape, shardings[name], cb
                    )
                    if stats is not None:
                        stats.arrays += 1
                    obs.add("restore.arrays")
                    # Offer the staging storage back; the arena takes it
                    # only once jax no longer holds it (the transfer done).
                    for key in [k for k in table if k[0] == name]:
                        engine.recycle(table.pop(key))
                if handoff and i < len(fields) - 1:
                    # The next kind's allocations then find this kind's
                    # storage free.
                    _release_staging(list(flat.values()))
            trees[field] = flat
    return trees


def _release_staging(arrays: list[jax.Array]) -> None:
    """Wait until jax no longer holds the host buffers ``arrays`` were
    placed from.  Placement returns before its host-to-device copies land;
    once they have, jax drops each buffer through a deferred list that it
    drains only on some later calls, so drain it here."""
    from jax._src.lib import _jax

    jax.block_until_ready(arrays)
    _jax.collect_garbage()


def _staging_bytes(jobs) -> int:
    """Arena storage the ``(name, dtype, region)`` jobs of one kind take."""
    return sum(
        BufferArena.bucket(max(
            math.prod(r.stop - r.start for r in region)
            * resolve_dtype(dtype).itemsize, 1,
        ))
        for _, dtype, region in jobs
    )


def _prefetch(reader, locate, kind: StateKind, jobs, engine: CheckpointEngine) -> list:
    """Read every ``(name, dtype, region)`` job of one state kind, in order.

    A region that ``locate`` finds whole in one raw shard file is read
    straight into its destination buffer as fixed-size byte ranges (no
    decoded copy in the handle cache, no second copy into staging); every
    other region is one ``reader`` call.  Range jobs and reader jobs share
    one ``engine.map`` and are all enumerated here, up front: a job never
    submits to the pool and waits on it, which would deadlock once every
    worker is busy.
    """
    out: list = [None] * len(jobs)
    tasks: list[Callable[[], np.ndarray | None]] = []
    slots: list[int | None] = []  # the job a task's result fills, if any
    for i, (name, dtype, region) in enumerate(jobs):
        found = locate(name, kind, region, dtype) if locate is not None else None
        if found is None:
            tasks.append(functools.partial(reader, name, kind, region, dtype))
            slots.append(i)
            continue
        path, offset = found
        shape = tuple(r.stop - r.start for r in region)
        arr = engine.alloc(shape, resolve_dtype(dtype), zero=False)
        out[i] = arr
        buf = memoryview(arr.reshape(-1).view(np.uint8))
        for lo in range(0, arr.nbytes, READ_RANGE_BYTES):
            tasks.append(functools.partial(
                read_into, path, offset + lo, buf[lo:lo + READ_RANGE_BYTES]
            ))
            slots.append(None)
        obs.add("restore.whole_fragment_reads")
        obs.add("restore.whole_fragment_bytes", arr.nbytes)
    for i, arr in zip(slots, engine.map(lambda task: task(), tasks)):
        if i is not None:
            out[i] = arr
    return out


def _whole_fragment_locator(
    source, engine: CheckpointEngine, plan: ShardingPlan | None = None,
    transforms: Mapping[str, ParamTransform] | None = None,
):
    """``locate(name, kind, region, dtype) -> (path, offset) | None`` for a
    disk checkpoint (None for any other source): where one raw shard file
    holds exactly the bytes the region's reader would serve.

    With ``transforms`` (the streamed reshard), only the regions the
    stream reader serves as one unclipped fragment read qualify: never a
    ``CONSOLIDATE`` parameter, never a region reaching into padding.
    """
    if not isinstance(source, DistCheckpoint):
        return None

    def locate(name, kind, region, dtype):
        if transforms is not None:
            if transforms[name].cls is TransformClass.CONSOLIDATE:
                return None
            clipped = clip_region_to_logical(
                region, plan.param_specs[name].logical_shape
            )
            if clipped is None or not clipped[2]:
                return None
            region = clipped[0]
        return source.whole_fragment(name, kind, region, dtype, engine=engine)

    return locate


def _build_state(
    reader,  # (name, kind, region, dtype) -> np.ndarray
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    step: int,
    stats: RestoreStats | None = None,
    engine: CheckpointEngine | None = None,
    locate: Callable | None = None,
) -> TrainState:
    import jax.numpy as jnp

    trees = _build_trees(reader, plan, jmesh, _FIELDS, stats, engine, locate=locate)
    return TrainState(
        params=unflatten_from_paths(trees["params"]),
        exp_avg=unflatten_from_paths(trees["exp_avg"]),
        exp_avg_sq=unflatten_from_paths(trees["exp_avg_sq"]),
        # Replicated over the mesh like the trainer's own step counter, not
        # committed to the default device alone.
        step=jax.device_put(jnp.asarray(step, jnp.int32), NamedSharding(jmesh, P())),
    )


def _source_reader(source, engine: CheckpointEngine):
    """Region reader serving straight fragment unions (DIRECT-shaped)."""

    def reader(name, kind, region, dtype):
        return read_region_from_source(source, name, kind, region, dtype, engine=engine)

    return reader


def state_from_source(
    source,
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    stats: RestoreStats | None = None,
    *,
    engine: CheckpointEngine | None = None,
) -> TrainState:
    """Restore a full TrainState from any fragment source (disk checkpoint
    or in-memory hot snapshot) via indexed region reads."""
    engine = engine or default_engine()
    reader = _source_reader(source, engine)
    return _build_state(
        reader, plan, jmesh, int(source.manifest.step), stats, engine,
        _whole_fragment_locator(source, engine),
    )


# Historical name, kept for disk-checkpoint call sites.
state_from_dist = state_from_source


def state_from_stream(
    source,
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    transforms: Mapping[str, ParamTransform],
    stats: RestoreStats | None = None,
    *,
    engine: CheckpointEngine | None = None,
) -> TrainState:
    """RESHARD_STREAM: reconfigure parallelism with no intermediate checkpoint.

    Per parameter, the plan table (``transforms``, from
    :func:`repro.core.plan.stream_transforms`) picks one of two in-memory
    routes — nothing is ever written to disk:

    * ``IDENTITY`` / ``RESLICE`` — Target device regions are served by the
      indexed region-read path straight from Source fragments.  Regions are
      clipped to the logical shape and alignment padding is zero-filled, so
      the result is bit-identical to what the UCP Load path produces.
    * ``CONSOLIDATE`` — the parameter's logical atom is assembled in memory
      (:func:`repro.core.convert.assemble_atom` — the exact kernel the UCP
      export uses) into the engine's byte-bounded atom cache, then Target
      regions are served from it exactly like ``state_from_ucp`` serves
      file-backed atoms.

    ``source`` is any :class:`~repro.core.engine.FragmentSource`: the disk
    checkpoint (``RESHARD_STREAM``) or a surviving hot snapshot
    (``HOT_RESHARD``).  Bit-identity with the VIA_UCP restore holds for
    every transform class by construction.
    """
    engine = engine or default_engine()
    reader = _stream_reader(source, plan, transforms, engine)
    return _build_state(
        reader, plan, jmesh, int(source.manifest.step), stats, engine,
        _whole_fragment_locator(source, engine, plan, transforms),
    )


def _stream_reader(
    source,
    plan: ShardingPlan,
    transforms: Mapping[str, ParamTransform],
    engine: CheckpointEngine,
):
    """The per-param plan-table region reader behind ``state_from_stream``
    (shared with the params-only serving restore)."""
    src_params = source.manifest.params

    def reader(name, kind, region, dtype):
        # Strict lookup: stream_transforms always produces a complete
        # table; a param missing from a hand-built one must fail loudly
        # rather than silently take the raw streaming path (which would be
        # wrong for e.g. an omitted params_to_average entry).
        tr = transforms[name]
        tgt_spec = plan.param_specs[name]
        if tr.cls is TransformClass.CONSOLIDATE:
            # ascontiguousarray: assemble_atom may return a strip_padding
            # view into the runtime-shaped staging buffer — caching the
            # view would pin the padded storage and under-count its weight.
            atom = engine.consolidated(
                source, name, kind,
                lambda: np.ascontiguousarray(
                    assemble_atom(source, src_params[name], kind, engine=engine)
                ),
            )
            return read_runtime_region(
                atom, tgt_spec, region, dtype, alloc=engine.alloc
            )
        # Stream: Source and Target share one runtime coordinate space (the
        # classifier guarantees it).  Clip the region to the logical shape
        # and zero-fill the remainder so alignment padding comes back as
        # zeros — the same canonical bytes the UCP Load path serves
        # (clip_region_to_logical is shared with read_runtime_region) —
        # instead of whatever the Source runtime left in its padded area.
        region = _canon_region(region, tgt_spec.runtime_shape)
        shape = tuple(r.stop - r.start for r in region)
        clipped = clip_region_to_logical(region, tgt_spec.logical_shape)
        if clipped is None:  # region entirely inside padding
            return engine.alloc(shape, resolve_dtype(dtype), zero=True)
        reads, dests, full = clipped
        inner = read_region_from_source(
            source, name, kind, reads, dtype, engine=engine
        )
        if full:
            return inner
        out = engine.alloc(shape, resolve_dtype(dtype), zero=True)
        out[dests] = inner
        engine.recycle(inner)
        return out

    return reader


def build_param_arrays(
    source,
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    *,
    transforms: Mapping[str, ParamTransform] | None = None,
    names: set[str] | None = None,
    stats: RestoreStats | None = None,
    engine: CheckpointEngine | None = None,
) -> dict[str, jax.Array]:
    """Materialize sharded *weight* arrays from a fragment source, flat.

    The serving-side building block: a flat ``{name: jax.Array}`` dict of
    FP32 parameter state only — no optimizer moments, so a fleet of
    inference replicas pays one third of a training restore's memory and
    I/O.  ``transforms=None`` means the source layout equals the target
    (straight fragment unions); a plan table from
    :func:`repro.core.plan.stream_transforms` streams a layout change.
    ``names`` restricts to a parameter subset — how a delta subscription
    updates a live replica in place (fetch only the changed params).
    """
    engine = engine or default_engine()
    reader = (
        _source_reader(source, engine)
        if transforms is None
        else _stream_reader(source, plan, transforms, engine)
    )
    trees = _build_trees(
        reader, plan, jmesh, (("params", StateKind.FP32),), stats, engine,
        names=names, locate=_whole_fragment_locator(source, engine, plan, transforms),
    )
    return trees["params"]


def params_from_source(
    source,
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    stats: RestoreStats | None = None,
    *,
    transforms: Mapping[str, ParamTransform] | None = None,
    engine: CheckpointEngine | None = None,
):
    """Weights-only restore: the params pytree, resharded onto ``jmesh``.

    Same region reads as :func:`state_from_source` /
    :func:`state_from_stream` restricted to FP32 — bit-identical to the
    ``.params`` tree of the corresponding full restore.
    """
    flat = build_param_arrays(
        source, plan, jmesh, transforms=transforms, stats=stats, engine=engine
    )
    return unflatten_from_paths(flat)


def state_from_ucp(
    ucp: UcpCheckpoint,
    plan: ShardingPlan,
    jmesh: jax.sharding.Mesh,
    stats: RestoreStats | None = None,
    *,
    engine: CheckpointEngine | None = None,
) -> TrainState:
    engine = engine or default_engine()

    def reader(name, kind, region, dtype):
        # handle-cached mmap — only the region's pages are touched, and the
        # atom file is opened once across all device regions.
        atom = engine.read_atom(ucp, name, kind)
        return read_runtime_region(
            atom, plan.param_specs[name], region, dtype, alloc=engine.alloc
        )

    return _build_state(reader, plan, jmesh, int(ucp.manifest.step), stats, engine)
