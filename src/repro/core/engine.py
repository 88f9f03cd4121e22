"""The shared checkpoint I/O engine: index, handle cache, worker pool.

Every save / convert / restore path in the repo routes its file I/O through
a :class:`CheckpointEngine`.  The engine owns the three things the paper's
efficiency claims (Fig. 11 zero save cost, Fig. 12 negligible
reconfiguration cost) depend on operationally:

* :class:`FragmentIndex` — a sorted interval index over the fragment
  atom-slices of one ``(checkpoint, param, kind)``, built once and cached.
  Region reads (``read_region_from_dist``, the direct-reshard path) query
  the index and touch only the fragments that overlap the requested region,
  instead of linearly scanning every writing rank and recomputing
  ``layout_for`` per call.
* :class:`HandleCache` — a bounded, thread-safe LRU of open mmap handles
  keyed by file path.  A restore of N parameters × R device regions opens
  each shard/atom file once, not once per region.  A region that one raw
  shard file holds whole bypasses it: ``repro.ckpt.restore`` reads that
  file's payload straight into the region's arena buffer, in byte ranges
  enumerated up front as jobs of the same :meth:`CheckpointEngine.map`
  (never submitted from inside a job, which would deadlock a full pool);
  caching a file read exactly once per restore would only pin memory.
* a bounded worker pool (:meth:`CheckpointEngine.map`) — shard writes and
  region reads are mmap/memcpy/fsync work that releases the GIL, so both
  directions fan out over threads; ``workers=1`` degrades to the exact
  serial order, which keeps the parallel paths benchmarkable against
  themselves.
* :class:`BufferArena` — recycled staging buffers for shard slicing and
  region assembly, because first-touch page faults on fresh allocations
  neither scale across threads nor amortize across checkpoints.

The engine is deliberately format-agnostic glue: it never interprets tensor
contents, so ``repro.core.ops`` stays pure and the on-disk formats are
unchanged — an engine-enabled reader and the serial reader are bit-identical.

**Fragment sources.**  The index and the region-read path are generic over
a *fragment source* — anything that answers the three questions a region
read needs (see :class:`FragmentSource`):

* ``.manifest`` — a :class:`~repro.core.dist_ckpt.DistManifest`-shaped
  header (``params``, ``mesh``, ``save_mode``);
* ``.writing_ranks(name, kind)`` — which ranks' fragments are *available*;
* ``.read_fragment(rank, name, kind, engine=...)`` — the fragment bytes.

:class:`~repro.core.dist_ckpt.DistCheckpoint` (atom-slice files on disk)
and :class:`repro.hot.snapshot.HotSnapshot` (peer-replicated shard buffers
in host memory) both implement it, so the DIRECT and direct-reshard restore
paths serve from disk and from the hot tier through one code path
(``repro.ckpt.restore.read_region_from_source``).
"""

from __future__ import annotations

import bisect
import contextlib
import math
import os
import sys
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Iterable, Protocol, Sequence, runtime_checkable

import numpy as np

import repro.obs as obs
from .tensor_io import resolve_dtype

__all__ = [
    "BufferArena",
    "CheckpointEngine",
    "FragmentIndex",
    "FragmentSource",
    "HandleCache",
    "default_engine",
    "default_workers",
    "source_cache_key",
]


def default_workers() -> int:
    """Pool width when the caller does not choose: enough threads to overlap
    fsync latency even on small hosts, bounded so huge hosts don't thrash."""
    return min(16, max(4, (os.cpu_count() or 2) * 2))


# ---------------------------------------------------------------------------
# Fragment sources
# ---------------------------------------------------------------------------


@runtime_checkable
class FragmentSource(Protocol):
    """Anything the indexed region-read path can serve fragments from.

    A fragment source pairs a manifest (the geometry: ``params``, ``mesh``,
    ``save_mode``) with a way to enumerate and read the fragments that are
    currently *available* — for a disk checkpoint that is every persisted
    shard file; for an in-memory hot snapshot it is every fragment with at
    least one surviving replica holder.  ``cache_key`` identifies the
    source's *contents* for the engine's index cache: it must change when
    availability changes (the hot tier bumps a generation counter on rank
    failure), and it must be stable across reads of unchanged contents.
    """

    @property
    def manifest(self) -> Any: ...

    @property
    def cache_key(self) -> str: ...

    def writing_ranks(self, name: str, kind) -> list[int]: ...

    def read_fragment(self, rank: int, name: str, kind, *, engine=None) -> np.ndarray: ...


def source_cache_key(source) -> str:
    """Index-cache identity of a source (``cache_key``, else the root path)."""
    key = getattr(source, "cache_key", None)
    return key if key is not None else str(source.root)


def _key_under_root(key: str, root: str) -> bool:
    """Whether a cache key belongs to ``root``: the root itself, a delta
    variant (``root@delta:N``), a derived key (``root::atom::...``), or a
    file under it (``root/...``) — but never a *sibling* that merely shares
    ``root`` as a string prefix (``root10`` vs ``root1``)."""
    if not key.startswith(root):
        return False
    rest = key[len(root):]
    return rest == "" or rest[0] in (os.sep, "@", ":")


# ---------------------------------------------------------------------------
# Buffer arena
# ---------------------------------------------------------------------------


class _ArenaBuffer(np.ndarray):
    """Marker subclass: storage owned by a :class:`BufferArena`.

    ``recycle`` walks an array's ``.base`` chain and only reclaims storage
    that bottoms out in one of these — foreign arrays pass through silently.
    """


class BufferArena:
    """Reusable staging buffers for shard slicing and region assembly.

    Freshly-mmapped anonymous pages cost a kernel fault + zero per page on
    first touch, and that fault path neither scales across threads nor
    amortizes across checkpoints — it is the dominant cost of allocating a
    new destination array per region/shard and it caps parallel
    restore/save at ~1x.  The arena keeps retired buffers (warm,
    already-faulted pages) on size-keyed free lists, so steady-state
    staging copies run at memcpy speed and parallelize.

    **Reclamation is refcount-gated.**  Consumers may hand a staging buffer
    to something that aliases rather than copies it — jax's CPU
    ``device_put`` zero-copies suitably-aligned arrays, and whether it does
    so varies by size/alignment.  ``recycle`` therefore never frees
    directly: the buffer parks on a *pending* list and its storage only
    re-enters the free lists once the view chain built by ``alloc`` has no
    outside referents (``sys.getrefcount``, CPython's immediate
    refcounting).  A zero-copy jax array keeps the chain alive, so its
    storage is reclaimed exactly when that array dies — never under it.

    ``alloc(..., zero=False)`` skips clearing when the caller proves it
    will overwrite every element (fragments fully cover the region);
    contents of a recycled buffer are otherwise arbitrary, so callers must
    pass ``zero=True`` unless they fully overwrite.

    **Retention is bounded** by ``max_bytes``; :meth:`hold` raises the
    bound for the length of one operation whose later steps reuse its
    earlier steps' buffers (a restore handing one state kind's staging to
    the next), and trims back to ``max_bytes`` when it ends.
    """

    def __init__(self, max_bytes: int = 1 << 30):
        self.max_bytes = int(max_bytes)
        self._lock = threading.Lock()
        self._free: dict[int, list[np.ndarray]] = {}  #: guarded by self._lock
        self._pending: list[np.ndarray] = []  #: guarded by self._lock -- recycled, chain maybe alive
        self._pooled_ids: set[int] = set()  #: guarded by self._lock -- ids parked in _free or _pending
        self._retained = 0  #: guarded by self._lock
        self._held = 0  #: guarded by self._lock -- retention above max_bytes lent to open holds
        self.allocs = 0
        self.reuses = 0
        self.alloc_bytes = 0  # requested bytes served from fresh storage
        self.reuse_bytes = 0  # requested bytes served from recycled storage

    @staticmethod
    def bucket(nbytes: int) -> int:
        """The storage ``alloc`` takes for ``nbytes``: a power of two (min
        one page), so near-miss sizes still reuse each other's storage;
        waste is bounded at 2x."""
        size = 4096
        while size < nbytes:
            size <<= 1
        return size

    def _reap_locked(self) -> None:  # repro: holds[self._lock]
        """Move pending buffers whose view chains died onto the free lists."""
        budget = self.max_bytes + self._held
        still: list[np.ndarray] = []
        for raw in self._pending:
            # References when the chain is dead: the _pending list, the
            # loop variable, and getrefcount's argument binding == 3.  A
            # live view (ours or an aliasing jax array's) adds a fourth.
            if sys.getrefcount(raw) <= 3:
                if self._retained + raw.nbytes <= budget:
                    self._free.setdefault(raw.nbytes, []).append(raw)
                    self._retained += raw.nbytes
                else:
                    self._pooled_ids.discard(id(raw))  # over budget: drop
            else:
                still.append(raw)
        self._pending = still

    @contextlib.contextmanager
    def hold(self, nbytes: int):
        """Retain up to ``nbytes`` more recycled storage while the block runs.

        The pending buffers free at entry are reaped under the plain
        ``max_bytes`` first, so what was recycled before the hold is kept
        as without it.  On exit the free lists are trimmed back to the
        bound, first fit in their order as the reap fills them: between
        holds the arena keeps what it keeps without them.  Holds nest
        additively; ``nbytes <= 0`` is no hold at all.
        """
        if nbytes <= 0:
            yield
            return
        with self._lock:
            self._reap_locked()
            self._held += nbytes
        try:
            yield
        finally:
            with self._lock:
                self._held -= nbytes
                self._trim_locked()

    def _trim_locked(self) -> None:  # repro: holds[self._lock]
        """Drop free buffers until the retained bytes fit the bound."""
        budget = self.max_bytes + self._held
        if self._retained <= budget:
            return
        kept = 0
        for stack in self._free.values():
            keep = []
            for raw in stack:
                if kept + raw.nbytes <= budget:
                    keep.append(raw)
                    kept += raw.nbytes
                else:
                    self._pooled_ids.discard(id(raw))
            stack[:] = keep
        self._retained = kept

    def alloc(self, shape, dtype, *, zero: bool = True) -> np.ndarray:
        dt = resolve_dtype(dtype) if isinstance(dtype, str) else np.dtype(dtype)
        nbytes = math.prod(int(s) for s in shape) * dt.itemsize if shape else dt.itemsize
        bucket = self.bucket(max(nbytes, 1))
        raw = None
        with self._lock:
            self._reap_locked()
            stack = self._free.get(bucket)
            if stack:
                raw = stack.pop()
                self._retained -= raw.nbytes
                self._pooled_ids.discard(id(raw))
                self.reuses += 1
                self.reuse_bytes += nbytes
                obs.add("engine.arena.reuse")
                obs.add("engine.arena.reuse_bytes", nbytes)
            else:
                self.allocs += 1
                self.alloc_bytes += nbytes
                obs.add("engine.arena.alloc")
                obs.add("engine.arena.alloc_bytes", nbytes)
        if raw is None:
            raw = np.empty(bucket, np.uint8).view(_ArenaBuffer)
        # plain-ndarray view (consumers like np.save / jax shouldn't see the
        # marker subclass); its .base chain still reaches the _ArenaBuffer.
        out = (
            raw[:nbytes]
            .view(dt)
            .reshape(tuple(int(s) for s in shape))
            .view(np.ndarray)
        )
        if zero:
            out[...] = np.zeros((), dt)
        return out

    def recycle(self, arr: np.ndarray | None) -> None:
        """Offer an arena-backed array's storage back for reuse.

        Storage re-enters circulation only after every view of it (the
        caller's and any aliasing consumer's) is gone — see class docstring.
        """
        # Walk to the DEEPEST marker view — that is the full bucket-sized
        # buffer allocated by alloc(); intermediate views (slice/view/
        # reshape) inherit the subclass but only cover nbytes of it.
        node, base = arr, None
        while node is not None:
            if isinstance(node, _ArenaBuffer):
                base = node
            node = getattr(node, "base", None)
        if base is None:
            return
        with self._lock:
            if id(base) in self._pooled_ids:  # double-recycle guard
                return
            self._pooled_ids.add(id(base))
            self._pending.append(base)

    def clear(self) -> None:
        with self._lock:
            self._free.clear()
            self._pending.clear()
            self._pooled_ids.clear()
            self._retained = 0


# ---------------------------------------------------------------------------
# Handle cache
# ---------------------------------------------------------------------------


class HandleCache:
    """Bounded LRU of open array handles, keyed by file path.

    Values are whatever the loader returns — an ``np.load(mmap_mode)`` view
    or a fully-materialized array (see ``CheckpointEngine.mmap_handles``).
    Bounded both by entry count and by bytes (materialized handles carry
    their array's weight; mmap views are nearly free).  Eviction simply
    drops the reference; the OS unmaps / the GC frees once the last slice
    taken from the handle dies, so evicted handles stay safe to use.
    """

    def __init__(
        self,
        capacity: int = 128,
        max_bytes: int = 1 << 30,
        metric: str = "engine.handle",
    ):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.max_bytes = int(max_bytes)
        # obs counter prefix — the engine's two caches (file handles,
        # consolidated atoms) report hit/miss/eviction under distinct names.
        # Precomputed so the disabled-tracer hot path allocates nothing.
        self.metric = metric
        self._m_hit = metric + ".hit"
        self._m_miss = metric + ".miss"
        self._m_evict = metric + ".eviction"
        self._lock = threading.Lock()
        self._entries: OrderedDict[str, Any] = OrderedDict()  #: guarded by self._lock
        self._bytes = 0  #: guarded by self._lock
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    @staticmethod
    def _weight(value: Any) -> int:
        # mmap views cost address space, not residency — count them light.
        if isinstance(value, np.memmap) or (
            isinstance(value, np.ndarray) and isinstance(value.base, np.memmap)
        ):
            return 0
        return int(getattr(value, "nbytes", 0))

    def get(self, path: str | os.PathLike, loader: Callable[[], Any]) -> Any:
        key = str(path)
        with self._lock:
            if key in self._entries:
                self.hits += 1
                self._entries.move_to_end(key)
                obs.add(self._m_hit)
                return self._entries[key]
            self.misses += 1
        obs.add(self._m_miss)
        value = loader()  # outside the lock: loads may fault pages / IO
        evicted = 0
        with self._lock:
            if key not in self._entries:
                self._entries[key] = value
                self._bytes += self._weight(value)
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity or (
                self._bytes > self.max_bytes and len(self._entries) > 1
            ):
                _, old = self._entries.popitem(last=False)
                self._bytes -= self._weight(old)
                self.evictions += 1
                evicted += 1
        if evicted:
            obs.add(self._m_evict, evicted)
        return value

    def invalidate(self, path: str | os.PathLike | None = None) -> None:
        """Drop one handle (or all) — needed when a file is rewritten."""
        with self._lock:
            if path is None:
                self._entries.clear()
                self._bytes = 0
            else:
                old = self._entries.pop(str(path), None)
                if old is not None:
                    self._bytes -= self._weight(old)

    def invalidate_prefix(self, prefix: str | os.PathLike) -> None:
        """Drop every handle under a directory (checkpoint rewritten/GC'd).
        Boundary-aware: never touches a sibling directory that merely
        shares the prefix as a string (``run10`` vs ``run1``)."""
        prefix = str(prefix)
        with self._lock:
            for key in [k for k in self._entries if _key_under_root(k, prefix)]:
                self._bytes -= self._weight(self._entries.pop(key))

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, path: str | os.PathLike) -> bool:
        with self._lock:
            return str(path) in self._entries


# ---------------------------------------------------------------------------
# Fragment index
# ---------------------------------------------------------------------------


class FragmentIndex:
    """Sorted interval index over one ``(fragment source, param, kind)``.

    Indexes the atom-slices of every available fragment entry (one
    representative writing rank per distinct fragment — replicas hold
    byte-identical data).  ``overlapping(region)`` returns exactly the
    entries that intersect a runtime-coordinate region, found by bisecting
    the dim-0 intervals and exact-checking the remaining dims, instead of
    scanning all ranks × entries.  ``source`` is any :class:`FragmentSource`
    (disk checkpoint or in-memory hot snapshot) — the index only consumes
    the manifest geometry and the available-rank enumeration.
    """

    def __init__(self, source, name: str, kind) -> None:
        manifest = source.manifest
        self.name = name
        self.kind = kind
        self.spec = manifest.params[name]
        self.layout = self.spec.layout_for(kind, manifest.mesh)
        items: list[tuple[int, int, int, Any]] = []
        seen_frags: set[int] = set()
        for rank in source.writing_ranks(name, kind):
            frag = self.layout.fragment_id[rank]
            if frag in seen_frags:
                continue
            seen_frags.add(frag)
            for e in self.layout.entries[rank]:
                if e.atom_slice:
                    a0, a1 = e.atom_slice[0]
                else:  # 0-d tensor: a single degenerate interval
                    a0, a1 = 0, 1
                items.append((a0, a1, rank, e))
        items.sort(key=lambda t: (t[0], t[1]))
        self._items = items
        self._starts = [t[0] for t in items]
        # prefix max of stops → leftward scan can stop as soon as no earlier
        # interval can still reach the query start (classic interval list).
        self._prefix_max_stop: list[int] = []
        m = -1
        for _, a1, _, _ in items:
            m = max(m, a1)
            self._prefix_max_stop.append(m)

    @property
    def num_entries(self) -> int:
        return len(self._items)

    def overlapping(
        self, region: Sequence[slice]
    ) -> list[tuple[int, Any, tuple[tuple[int, int], ...]]]:
        """Entries intersecting ``region`` (unit-step runtime slices).

        Returns ``(rank, entry, overlaps)`` triples where ``overlaps`` is the
        per-dim ``(lo, hi)`` intersection in atom coordinates.  Distinct
        fragments are pairwise disjoint, so every returned entry contributes
        unique elements of the region.
        """
        region = tuple(region)
        if region:
            q_start, q_stop = region[0].start, region[0].stop
        else:
            q_start, q_stop = 0, 1
        out: list[tuple[int, Any, tuple[tuple[int, int], ...]]] = []
        j = bisect.bisect_left(self._starts, q_stop) - 1  # start0 < q_stop
        while j >= 0 and self._prefix_max_stop[j] > q_start:
            a0, a1, rank, e = self._items[j]
            j -= 1
            if a1 <= q_start:
                continue
            ovs: list[tuple[int, int]] = []
            ok = True
            for (f0, f1), r in zip(e.atom_slice, region):
                lo, hi = max(f0, r.start), min(f1, r.stop)
                if hi <= lo:
                    ok = False
                    break
                ovs.append((lo, hi))
            if ok:
                out.append((rank, e, tuple(ovs)))
        return out


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------


class CheckpointEngine:
    """Shared I/O engine: fragment indexes + handle cache + worker pool.

    One engine per process (``default_engine()``) is normally enough — the
    caches are keyed by checkpoint root so several checkpoints can share it.
    Benchmarks construct private engines to compare ``workers=1`` against
    ``workers>=4`` under otherwise identical caching.
    """

    def __init__(
        self,
        *,
        workers: int | None = None,
        handle_cache_size: int = 1024,
        handle_cache_bytes: int = 1 << 30,
        arena_max_bytes: int = 1 << 30,
        atom_cache_bytes: int = 1 << 30,
        mmap_handles: bool | None = None,
        use_arena: bool | None = None,
    ) -> None:
        """``workers=1`` is the reference serial profile — lazy mmap
        handles, fresh ``np.zeros`` staging, no batching: exactly the
        pre-engine code path, kept so the parallel engine stays
        benchmarkable against it.  ``workers>1`` enables the engine
        machinery: ``mmap_handles=False`` materializes each shard/atom file
        into the handle cache on first touch, so the several regions cut
        from one file copy out of memory (lazy mmap views instead re-fault
        pages through the filesystem on every access, and those faults
        serialize across threads), and ``use_arena=True`` recycles staging
        buffers (see :class:`BufferArena`).  Both flags can also be forced
        explicitly.  A region one raw shard file serves whole (every region
        of a same-layout DIRECT resume) is read straight into its staging
        buffer instead and never enters the handle cache: materializing
        it there would be a second full copy of each byte."""
        self.workers = default_workers() if workers is None else int(workers)
        if self.workers < 1:
            raise ValueError(f"workers must be >= 1, got {workers}")
        serial = self.workers == 1
        self.mmap_handles = serial if mmap_handles is None else bool(mmap_handles)
        self.use_arena = (not serial) if use_arena is None else bool(use_arena)
        self.handles = HandleCache(handle_cache_size, handle_cache_bytes)
        # In-memory consolidated atoms (the stream-restore fallback for
        # params whose transform needs consolidation) — byte-bounded LRU so
        # a restore's peak memory for fallback atoms is capped.
        self.atoms = HandleCache(256, atom_cache_bytes, metric="engine.atom")
        self.arena = BufferArena(arena_max_bytes)
        self._indexes: dict[tuple[str, str, str], FragmentIndex] = {}  #: guarded by self._index_lock
        self._index_lock = threading.Lock()
        self._atom_locks: dict[str, threading.Lock] = {}  #: guarded by self._atom_locks_lock
        self._atom_locks_lock = threading.Lock()
        self._pool: ThreadPoolExecutor | None = None  #: guarded by self._pool_lock
        self._pool_lock = threading.Lock()

    # ----------------------------------------------------------------- arena
    def alloc(self, shape, dtype, *, zero: bool = True) -> np.ndarray:
        """Staging buffer: arena-backed (see :class:`BufferArena`), or a
        plain fresh ``np.zeros`` under the serial reference profile."""
        if not self.use_arena:
            dt = resolve_dtype(dtype) if isinstance(dtype, str) else np.dtype(dtype)
            return np.zeros(tuple(int(s) for s in shape), dt)
        return self.arena.alloc(shape, dtype, zero=zero)

    def recycle(self, arr: np.ndarray | None) -> None:
        if self.use_arena:
            self.arena.recycle(arr)

    # ------------------------------------------------------------------ pool
    def _get_pool(self) -> ThreadPoolExecutor:
        with self._pool_lock:
            if self._pool is None:
                self._pool = ThreadPoolExecutor(
                    max_workers=self.workers, thread_name_prefix="ckpt-io"
                )
            return self._pool

    def map(self, fn: Callable[[Any], Any], items: Iterable[Any]) -> list[Any]:
        """Run ``fn`` over ``items``; ordered results.

        ``workers == 1`` executes inline in iteration order — the exact
        serial code path, not a one-thread pool — so serial-vs-parallel
        comparisons measure concurrency and nothing else.
        """
        items = list(items)
        if self.workers == 1 or len(items) <= 1:
            return [fn(x) for x in items]
        parent = obs.current()
        if parent is not None:
            # Explicit span handoff into the pool: worker-side spans nest
            # under the submitting span (which stays open — map() blocks on
            # the results), instead of floating as per-thread roots.
            inner = fn

            def fn(x):
                with obs.attach(parent):
                    return inner(x)

        return list(self._get_pool().map(fn, items))

    def close(self) -> None:
        with self._pool_lock:
            if self._pool is not None:
                self._pool.shutdown(wait=True)
                self._pool = None
        self.handles.invalidate()
        self.atoms.invalidate()
        self.arena.clear()
        with self._atom_locks_lock:
            self._atom_locks.clear()
        with self._index_lock:
            self._indexes.clear()

    def __enter__(self) -> "CheckpointEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ----------------------------------------------------------------- index
    def index_for(self, source, name: str, kind) -> FragmentIndex:
        """The (cached) fragment index of one ``(source, param, kind)``."""
        key = (source_cache_key(source), name, getattr(kind, "value", str(kind)))
        # Optimistic unlocked peek: dict.get is GIL-atomic and an index is
        # immutable once inserted, so a stale miss just falls through to
        # the locked setdefault below.
        idx = self._indexes.get(key)  # repro: allow[lock-discipline] -- GIL-atomic read of an insert-only dict; misses retry under the lock
        if idx is not None:
            obs.add("engine.index.hit")
            return idx
        with obs.span("engine.index_build", param=name):
            obs.add("engine.index.build")
            idx = FragmentIndex(source, name, kind)
        with self._index_lock:
            return self._indexes.setdefault(key, idx)

    # ----------------------------------------------------------------- reads
    def read_shard(self, ckpt, rank: int, name: str, kind) -> np.ndarray:
        """Handle-cached read of one distributed shard file."""
        path = ckpt.shard_path(rank, name, kind)
        return self.handles.get(
            path, lambda: ckpt.read_shard(rank, name, kind, mmap=self.mmap_handles)
        )

    def read_fragment(self, source, rank: int, name: str, kind) -> np.ndarray:
        """One available fragment of any :class:`FragmentSource`.

        Disk checkpoints route through the handle cache (each shard file
        opened once across regions and parameters); in-memory sources hand
        their buffer back directly — both land in the same region-read loop.
        """
        read = getattr(source, "read_fragment", None)
        if read is not None:
            return read(rank, name, kind, engine=self)
        return self.read_shard(source, rank, name, kind)

    def read_atom(self, ucp, name: str, kind) -> np.ndarray:
        """Handle-cached read of one UCP atom file."""
        path = ucp.atom_path(name, kind)
        return self.handles.get(
            path, lambda: ucp.read_atom(name, kind, mmap=self.mmap_handles)
        )

    def consolidated(self, source, name: str, kind, builder: Callable[[], np.ndarray]) -> np.ndarray:
        """Memoized in-memory consolidated atom of one ``(source, param, kind)``.

        The stream-restore path consolidates the minority of params whose
        transform genuinely needs the atom (fused repartitioning, padding
        change, replica averaging) — each is assembled once per source and
        then serves every Target device region from memory.  Keyed like the
        fragment indexes (``cache_key``), so ``invalidate(root)`` drops a
        rewritten checkpoint's atoms too.

        Single-flight per key: a parallel restore prefetches many regions
        of the same parameter concurrently, and without serialization every
        cache miss would assemble its own copy of the full atom (the cache
        loader runs outside the cache lock by design).
        """
        key = f"{source_cache_key(source)}::atom::{name}@{getattr(kind, 'value', kind)}"
        if obs.active() is not None:
            inner = builder

            def builder():
                with obs.span("restore.consolidate", param=name):
                    return inner()

        return self._single_flight(key, builder)

    def shared_region(
        self,
        source,
        name: str,
        kind,
        region: Sequence[slice],
        dtype,
        builder: Callable[[], np.ndarray],
    ) -> np.ndarray:
        """Memoized region read — the *serving hot set* for fan-out sources.

        A fleet of readers restoring onto the same target layout requests
        the same ``(source, param, kind, region)`` tuples over and over;
        sources that opt in (``share_regions = True``, e.g.
        ``repro.serve.PeerFragmentSource``) get each distinct region
        assembled once and then served to every reader from the engine's
        byte-bounded atom cache — the fan-out analogue of the consolidated-
        atom cache, one level finer.  Single-flight per key, so N readers
        racing on a cold region build it once, not N times.

        The cached array is shared: consumers must treat it as read-only
        (the restore paths copy out of staging buffers by construction,
        and ``engine.recycle`` of a cached array is safe — arena
        reclamation is refcount-gated and the cache entry keeps the view
        chain alive until eviction).
        """
        kv = getattr(kind, "value", kind)
        span = ",".join(f"{r.start}:{r.stop}" for r in region)
        key = (
            f"{source_cache_key(source)}::region::{name}@{kv}"
            f"::{np.dtype(resolve_dtype(dtype) if isinstance(dtype, str) else dtype).str}"
            f"::{span}"
        )
        return self._single_flight(key, builder)

    def memo(self, key: str, builder: Callable[[], Any]) -> Any:
        """Single-flight memoization under an explicit key in the atom
        cache — for derived-value sharing that doesn't fit the region or
        atom key schema (e.g. a serving fleet's built param-array set,
        shared across replica threads because ``jax.Array`` is immutable).
        Keys should start with the owning source's ``cache_key`` so
        :meth:`invalidate` of that root clears them too."""
        return self._single_flight(key, builder)

    def _single_flight(self, key: str, builder: Callable[[], np.ndarray]) -> np.ndarray:
        with self._atom_locks_lock:
            lock = self._atom_locks.setdefault(key, threading.Lock())
        with lock:
            return self.atoms.get(key, builder)

    def invalidate(self, root: str | os.PathLike | None = None) -> None:
        """Forget cached state (all of it, or one checkpoint root's indexes).

        Call after rewriting files in place — e.g. a crashed save retried
        into the same directory.
        """
        if root is None:
            self.handles.invalidate()
            self.atoms.invalidate()
            with self._atom_locks_lock:
                self._atom_locks.clear()
            with self._index_lock:
                self._indexes.clear()
            return
        root = str(root)
        self.handles.invalidate_prefix(root)
        self.atoms.invalidate_prefix(root)
        with self._atom_locks_lock:
            for key in [k for k in self._atom_locks if _key_under_root(k, root)]:
                del self._atom_locks[key]
        with self._index_lock:
            # Boundary-aware prefix match: a delta checkpoint's cache_key is
            # "<root>@delta:<base_step>" (see DistCheckpoint.cache_key) and
            # must be dropped with its root — but a sibling root that shares
            # the string prefix must not be.
            for key in [k for k in self._indexes if _key_under_root(k[0], root)]:
                del self._indexes[key]

    def invalidate_chain(self, ckpt) -> None:
        """Invalidate a checkpoint root *and* every ancestor directory its
        delta chain references — a reader that failed mid-chain may hold
        stale handles/indexes of any link, not just the tip."""
        roots = getattr(ckpt, "chain_roots", None)
        for root in roots() if roots is not None else [ckpt.root]:
            self.invalidate(root)


_default_engine: CheckpointEngine | None = None
_default_lock = threading.Lock()


def default_engine() -> CheckpointEngine:
    """The process-wide shared engine (lazily created)."""
    global _default_engine
    with _default_lock:
        if _default_engine is None:
            _default_engine = CheckpointEngine()
        return _default_engine
