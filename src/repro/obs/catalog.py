"""Authoritative name catalogs for the obs layer.

One entry per span/timed/event/counter name used anywhere in the tree,
mirroring the DESIGN.md §9 taxonomy.  The tables are plain dict literals
on purpose: the static analyzer (:mod:`repro.analysis`) parses this file
with ``ast`` — never imports it — and checks, at PR time, that

* every literal ``obs.span("…")`` / ``obs.timed("…")`` / ``obs.event("…")``
  name in the tree appears here (no unregistered instrumentation), and
* every SPANS/TIMED/EVENTS entry has at least one call site (no stale
  catalog rows), and every span/timed name is mentioned in DESIGN.md §9;
* ``JIT_SPANS`` equals the names ``repro.obs.trace.JAX_SPANS`` maps JAX's
  compile events to (those spans have no ``obs.span`` call site), and every
  name ``trace.JAX_COUNTERS`` bumps is in ``COUNTERS``.

Counters are membership-only: dynamic families (listed at the bottom of
``COUNTERS``) are emitted through precomputed names, so a literal-string
scan cannot prove coverage for them.

Keep keys sorted within each group when editing; the values are the same
one-line "where it sits" descriptions :data:`repro.chaos.points.CATALOG`
uses.
"""

from __future__ import annotations

__all__ = ["SPANS", "TIMED", "JIT_SPANS", "EVENTS", "COUNTERS"]

# obs.span(name) — scoped regions with containment in the exported trace.
SPANS: dict[str, str] = {
    "ckpt.commit": "DistCheckpoint.commit: manifest rewrite + COMMIT marker",
    "ckpt.gc": "CheckpointManager.gc: one full collection pass",
    "convert.param": "convert.to_ucp, one parameter re-atomized",
    "drain.shard": "persist_snapshot, one hot fragment promoted to disk",
    "engine.index_build": "CheckpointEngine, shard index built for one checkpoint",
    "hot.capture": "HotTier.capture: staging one step into the ring",
    "hot.drain_job": "HotDrainer worker: one queued promotion end-to-end",
    "manager.save": "CheckpointManager.save: policy + write + commit + gc",
    "restore.consolidate": "restore, cross-shard regions consolidated",
    "restore.materialize": "restore, planned reads executed into arrays",
    "restore.plan": "restore, read plan computed from manifests",
    "restore.prefetch": "restore, one state kind's host region reads into staging buffers",
    "restore.tier": "one recovery-ladder attempt (hot / local / peer / disk)",
    "save.async_job": "AsyncSaver worker: one queued save end-to-end",
    "save.fsync": "save path, directory+file fsync barrier",
    "save.manifest": "save path, digest manifest rewrite",
    "save.resolve_base": "delta save, base checkpoint resolved (and pinned)",
    "save.shard": "save path, one shard persisted",
    "save.stage": "save path, arrays staged out of device buffers",
    "serve.fetch": "PeerFragmentSource.read_fragment: one fetch-ladder walk",
    "serve.publish": "PublicationRegistry.publish: store + deliver to subscribers",
    "serve.sync": "fleet reader syncing one publication into its engine",
    "train.batch": "Trainer.run, the step's batch built on the host",
    "train.dispatch": "Trainer.run, the jitted step called (returns at dispatch)",
    "train.wait": "Trainer.run, block_until_ready on the step's outputs",
}

# obs.timed(name) — always-measuring stopwatches at operation granularity.
TIMED: dict[str, str] = {
    "ckpt.restore": "one restore() call, any tier",
    "ckpt.save": "one write_distributed() call",
    "convert.to_ucp": "one DistCheckpoint -> UCP atom-store conversion",
    "dryrun.analyze": "dryrun, HLO text rendered + trip-count analysis",
    "dryrun.cell": "dryrun, one (arch x shape x mesh) cell end-to-end",
    "dryrun.compile": "dryrun, lowered module compiled",
    "dryrun.lower": "dryrun, jitted step lowered with abstract inputs",
    "hot.drain": "one snapshot promotion (persist_snapshot)",
    "serve.decode": "serving benchmark decode step",
    "serve.prefill": "serving benchmark prefill step",
    "train.step": "one training step (forward+backward+update)",
}

# JAX compile-path events recorded as spans by repro.obs itself
# (trace.JAX_SPANS), on the thread that jits, under its current span.
JIT_SPANS: dict[str, str] = {
    "jit.compile": "XLA compile of a lowered module, or its persistent-cache load",
    "jit.lower": "jaxpr lowered to an MLIR module",
    "jit.trace": "Python function traced to a jaxpr",
}

# obs.event(name) — instantaneous markers.
EVENTS: dict[str, str] = {
    "chaos.fault": "chaos controller fired an armed fault",
    "chaos.invariant_check": "chaos ladder ran the invariant checker",
    "chaos.point": "a fault_point hook was crossed (controller active)",
    "codec.ef_fallback": "error-feedback codec fell back to raw encoding",
    "restore.fallback": "recovery ladder moved to the next tier",
    "restore.hot_skip": "hot tier skipped: snapshot generation unusable",
    "restore.hot_unservable": "hot tier skipped: failed ranks made it unservable",
    "save.rebase": "delta save rebased onto a full save (chain cap / lost base)",
    "serve.digest_mismatch": "fetched fragment failed digest check, refetching",
}

# obs.add(name, n) — monotonic counters.  Exact names first, then the
# dynamic families (emitted through precomputed strings, kept here so the
# family members are still registered names).
COUNTERS: dict[str, str] = {
    "codec.decode_bytes": "bytes decoded on the read path",
    "codec.decode_shards": "shards decoded on the read path",
    "codec.encode_bytes_coded": "encoded output bytes written by the codec",
    "codec.encode_bytes_raw": "raw input bytes seen by the codec",
    "codec.encode_shards": "shards encoded on the save path",
    "convert.atoms_written": "UCP atoms written by conversion",
    "convert.bytes_read": "bytes read by conversion",
    "convert.bytes_written": "bytes written by conversion",
    "convert.params": "parameters converted",
    "engine.arena.alloc": "buffer arena: fresh allocations",
    "engine.arena.alloc_bytes": "buffer arena: requested bytes served from fresh storage",
    "engine.arena.reuse": "buffer arena: pooled-buffer reuses",
    "engine.arena.reuse_bytes": "buffer arena: requested bytes served from recycled storage",
    "engine.index.build": "shard indexes built",
    "engine.index.hit": "shard index cache hits",
    "gc.collected_bytes": "bytes reclaimed by GC",
    "gc.collected_steps": "step directories reclaimed by GC",
    "gc.pinned_steps": "deletions skipped because a chain pin held the step",
    "gc.wreckage_removed": "uncommitted wreckage directories removed",
    "hot.captures": "hot-tier captures",
    "hot.evictions": "hot-tier ring evictions",
    "hot.fragments": "fragments currently resident (bumped per capture)",
    "hot.mirrored_bytes": "bytes mirrored to replica ranks",
    "hot.resident_bytes": "bytes resident in the hot ring",
    "hot.stored_bytes": "bytes stored per capture",
    "jit.cache_hits": "persistent compilation cache hits (trace.JAX_COUNTERS)",
    "jit.cache_misses": "persistent compilation cache misses (trace.JAX_COUNTERS)",
    "restore.arrays": "arrays materialized by restore",
    "restore.bytes_read": "bytes read by restore",
    "restore.count": "restore() calls",
    "restore.region_fragments": "fragments feeding consolidated regions",
    "restore.region_reads": "consolidated region reads",
    "restore.whole_fragment_bytes": "bytes read straight from whole raw shard files",
    "restore.whole_fragment_reads": "regions read straight from one whole raw shard file",
    "save.bytes_written": "bytes written by one save",
    "save.shards_inherited": "delta save: shards inherited from the base",
    "save.shards_written": "shards physically written",
    "serve.changed_shards": "shards that changed across a publication",
    "serve.publications": "publications delivered",
    "serve.syncs": "fleet reader syncs completed",
    "ssd.intra_fallback": "ssd_chunked traced with the intra-chunk term as whole-tensor jnp",
    "ssd.intra_kernel": "ssd_chunked traced with the intra-chunk term as the Pallas kernel pair",
    # -- dynamic families --------------------------------------------------
    # save.<mode> (saver/drain: f"save.{result.mode}")
    "save.delta": "saves that took the delta path",
    "save.full": "saves that took the full path",
    # serve.<FanoutStats field> (peer._OBS_COUNTERS)
    "serve.digest_failures": "fetch ladder: digest verification failures",
    "serve.disk_bytes_read": "fetch ladder: bytes read from disk tier",
    "serve.disk_fetches": "fetch ladder: disk-tier fetches",
    "serve.local_hits": "fetch ladder: local-store hits",
    "serve.peer_bytes_read": "fetch ladder: bytes read from peers",
    "serve.peer_fetches": "fetch ladder: peer-tier fetches",
    "serve.refetches": "fetch ladder: refetches after digest failure",
    # <HandleCache.metric>.{hit,miss,eviction} (engine caches)
    "engine.atom.eviction": "atom handle cache evictions",
    "engine.atom.hit": "atom handle cache hits",
    "engine.atom.miss": "atom handle cache misses",
    "engine.handle.eviction": "shard handle cache evictions",
    "engine.handle.hit": "shard handle cache hits",
    "engine.handle.miss": "shard handle cache misses",
}
