"""The *distributed* checkpoint format (the Source/Target side of UCP).

Layout on disk::

    <ckpt_dir>/step_<N>/
        MANIFEST.json                      # mesh, param specs, scalars, config
        ranks/rank_00000/<name>@<kind>.npy # local (padded) shard arrays
        ...
        COMMIT                             # written last: atomic completion

Every rank persists exactly the shards it owns (paper §2: "each GPU is only
responsible for checkpointing a fraction of the entire model state").
Replicated fragments are deduplicated: only the lowest rank of each replica
group writes (``save_mode="dedup"``), which is what production systems do
for the DP dimension; ``save_mode="all"`` is kept for benchmarking the
difference.

Pipeline-parallel stage partitioning needs no special casing: a PP Source is
simply a mesh with a ``pipe`` axis and stacked parameters sharded along it,
so per-stage ownership falls out of the ordinary fragment layout
(see DESIGN.md §2).

**Delta checkpoints** (``save_mode="delta"``, DESIGN.md §1): a delta step
directory physically contains only the shards whose content digest changed
since the base checkpoint; every unchanged shard is a *manifest reference*
(``shard_sources``: digest key → owning step, flattened through the chain
at save time so resolution is one hop, never a walk).  ``shard_path``
resolves each shard to the sibling step directory that owns its bytes, so
every reader — DIRECT restore, streaming reshard, UCP export, validation —
serves delta chains through the unchanged fragment-read path.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from pathlib import Path
from typing import Any, Iterator, Mapping

import numpy as np

import repro.obs as obs
from repro.chaos.points import fault_point

from . import clock, codec
from .layout import MeshSpec, ShardLayout
from .patterns import ParamSpec, StateKind
from .tensor_io import (
    content_digest,
    dtype_name,
    load_tensor,
    npy_payload_offset,
    resolve_dtype,
    save_tensor,
)

__all__ = [
    "DistManifest",
    "DistCheckpoint",
    "check_chain_committed",
    "delta_incompatibility",
    "flatten_provenance",
    "resolve_delta_base",
    "shard_filename",
    "shard_digest_key",
    "writing_ranks_for",
    "FORMAT_VERSION",
]

FORMAT_VERSION = "repro-dist/v1"


def shard_filename(name: str, kind: StateKind) -> str:
    return f"{name}@{kind.value}.npy"


def shard_digest_key(rank: int, name: str, kind: StateKind) -> str:
    """Manifest key of one shard's content digest (mirrors the file layout)."""
    return f"rank_{rank:05d}/{name}@{kind.value}"


def writing_ranks_for(spec: ParamSpec, layout: ShardLayout, save_mode: str) -> list[int]:
    """Which ranks persist one (param, kind) under ``save_mode``.

    Shared by the disk format and the hot in-memory tier so both enumerate
    exactly the same fragment owners.  ``average`` params never dedup:
    every replica holds *different* data.
    """
    if save_mode == "all" or spec.average:
        return [r for r in layout.mesh.ranks() if layout.entries[r]]
    # "delta" enumerates exactly like "dedup": the write *set* is identical,
    # a delta save merely skips the members whose bytes didn't change.
    return [r for r in layout.primary_ranks() if layout.entries[r]]


def delta_incompatibility(base: "DistManifest", mesh, params, save_mode: str) -> str | None:
    """Why a delta against ``base`` is invalid (None == a delta is fine).

    A delta inherits unchanged shards by reference, which is only sound
    when the new snapshot's shard *geometry* is byte-for-byte the same as
    the base's: same mesh, same parameter set, identical per-param specs,
    and a matching write set (``"all"`` enumerates different owners than
    ``"dedup"``/``"delta"``).  Callers fall back to a full save (rebase)
    when this returns a reason.
    """
    if save_mode == "all" or base.save_mode == "all":
        return "save_mode 'all' has a different write set; delta requires dedup"
    if not base.shard_digests:
        return "base checkpoint predates content digests; nothing to diff against"
    if base.mesh != mesh:
        return f"mesh changed {dict(base.mesh.axes)} -> {dict(mesh.axes)}"
    if set(base.params) != set(params):
        return "parameter set changed"
    for name, spec in params.items():
        if base.params[name].to_json() != spec.to_json():
            return f"param spec changed for {name}"
    return None


def resolve_delta_base(
    base, root, mesh, params, save_mode: str
) -> "tuple[DistCheckpoint | None, str]":
    """Resolve and vet a delta base: ``(base, "")`` when a delta against it
    is valid, else ``(None, reason)`` — the caller rebases to a full save.

    ``base`` may be a :class:`DistCheckpoint` or a zero-arg callable
    returning one (resolved here, on the *writing* thread, so a queued
    delta diffs against the newest step that actually committed).  Shared
    by ``write_distributed`` and the hot drainer's ``persist_snapshot`` so
    the disk and hot-promotion paths cannot drift.
    """
    if callable(base):
        base = base()
    if base is None:
        return None, "no committed base checkpoint"
    if not base.is_committed:
        return None, f"base {base.root} is not committed"
    if base.root.parent != Path(root).parent:
        return None, (
            f"base {base.root} is not a sibling of {root}; "
            "chain resolution requires sibling step directories"
        )
    reason = delta_incompatibility(base.manifest, mesh, params, save_mode)
    if reason:
        return None, reason
    return base, ""


def flatten_provenance(
    manifest: "DistManifest", base: "DistCheckpoint", inherited_keys
) -> None:
    """Record delta provenance on ``manifest``: every inherited shard maps
    to the step that *actually wrote its bytes* (one hop through the base's
    own — already flat — provenance), plus the sibling directory name of
    every owning step."""
    bm = base.manifest
    sources = {k: bm.shard_sources.get(k, bm.step) for k in inherited_keys}
    manifest.base_step = bm.step
    manifest.shard_sources = sources
    manifest.base_dirs = {
        str(owner): (
            base.root.name if owner == bm.step else bm.base_dirs[str(owner)]
        )
        for owner in set(sources.values())
    }


def check_chain_committed(ckpt: "DistCheckpoint") -> None:
    """Pre-commit guard for a delta: every ancestor directory it references
    must still be a committed checkpoint.  Committing a delta whose chain
    was GC'd in the meantime would produce a committed-but-unservable step;
    failing here leaves ordinary uncommitted wreckage instead (the chain
    stays servable from the last commit)."""
    for chain_root in ckpt.chain_roots()[1:]:
        if not (chain_root / "COMMIT").exists():
            raise RuntimeError(
                f"delta for step {ckpt.manifest.step} references "
                f"{chain_root}, which is no longer a committed checkpoint"
            )


@dataclasses.dataclass
class DistManifest:
    """Self-describing header of a distributed checkpoint.

    ``scalars`` carries replicated small state (step counter, RNG key, data
    iterator cursor, LR-schedule state) as plain JSON — these are
    ``replicated_params`` in the paper's taxonomy but too small to matter
    as tensors.

    ``shard_digests`` maps :func:`shard_digest_key` → content digest
    (``sha256:...``; older manifests ``crc32:...``) of every persisted shard, recorded at save time and
    checked by :meth:`DistCheckpoint.validate` / ``restore(verify=True)``.
    Empty for checkpoints written before digests existed (verification is
    then a no-op, not a failure).  The table always covers the *full*
    shard set — including shards a delta inherits — so the next delta
    diffs against this manifest alone, never walking the chain.

    Codec tables (``repro.core.codec``, DESIGN.md §10; both sparse, both
    empty for all-raw checkpoints so the JSON round-trips unchanged):

    * ``shard_codecs`` — digest key → self-describing codec tag
      (``int8:b256``, ``int8ef:b256``, ``fp8:e4m3:b256``…) for every
      non-raw shard; :meth:`DistCheckpoint.read_shard` decodes exactly
      these, so every consumer above it serves coded shards unchanged;
    * ``shard_pre_digests`` — digest key → *pre-encode* digest of the raw
      update, recorded only where it differs from the served digest (i.e.
      for lossy tags).  ``shard_digests`` stays the digest of *served*
      (decoded) content — validation, peer-fetch verification and
      publications keep their "digest == what a reader gets" meaning —
      while the delta diff runs against :meth:`pre_encode_digests` so
      codec choice never defeats the diff.

    Delta provenance (``save_mode="delta"``):

    * ``base_step`` — the committed step this delta was diffed against;
    * ``shard_sources`` — digest key → owning step for every shard whose
      bytes live in an *ancestor* directory (own shards are omitted).
      Flattened at save time: a shard untouched for five deltas maps to
      the step that actually wrote it, not to the immediate base;
    * ``base_dirs`` — owning step → sibling directory name, so readers
      resolve ancestors without assuming a naming scheme.
    """

    step: int
    mesh: MeshSpec
    params: dict[str, ParamSpec]
    scalars: dict[str, Any]
    config_fingerprint: dict[str, Any]
    save_mode: str = "dedup"  # "dedup" | "all" | "delta"
    format_version: str = FORMAT_VERSION
    created_at: float = 0.0
    shard_digests: dict[str, str] = dataclasses.field(default_factory=dict)
    shard_codecs: dict[str, str] = dataclasses.field(default_factory=dict)
    shard_pre_digests: dict[str, str] = dataclasses.field(default_factory=dict)
    base_step: int | None = None
    shard_sources: dict[str, int] = dataclasses.field(default_factory=dict)
    base_dirs: dict[str, str] = dataclasses.field(default_factory=dict)

    def codec_tag(self, key: str) -> str:
        """Codec tag of one shard (``"raw"`` when absent from the table)."""
        return self.shard_codecs.get(key, "raw")

    def pre_encode_digests(self) -> dict[str, str]:
        """The effective *pre-encode* digest table the delta diff runs
        against: served digests overlaid with the sparse lossy-shard
        entries.  For an all-raw checkpoint this is ``shard_digests``."""
        if not self.shard_pre_digests:
            return self.shard_digests
        return {**self.shard_digests, **self.shard_pre_digests}

    def to_json(self) -> dict:
        out = {
            "format_version": self.format_version,
            "step": self.step,
            "mesh": self.mesh.to_json(),
            "params": {n: p.to_json() for n, p in self.params.items()},
            "scalars": self.scalars,
            "config_fingerprint": self.config_fingerprint,
            "save_mode": self.save_mode,
            "created_at": self.created_at,
            "shard_digests": self.shard_digests,
        }
        # Sparse codec tables: all-raw manifests round-trip byte-unchanged.
        if self.shard_codecs:
            out["shard_codecs"] = self.shard_codecs
        if self.shard_pre_digests:
            out["shard_pre_digests"] = self.shard_pre_digests
        if self.base_step is not None:
            out["base_step"] = self.base_step
            out["shard_sources"] = self.shard_sources
            out["base_dirs"] = self.base_dirs
        return out

    @classmethod
    def from_json(cls, d: Mapping) -> "DistManifest":
        if d.get("format_version") != FORMAT_VERSION:
            raise ValueError(f"unsupported checkpoint format {d.get('format_version')!r}")
        return cls(
            step=int(d["step"]),
            mesh=MeshSpec.from_json(d["mesh"]),
            params={n: ParamSpec.from_json(p) for n, p in d["params"].items()},
            scalars=dict(d["scalars"]),
            config_fingerprint=dict(d["config_fingerprint"]),
            save_mode=str(d.get("save_mode", "dedup")),
            created_at=float(d.get("created_at", 0.0)),
            shard_digests={str(k): str(v) for k, v in d.get("shard_digests", {}).items()},
            shard_codecs={str(k): str(v) for k, v in d.get("shard_codecs", {}).items()},
            shard_pre_digests={
                str(k): str(v) for k, v in d.get("shard_pre_digests", {}).items()
            },
            base_step=int(d["base_step"]) if d.get("base_step") is not None else None,
            shard_sources={str(k): int(v) for k, v in d.get("shard_sources", {}).items()},
            base_dirs={str(k): str(v) for k, v in d.get("base_dirs", {}).items()},
        )


class DistCheckpoint:
    """Reader/writer for one committed (or in-progress) distributed checkpoint."""

    def __init__(self, root: str | os.PathLike, manifest: DistManifest):
        self.root = Path(root)
        self.manifest = manifest

    # ------------------------------------------------------------------ paths
    def rank_dir(self, rank: int) -> Path:
        return self.root / "ranks" / f"rank_{rank:05d}"

    def own_shard_path(self, rank: int, name: str, kind: StateKind) -> Path:
        """Where this checkpoint *writes* the shard — always its own tree,
        never an ancestor's (the write side must not follow provenance)."""
        return self.rank_dir(rank) / shard_filename(name, kind)

    def owner_step(self, rank: int, name: str, kind: StateKind) -> int:
        """The step whose directory physically holds this shard's bytes."""
        return self.manifest.shard_sources.get(
            shard_digest_key(rank, name, kind), self.manifest.step
        )

    def shard_path(self, rank: int, name: str, kind: StateKind) -> Path:
        """Chain-resolved read path of one shard (one hop: provenance is
        flattened at save time, so this never walks more than one link)."""
        owner = self.manifest.shard_sources.get(shard_digest_key(rank, name, kind))
        if owner is None:
            return self.own_shard_path(rank, name, kind)
        base = self.root.parent / self.manifest.base_dirs[str(owner)]
        return base / "ranks" / f"rank_{rank:05d}" / shard_filename(name, kind)

    def referenced_steps(self) -> set[int]:
        """Ancestor steps whose directories this checkpoint's shards live in
        (empty for a full checkpoint).  GC must keep these alive."""
        return set(self.manifest.shard_sources.values())

    def chain_roots(self) -> list[Path]:
        """This root plus every ancestor directory it references — the full
        set of directories a reader of this checkpoint may open files in
        (engine invalidation walks exactly this list)."""
        return [self.root] + [
            self.root.parent / d for d in self.manifest.base_dirs.values()
        ]

    @property
    def commit_path(self) -> Path:
        return self.root / "COMMIT"

    @property
    def is_committed(self) -> bool:
        return self.commit_path.exists()

    @property
    def cache_key(self) -> str:
        """Engine index-cache identity (see ``repro.core.engine.FragmentSource``).

        A delta's key includes the owning base step: re-saving the same
        step directory against a different base must never serve stale
        index entries (prefix invalidation by root still matches both)."""
        if self.manifest.base_step is None:
            return str(self.root)
        return f"{self.root}@delta:{self.manifest.base_step}"

    # ------------------------------------------------------------------ write
    @classmethod
    def create(cls, root: str | os.PathLike, manifest: DistManifest) -> "DistCheckpoint":
        root = Path(root)
        root.mkdir(parents=True, exist_ok=True)
        # Injectable clock: stamps are informational only (discovery and GC
        # order by step directory name), so skew is testable, not load-bearing.
        manifest.created_at = clock.now()
        ckpt = cls(root, manifest)
        ckpt.rewrite_manifest()
        return ckpt

    def rewrite_manifest(self) -> None:
        """(Re)write MANIFEST.json atomically — used at create time and again
        after the shard pass filled in ``shard_digests``."""
        tmp = self.root / "MANIFEST.json.tmp"
        tmp.write_text(json.dumps(self.manifest.to_json(), indent=1))
        os.replace(tmp, self.root / "MANIFEST.json")

    def write_shard(
        self, rank: int, name: str, kind: StateKind, shard: np.ndarray,
        *, fsync: bool = True,
    ) -> int:
        """Persist one rank's local shard; returns bytes written.

        ``fsync=False`` defers durability to the caller — the parallel save
        path batches one fsync pass over all shard files before ``commit()``
        instead of paying a synchronous flush per file.
        """
        self.rank_dir(rank).mkdir(parents=True, exist_ok=True)
        save_tensor(self.own_shard_path(rank, name, kind), shard, fsync=fsync)
        return shard.nbytes

    def writing_ranks(self, name: str, kind: StateKind) -> list[int]:
        """Which ranks persist this (param, kind) under the manifest save_mode."""
        spec = self.manifest.params[name]
        layout = spec.layout_for(kind, self.manifest.mesh)
        return writing_ranks_for(spec, layout, self.manifest.save_mode)

    def commit(self) -> None:
        """Atomic completion marker — written last, fsync'd.

        A checkpoint directory without COMMIT is treated as garbage by
        discovery (crash-during-save safety).
        """
        with obs.span("ckpt.commit", step=self.manifest.step):
            fault_point("dist.pre_commit", step=self.manifest.step, root=str(self.root))
            tmp = self.root / "COMMIT.tmp"
            with open(tmp, "w") as f:
                f.write(json.dumps({"step": self.manifest.step, "t": clock.now()}))
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.commit_path)
            fault_point("dist.committed", step=self.manifest.step, root=str(self.root))

    # ------------------------------------------------------------------- read
    @classmethod
    def open(cls, root: str | os.PathLike) -> "DistCheckpoint":
        root = Path(root)
        manifest = DistManifest.from_json(json.loads((root / "MANIFEST.json").read_text()))
        return cls(root, manifest)

    def read_shard(
        self, rank: int, name: str, kind: StateKind, *, mmap: bool = True,
        cache=None,
    ) -> np.ndarray:
        """Open one shard (mmap).  ``cache``: optional
        :class:`~repro.core.engine.HandleCache` so repeated opens of the
        same file reuse one handle.

        This is THE decode point for coded shards (DESIGN.md §10): when the
        manifest tags this shard with a non-raw codec, the payload is
        decoded here — once per file when a cache is supplied — so every
        consumer above (DIRECT restore, streaming reshard, UCP conversion,
        hot promotion, peer fan-out, validation) serves coded checkpoints
        through the unchanged fragment-read path."""
        path = self.shard_path(rank, name, kind)
        spec = self.manifest.params[name]
        tag = self.manifest.codec_tag(shard_digest_key(rank, name, kind))
        dtype = spec.states[kind].dtype
        if tag == "raw":
            loader = lambda: load_tensor(path, dtype=dtype, mmap=mmap)
        else:
            loader = lambda: codec.decode_file(path, tag, dtype=dtype)
        if cache is not None:
            return cache.get(path, loader)
        return loader()

    def read_fragment(
        self, rank: int, name: str, kind: StateKind, *, engine=None
    ) -> np.ndarray:
        """FragmentSource read: the shard file, handle-cached when an
        engine is supplied (one open per file across regions and params)."""
        if engine is not None:
            return engine.read_shard(self, rank, name, kind)
        return self.read_shard(rank, name, kind)

    def whole_fragment(
        self, name: str, kind: StateKind, region: tuple[slice, ...], dtype, *, engine
    ) -> tuple[Path, int] | None:
        """``(path, payload offset)`` when one raw shard file holds exactly
        ``region`` (unit-step runtime slices) in ``dtype``, byte for byte,
        so its payload can be read straight into the region's buffer; else
        None (a partial overlap or a union of files, a coded shard, another
        dtype or a header that is not the manifest's C-order shape).

        The region's fragment hits must all come from one rank's file, each
        at the same position in the shard as in the region, together
        covering both: a fused dimension's sub-fragments (one entry per
        part) then tile the file in place just as one whole entry does."""
        spec = self.manifest.params[name]
        if resolve_dtype(spec.states[kind].dtype) != resolve_dtype(dtype):
            return None
        idx = engine.index_for(self, name, kind)
        hits = idx.overlapping(region)
        local = tuple(idx.layout.local_shape)
        if (
            not hits
            or tuple(r.stop - r.start for r in region) != local
            or len({rank for rank, _, _ in hits}) != 1
        ):
            return None
        covered = 0
        for _, e, ovs in hits:
            if any(
                s0 + (lo - a0) != lo - r.start
                for (a0, _), (s0, _), (lo, _), r in zip(
                    e.atom_slice, e.shard_slice, ovs, region
                )
            ):
                return None
            covered += math.prod(hi - lo for lo, hi in ovs)
        if covered != math.prod(local):  # fragments are disjoint: a sum
            return None
        rank = hits[0][0]
        if self.manifest.codec_tag(shard_digest_key(rank, name, kind)) != "raw":
            return None
        path = self.shard_path(rank, name, kind)
        offset = npy_payload_offset(path, local, dtype)
        return None if offset is None else (path, offset)

    def iter_param_fragments(
        self, name: str, kind: StateKind, *, engine=None
    ) -> Iterator[tuple[int, ShardLayout, np.ndarray]]:
        """Yield ``(rank, layout, shard)`` for every persisted fragment owner.

        This is the read side of the paper's ``Extract`` — it enumerates the
        parameter states contained in the distributed checkpoint, one owning
        rank at a time, without materializing anything (mmap).  ``engine``:
        optional :class:`~repro.core.engine.CheckpointEngine` whose handle
        cache deduplicates file opens across parameters and callers.
        """
        spec = self.manifest.params[name]
        layout = spec.layout_for(kind, self.manifest.mesh)
        cache = engine.handles if engine is not None else None
        mmap = engine.mmap_handles if engine is not None else True
        for rank in self.writing_ranks(name, kind):
            yield rank, layout, self.read_shard(rank, name, kind, mmap=mmap, cache=cache)

    def total_bytes(self) -> int:
        return sum(
            p.stat().st_size for p in self.root.glob("ranks/**/*.npy")
        )

    # -------------------------------------------------------------- integrity
    def validate(self) -> list[str]:
        """Integrity check: every expected shard file exists, and (when the
        manifest carries digests) its content bytes match the digest recorded
        at save time.  Returns a list of problems; empty == clean."""
        problems: list[str] = []
        for name, spec in self.manifest.params.items():
            for kind in spec.states:
                for rank in self.writing_ranks(name, kind):
                    path = self.shard_path(rank, name, kind)
                    if not path.exists():
                        problems.append(f"missing shard file {path}")
                        continue
                    want = self.manifest.shard_digests.get(
                        shard_digest_key(rank, name, kind)
                    )
                    if want is None:
                        continue  # pre-digest checkpoint: existence only
                    try:
                        arr = self.read_shard(rank, name, kind)
                    except Exception as e:  # repro: allow[except-discipline] -- validate(): unreadable == corrupt, whatever the decode raised
                        problems.append(f"unreadable shard {path}: {e}")
                        continue
                    try:
                        # recompute with the recorded digest's own algorithm
                        # (older manifests carry crc32, new ones sha256)
                        got = content_digest(arr, want.split(":", 1)[0])
                    except ValueError:
                        problems.append(
                            f"{shard_digest_key(rank, name, kind)}: "
                            f"unrecognized recorded digest {want!r}"
                        )
                        continue
                    if got != want:
                        problems.append(
                            f"{shard_digest_key(rank, name, kind)}: "
                            f"digest {got} != recorded {want}"
                        )
        return problems
