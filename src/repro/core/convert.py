"""Distributed checkpoint → UCP conversion driver (paper Algorithm 1).

Since the streaming reshard landed (``ResumeMode.RESHARD_STREAM``), this
driver is an *explicit export tool* (``CheckpointManager.export_ucp``) and
the resume fallback of last resort — the resume hot path streams Source
fragments straight into the Target layout and never materializes an atom
checkpoint on disk.  The per-parameter transform kernel is shared:
:func:`assemble_atom` consolidates one parameter state from any
:class:`~repro.core.engine.FragmentSource`, and both the export path here
and the in-memory consolidation fallback of the stream restore
(``repro.ckpt.restore.state_from_stream``) call it, so the two paths are
bit-identical by construction.

Parallelism: Union is independent per parameter (paper: "can execute in
parallel at individual parameter level; more parallelism leads to faster
speed but is also more memory intensive"), so the driver fans out over a
thread pool — the work is mmap reads + memcpy, which release the GIL.
A parameter that needs no padding-strip or averaging unions directly into
a memory-mapped atom file, making peak working memory O(largest shard)
instead of O(largest parameter).
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Sequence

import numpy as np

import repro.obs as obs

from .atoms import AtomInfo, UcpCheckpoint, UcpManifest
from .dist_ckpt import DistCheckpoint
from .engine import CheckpointEngine
from .ops import strip_padding
from .patterns import ParamSpec, StateKind, STATE_KINDS
from .tensor_io import content_digest, resolve_dtype

__all__ = ["ConvertStats", "assemble_atom", "convert_to_ucp"]


def assemble_atom(
    source,
    spec: ParamSpec,
    kind: StateKind,
    *,
    out: np.ndarray | None = None,
    engine: CheckpointEngine | None = None,
) -> np.ndarray:
    """Consolidate one parameter state into its (logical) atom.

    Pattern dispatch (Algorithm 1), generalized over any
    :class:`~repro.core.engine.FragmentSource` — a distributed checkpoint
    on disk or an in-memory hot snapshot:

    * ``replicated_params`` / ``unique_params`` — exactly one distinct
      fragment exists; its shard is the atom (``ucp_p = fp_1``)
    * ``fragment_params`` — scatter every available fragment into place
      (``Concat``), including fused sub-fragments and stage partitions
    * ``params_to_average`` — scatter all divergent replicas then mean
      (``StripPadding`` collapses the leading replica dim)

    ``out``: optional pre-opened (mem-mapped) destination of *logical*
    shape.  When given and the parameter needs no padding-strip or
    averaging, fragments stream directly into it — constant working memory
    regardless of parameter size.
    """
    mesh = source.manifest.mesh
    layout = spec.layout_for(kind, mesh)
    dtype = resolve_dtype(spec.states[kind].dtype)
    direct = (
        out is not None
        and not spec.average
        and tuple(spec.runtime_shape) == tuple(spec.logical_shape)
    )
    target = out if direct else np.zeros(spec.runtime_shape, dtype=dtype)

    for rank in source.writing_ranks(spec.name, kind):
        if engine is not None:
            shard = engine.read_fragment(source, rank, spec.name, kind)
        else:
            shard = source.read_fragment(rank, spec.name, kind)
        for e in layout.entries[rank]:
            target[e.atom_index()] = shard[e.shard_index()]

    atom = target if direct else strip_padding(target, spec)
    if out is not None and not direct:
        out[...] = atom
        atom = out
    return atom


@dataclasses.dataclass
class ConvertStats:
    params: int = 0
    atoms_written: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    wall_time_s: float = 0.0

    def throughput_mb_s(self) -> float:
        if self.wall_time_s == 0:
            return float("inf")
        return (self.bytes_written / 1e6) / self.wall_time_s


def _convert_one(
    ckpt: DistCheckpoint,
    ucp: UcpCheckpoint,
    spec: ParamSpec,
    engine: CheckpointEngine | None = None,
) -> tuple[int, int, int, dict[StateKind, str]]:
    """Union + StripPadding + Save for one parameter (all state kinds).

    Returns ``(bytes_read, bytes_written, atoms_written, digests)`` — one
    atom file per state kind the parameter carries (up to 3), not one per
    parameter; ``digests`` records each atom's content digest for the
    manifest (verified by ``UcpCheckpoint.validate``).
    """
    with obs.span("convert.param", param=spec.name) as sp:
        result = _convert_one_traced(ckpt, ucp, spec, engine)
        sp.set(bytes_written=result[1], atoms=result[2])
    return result


def _convert_one_traced(
    ckpt: DistCheckpoint,
    ucp: UcpCheckpoint,
    spec: ParamSpec,
    engine: CheckpointEngine | None = None,
) -> tuple[int, int, int, dict[StateKind, str]]:
    read = written = atoms = 0
    digests: dict[StateKind, str] = {}
    for kind in STATE_KINDS:
        if kind not in spec.states:
            continue
        dtype = resolve_dtype(spec.states[kind].dtype)
        if not spec.average and tuple(spec.runtime_shape) == tuple(spec.logical_shape):
            out = ucp.create_atom_memmap(
                spec.name, kind, tuple(spec.logical_shape), spec.states[kind].dtype
            )
            atom = assemble_atom(ckpt, spec, kind, out=out, engine=engine)
            if hasattr(out, "flush"):
                out.flush()
        else:
            atom = assemble_atom(ckpt, spec, kind, engine=engine)
            ucp.write_atom(spec.name, kind, np.ascontiguousarray(atom))
        digests[kind] = content_digest(atom)
        read += int(np.prod(spec.runtime_shape)) * dtype.itemsize
        written += atom.nbytes
        atoms += 1
    return read, written, atoms, digests


def convert_to_ucp(
    ckpt: DistCheckpoint | str,
    out_dir: str,
    *,
    names: Sequence[str] | None = None,
    workers: int | None = None,
    engine: CheckpointEngine | None = None,
) -> tuple[UcpCheckpoint, ConvertStats]:
    """Convert a committed distributed checkpoint into a UCP atom checkpoint.

    Implements Algorithm 1: per parameter, pattern-match → Union →
    StripPadding → Save, parallel at parameter granularity.  ``engine``
    supplies the worker pool and shard handle cache; an explicit
    ``workers`` that disagrees with the engine's width wins (a private
    pool is used for this call), matching ``write_distributed``.  With
    neither given, a private pool of width 4 is used (``workers<=1`` is
    fully serial).
    """
    if isinstance(ckpt, (str, Path)):
        ckpt = DistCheckpoint.open(ckpt)
    if not ckpt.is_committed:
        raise ValueError(f"refusing to convert uncommitted checkpoint {ckpt.root}")

    manifest = ckpt.manifest
    todo = {
        n: s
        for n, s in manifest.params.items()
        if names is None or n in set(names)
    }

    atoms: dict[str, AtomInfo] = {
        n: AtomInfo(
            name=n,
            logical_shape=tuple(s.logical_shape),
            dtypes={k: st.dtype for k, st in s.states.items()},
            stacked_dim=s.stacked_dim,
            kind=s.kind,
        )
        for n, s in todo.items()
    }
    ucp = UcpCheckpoint.create(
        out_dir,
        UcpManifest(
            step=manifest.step,
            atoms=atoms,
            scalars=dict(manifest.scalars),
            provenance={
                "source_checkpoint": str(ckpt.root),
                "source_mesh": manifest.mesh.to_json(),
                "source_config": manifest.config_fingerprint,
                "source_save_mode": manifest.save_mode,
            },
        ),
    )

    stats = ConvertStats(params=len(todo))
    with obs.timed("convert.to_ucp", step=manifest.step, params=len(todo)) as sw:
        owns_engine = False
        if workers is not None and (engine is None or engine.workers != workers):
            engine = CheckpointEngine(workers=max(1, workers))
            owns_engine = True
        elif engine is None:
            engine = CheckpointEngine(workers=4)
            owns_engine = True
        try:
            specs = list(todo.values())
            results = engine.map(
                lambda s: _convert_one(ckpt, ucp, s, engine), specs
            )
        finally:
            if owns_engine:
                engine.close()
        for spec, (r, w, a, digests) in zip(specs, results):
            stats.bytes_read += r
            stats.bytes_written += w
            stats.atoms_written += a
            ucp.manifest.atoms[spec.name] = dataclasses.replace(
                ucp.manifest.atoms[spec.name], digests=digests
            )
        ucp._write_manifest()  # digests land before COMMIT
        ucp.commit()
        sw.set(bytes_written=stats.bytes_written, atoms=stats.atoms_written)
    stats.wall_time_s = sw.elapsed_s
    obs.add("convert.params", stats.params)
    obs.add("convert.atoms_written", stats.atoms_written)
    obs.add("convert.bytes_read", stats.bytes_read)
    obs.add("convert.bytes_written", stats.bytes_written)
    return ucp, stats
