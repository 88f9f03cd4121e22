"""Tensor file I/O for checkpoints: ``.npy`` with dtype-faithful views.

``.npy`` is used for both distributed shard files and consolidated atom
files because ``np.load(..., mmap_mode="r")`` gives lazy page-granular
reads: a Target rank loading a slice of an atom touches only the byte
range it owns.  This is the CPU-host analogue of the paper's DeepNVMe
fast-path (§Table 2, ``Load``) — sequential, offset-addressed reads.

NumPy cannot represent ``bfloat16`` natively; ``ml_dtypes`` extends it, but
round-trips through ``.npy`` as an anonymous 2-byte void.  We therefore
persist the logical dtype in the filename-adjacent metadata and re-view on
read.
"""

from __future__ import annotations

import hashlib
import os
import zlib
from mmap import PAGESIZE
from pathlib import Path

import ml_dtypes
import numpy as np

_EXTENDED: dict[str, np.dtype] = {
    "bfloat16": np.dtype(ml_dtypes.bfloat16),
    "float8_e4m3fn": np.dtype(ml_dtypes.float8_e4m3fn),
    "float8_e5m2": np.dtype(ml_dtypes.float8_e5m2),
}

__all__ = [
    "IntegrityError",
    "content_digest",
    "digest_matches",
    "resolve_dtype",
    "dtype_name",
    "save_tensor",
    "load_tensor",
    "npy_payload_offset",
    "read_into",
    "READ_RANGE_BYTES",
    "open_memmap",
    "fsync_path",
]

#: Size of one byte-range job of a whole-file read (see :func:`read_into`).
READ_RANGE_BYTES = 32 << 20


class IntegrityError(ValueError):
    """A checkpoint's bytes do not match its recorded content digests."""


def content_digest(arr: np.ndarray, algo: str = "sha256") -> str:
    """Digest of an array's *content* bytes (layout/file-header agnostic).

    Digests are self-describing (``<algo>:<hex>``) and computed over the
    C-order element bytes.  The default is sha256 truncated to 128 bits:
    hardware-accelerated sha is as fast as zlib's crc32 on modern hosts,
    and — unlike crc32 — collision-resistant enough that a digest match
    may be treated as byte equality, which is what the delta save's
    changed-shard diff does (``save_mode="delta"``).  ``"crc32"`` is kept
    for verifying manifests recorded before the upgrade (a delta diff
    against a crc32-era digest simply never matches, so the shard is
    rewritten and the chain upgrades itself — mismatch is always safe).
    """
    a = np.ascontiguousarray(arr)
    try:
        buf = memoryview(a).cast("B")
    except (TypeError, ValueError, BufferError):
        # extended dtypes (bfloat16 et al.) may not export a buffer format;
        # reinterpret as raw bytes instead (same content, same digest).
        buf = a.tobytes()
    if algo == "sha256":
        return f"sha256:{hashlib.sha256(buf).hexdigest()[:32]}"
    if algo == "crc32":
        return f"crc32:{zlib.crc32(buf) & 0xFFFFFFFF:08x}"
    raise ValueError(f"unknown digest algorithm {algo!r}")


def digest_matches(arr: np.ndarray, recorded: str) -> bool:
    """Whether an array's content matches a recorded digest, using the
    algorithm the digest itself names (old manifests carry crc32).  A
    malformed/unrecognized recorded digest cannot match anything — it is
    reported as a mismatch, never raised (validation must turn corruption
    into findings, not crashes)."""
    try:
        return content_digest(arr, recorded.split(":", 1)[0]) == recorded
    except ValueError:
        return False


def resolve_dtype(name: str) -> np.dtype:
    if name in _EXTENDED:
        return _EXTENDED[name]
    return np.dtype(name)


def dtype_name(dtype) -> str:
    dt = np.dtype(dtype)
    for name, ext in _EXTENDED.items():
        if dt == ext:
            return name
    return dt.name


def save_tensor(path: str | os.PathLike, arr: np.ndarray, *, fsync: bool = True) -> None:
    """Atomically write an array (tmp + rename) so readers never see torn files.

    ``fsync=False`` defers durability to the caller (``fsync_path`` later,
    before the checkpoint COMMIT marker) — the parallel save path batches
    fsyncs this way instead of paying one synchronous flush per shard file.
    """
    path = Path(path)
    tmp = path.with_suffix(path.suffix + ".tmp")
    with open(tmp, "wb") as f:
        # No ascontiguousarray: np.save streams non-contiguous arrays to a
        # real file in bounded chunks (ndarray.tofile), so strided shard
        # views are written without materializing a full staging copy.
        np.save(f, arr)
        f.flush()
        if fsync:
            os.fsync(f.fileno())
    os.replace(tmp, path)


def fsync_path(path: str | os.PathLike) -> None:
    """Flush one already-written file to stable storage (batched-fsync leg)."""
    fd = os.open(str(path), os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def load_tensor(
    path: str | os.PathLike, dtype: str | None = None, *, mmap: bool = True
) -> np.ndarray:
    """Load (lazily when ``mmap``) and restore the logical dtype if needed."""
    arr = np.load(path, mmap_mode="r" if mmap else None)
    if dtype is not None:
        want = resolve_dtype(dtype)
        if arr.dtype != want:
            if arr.dtype.itemsize != want.itemsize:
                raise ValueError(
                    f"{path}: stored itemsize {arr.dtype.itemsize} cannot view "
                    f"as {dtype} (itemsize {want.itemsize})"
                )
            arr = arr.view(want)
    return arr


def npy_payload_offset(
    path: str | os.PathLike, shape: tuple[int, ...], dtype: str
) -> int | None:
    """Byte offset of a ``.npy`` file's payload when its bytes are exactly
    a C-order ``shape`` array of ``dtype`` as :func:`load_tensor` serves it
    (the header's shape, C order, and the itemsize ``load_tensor`` views
    across — bf16/fp8 are stored as void), else None."""
    fmt = np.lib.format
    with open(path, "rb") as f:
        version = fmt.read_magic(f)
        if version == (1, 0):
            stored_shape, fortran, stored = fmt.read_array_header_1_0(f)
        elif version == (2, 0):
            stored_shape, fortran, stored = fmt.read_array_header_2_0(f)
        else:
            return None
        offset = f.tell()
    if (
        fortran
        or stored.hasobject
        or tuple(stored_shape) != tuple(shape)
        or stored.itemsize != resolve_dtype(dtype).itemsize
    ):
        return None
    return offset


def read_into(path: str | os.PathLike, offset: int, out: memoryview) -> None:
    """Fill ``out`` with the file's bytes from ``offset`` on, straight into
    the caller's buffer (no intermediate copy).  Short reads are resumed;
    end of file before ``out`` is full raises, as ``np.load`` does on a
    truncated file.

    Each page of ``out`` is touched first, so a fresh buffer's page faults
    are taken in user space, where they run in parallel across threads; a
    sandboxed kernel (gVisor) serializes the faults a read syscall takes.
    On a TPU v5e host, 16 threads read fresh pages at 1.1-1.2 GB/s plain
    and at 1.35-1.4 GB/s touched first."""
    np.frombuffer(out, np.uint8)[::PAGESIZE] = 0
    fd = os.open(path, os.O_RDONLY)
    try:
        done = 0
        while done < len(out):
            n = os.preadv(fd, [out[done:]], offset + done)
            if n == 0:
                raise ValueError(
                    f"{path}: file ends {len(out) - done} bytes short of the "
                    f"payload range at {offset}+{len(out)}"
                )
            done += n
    finally:
        os.close(fd)


def open_memmap(
    path: str | os.PathLike, shape: tuple[int, ...], dtype: str
) -> np.memmap:
    """Writable memmap for streaming, constant-memory Union (see convert.py)."""
    dt = resolve_dtype(dtype)
    # np.lib.format rejects extended dtypes on header write; use the raw
    # void view on disk, callers see the logical dtype through .view().
    disk_dt = dt if dt.name in np.sctypeDict or dt.kind in "fiub" else None
    try:
        mm = np.lib.format.open_memmap(str(path), mode="w+", dtype=dt, shape=shape)
        return mm
    except (ValueError, TypeError):
        mm = np.lib.format.open_memmap(
            str(path), mode="w+", dtype=np.dtype((np.void, dt.itemsize)), shape=shape
        )
        return mm.view(dt)
