"""Device string encoding.

XLA needs static shapes, so variable-width strings are hostile to the device
path (SURVEY §7 "Strings on TPU").  The device representation here is a
fixed-width padded byte matrix:

    bytes:   uint8[rows, max_len]   (UTF-8 payload, zero padded)
    lengths: int32[rows]            (byte length per row)

This supports vectorized upper/lower/substring/length/contains/starts/ends/
concat/compare on the VPU.  Regex-class ops fall back to the host engine,
mirroring the reference's regex bail-outs (GpuOverrides.scala:326-371).

Host side, a string column is one of two things, and the column knows
which (``data/column.py``): an ``object`` ndarray of python ``str``
(``None`` in null slots), as ``decode``, ``from_pylist`` and the host
operators make it, or the Arrow array a scan decoded
(``ArrowStringColumn``: validity, offsets, bytes), which becomes python
objects only when someone reads its ``data``.  Both upload through one
function, :func:`encode_buffers`: ``encode`` asks Arrow for an object
array's offsets and bytes first, a scanned column has them in hand
(:func:`arrow_buffers`).  The device arrays are the same to the byte.
"""
from __future__ import annotations

import threading
from typing import Optional, Tuple

import numpy as np

# pyarrow's object-ndarray converters are not reliably thread-safe in
# this environment when task threads convert while XLA's own thread pool
# is busy (observed hard SIGSEGV in pa.array under the concurrent
# collect path); one lock serializes the C conversion — still ~40x the
# python loop — and costs nothing in the single-thread case
_PA_LOCK = threading.Lock()


def _pa():
    """pyarrow with its memory pool forced to the system allocator —
    arrow's bundled mimalloc pool segfaults under this image's
    concurrent XLA-CPU + task-thread workload (observed repeatedly in
    pa.array during multithreaded collects; system pool is stable)."""
    import pyarrow as pa

    if not getattr(_pa, "_pool_set", False):
        try:
            pa.set_memory_pool(pa.system_memory_pool())
        except Exception:  # noqa: BLE001
            pass
        _pa._pool_set = True
    return pa


import os as _os

_FORCE_SLOW_ENCODE = _os.environ.get("SRT_SLOW_ENCODE") == "1"
_FORCE_SLOW_DECODE = _os.environ.get("SRT_SLOW_DECODE") == "1"


def _encode_slow(values, validity, max_len):
    n = len(values)
    encoded = []
    for i in range(n):
        if validity is not None and not validity[i]:
            encoded.append(b"")
        else:
            v = values[i]
            encoded.append(v.encode("utf-8") if isinstance(v, str)
                           else (v if isinstance(v, bytes) else b""))
    lengths = np.fromiter((len(b) for b in encoded), dtype=np.int32, count=n)
    ml = int(lengths.max()) if n else 0
    if max_len is None:
        max_len = max(1, ml)
    elif ml > max_len:
        raise ValueError(f"string of {ml} bytes exceeds max_len {max_len}")
    out = np.zeros((n, max_len), dtype=np.uint8)
    for i, b in enumerate(encoded):
        if b:
            out[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    return out, lengths


def encode(values: np.ndarray, validity: Optional[np.ndarray],
           max_len: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Encode an object ndarray of str into (bytes[rows,max_len], lengths).

    Vectorized via arrow's C encoder (offsets+data buffers) — the
    per-row python loop was the single hottest host-path line in the
    r3 bench (≈40% of a q1 collect).  Falls back to the python loop for
    mixed/bytes inputs."""
    n = len(values)
    if n == 0 or _FORCE_SLOW_ENCODE:
        return _encode_slow(values, validity, max_len)
    try:
        pa = _pa()
    except ImportError:
        return _encode_slow(values, validity, max_len)
    try:
        vals = np.asarray(values, dtype=object)
        if validity is not None:
            vals = np.where(np.asarray(validity, dtype=bool), vals, None)
        with _PA_LOCK:
            arr = pa.array(vals, type=pa.string())
        # null rows have equal offsets, so their lengths are already 0
        offsets, data = arrow_buffers(arr)
    except Exception:  # noqa: BLE001 — any arrow failure: exact slow path
        return _encode_slow(values, validity, max_len)
    return encode_buffers(offsets, data, None, max_len)


def arrow_buffers(arr) -> Tuple[np.ndarray, np.ndarray]:
    """``(offsets[n + 1], bytes)`` of a ``pa.StringArray`` or
    ``LargeStringArray`` as numpy views of Arrow's buffers (no copy).
    A sliced array's offsets start where its ``offset`` says, so they
    need not start at 0; ``bytes`` is the whole data buffer."""
    n = len(arr)
    bufs = arr.buffers()
    odt = np.int64 if arr.type == _pa().large_string() else np.int32
    if n == 0 or bufs[1] is None:  # an empty array may hold no offset
        offsets = np.zeros(n + 1, dtype=odt)
    else:
        offsets = np.frombuffer(bufs[1], dtype=odt)[
            arr.offset:arr.offset + n + 1]
    data = (np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None
            else np.empty(0, dtype=np.uint8))
    return offsets, data


def encode_buffers(offsets: np.ndarray, data: np.ndarray,
                   validity: Optional[np.ndarray],
                   max_len: Optional[int] = None
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Arrow's string layout into (bytes[rows,max_len], lengths), to
    ``encode``'s contract: ``max_len`` or the greatest length (at least
    1), ``ValueError`` when a string is longer, zero padding, length 0
    and zero bytes in a null row (Arrow allows bytes under one).
    ``offsets`` has rows + 1 entries of any integer type and may start
    anywhere in ``data`` (a sliced array)."""
    n = len(offsets) - 1
    if n == 0:
        return (np.zeros((0, 1 if max_len is None else max_len),
                         dtype=np.uint8), np.zeros(0, dtype=np.int32))
    lengths = np.diff(offsets).astype(np.int32)
    data = data[int(offsets[0]):int(offsets[-1])]
    if validity is not None:
        valid = np.asarray(validity, dtype=bool)
        if lengths[~valid].any():
            data = data[np.repeat(valid, lengths)]
        lengths = np.where(valid, lengths, np.int32(0))
    ml = int(lengths.max())
    if max_len is None:
        max_len = max(1, ml)
    elif ml > max_len:
        raise ValueError(f"string of {ml} bytes exceeds max_len {max_len}")
    out = np.zeros((n, max_len), dtype=np.uint8)
    if data.size == n * ml:
        # every row as long as the longest (flags, codes, keys): the
        # bytes are the matrix's first ml columns already
        out[:, :ml] = data.reshape(n, ml)
        return out, lengths
    # row-major boolean scatter: the True cells enumerate in exactly
    # concatenated-row order, which is the arrow data buffer's layout
    mask = np.arange(max_len, dtype=np.int32) < lengths[:, None]
    out[mask] = data
    return out, lengths


def decode(byte_mat: np.ndarray, lengths: np.ndarray,
           validity: Optional[np.ndarray] = None) -> np.ndarray:
    """Decode (bytes, lengths) back to an object ndarray of str."""
    n = byte_mat.shape[0]
    lengths = np.asarray(lengths)
    try:
        if _FORCE_SLOW_DECODE:
            raise RuntimeError("forced slow decode")
        pa = _pa()

        w = byte_mat.shape[1] if byte_mat.ndim == 2 else 0
        # clamp HARD: invalid/padding lanes carry arbitrary gathered
        # lengths (negative or > width); unclamped they make the cumsum
        # offsets non-monotonic and from_buffers then reads out of
        # bounds — corrupt str objects that crash far away (observed
        # SIGSEGV in a later pa.array over re-encoded output)
        ln = np.clip(lengths.astype(np.int64), 0, w)
        if validity is not None:
            ln = np.where(np.asarray(validity, dtype=bool), ln, 0)
        mask = np.arange(w, dtype=np.int64) < ln[:, None]
        flat = np.ascontiguousarray(byte_mat[mask])
        offsets = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(ln, out=offsets[1:])
        with _PA_LOCK:
            arr = pa.StringArray.from_buffers(
                n, pa.py_buffer(offsets.tobytes()),
                pa.py_buffer(flat.tobytes()))
            out = arr.to_numpy(zero_copy_only=False)
        if out.dtype != object:
            out = out.astype(object)
    except Exception:  # noqa: BLE001 — e.g. invalid utf-8: exact slow path
        w = byte_mat.shape[1] if byte_mat.ndim == 2 else 0
        out = np.empty(n, dtype=object)
        for i in range(n):
            k = max(0, min(int(lengths[i]), w))
            out[i] = bytes(byte_mat[i, :k]).decode("utf-8",
                                                   errors="replace")
    if validity is not None:
        out[~np.asarray(validity, dtype=bool)] = None
    return out


def pad_rows(byte_mat: np.ndarray, lengths: np.ndarray,
             target_rows: int) -> Tuple[np.ndarray, np.ndarray]:
    n, w = byte_mat.shape
    if target_rows == n:
        return byte_mat, lengths
    bm = np.zeros((target_rows, w), dtype=np.uint8)
    bm[:n] = byte_mat
    ln = np.zeros(target_rows, dtype=np.int32)
    ln[:n] = lengths
    return bm, ln
