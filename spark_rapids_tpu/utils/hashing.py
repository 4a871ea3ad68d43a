"""Spark-compatible Murmur3 x86_32 hashing, vectorized.

The reference relies on cudf's Spark-compatible murmur3 for hash
partitioning so GPU exchange placement matches CPU Spark bit-for-bit.
Here the same hash is implemented twice: a numpy version for the host
engine and a jnp version traced into device programs, so device hash
partitioning is bit-identical to the host oracle.

Semantics mirror Spark's ``Murmur3Hash`` expression (seed 42):
  * int/short/byte/bool/date -> hashInt(value as int32)
  * long/timestamp           -> hashLong
  * float  -> hashInt(floatToIntBits), with -0.0f canonicalized to 0.0f
  * double -> hashLong(doubleToLongBits), -0.0 canonicalized
  * string -> hashUnsafeBytes over UTF-8 (signed tail bytes)
  * null inputs leave the running hash unchanged
"""
from __future__ import annotations

import numpy as np

SEED = np.uint32(42)
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_M5 = np.uint32(0xE6546B64)


# --------------------------------------------------------------------------
# numpy implementation (host engine)
# --------------------------------------------------------------------------
def _rotl32(x, r):
    x = x.astype(np.uint32, copy=False)
    return ((x << np.uint32(r)) | (x >> np.uint32(32 - r))).astype(np.uint32)


def _mix_k1(k1):
    k1 = (k1.astype(np.uint32) * _C1).astype(np.uint32)
    k1 = _rotl32(k1, 15)
    return (k1 * _C2).astype(np.uint32)


def _mix_h1(h1, k1):
    h1 = (h1 ^ k1).astype(np.uint32)
    h1 = _rotl32(h1, 13)
    return (h1 * np.uint32(5) + _M5).astype(np.uint32)


def _fmix(h1, length):
    h1 = (h1 ^ np.uint32(length)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(16)
    h1 = (h1 * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(13)
    h1 = (h1 * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(16)
    return h1


def hash_int_np(values: np.ndarray, seed: np.ndarray) -> np.ndarray:
    """Murmur3 hashInt over an int32-coercible array; seed may be an array."""
    k1 = values.astype(np.int32).view(np.uint32)
    h1 = _mix_h1(seed.astype(np.uint32), _mix_k1(k1))
    return _fmix(h1, 4)


def hash_long_np(values: np.ndarray, seed: np.ndarray) -> np.ndarray:
    v = values.astype(np.int64).view(np.uint64)
    low = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    high = (v >> np.uint64(32)).astype(np.uint32)
    h1 = _mix_h1(seed.astype(np.uint32), _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, 8)


def _float_bits_np(values: np.ndarray) -> np.ndarray:
    v = values.astype(np.float32)
    v = np.where(v == 0.0, np.float32(0.0), v)  # canonicalize -0.0
    v = np.where(np.isnan(v), np.float32(np.nan), v)
    return v.view(np.int32)


def _double_bits_np(values: np.ndarray) -> np.ndarray:
    v = values.astype(np.float64)
    v = np.where(v == 0.0, np.float64(0.0), v)
    return v.view(np.int64)


def hash_bytes_np(byte_mat: np.ndarray, lengths: np.ndarray,
                  seed: np.ndarray) -> np.ndarray:
    """hashUnsafeBytes over a fixed-width byte matrix with per-row lengths.

    Vectorized over rows; loops over the (static) width."""
    n, width = byte_mat.shape
    h1 = np.broadcast_to(seed.astype(np.uint32), (n,)).copy()
    lengths = lengths.astype(np.int32)
    n_blocks = width // 4
    if width % 4:
        pad = np.zeros((n, 4 - width % 4), dtype=np.uint8)
        byte_mat = np.concatenate([byte_mat, pad], axis=1)
        n_blocks = (width + 3) // 4
    blocks = byte_mat[:, : n_blocks * 4].reshape(n, n_blocks, 4)
    words = (blocks[..., 0].astype(np.uint32)
             | (blocks[..., 1].astype(np.uint32) << np.uint32(8))
             | (blocks[..., 2].astype(np.uint32) << np.uint32(16))
             | (blocks[..., 3].astype(np.uint32) << np.uint32(24)))
    aligned = (lengths // 4).astype(np.int32)
    for b in range(n_blocks):
        active = aligned > b
        h1 = np.where(active, _mix_h1(h1, _mix_k1(words[:, b])), h1)
    # tail: one signed byte at a time (Java getByte is signed)
    for t in range(3):
        idx = aligned * 4 + t
        active = idx < lengths
        byte = np.take_along_axis(
            byte_mat, np.clip(idx, 0, byte_mat.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        signed = byte.astype(np.int8).astype(np.int32).view(np.uint32)
        h1 = np.where(active, _mix_h1(h1, _mix_k1(signed)), h1)
    return _fmix_per_len(h1, lengths)


def _fmix_per_len(h1, lengths):
    h1 = (h1 ^ lengths.astype(np.uint32)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(16)
    h1 = (h1 * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(13)
    h1 = (h1 * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(16)
    return h1


def hash_host_column(col, seed: np.ndarray) -> np.ndarray:
    """Fold one HostColumn into a running per-row hash (uint32).
    Null rows pass ``seed`` through unchanged (Spark semantics)."""
    from ..types import TypeId

    n = col.num_rows
    seed = np.broadcast_to(seed.astype(np.uint32), (n,))
    tid = col.dtype.id
    if tid in (TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.DATE32):
        h = hash_int_np(col.data.astype(np.int32), seed)
    elif tid is TypeId.BOOL:
        h = hash_int_np(col.data.astype(np.int32), seed)
    elif tid in (TypeId.INT64, TypeId.TIMESTAMP):
        h = hash_long_np(col.data.astype(np.int64), seed)
    elif tid is TypeId.FLOAT32:
        h = hash_int_np(_float_bits_np(col.data), seed)
    elif tid is TypeId.FLOAT64:
        h = hash_long_np(_double_bits_np(col.data), seed)
    elif tid is TypeId.STRING:
        from ..data import strings as dstrings

        bm, ln = dstrings.encode(col.data, col.validity)
        h = hash_bytes_np(bm, ln, seed)
    else:
        raise TypeError(f"unhashable dtype {col.dtype}")
    if col.validity is not None:
        h = np.where(col.validity, h, seed)
    return h.astype(np.uint32)


def hash_batch_np(cols, seed: int = 42) -> np.ndarray:
    """Hash a sequence of HostColumns row-wise (Spark Murmur3Hash(exprs))."""
    assert cols
    h = np.full(cols[0].num_rows, np.uint32(seed), dtype=np.uint32)
    for c in cols:
        h = hash_host_column(c, h)
    return h.view(np.int32)


# --------------------------------------------------------------------------
# jnp implementation (device engine) — mirrors the numpy version so device
# partitioning is bit-identical.
# --------------------------------------------------------------------------
def _jnp_ops():
    import jax.numpy as jnp

    U = jnp.uint32

    def rotl(x, r):
        return (x << U(r)) | (x >> U(32 - r))

    def mix_k1(k1):
        return rotl(k1 * U(0xCC9E2D51), 15) * U(0x1B873593)

    def mix_h1(h1, k1):
        h1 = rotl(h1 ^ k1, 13)
        return h1 * U(5) + U(0xE6546B64)

    def fmix(h1, length):
        h1 = h1 ^ length.astype(jnp.uint32)
        h1 ^= h1 >> U(16)
        h1 = h1 * U(0x85EBCA6B)
        h1 ^= h1 >> U(13)
        h1 = h1 * U(0xC2B2AE35)
        h1 ^= h1 >> U(16)
        return h1

    return jnp, U, mix_k1, mix_h1, fmix


def hash_int_jnp(values, seed):
    jnp, U, mix_k1, mix_h1, fmix = _jnp_ops()
    k1 = jnp.asarray(values, jnp.int32).view(jnp.uint32)
    return fmix(mix_h1(seed.astype(jnp.uint32), mix_k1(k1)),
                jnp.uint32(4))


def hash_long_jnp(values, seed):
    jnp, U, mix_k1, mix_h1, fmix = _jnp_ops()
    v = jnp.asarray(values, jnp.int64).view(jnp.uint64)
    low = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    high = (v >> jnp.uint64(32)).astype(jnp.uint32)
    h1 = mix_h1(seed.astype(jnp.uint32), mix_k1(low))
    h1 = mix_h1(h1, mix_k1(high))
    return fmix(h1, jnp.uint32(8))


def hash_bytes_jnp(byte_mat, lengths, seed):
    jnp, U, mix_k1, mix_h1, fmix = _jnp_ops()
    n, width = byte_mat.shape
    h1 = jnp.broadcast_to(seed.astype(jnp.uint32), (n,))
    pad_w = (-width) % 4
    if pad_w:
        byte_mat = jnp.pad(byte_mat, ((0, 0), (0, pad_w)))
    n_blocks = (width + 3) // 4
    blocks = byte_mat.reshape(n, n_blocks, 4).astype(jnp.uint32)
    words = (blocks[..., 0] | (blocks[..., 1] << U(8))
             | (blocks[..., 2] << U(16)) | (blocks[..., 3] << U(24)))
    aligned = (lengths // 4).astype(jnp.int32)
    for b in range(n_blocks):
        active = aligned > b
        h1 = jnp.where(active, mix_h1(h1, mix_k1(words[:, b])), h1)
    for t in range(3):
        idx = aligned * 4 + t
        active = idx < lengths
        safe = jnp.clip(idx, 0, byte_mat.shape[1] - 1)
        byte = jnp.take_along_axis(byte_mat, safe[:, None], axis=1)[:, 0]
        signed = byte.astype(jnp.int8).astype(jnp.int32).view(jnp.uint32)
        h1 = jnp.where(active, mix_h1(h1, mix_k1(signed)), h1)
    return fmix(h1, lengths.astype(jnp.uint32))


def device_hash_gap(dtype):
    """Why the device cannot hash ``dtype`` the way Spark does, or None
    when it can.  The plan rules of every operator that hashes keys on
    the device (hash exchange, hash join) tag themselves with this, so
    the gap shows in ``explain()`` and never at a dispatch.

    The one gap: FLOAT64 on the TPU backend.  Spark hashes the 64 IEEE
    bits of a double; the TPU holds a float64 as an unevaluated sum of
    two float32 (about 48 mantissa bits, float32's exponent range), so
    those bits do not exist on the device — its compiler answers every
    bitcast out of f64 with "UNIMPLEMENTED: While rewriting computation
    to not contain X64 element types, XLA encountered an HLO for which
    this rewriting is not implemented: ... bitcast-convert"."""
    import jax

    from ..types import TypeId

    if dtype.id is TypeId.FLOAT64 and jax.default_backend() == "tpu":
        return ("Spark's hash of a FLOAT64 key needs its 64 IEEE bits, "
                "which the TPU does not hold (f64 is a pair of f32 there "
                "and XLA cannot bitcast it)")
    return None


def hash_device_column(col, seed):
    """Fold one DeviceColumn into a running per-row uint32 hash (traced)."""
    import jax.numpy as jnp

    from ..types import TypeId

    gap = device_hash_gap(col.dtype)
    if gap is not None:
        raise TypeError(gap)
    tid = col.dtype.id
    if tid in (TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.DATE32,
               TypeId.BOOL):
        h = hash_int_jnp(col.data.astype(jnp.int32), seed)
    elif tid in (TypeId.INT64, TypeId.TIMESTAMP):
        h = hash_long_jnp(col.data, seed)
    elif tid is TypeId.FLOAT32:
        v = col.data.astype(jnp.float32)
        v = jnp.where(v == 0.0, jnp.float32(0.0), v)
        h = hash_int_jnp(v.view(jnp.int32), seed)
    elif tid is TypeId.FLOAT64:
        v = col.data.astype(jnp.float64)
        v = jnp.where(v == 0.0, jnp.float64(0.0), v)
        h = hash_long_jnp(v.view(jnp.int64), seed)
    elif tid is TypeId.STRING:
        h = hash_bytes_jnp(col.data, col.lengths, seed)
    else:
        raise TypeError(f"unhashable dtype {col.dtype}")
    return jnp.where(col.validity, h, seed)


def hash_device_batch(cols, seed: int = 42):
    import jax.numpy as jnp

    assert cols
    n = cols[0].data.shape[0]
    h = jnp.full((n,), seed, dtype=jnp.uint32)
    for c in cols:
        h = hash_device_column(c, h)
    return h.view(jnp.int32)


def pmod(hash_values, num_partitions: int):
    """Spark's non-negative modulo used by HashPartitioning."""
    if isinstance(hash_values, np.ndarray):
        r = hash_values.astype(np.int64) % num_partitions
        return np.where(r < 0, r + num_partitions, r).astype(np.int32)
    import jax.numpy as jnp

    r = hash_values.astype(jnp.int64) % num_partitions
    return jnp.where(r < 0, r + num_partitions, r).astype(jnp.int32)
