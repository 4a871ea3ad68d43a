"""Columnar data plane.

Capability parity with the reference's L4 (GpuColumnVector.java,
RapidsHostColumnVector.java, GpuColumnVectorFromBuffer.java, GpuBatchUtils):
host columns mirror ``RapidsHostColumnVector`` (real row access), device
columns mirror ``GpuColumnVector`` (data lives in TPU HBM; row accessors are
deliberately absent).

TPU-first design decisions (SURVEY §7 architecture mapping):
  * A device batch is a pytree of jax arrays: (data, validity) per column,
    strings as (bytes-matrix, lengths, validity).
  * Row counts are padded to power-of-two *buckets* so XLA compile caches hit
    across batches; ``num_rows`` tracks the logical count, rows past it are
    invalid padding.  This is the static-shape answer to cudf's natively
    dynamic shapes (SURVEY §7 "Hard parts": bucketed padding + validity
    masks everywhere).
  * Validity is a boolean mask (True = valid), always materialized on the
    device so kernels are branch-free.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field as dc_field
from typing import Any, List, Optional, Sequence

import numpy as np

from ..types import DType, Field, Schema, TypeId, STRING, from_numpy
from ..utils.tracing import trace_range
from . import strings as dstrings


# --------------------------------------------------------------------------
# Host side
# --------------------------------------------------------------------------
def _bool_or_none(validity: Optional[np.ndarray]) -> Optional[np.ndarray]:
    """A column's validity as it is kept: bool, and None when all valid."""
    if validity is not None and validity.dtype != np.bool_:
        validity = validity.astype(np.bool_)
    if validity is not None and bool(validity.all()):
        validity = None
    return validity


class HostColumn:
    """A host column: numpy data + optional validity (True = valid).

    Reference analogue: RapidsHostColumnVector.java (host twin with real row
    accessors)."""

    __slots__ = ("dtype", "data", "validity")

    def __init__(self, dtype: DType, data: np.ndarray,
                 validity: Optional[np.ndarray] = None):
        self.dtype = dtype
        self.data = data
        self.validity = _bool_or_none(validity)

    # ----- construction ----------------------------------------------------
    @staticmethod
    def from_pylist(values: Sequence[Any], dtype: DType) -> "HostColumn":
        n = len(values)
        validity = np.fromiter((v is not None for v in values),
                               dtype=np.bool_, count=n)
        all_valid = bool(validity.all())
        if dtype.id is TypeId.STRING:
            data = np.empty(n, dtype=object)
            for i, v in enumerate(values):
                data[i] = v if v is not None else None
        else:
            data = np.zeros(n, dtype=dtype.np_dtype)
            for i, v in enumerate(values):
                if v is not None:
                    data[i] = v
        return HostColumn(dtype, data, None if all_valid else validity)

    @staticmethod
    def from_numpy(arr: np.ndarray, dtype: Optional[DType] = None,
                   validity: Optional[np.ndarray] = None) -> "HostColumn":
        if dtype is None:
            dtype = from_numpy(arr.dtype)
        if arr.dtype != dtype.np_dtype and dtype.id is not TypeId.STRING:
            arr = arr.astype(dtype.np_dtype)
        return HostColumn(dtype, arr, validity)

    @staticmethod
    def nulls(n: int, dtype: DType) -> "HostColumn":
        if dtype.id is TypeId.STRING:
            data = np.empty(n, dtype=object)
        else:
            data = np.zeros(n, dtype=dtype.np_dtype)
        return HostColumn(dtype, data, np.zeros(n, dtype=np.bool_))

    # ----- accessors --------------------------------------------------------
    @property
    def num_rows(self) -> int:
        return len(self.data)

    @property
    def null_count(self) -> int:
        if self.validity is None:
            return 0
        return int((~self.validity).sum())

    def is_valid(self) -> np.ndarray:
        if self.validity is None:
            return np.ones(self.num_rows, dtype=np.bool_)
        return self.validity

    def __getitem__(self, i: int):
        if self.validity is not None and not self.validity[i]:
            return None
        v = self.data[i]
        if self.dtype.id is TypeId.STRING:
            return v
        return v.item() if hasattr(v, "item") else v

    def to_pylist(self) -> List[Any]:
        return [self[i] for i in range(self.num_rows)]

    def arrow_strings(self):
        """The Arrow array a scanned STRING column still holds (see
        :class:`ArrowStringColumn`); None for every other column."""
        return None

    def string_bytes(self) -> int:
        """A STRING column's logical (UTF-8) bytes.  SAMPLED for an
        object array (~1k strided rows extrapolated) — an estimate is
        all the callers need, and the exact per-row encode was a
        measurable slice of every upload path.  Strided, not prefix,
        sampling: sorted/clustered columns would bias a prefix sample
        by orders of magnitude."""
        n = self.num_rows
        if not n:
            return 0
        sample = self.data[:: max(1, n // 1024)]
        sampled = sum(len(s.encode("utf-8")) if isinstance(s, str) else 0
                      for s in sample)
        return int(sampled * (n / len(sample)))

    # ----- transforms -------------------------------------------------------
    def take(self, indices: np.ndarray) -> "HostColumn":
        data = self.data[indices]
        validity = None if self.validity is None else self.validity[indices]
        return HostColumn(self.dtype, data, validity)

    def slice(self, start: int, stop: int) -> "HostColumn":
        v = None if self.validity is None else self.validity[start:stop]
        return HostColumn(self.dtype, self.data[start:stop], v)

    @staticmethod
    def concat(cols: Sequence["HostColumn"]) -> "HostColumn":
        assert cols, "concat of zero columns"
        dtype = cols[0].dtype
        if any(c.validity is not None for c in cols):
            validity = np.concatenate([c.is_valid() for c in cols])
        else:
            validity = None
        if all(isinstance(c, ArrowStringColumn) and c._objects is None
               for c in cols):
            return ArrowStringColumn(
                dtype, _concat_strings([c._arrow for c in cols]), validity)
        data = np.concatenate([c.data for c in cols])
        return HostColumn(dtype, data, validity)

    def __repr__(self):  # pragma: no cover
        return f"HostColumn({self.dtype}, rows={self.num_rows}, nulls={self.null_count})"


# one lock for every column: the conversion holds the GIL from end to
# end, so two of them never ran side by side anyway
_MATERIALIZE_LOCK = threading.Lock()


class ArrowStringColumn(HostColumn):
    """A STRING column as the scan decoded it: Arrow's array (validity,
    offsets, bytes), not python objects.  The upload builds its byte
    matrix from the buffers (``host_to_device``); whoever reads ``data``
    gets the object ndarray of ``str`` (``None`` in null slots) every
    other STRING column holds, made on the first read and kept.  What
    runs between a scan and an upload (``num_rows``, ``slice``,
    ``concat``, ``take``, ``estimate_bytes``) stays on the Arrow side;
    once the objects exist, ``slice``, ``concat`` and ``take`` cut them
    and give a plain ``HostColumn``, so no string is converted twice.
    A pickle carries the objects and loads as a plain ``HostColumn``.

    ``arr``: a ``pa.StringArray`` or ``LargeStringArray``, one chunk,
    dictionary already decoded; ``validity`` says what its bitmap says."""

    __slots__ = ("_arrow", "_objects")

    def __init__(self, dtype: DType, arr,
                 validity: Optional[np.ndarray] = None):
        self.dtype = dtype
        self._arrow = arr
        self._objects = None
        self.validity = _bool_or_none(validity)

    @property
    def data(self) -> np.ndarray:
        objs = self._objects
        if objs is None:
            with _MATERIALIZE_LOCK:
                objs = self._objects
                if objs is None:
                    with trace_range("HostStrings.materialize"):
                        objs = np.asarray(self._arrow.to_pylist(),
                                          dtype=object)
                    self._objects = objs
        return objs

    def arrow_strings(self):
        return self._arrow

    @property
    def num_rows(self) -> int:
        return len(self._arrow)

    def string_bytes(self) -> int:
        """Exact, from the offsets: what the sample approximates."""
        offsets, _ = dstrings.arrow_buffers(self._arrow)
        if self.validity is None:
            return int(offsets[-1]) - int(offsets[0])
        return int(np.diff(offsets)[self.validity].sum())

    def take(self, indices: np.ndarray) -> "HostColumn":
        idx = np.asarray(indices)
        if self._objects is not None or idx.ndim != 1 \
                or idx.dtype.kind not in "iub":
            return super().take(indices)
        validity = None if self.validity is None else self.validity[idx]
        pa = dstrings._pa()
        if idx.dtype.kind == "b":
            arr = self._arrow.filter(pa.array(idx))
        else:  # numpy's negative indices count from the end
            arr = self._arrow.take(pa.array(
                np.where(idx < 0, idx + len(self._arrow), idx)))
        return ArrowStringColumn(self.dtype, arr, validity)

    def slice(self, start: int, stop: int) -> "HostColumn":
        if self._objects is not None:
            return super().slice(start, stop)
        start, stop, _ = slice(start, stop).indices(len(self._arrow))
        v = None if self.validity is None else self.validity[start:stop]
        return ArrowStringColumn(
            self.dtype, self._arrow.slice(start, max(stop - start, 0)), v)

    def __reduce__(self):
        return HostColumn, (self.dtype, self.data, self.validity)

    def __repr__(self):  # pragma: no cover
        return (f"ArrowStringColumn(rows={self.num_rows}, "
                f"nulls={self.null_count})")


def _concat_strings(arrs):
    """One Arrow string array of several; 64-bit offsets when the parts'
    types differ or their bytes would not fit 32-bit ones."""
    pa = dstrings._pa()
    total = 0
    for a in arrs:
        offsets, _ = dstrings.arrow_buffers(a)
        total += int(offsets[-1]) - int(offsets[0])
    if total >= (1 << 31) - 1 or any(a.type != arrs[0].type for a in arrs):
        arrs = [a.cast(pa.large_string()) for a in arrs]
    return pa.concat_arrays(arrs)


class HostBatch:
    """An ordered set of equal-length host columns (Spark ColumnarBatch
    analogue on the host side)."""

    __slots__ = ("schema", "columns")

    def __init__(self, schema: Schema, columns: List[HostColumn]):
        assert len(schema) == len(columns)
        self.schema = schema
        self.columns = columns

    @property
    def num_rows(self) -> int:
        return self.columns[0].num_rows if self.columns else 0

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def column(self, i) -> HostColumn:
        if isinstance(i, str):
            i = self.schema.index_of(i)
        return self.columns[i]

    def take(self, indices: np.ndarray) -> "HostBatch":
        return HostBatch(self.schema, [c.take(indices) for c in self.columns])

    def slice(self, start: int, stop: int) -> "HostBatch":
        return HostBatch(self.schema,
                         [c.slice(start, stop) for c in self.columns])

    @staticmethod
    def concat(batches: Sequence["HostBatch"]) -> "HostBatch":
        assert batches
        schema = batches[0].schema
        cols = [HostColumn.concat([b.columns[i] for b in batches])
                for i in range(len(schema))]
        return HostBatch(schema, cols)

    @staticmethod
    def from_pydict(d, schema: Optional[Schema] = None) -> "HostBatch":
        if schema is None:
            fields, cols = [], []
            for name, values in d.items():
                values = list(values)
                dtype = _infer_pylist_dtype(values)
                col = HostColumn.from_pylist(values, dtype)
                fields.append(Field(name, col.dtype))
                cols.append(col)
            return HostBatch(Schema(fields), cols)
        cols = [HostColumn.from_pylist(list(d[f.name]), f.dtype)
                for f in schema]
        return HostBatch(schema, cols)

    def to_pydict(self):
        return {f.name: c.to_pylist()
                for f, c in zip(self.schema, self.columns)}

    def to_rows(self) -> List[tuple]:
        cols = [c.to_pylist() for c in self.columns]
        return list(zip(*cols)) if cols else []

    def estimate_bytes(self) -> int:
        """Reference analogue: GpuBatchUtils row/byte estimation.
        A string column counts its logical bytes (``string_bytes``:
        sampled for python objects, exact for Arrow's offsets) and a
        4-byte offset a row."""
        total = 0
        for c in self.columns:
            if c.dtype.id is TypeId.STRING:
                total += c.string_bytes() + 4 * c.num_rows
            else:
                total += c.data.nbytes
            total += (c.num_rows + 7) // 8  # validity bitmap estimate
        return total

    def __repr__(self):  # pragma: no cover
        return f"HostBatch(rows={self.num_rows}, schema={self.schema})"


def _infer_pylist_dtype(values) -> DType:
    """Infer a column dtype from python values, skipping Nones (Spark
    createDataFrame-style: python int -> bigint, float -> double)."""
    from . import column as _self  # noqa: F401

    from ..types import BOOL, FLOAT64, INT64, STRING

    for v in values:
        if v is None:
            continue
        if isinstance(v, bool) or isinstance(v, np.bool_):
            return BOOL
        if isinstance(v, (int, np.integer)):
            return INT64
        if isinstance(v, (float, np.floating)):
            return FLOAT64
        if isinstance(v, str):
            return STRING
        raise TypeError(f"cannot infer dtype from {v!r}")
    return STRING  # all-null column


# --------------------------------------------------------------------------
# Bucketing
# --------------------------------------------------------------------------
def bucket_rows(n: int, min_rows: int = 128) -> int:
    """Pad row counts to power-of-two buckets (>= min_rows) so the per-shape
    XLA compile cache is reused across batches."""
    b = max(min_rows, 1)
    # next power of two >= max(n, 1)
    need = max(n, 1)
    while b < need:
        b <<= 1
    return b


# --------------------------------------------------------------------------
# Device side
# --------------------------------------------------------------------------
@dataclass
class DeviceColumn:
    """A device column: jax arrays resident in TPU HBM.

    Reference analogue: GpuColumnVector.java — row accessors intentionally
    do not exist; use ``to_host`` at the boundary.

    ``data``: jnp[padded] for fixed-width types; jnp.uint8[padded, max_len]
    for strings. ``lengths``: jnp.int32[padded], strings only.
    ``validity``: jnp.bool_[padded], always present."""

    dtype: DType
    data: Any
    validity: Any
    lengths: Any = None

    @property
    def padded_rows(self) -> int:
        return int(self.data.shape[0])


class DeviceBatch:
    """A batch of device columns with a logical row count <= padded rows.

    Registered as a jax pytree so batches flow through jit/shard_map."""

    __slots__ = ("schema", "columns", "num_rows")

    def __init__(self, schema: Schema, columns: List[DeviceColumn],
                 num_rows: int):
        self.schema = schema
        self.columns = columns
        self.num_rows = num_rows

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def padded_rows(self) -> int:
        return self.columns[0].padded_rows if self.columns else 0

    def column(self, i) -> DeviceColumn:
        if isinstance(i, str):
            i = self.schema.index_of(i)
        return self.columns[i]

    def row_mask(self):
        """bool[padded]: True for logical rows, False for padding.
        Distinct from per-column validity — a null row still counts here
        (count(*) semantics)."""
        import jax.numpy as jnp

        return jnp.arange(self.padded_rows, dtype=jnp.int32) < \
            jnp.asarray(self.num_rows, dtype=jnp.int32)

    def device_bytes(self) -> int:
        total = 0
        for c in self.columns:
            total += c.data.size * c.data.dtype.itemsize
            total += c.validity.size
            if c.lengths is not None:
                total += c.lengths.size * 4
        return total

    def block_until_ready(self) -> "DeviceBatch":
        for c in self.columns:
            c.data.block_until_ready()
        return self

    def __repr__(self):  # pragma: no cover
        return (f"DeviceBatch(rows={self.num_rows}, "
                f"padded={self.padded_rows}, schema={self.schema})")


# --------------------------------------------------------------------------
# Transfers (reference analogue: GpuRowToColumnarExec upload path /
# GpuColumnarToRowExec download path, minus the row codegen — the host
# engine here is already columnar, so the boundary is numpy <-> jax).
#
# A batch goes up as ONE batched ``jax.device_put`` of its arrays and
# comes down as one ``jax.device_get``.  (Packing the arrays into one
# byte buffer, split again by a device program, was tried on a v5e: at
# 2M rows x 22 arrays the split program wanted 5.6 GB of temp and the
# upload took 281 ms against 16 ms for the batched put — PERF.md, PR 21.)
# --------------------------------------------------------------------------
def host_to_device(batch: HostBatch, min_bucket_rows: int = 128,
                   device=None, string_widths=None,
                   string_guard_bytes: int = 0) -> DeviceBatch:
    """``string_widths``: optional col-index -> byte-matrix width map so
    several uploads share static string shapes (mesh stacking needs
    every shard's columns shape-equal).

    ``string_guard_bytes`` > 0 fails the upload when any string
    column's byte matrix (padded rows x max encoded length) would
    exceed that size — byte-matrix HBM scales with the ONE longest
    string, so a pathological value silently multiplies the batch
    footprint; better a diagnosable error naming the column than an
    opaque device OOM (conf: stringColumnBytesGuard)."""
    import jax

    n = batch.num_rows
    padded = bucket_rows(n, min_bucket_rows)

    arrays: List[np.ndarray] = []
    spec: List[bool] = []  # per column: is_string
    for ci, c in enumerate(batch.columns):
        valid_np = c.is_valid()
        validity = np.zeros(padded, dtype=np.bool_)
        validity[:n] = valid_np
        if c.dtype.id is TypeId.STRING:
            width = (string_widths or {}).get(ci)
            arr = c.arrow_strings()
            with trace_range("HostToDevice.strings"):
                if arr is not None:  # from a scan: no python object made
                    bm, ln = dstrings.encode_buffers(
                        *dstrings.arrow_buffers(arr), c.validity,
                        max_len=width)
                else:
                    bm, ln = dstrings.encode(c.data, c.validity,
                                             max_len=width)
            if string_guard_bytes > 0 \
                    and padded * bm.shape[1] > string_guard_bytes:
                raise RuntimeError(
                    f"string column '{batch.schema.names[ci]}' would "
                    f"need a {padded} x {bm.shape[1]} byte matrix "
                    f"({padded * bm.shape[1] / 1e9:.2f} GB) on device, "
                    "over the guard (spark.rapids.tpu.sql."
                    "stringColumnBytesGuard). Shrink "
                    "spark.rapids.tpu.sql.reader.batchSizeRows, filter "
                    "or substring the column earlier, or raise the "
                    "guard.")
            bm, ln = dstrings.pad_rows(bm, ln, padded)
            arrays.extend([bm, validity, ln])
            spec.append(True)
        else:
            data = np.zeros(padded, dtype=c.dtype.np_dtype)
            if c.validity is None:
                data[:n] = c.data
            else:  # zero invalid lanes so device kernels stay deterministic
                data[:n] = np.where(valid_np, c.data,
                                    np.zeros_like(c.data))
            arrays.extend([data, validity])
            spec.append(False)

    dev = jax.device_put(arrays, device)

    cols: List[DeviceColumn] = []
    i = 0
    for c, is_str in zip(batch.columns, spec):
        if is_str:
            cols.append(DeviceColumn(c.dtype, dev[i], dev[i + 1],
                                     dev[i + 2]))
            i += 3
        else:
            cols.append(DeviceColumn(c.dtype, dev[i], dev[i + 1]))
            i += 2
    return DeviceBatch(batch.schema, cols, n)


def slice_device_batch(batch: DeviceBatch, start: int, stop: int,
                       min_bucket_rows: int = 128) -> DeviceBatch:
    """Row-range view [start, stop) of a device batch, re-bucketed to its
    own padded size (used to cut sorted runs into spillable tiles)."""
    import jax.numpy as jnp

    n = stop - start
    padded = bucket_rows(n, min_bucket_rows)
    cols: List[DeviceColumn] = []
    for c in batch.columns:
        validity = jnp.zeros(padded, dtype=jnp.bool_
                             ).at[:n].set(c.validity[start:stop])
        if c.lengths is not None:
            data = jnp.zeros((padded, c.data.shape[1]), dtype=c.data.dtype
                             ).at[:n].set(c.data[start:stop])
            lengths = jnp.zeros(padded, dtype=c.lengths.dtype
                                ).at[:n].set(c.lengths[start:stop])
            cols.append(DeviceColumn(c.dtype, data, validity, lengths))
        else:
            data = jnp.zeros(padded, dtype=c.data.dtype
                             ).at[:n].set(c.data[start:stop])
            cols.append(DeviceColumn(c.dtype, data, validity))
    return DeviceBatch(batch.schema, cols, n)


def pad_device_batch(batch: DeviceBatch, capacity: int,
                     widths=None) -> DeviceBatch:
    """Pad a device batch's row capacity (and, optionally, per-column
    string byte-matrix widths: ``widths`` maps column index -> target
    width) WITHOUT changing ``num_rows`` — shape unification so
    independent executions of the same operator (e.g. grace-join bucket
    pairs) share ONE compiled program instead of tracing per shape.
    Padding rows stay outside ``row_mask()``; never shrinks."""
    import jax.numpy as jnp

    capacity = max(capacity, batch.padded_rows)
    cols: List[DeviceColumn] = []
    changed = False
    for ci, c in enumerate(batch.columns):
        data, validity, lengths = c.data, c.validity, c.lengths
        extra = capacity - data.shape[0]
        if c.lengths is not None:
            w = max((widths or {}).get(ci, 0), data.shape[1])
            if w > data.shape[1]:
                data = jnp.pad(data, ((0, 0), (0, w - data.shape[1])))
            if extra:
                data = jnp.pad(data, ((0, extra), (0, 0)))
                lengths = jnp.pad(lengths, (0, extra))
        elif extra:
            data = jnp.pad(data, (0, extra))
        if extra:
            validity = jnp.pad(validity, (0, extra))
        if data is not c.data or validity is not c.validity:
            changed = True
        cols.append(DeviceColumn(c.dtype, data, validity, lengths))
    if not changed:
        return batch
    return DeviceBatch(batch.schema, cols, batch.num_rows)


def device_to_host(batch: DeviceBatch, trim: bool = True) -> HostBatch:
    """Download a device batch in ONE batched transfer.

    Per-column ``np.asarray`` costs one device round trip per array
    (a 7-column batch paid ~20 of them).  Instead: one host sync
    for the row count, a device-side trim of the padding to the row
    bucket (capacity-retry outputs can be heavily over-padded), then
    a single
    ``jax.device_get`` of every array.

    ``trim=False`` skips the device-side trim: the trim ALLOCATES new
    device buffers, which the spill path (called exactly when HBM is
    exhausted) must not do."""
    return device_to_host_many([batch], trim=trim)[0]


def device_to_host_many(batches: List[DeviceBatch],
                        trim: bool = True) -> List[HostBatch]:
    """Download SEVERAL device batches in two batched transfers: one
    sync for every row count, one ``jax.device_get`` of every array of
    every batch.  The cross-batch form of :func:`device_to_host` — a
    result drain of B small batches pays 2 round trips instead of 2B
    (the host boundary below a limit/collect is exactly such a
    stream)."""
    import jax

    if not batches:
        return []
    # the row counts come out of the programs that make the arrays:
    # reading them back IS the wait for the device, and the copy below
    # finds the arrays done (but for the trim it dispatches itself)
    with trace_range("DeviceToHost.wait"):
        ns = [int(n)
              for n in jax.device_get([b.num_rows for b in batches])]
    with trace_range("DeviceToHost.copy"):
        return _copy_to_host(batches, ns, trim)


def _copy_to_host(batches: List[DeviceBatch], ns: List[int],
                  trim: bool) -> List[HostBatch]:
    """One ``jax.device_get`` of every array of every batch (trimmed on
    the device to the rows' bucket first), then the host-side trim and
    string decode."""
    import jax

    arrs = []
    specs = []  # per batch, per column: has_lengths
    for batch, n in zip(batches, ns):
        k = bucket_rows(max(n, 1)) if trim else batch.padded_rows
        spec = []
        for c in batch.columns:
            data, validity, lengths = c.data, c.validity, c.lengths
            if k < batch.padded_rows:
                data, validity = data[:k], validity[:k]
                lengths = lengths[:k] if lengths is not None else None
            arrs.extend([data, validity] if lengths is None
                        else [data, validity, lengths])
            spec.append(lengths is not None)
        specs.append(spec)
    host = jax.device_get(arrs)
    out: List[HostBatch] = []
    i = 0
    for batch, n, spec in zip(batches, ns, specs):
        cols: List[HostColumn] = []
        for c, has_len in zip(batch.columns, spec):
            if has_len:
                bm, validity, ln = host[i:i + 3]
                i += 3
            else:
                bm, validity = host[i:i + 2]
                i += 2
            validity = np.asarray(validity)[:n]
            if c.dtype.id is TypeId.STRING:
                data = dstrings.decode(np.asarray(bm)[:n],
                                       np.asarray(ln)[:n], validity)
            else:
                data = np.asarray(bm)[:n].astype(c.dtype.np_dtype,
                                                 copy=False)
            cols.append(HostColumn(c.dtype, data,
                                   None if validity.all() else validity))
        out.append(HostBatch(batch.schema, cols))
    return out


# --------------------------------------------------------------------------
# pytree registration: DeviceBatch flattens to its arrays so it can cross
# jit/shard_map boundaries; schema/num_rows ride in the treedef (static).
# --------------------------------------------------------------------------
def _flatten_device_batch(b: DeviceBatch):
    import jax.numpy as jnp

    try:
        num_rows = jnp.asarray(b.num_rows, dtype=jnp.int32)
    except TypeError:
        # structural re-flatten with sentinel leaves (jax builds dummy
        # trees with object() leaves inside device_put/flatten_axes):
        # flatten must stay PURELY structural there or every
        # device_put of a DeviceBatch pytree explodes
        num_rows = b.num_rows
    leaves = [num_rows]
    spec = []
    for c in b.columns:
        if c.lengths is not None:
            leaves.extend([c.data, c.validity, c.lengths])
            spec.append((c.dtype, True))
        else:
            leaves.extend([c.data, c.validity])
            spec.append((c.dtype, False))
    aux = (b.schema, tuple(spec))
    return leaves, aux


def _unflatten_device_batch(aux, leaves):
    schema, spec = aux
    it = iter(leaves)
    num_rows = next(it)
    cols = []
    for dtype, has_len in spec:
        data = next(it)
        validity = next(it)
        lengths = next(it) if has_len else None
        cols.append(DeviceColumn(dtype, data, validity, lengths))
    return DeviceBatch(schema, cols, num_rows)


def _flatten_device_column(c: DeviceColumn):
    if c.lengths is not None:
        return [c.data, c.validity, c.lengths], (c.dtype, True)
    return [c.data, c.validity], (c.dtype, False)


def _unflatten_device_column(aux, leaves):
    dtype, has_len = aux
    if has_len:
        return DeviceColumn(dtype, leaves[0], leaves[1], leaves[2])
    return DeviceColumn(dtype, leaves[0], leaves[1])


def register_pytrees():
    import jax

    try:
        jax.tree_util.register_pytree_node(
            DeviceBatch, _flatten_device_batch, _unflatten_device_batch)
        jax.tree_util.register_pytree_node(
            DeviceColumn, _flatten_device_column, _unflatten_device_column)
    except ValueError:
        pass  # already registered
