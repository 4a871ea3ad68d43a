"""Arrow <-> engine columnar conversion.

SURVEY §7 architecture mapping: "Row<->columnar transitions -> Arrow
interchange at the host boundary".  pyarrow does host-side file decode
(the reference does host-side footer/stripe assembly then device decode
via cudf — on TPU the decode stays on host, the upload is the device
boundary)."""
from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa

from .. import types as T
from ..data.column import ArrowStringColumn, HostBatch, HostColumn
from ..utils.tracing import trace_range

_ARROW_TO_DTYPE = {
    pa.bool_(): T.BOOL,
    pa.int8(): T.INT8,
    pa.int16(): T.INT16,
    pa.int32(): T.INT32,
    pa.int64(): T.INT64,
    pa.float32(): T.FLOAT32,
    pa.float64(): T.FLOAT64,
    pa.date32(): T.DATE32,
    pa.string(): T.STRING,
    pa.large_string(): T.STRING,
}


def arrow_type_to_dtype(at: pa.DataType) -> T.DType:
    if at in _ARROW_TO_DTYPE:
        return _ARROW_TO_DTYPE[at]
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    if pa.types.is_decimal(at):
        raise TypeError("decimal not supported (same gate as reference)")
    if pa.types.is_dictionary(at):
        return arrow_type_to_dtype(at.value_type)
    raise TypeError(f"unsupported arrow type {at}")


def dtype_to_arrow(dt: T.DType) -> pa.DataType:
    for at, d in _ARROW_TO_DTYPE.items():
        if d == dt and at != pa.large_string():
            return at
    if dt.id is T.TypeId.TIMESTAMP:
        return pa.timestamp("us", tz="UTC")
    raise TypeError(f"no arrow type for {dt}")


def arrow_schema_to_schema(s: pa.Schema) -> T.Schema:
    return T.Schema([T.Field(f.name, arrow_type_to_dtype(f.type),
                             f.nullable) for f in s])


def schema_to_arrow(s: T.Schema) -> pa.Schema:
    return pa.schema([pa.field(f.name, dtype_to_arrow(f.dtype),
                               f.nullable) for f in s])


def _one_array(col, dtype: T.DType):
    """A table's column as one array, dictionary decoded, and its
    validity as a bool ndarray (None when nothing is null)."""
    arr = col.combine_chunks()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.chunk(0) if arr.num_chunks else pa.array(
            [], type=dtype_to_arrow(dtype))
    if pa.types.is_dictionary(arr.type):
        arr = arr.dictionary_decode()
    validity = None
    if arr.null_count:
        validity = np.asarray(arr.is_valid())
    return arr, validity


def arrow_to_host_batch(tbl, schema: Optional[T.Schema] = None,
                        string_bytes=None) -> HostBatch:
    """``string_bytes``: an optional metric that is given the string
    columns' logical (UTF-8) bytes, read off Arrow's offsets."""
    if isinstance(tbl, pa.RecordBatch):
        tbl = pa.Table.from_batches([tbl])
    if schema is None:
        schema = arrow_schema_to_schema(tbl.schema)
    cols = []
    for f in schema:
        if f.dtype.id is T.TypeId.STRING:
            # every caller is the scan's decode: a child of ScanDecode.
            # The column keeps Arrow's array; no python object is made
            with trace_range("ScanDecode.strings"):
                arr, validity = _one_array(tbl.column(f.name), f.dtype)
                col = ArrowStringColumn(f.dtype, arr, validity)
                if string_bytes is not None:
                    string_bytes.add(col.string_bytes())
            cols.append(col)
            continue
        arr, validity = _one_array(tbl.column(f.name), f.dtype)
        if f.dtype.id is T.TypeId.TIMESTAMP:
            data = arr.cast(pa.timestamp("us")).to_numpy(
                zero_copy_only=False).astype("datetime64[us]").astype(
                np.int64)
        elif f.dtype.id is T.TypeId.DATE32:
            data = arr.to_numpy(zero_copy_only=False).astype(
                "datetime64[D]").astype(np.int32)
        else:
            data = arr.to_numpy(zero_copy_only=False)
            if validity is not None:
                # arrow uses NaN/masked for nulls; re-zero invalid lanes
                data = np.where(validity, data, 0).astype(f.dtype.np_dtype)
            else:
                data = data.astype(f.dtype.np_dtype)
        cols.append(HostColumn(f.dtype, data, validity))
    return HostBatch(schema, cols)


def host_batch_to_arrow(batch: HostBatch) -> pa.Table:
    arrays = []
    for f, c in zip(batch.schema, batch.columns):
        at = dtype_to_arrow(f.dtype)
        mask = None if c.validity is None else ~c.validity
        if f.dtype.id is T.TypeId.STRING:
            vals = [v if (c.validity is None or c.validity[i]) else None
                    for i, v in enumerate(c.data)]
            arrays.append(pa.array(vals, type=at))
        elif f.dtype.id is T.TypeId.TIMESTAMP:
            arrays.append(pa.array(c.data.astype("datetime64[us]"),
                                   type=at, mask=mask))
        elif f.dtype.id is T.TypeId.DATE32:
            arrays.append(pa.array(c.data.astype("datetime64[D]"),
                                   type=at, mask=mask))
        else:
            arrays.append(pa.array(c.data, type=at, mask=mask))
    return pa.Table.from_arrays(arrays, schema=schema_to_arrow(batch.schema))
