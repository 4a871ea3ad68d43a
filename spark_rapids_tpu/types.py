"""Data type system for the TPU columnar engine.

Capability parity with the reference's Spark<->cudf DType mapping
(reference: sql-plugin/.../GpuColumnVector.java:134-206) and the plan-rewrite
type gate (reference: GpuOverrides.scala:375-387).  Here the mapping is
SQL type <-> numpy dtype (host columns) <-> jnp dtype (device columns).

TPU-first notes:
  * TIMESTAMP is int64 microseconds since epoch, UTC only — same gate as the
    reference (timestamps allowed only when the session zone is UTC).
  * STRING columns are variable-width on the host (object ndarray of ``str``)
    and fixed-width padded uint8 matrices on the device (see data/strings.py);
    XLA needs static shapes, so the device encoding carries (bytes, lengths).
  * FLOAT64/INT64 require jax x64 mode, enabled at package import.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass

import numpy as np


class TypeId(enum.Enum):
    BOOL = "boolean"
    INT8 = "tinyint"
    INT16 = "smallint"
    INT32 = "int"
    INT64 = "bigint"
    FLOAT32 = "float"
    FLOAT64 = "double"
    DATE32 = "date"          # int32 days since unix epoch
    TIMESTAMP = "timestamp"  # int64 microseconds since unix epoch, UTC
    STRING = "string"
    NULL = "void"            # untyped null literal


@dataclass(frozen=True)
class DType:
    """An engine data type.  Hashable; use the singletons below."""

    id: TypeId

    # ----- classification -------------------------------------------------
    @property
    def is_numeric(self) -> bool:
        return self.id in _NUMERIC

    @property
    def is_integral(self) -> bool:
        return self.id in _INTEGRAL

    @property
    def is_floating(self) -> bool:
        return self.id in (TypeId.FLOAT32, TypeId.FLOAT64)

    @property
    def is_datetime(self) -> bool:
        return self.id in (TypeId.DATE32, TypeId.TIMESTAMP)

    @property
    def is_string(self) -> bool:
        return self.id is TypeId.STRING

    @property
    def is_bool(self) -> bool:
        return self.id is TypeId.BOOL

    # ----- physical representation ---------------------------------------
    @property
    def np_dtype(self) -> np.dtype:
        """numpy dtype of the physical host representation.

        A STRING host column's ``data`` is an ``object`` ndarray of
        python str (a scanned one makes it on first read, from the
        Arrow array it keeps: ``data/column.py:ArrowStringColumn``); the
        physical dtype here refers to the non-string payload.
        """
        return _NP[self.id]

    @property
    def jnp_dtype(self):
        import jax.numpy as jnp  # local import: keep module importable pre-jax

        return _JNP(jnp)[self.id]

    @property
    def byte_width(self) -> int:
        if self.id is TypeId.STRING:
            return 8  # estimate, matches reference GpuBatchUtils default-ish
        return _NP[self.id].itemsize

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return self.id.value

    @property
    def sql_name(self) -> str:
        return self.id.value


_NUMERIC = {
    TypeId.INT8,
    TypeId.INT16,
    TypeId.INT32,
    TypeId.INT64,
    TypeId.FLOAT32,
    TypeId.FLOAT64,
}
_INTEGRAL = {TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.INT64}

_NP = {
    TypeId.BOOL: np.dtype(np.bool_),
    TypeId.INT8: np.dtype(np.int8),
    TypeId.INT16: np.dtype(np.int16),
    TypeId.INT32: np.dtype(np.int32),
    TypeId.INT64: np.dtype(np.int64),
    TypeId.FLOAT32: np.dtype(np.float32),
    TypeId.FLOAT64: np.dtype(np.float64),
    TypeId.DATE32: np.dtype(np.int32),
    TypeId.TIMESTAMP: np.dtype(np.int64),
    TypeId.STRING: np.dtype(object),
    TypeId.NULL: np.dtype(np.bool_),
}


def _JNP(jnp):
    return {
        TypeId.BOOL: jnp.bool_,
        TypeId.INT8: jnp.int8,
        TypeId.INT16: jnp.int16,
        TypeId.INT32: jnp.int32,
        TypeId.INT64: jnp.int64,
        TypeId.FLOAT32: jnp.float32,
        TypeId.FLOAT64: jnp.float64,
        TypeId.DATE32: jnp.int32,
        TypeId.TIMESTAMP: jnp.int64,
        TypeId.STRING: jnp.uint8,
        TypeId.NULL: jnp.bool_,
    }


BOOL = DType(TypeId.BOOL)
INT8 = DType(TypeId.INT8)
INT16 = DType(TypeId.INT16)
INT32 = DType(TypeId.INT32)
INT64 = DType(TypeId.INT64)
FLOAT32 = DType(TypeId.FLOAT32)
FLOAT64 = DType(TypeId.FLOAT64)
DATE32 = DType(TypeId.DATE32)
TIMESTAMP = DType(TypeId.TIMESTAMP)
STRING = DType(TypeId.STRING)
NULL = DType(TypeId.NULL)

ALL_TYPES = (BOOL, INT8, INT16, INT32, INT64, FLOAT32, FLOAT64, DATE32,
             TIMESTAMP, STRING)

_BY_NAME = {t.sql_name: t for t in ALL_TYPES}
_BY_NAME.update({
    "long": INT64, "integer": INT32, "short": INT16, "byte": INT8,
    "bool": BOOL, "real": FLOAT32, "str": STRING, "void": NULL,
})


def from_name(name: str) -> DType:
    return _BY_NAME[name.lower()]


def from_numpy(dt) -> DType:
    dt = np.dtype(dt)
    for tid, nd in _NP.items():
        if tid in (TypeId.DATE32, TypeId.TIMESTAMP, TypeId.NULL):
            continue
        if nd == dt:
            return DType(tid)
    if dt == np.dtype(object) or dt.kind in ("U", "S"):
        return STRING
    raise TypeError(f"unsupported numpy dtype {dt}")


# --------------------------------------------------------------------------
# Type gate — which types the device engine handles at all.
# Reference: GpuOverrides.isSupportedType (GpuOverrides.scala:375-387):
# primitives + Date + String always; Timestamp only under UTC; no
# decimal/array/map/struct/binary/interval.  Same surface here.
# --------------------------------------------------------------------------
def is_supported_type(t: DType, *, session_zone_utc: bool = True) -> bool:
    if t.id is TypeId.TIMESTAMP:
        return session_zone_utc
    return t.id in (
        TypeId.BOOL, TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.INT64,
        TypeId.FLOAT32, TypeId.FLOAT64, TypeId.DATE32, TypeId.STRING,
        TypeId.NULL,
    )


# numeric promotion table used by binary arithmetic (Spark semantics:
# result type of an arithmetic op between integrals widens to the larger,
# mixing with floating promotes to floating; division is always double).
_RANK = {
    TypeId.INT8: 0, TypeId.INT16: 1, TypeId.INT32: 2, TypeId.INT64: 3,
    TypeId.FLOAT32: 4, TypeId.FLOAT64: 5,
}


def promote(a: DType, b: DType) -> DType:
    if not (a.is_numeric and b.is_numeric):
        raise TypeError(f"cannot promote {a} and {b}")
    ra, rb = _RANK[a.id], _RANK[b.id]
    winner = a if ra >= rb else b
    # int64 + float32 -> float64 divergence-avoidance (Spark promotes to
    # double when a float meets a >32-bit integral)
    loser = b if ra >= rb else a
    if winner.id is TypeId.FLOAT32 and loser.id in (TypeId.INT64,):
        return FLOAT64
    return winner


@dataclass(frozen=True)
class Field:
    name: str
    dtype: DType
    nullable: bool = True

    def __repr__(self) -> str:  # pragma: no cover
        n = "" if self.nullable else " not null"
        return f"{self.name}:{self.dtype}{n}"


class Schema:
    """Ordered collection of fields with name lookup."""

    def __init__(self, fields):
        self.fields = list(fields)
        self._index = {}
        for i, f in enumerate(self.fields):
            # last wins for duplicate names (matches positional binding use)
            self._index[f.name] = i

    def __len__(self):
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __getitem__(self, key):
        if isinstance(key, int):
            return self.fields[key]
        return self.fields[self._index[key]]

    def __contains__(self, name):
        return name in self._index

    def index_of(self, name: str) -> int:
        return self._index[name]

    @property
    def names(self):
        return [f.name for f in self.fields]

    @property
    def dtypes(self):
        return [f.dtype for f in self.fields]

    def __eq__(self, other):
        return isinstance(other, Schema) and self.fields == other.fields

    def __repr__(self) -> str:  # pragma: no cover
        return "Schema(" + ", ".join(map(repr, self.fields)) + ")"
