"""File scans — Parquet / ORC / CSV.

Capability parity with the reference's L5 scan layer (GpuParquetScan.scala,
GpuOrcScan.scala, GpuBatchScanExec.scala CSV): per-file partitions,
row-group batching to the reader size targets
(spark.rapids.tpu.sql.reader.batchSizeRows/Bytes — reference
RapidsConf.scala:295-309), and predicate pushdown hooks.

Host-side decode is pyarrow (the reference re-assembles raw chunks on the
host then device-decodes with cudf; on TPU the host decodes and the device
upload happens at the columnar transition inserted by the rewrite engine).
"""
from __future__ import annotations

import glob as globmod
import os
from typing import List

from .. import types as T
from ..config import READER_BATCH_SIZE_BYTES, READER_BATCH_SIZE_ROWS
from ..data.column import HostBatch
from ..ops import miscexprs
from ..plan import logical as L
from ..plan import physical as P
from ..utils.tracing import trace_steps
from . import arrow_convert as ac


#: Spark's directory name for a null partition value (single source of
#: truth — the writers import it from here)
HIVE_NULL = "__HIVE_DEFAULT_PARTITION__"

#: characters escaped in partition directory names (reference:
#: ExternalCatalogUtils.escapePathName) — without this a value
#: containing '/' would silently nest directories and corrupt readback
_PATH_ESCAPE_CHARS = set('"#%\'*/:=?\\{[]^\x7f') | \
    {chr(c) for c in range(0x20)}


def escape_path_name(value: str) -> str:
    return "".join(f"%{ord(ch):02X}" if ch in _PATH_ESCAPE_CHARS else ch
                   for ch in value)


def partition_dir_name(key: str, value) -> str:
    """The canonical ``key=value`` directory segment — THE single
    naming rule both writers (host io/writers.py and device
    exec/write.py) must share, else the same data writes different
    layouts per engine.  Nulls use the Hive sentinel; -0.0 normalizes
    to 0.0 so the two zeros (numerically equal, differently rendered)
    cannot straddle group and name boundaries."""
    import numpy as np

    if value is None:
        return f"{key}={HIVE_NULL}"
    if isinstance(value, (float, np.floating)) and value == 0.0:
        value = type(value)(0.0)
    return f"{key}={escape_path_name(str(value))}"


def unescape_path_name(value: str) -> str:
    out = []
    i = 0
    while i < len(value):
        if value[i] == "%" and i + 3 <= len(value):
            try:
                out.append(chr(int(value[i + 1:i + 3], 16)))
                i += 3
                continue
            except ValueError:
                pass
        out.append(value[i])
        i += 1
    return "".join(out)


def expand_paths(paths: List[str]) -> List[str]:
    return discover_files(paths)[0]


def file_fingerprint(path: str) -> dict:
    """Stable identity record of one leaf file — ``path`` plus the
    ``size``/``mtime_ns`` pair a single ``os.stat`` observes (−1/−1
    when the file vanished between listing and stat).  THE shared
    currency between the streaming source ledger and the recovery
    data-material fingerprint: both consume these records, so a file is
    stat-ed exactly once per discovery."""
    try:
        st = os.stat(path)
        return {"path": path, "size": int(st.st_size),
                "mtime_ns": int(st.st_mtime_ns)}
    except OSError:
        return {"path": path, "size": -1, "mtime_ns": -1}


def discover_files(paths: List[str]):
    """Recursive file listing with Hive-partition discovery: files under
    ``key=value`` directories carry those values (reference:
    PartitioningAwareFileIndex + the per-batch constant append in
    ColumnarPartitionReaderWithPartitionValues.scala:96).

    Returns ``(files, part_values, part_keys, fingerprints)`` — per-file
    dicts of raw (string) partition values, the ordered key list (empty
    for flat layouts), and one :func:`file_fingerprint` record per file
    (stat-ed during the walk — discovery is the only stat pass)."""
    files: List[str] = []
    values: List[dict] = []
    fingerprints: List[dict] = []

    def add(path: str, acc) -> None:
        files.append(path)
        values.append(dict(acc))
        fingerprints.append(file_fingerprint(path))

    def walk(d, acc):
        for f in sorted(os.listdir(d)):
            if f.startswith((".", "_")):
                continue
            full = os.path.join(d, f)
            if os.path.isdir(full):
                k, eq, v = f.partition("=")
                walk(full,
                     acc + [(k, unescape_path_name(v))] if eq else acc)
            else:
                add(full, acc)

    for p in paths:
        if os.path.isdir(p):
            walk(p, [])
        elif any(ch in p for ch in "*?["):
            for g in sorted(globmod.glob(p)):
                add(g, [])
        else:
            add(p, [])
    keys: List[str] = []
    for pv in values:
        for k in pv:
            if k not in keys:
                keys.append(k)
    return files, values, keys, fingerprints


def _infer_partition_fields(values: List[dict],
                            keys: List[str]) -> List[T.Field]:
    """Spark-style partition-value type inference: int64 if every value
    parses as an integer, float64 if numeric, else string; the
    HIVE_NULL sentinel is a null of whatever the others infer."""
    fields = []
    for k in keys:
        raw = [pv.get(k) for pv in values]
        present = [v for v in raw if v is not None and v != HIVE_NULL]
        dtype = T.INT64
        for v in present:
            try:
                if not (-(2 ** 63) <= int(v) < 2 ** 63):
                    dtype = None  # out of int64 range: wider type
                    break
            except ValueError:
                dtype = None
                break
        if dtype is None:
            dtype = T.FLOAT64
            for v in present:
                try:
                    float(v)
                except ValueError:
                    dtype = T.STRING
                    break
        fields.append(T.Field(k, dtype))
    return fields


def _parse_partition_value(raw, dtype):
    if raw is None or raw == HIVE_NULL:
        return None
    if dtype.id is T.TypeId.STRING:
        return raw
    return dtype.np_dtype.type(raw)


def infer_schema(fmt: str, paths: List[str], options: dict) -> T.Schema:
    if fmt == "csv":
        validate_csv_options(options)
    files, values, keys, _fps = discover_files(paths)
    if not files:
        raise FileNotFoundError(f"no files for {paths}")
    f0 = files[0]
    if fmt == "parquet":
        import pyarrow.parquet as pq

        schema = ac.arrow_schema_to_schema(pq.read_schema(f0))
    elif fmt == "orc":
        import pyarrow.orc as orc

        schema = ac.arrow_schema_to_schema(orc.ORCFile(f0).schema)
    elif fmt == "csv":
        import pyarrow.csv as pacsv

        tbl = pacsv.read_csv(f0, **_csv_args(options))
        schema = ac.arrow_schema_to_schema(tbl.schema)
    else:
        raise ValueError(fmt)
    # partition columns append after the file columns (Spark layout)
    part_fields = [f for f in _infer_partition_fields(values, keys)
                   if f.name not in schema.names]
    if part_fields:
        schema = T.Schema(list(schema.fields) + part_fields)
    return schema


def _csv_args(options: dict):
    import pyarrow.csv as pacsv

    read_opts = pacsv.ReadOptions(
        autogenerate_column_names=not options.get("header", True))
    parse_opts = pacsv.ParseOptions(
        delimiter=options.get("sep", ","))
    conv = pacsv.ConvertOptions()
    if "schema" in options:
        sch = options["schema"]
        conv = pacsv.ConvertOptions(column_types={
            f.name: ac.dtype_to_arrow(f.dtype) for f in sch})
        if not options.get("header", True):
            read_opts = pacsv.ReadOptions(
                column_names=[f.name for f in sch])
    return {"read_options": read_opts, "parse_options": parse_opts,
            "convert_options": conv}


class FileScanExec(P.PhysicalPlan):
    """One partition per file; within a file, batches split to reader size
    targets (reference: populateCurrentBlockChunk GpuParquetScan.scala:571)."""

    def __init__(self, fmt: str, files: List[str], schema: T.Schema,
                 options: dict, conf, part_values=None, part_keys=None,
                 file_fingerprints=None):
        super().__init__()
        self.fmt = fmt
        self.files = files
        #: per-file identity records captured at discovery time (path,
        #: size, mtime_ns) — the recovery data-material fingerprint and
        #: the streaming source ledger read THESE instead of re-stat-ing
        self.file_fingerprints = (
            file_fingerprints if file_fingerprints is not None
            else [file_fingerprint(p) for p in files])
        self._schema = schema
        self.options = options
        self.max_rows = conf.get(READER_BATCH_SIZE_ROWS)
        self.max_bytes = conf.get(READER_BATCH_SIZE_BYTES)
        self.n_partitions = max(1, len(files))
        self.metrics_skipped_groups = 0
        self.metrics_skipped_stripes = 0
        self.metrics_skipped_files = 0
        #: (rows, batches, bytes) metrics of the running execution
        self._decode_counters = (None,) * 4
        # Hive-partition layout: per-file raw values + the derived
        # constant columns appended to every batch
        self.part_values = part_values or [{} for _ in files]
        self.part_fields = [
            schema.fields[schema.index_of(k)] for k in (part_keys or [])
            if k in schema.names]
        part_names = {f.name for f in self.part_fields}
        self._file_schema = T.Schema(
            [f for f in schema.fields if f.name not in part_names])

    @property
    def schema(self):
        return self._schema

    def _read_file(self, fi: int):
        """File ``fi``'s batches, each decoded inside a ``ScanDecode``
        range on the thread that pulls it (the ``h2d-prefetch-*``
        producer by default): the reader's step, the Arrow-to-host
        conversion and the partition columns.  The range is closed
        when the batch is handed on."""
        rows, batches, nbytes, _ = self._decode_counters
        for hb in trace_steps("ScanDecode", self._decode_file(fi)):
            if rows is not None:
                rows.add(hb.num_rows)
                batches.add(1)
                nbytes.add(hb.estimate_bytes())
            yield hb

    def _decode_file(self, fi: int):
        import numpy as np

        path = self.files[fi]
        miscexprs.context.input_file = path
        miscexprs.context.input_file_block_start = 0
        miscexprs.context.input_file_block_length = os.path.getsize(path)
        pv = self.part_values[fi] if fi < len(self.part_values) else {}
        string_bytes = self._decode_counters[3]

        def finish(file_batch):
            return self._append_partitions(file_batch, pv, np)

        if not self._file_schema.fields and self.part_fields:
            # projection kept ONLY partition columns (e.g. count(*) over
            # a filter on the partition key): no file column is read,
            # but the row count still comes from the file metadata
            n = self._count_rows(path)
            for lo in range(0, n, self.max_rows):
                yield self._partition_only_batch(
                    min(self.max_rows, n - lo), pv, np)
            return

        if self.fmt == "parquet":
            import pyarrow.parquet as pq

            pf = pq.ParquetFile(path)
            cols = self._projected_names()
            groups = self._prune_row_groups(pf)
            if not groups:
                return
            for rb in pf.iter_batches(batch_size=self.max_rows,
                                      row_groups=groups, columns=cols):
                yield finish(ac.arrow_to_host_batch(
                    rb, self._file_schema, string_bytes))
        elif self.fmt == "orc":
            import pyarrow.orc as orc

            f = orc.ORCFile(path)
            for i in self._prune_stripes(f, path):
                stripe = f.read_stripe(i, columns=self._projected_names())
                batch = ac.arrow_to_host_batch(stripe, self._file_schema,
                                               string_bytes)
                for b in _split_to_target(batch, self.max_rows):
                    yield finish(b)
        elif self.fmt == "csv":
            import pyarrow.csv as pacsv

            tbl = pacsv.read_csv(path, **_csv_args(self.options))
            batch = ac.arrow_to_host_batch(tbl, self._file_schema,
                                           string_bytes)
            for b in _split_to_target(batch, self.max_rows):
                yield finish(b)
        else:
            raise ValueError(self.fmt)

    def _partition_columns(self, n: int, pv: dict, np) -> dict:
        from ..data.column import HostColumn

        out = {}
        for f in self.part_fields:
            v = _parse_partition_value(pv.get(f.name), f.dtype)
            if v is None:
                out[f.name] = HostColumn.nulls(n, f.dtype)
            elif f.dtype.id is T.TypeId.STRING:
                data = np.empty(n, dtype=object)
                data[:] = v
                out[f.name] = HostColumn(f.dtype, data, None)
            else:
                out[f.name] = HostColumn(
                    f.dtype, np.full(n, v, dtype=f.dtype.np_dtype), None)
        return out

    def _append_partitions(self, batch: HostBatch, pv: dict, np):
        """Append the file's constant partition columns, output columns
        ordered by the scan schema (reference:
        ColumnarPartitionReaderWithPartitionValues.scala:96)."""
        if not self.part_fields:
            return batch
        by_name = dict(zip(self._file_schema.names, batch.columns))
        by_name.update(self._partition_columns(batch.num_rows, pv, np))
        return HostBatch(self._schema,
                         [by_name[name] for name in self._schema.names])

    def _partition_only_batch(self, n: int, pv: dict, np) -> HostBatch:
        cols = self._partition_columns(n, pv, np)
        return HostBatch(self._schema,
                         [cols[name] for name in self._schema.names])

    def _count_rows(self, path: str) -> int:
        if self.fmt == "parquet":
            import pyarrow.parquet as pq

            return pq.ParquetFile(path).metadata.num_rows
        if self.fmt == "orc":
            import pyarrow.orc as orc

            return orc.ORCFile(path).nrows
        import pyarrow.csv as pacsv

        return pacsv.read_csv(path, **_csv_args(self.options)).num_rows

    def _projected_names(self):
        return self._file_schema.names

    def _prune_row_groups(self, pf):
        """Keep row groups whose min-max statistics admit the pushed
        predicates (reference: the footer row-group filtering in
        GpuParquetScan.scala:316 reusing Spark's ParquetFilters)."""
        preds = self.options.get("_scan_predicates") or []
        n_groups = pf.metadata.num_row_groups
        if not preds:
            return list(range(n_groups))
        col_idx = {pf.metadata.schema.column(i).name: i
                   for i in range(pf.metadata.num_columns)}
        kept = []
        for g in range(n_groups):
            rg = pf.metadata.row_group(g)
            admit = True
            for name, op, value in preds:
                i = col_idx.get(name)
                if i is None:
                    continue
                st = rg.column(i).statistics
                if st is None or not st.has_min_max:
                    continue
                dtype = self._schema[self._schema.index_of(name)].dtype \
                    if name in self._schema else None
                lo = _stat_value(st.min, dtype)
                hi = _stat_value(st.max, dtype)
                try:
                    if op == "==" and (value < lo or value > hi):
                        admit = False
                    elif op == "<" and lo >= value:
                        admit = False
                    elif op == "<=" and lo > value:
                        admit = False
                    elif op == ">" and hi <= value:
                        admit = False
                    elif op == ">=" and hi < value:
                        admit = False
                except TypeError:  # incomparable stats type: keep group
                    pass
                if not admit:
                    break
            if admit:
                kept.append(g)
        self.metrics_skipped_groups += n_groups - len(kept)
        return kept

    def _prune_stripes(self, f, path):
        """ORC stripe selection under pushed predicates (reference:
        GpuOrcScan.scala stripe planning + OrcFilters SARG pushdown).
        pyarrow exposes no stripe statistics, so the predicate COLUMNS
        of each stripe are decoded first (cheap when the projection is
        wider) and min/max evaluated on host; excluded stripes never
        decode their remaining columns."""
        import numpy as np

        preds = self.options.get("_scan_predicates") or []
        names = set(self._file_schema.names)
        preds = [p for p in preds if p[0] in names]
        if not preds or f.nstripes <= 1:
            return list(range(f.nstripes))
        pred_cols = sorted({name for name, _op, _v in preds})
        kept = []
        for i in range(f.nstripes):
            tbl = f.read_stripe(i, columns=pred_cols)
            admit = True
            for name, op, value in preds:
                col = tbl.column(name)
                vals = col.to_numpy(zero_copy_only=False)
                mask = ~np.asarray([v is None for v in vals]) \
                    if vals.dtype == object else ~np.isnan(vals) \
                    if np.issubdtype(vals.dtype, np.floating) \
                    else np.ones(len(vals), dtype=bool)
                if not mask.any():
                    continue
                lo, hi = vals[mask].min(), vals[mask].max()
                try:
                    if op == "==" and (value < lo or value > hi):
                        admit = False
                    elif op == "<" and lo >= value:
                        admit = False
                    elif op == "<=" and lo > value:
                        admit = False
                    elif op == ">" and hi <= value:
                        admit = False
                    elif op == ">=" and hi < value:
                        admit = False
                except TypeError:
                    pass
                if not admit:
                    break
            if admit:
                kept.append(i)
        self.metrics_skipped_stripes += f.nstripes - len(kept)
        return kept

    def _partition_pruned_files(self):
        """Whole-file pruning from pushed predicates on partition
        columns (reference: Spark's partition pruning in the file index
        feeding GpuFileSourceScanExec)."""
        preds = self.options.get("_scan_predicates") or []
        part_types = {f.name: f.dtype for f in self.part_fields}
        preds = [p for p in preds if p[0] in part_types]
        if not preds:
            return list(range(len(self.files)))
        kept = []
        for i in range(len(self.files)):
            pv = self.part_values[i] if i < len(self.part_values) else {}
            admit = True
            for name, op, value in preds:
                v = _parse_partition_value(pv.get(name),
                                           part_types[name])
                if v is None:
                    admit = False  # null never satisfies a comparison
                    break
                try:
                    ok = {"==": v == value, "<": v < value,
                          "<=": v <= value, ">": v > value,
                          ">=": v >= value}[op]
                except TypeError:
                    continue
                if not ok:
                    admit = False
                    break
            if admit:
                kept.append(i)
        return kept

    def execute(self, ctx):
        # what ScanDecode produced (estimated bytes: strings sampled;
        # the string columns' logical bytes exact, from Arrow's offsets)
        reg = ctx.metrics
        self._decode_counters = (
            reg.metric(f"{self.name}.decodedRows"),
            reg.metric(f"{self.name}.decodedBatches"),
            reg.metric(f"{self.name}.decodedBytes"),
            reg.metric(f"{self.name}.decodedStringBytes"))

        def make(fi):
            return lambda: self._read_file(fi)

        kept = self._partition_pruned_files()
        self.metrics_skipped_files = len(self.files) - len(kept)
        return P.PartitionedData(
            [make(i) for i in kept]
            or [lambda: iter(())])

    def describe(self):
        return f"FileScan[{self.fmt}]({len(self.files)} files)"


def _stat_value(v, dtype=None):
    """Normalize a parquet statistics value to the engine's host
    representation for the scan column's dtype: DATE32 -> int32 days
    since epoch, TIMESTAMP -> int64 microseconds since epoch."""
    import datetime as dt

    if isinstance(v, dt.datetime):
        if dtype is not None and dtype.id is T.TypeId.TIMESTAMP:
            epoch = dt.datetime(1970, 1, 1, tzinfo=v.tzinfo)
            return int((v - epoch).total_seconds() * 1_000_000)
        v = v.date()
    if isinstance(v, dt.date):
        return (v - dt.date(1970, 1, 1)).days
    return v


def _split_to_target(batch: HostBatch, max_rows: int):
    n = batch.num_rows
    if n <= max_rows:
        yield batch
        return
    for lo in range(0, n, max_rows):
        yield batch.slice(lo, min(lo + max_rows, n))


#: CSV reader options the scan supports; anything else is rejected up
#: front (reference: GpuCSVScan.tagSupport's option gates,
#: GpuBatchScanExec.scala:90-237 — unsupported parse modes fall back)
_CSV_SUPPORTED_OPTIONS = {"header", "sep", "schema", "_scan_predicates"}


def validate_csv_options(options: dict) -> None:
    unknown = set(options) - _CSV_SUPPORTED_OPTIONS
    if unknown:
        raise ValueError(
            f"unsupported CSV options {sorted(unknown)}; supported: "
            f"{sorted(_CSV_SUPPORTED_OPTIONS - {'_scan_predicates'})} "
            "(the reference CSV scan likewise gates unsupported parse "
            "options, GpuCSVScan.tagSupport)")
    sep = options.get("sep", ",")
    if not isinstance(sep, str) or len(sep) != 1:
        raise ValueError(f"CSV sep must be a single character, got "
                         f"{sep!r}")


def create_scan_exec(node: L.FileScan, conf) -> FileScanExec:
    if node.fmt == "csv":
        validate_csv_options(node.options)
    files, values, keys, fps = discover_files(node.paths)
    return FileScanExec(node.fmt, files, node.schema, node.options, conf,
                        part_values=values, part_keys=keys,
                        file_fingerprints=fps)
