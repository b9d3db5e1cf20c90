"""Parquet scan execs (reference: GpuParquetScan.scala, 699 LoC).

The reference's pattern — CPU footer parse + predicate-pushdown row-group
clipping + host staging, then device decode (GpuParquetScan.scala:342,576) —
maps here to: pyarrow reads footers and decodes row groups into host Arrow
memory (the CPU stage), and the TPU exec uploads straight into bucketed device
buffers (the device stage). Row-group pruning via parquet statistics happens on
the CPU before any data is read (clipBlocks analog, GpuParquetScan.scala:688).
Chunking honors maxReadBatchSizeRows AND maxReadBatchSizeBytes like
populateCurrentBlockChunk (GpuParquetScan.scala:599); schema evolution fills
missing columns with nulls (evolveSchemaIfNeededAndClose, :520); hive partition
values are appended per batch (ColumnarPartitionReaderWithPartitionValues)."""
from __future__ import annotations

import os
from functools import lru_cache
from typing import Iterator, List, Optional, Sequence, Tuple

import pyarrow as pa
import pyarrow.parquet as pq

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import DType, Schema
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.execs.base import ExecContext, LeafExec
from spark_rapids_tpu.exprs.core import Expression
from spark_rapids_tpu.utils import tracing as _tracing
from spark_rapids_tpu.io.datasource import (ColumnStats, PartitionedFile,
                                            append_partition_columns,
                                            assigned_files, evolve_schema,
                                            fill_file_meta,
                                            stats_may_contain)


def _row_group_stats(md, rg_index: int) -> dict:
    """Column min/max/null stats for one row group from footer metadata."""
    rg = md.row_group(rg_index)
    out = {}
    for i in range(rg.num_columns):
        col = rg.column(i)
        name = col.path_in_schema
        st = col.statistics
        if st is None:
            out[name] = ColumnStats()
            continue
        out[name] = ColumnStats(
            min=st.min if st.has_min_max else None,
            max=st.max if st.has_min_max else None,
            null_count=st.null_count if st.has_null_count else None,
            num_values=rg.num_rows)
    return out


def clip_row_groups(pf: pq.ParquetFile,
                    filters: Sequence[Expression]) -> List[int]:
    """Row groups whose statistics say they may contain matching rows
    (clipBlocks analog)."""
    md = pf.metadata
    if not filters:
        return list(range(md.num_row_groups))
    kept = []
    for i in range(md.num_row_groups):
        stats = _row_group_stats(md, i)
        if all(stats_may_contain(f, stats) for f in filters):
            kept.append(i)
    return kept


@lru_cache(maxsize=512)
def _clipped_groups_cached(path: str, mtime_ns: int, size: int,
                           filters: Tuple[Expression, ...]):
    """One footer parse per (file state, filters): the pruned row-group list,
    its exact row count, and per-group row counts — shared by the sizing pass
    (file_row_counts), the plan-time shard assignment (row_group_units) and
    the read pass so metadata is never re-parsed per pass."""
    pf = pq.ParquetFile(path)
    groups = clip_row_groups(pf, filters)
    group_rows = tuple(pf.metadata.row_group(i).num_rows for i in groups)
    return tuple(groups), sum(group_rows), group_rows


def clipped_groups(path: str, filters: Tuple[Expression, ...]):
    st = os.stat(path)
    return _clipped_groups_cached(path, st.st_mtime_ns, st.st_size,
                                  tuple(filters))


def _iter_file_tables(f: PartitionedFile, data_schema: Schema,
                      partition_schema: Schema,
                      filters: Sequence[Expression],
                      max_rows: int, max_bytes: int,
                      device_dict: bool = False, device_rle: bool = False,
                      unifier=None,
                      groups: Optional[Sequence[int]] = None
                      ) -> Iterator[pa.Table]:
    pf = pq.ParquetFile(f.path)
    if groups is None:
        groups = list(clipped_groups(f.path, tuple(filters))[0])
    else:
        # caller-restricted read (a mesh shard's plan-time assignment):
        # the units are already statistics-clipped at plan time
        groups = list(groups)
    if not groups:
        return
    md = pf.metadata
    # rows-per-batch from the byte budget using the file's average row width
    # (populateCurrentBlockChunk's size accounting)
    total_rows = max(1, md.num_rows)
    total_bytes = sum(md.row_group(i).total_byte_size
                      for i in range(md.num_row_groups)) or total_rows
    rows_by_bytes = max(1, int(max_bytes * total_rows / total_bytes))
    batch_rows = min(max_rows, rows_by_bytes)
    file_cols = set(md.schema.names)
    want = [f2.name for f2 in data_schema if f2.name in file_cols]
    # legacy-calendar detection from the writer's file metadata
    # (RebaseHelper.scala:82, GpuParquetScan.scala:216): Spark < 3 /
    # LEGACY-mode files store hybrid-Julian day counts — rebase them
    from spark_rapids_tpu.io.rebase import file_rebase_mode
    needs_rebase = file_rebase_mode(md.metadata) == "legacy"
    if device_dict and not needs_rebase:
        # fixed-width columns come straight off the PAGE BYTES as the
        # file's own encoding (io/parquet_pages.py): narrow indices + the
        # small dictionary — or, for RLE-dominant chunks, the run form
        # itself — cross the host link and decode with an on-device
        # gather/expansion, the GpuParquetScan.scala:576 device-decode
        # role. Mixed-encoding chunks keep their dictionary prefix encoded
        # and host-decode only the PLAIN tail; strings read through
        # pyarrow's still-encoded dictionary read.
        yield from _iter_dict_tables(pf, f, groups, want, data_schema,
                                     partition_schema, batch_rows,
                                     device_rle, unifier)
        return
    batches = pf.iter_batches(batch_size=batch_rows, row_groups=groups,
                              columns=want)
    while True:
        # one span per batch, closed before the yield: the consumer's time
        # is not the scan's (the last one finds the end: no rows)
        with _tracing.span("scan.read_group", _tracing.LAYER_TRANSFER) as grp:
            with _tracing.span("scan.arrow_read",
                               _tracing.LAYER_TRANSFER) as sp:
                rb = next(batches, None)
                if sp is not None and rb is not None:
                    sp.note(columns=want, rows=rb.num_rows, bytes=rb.nbytes)
            if rb is not None:
                t = evolve_schema(pa.Table.from_batches([rb]), data_schema)
                if needs_rebase:
                    t = _rebase_legacy_datetimes(t)
                t = append_partition_columns(t, partition_schema,
                                             f.partition_values)
            if grp is not None:
                grp.note(rows=rb.num_rows if rb is not None else 0,
                         columns=len(want))
        if rb is None:
            return
        yield t


def _iter_dict_tables(pf: pq.ParquetFile, f: PartitionedFile,
                      groups, want, data_schema: Schema,
                      partition_schema: Schema, batch_rows: int,
                      device_rle: bool = False,
                      unifier=None) -> Iterator[pa.Table]:
    """Per-row-group read keeping fixed-width columns encoded from the raw
    page bytes (dictionary indices, or the run form for RLE-dominant
    chunks); pyarrow reads the rest. Yields batch_rows-bounded slices
    (dictionary and run-end-encoded arrays slice zero-copy).

    Every dictionary column is remapped through the scan's
    DictionaryUnifier so all batches of one scan share a prefix-compatible
    dictionary identified by a token in the field metadata — that is what
    lets concat_device_batches carry the encoding across batches and the
    encoded-domain operators run on stable indices. Mixed-encoding chunks
    split the row group at the dictionary-prefix/PLAIN-tail boundary:
    prefix segments stay encoded, tail segments carry the host-decoded
    values."""
    from spark_rapids_tpu.columnar.encoding import DictionaryUnifier
    from spark_rapids_tpu.io.parquet_pages import read_dict_column
    if unifier is None:
        unifier = DictionaryUnifier()
    md = pf.metadata
    names = list(md.schema.names)
    arrow_schema = pf.schema_arrow
    # strings ride pyarrow's own still-encoded read (read_dictionary is
    # BYTE_ARRAY-only); the upload gathers their byte-matrix rows on device
    str_cols = [f2.name for f2 in data_schema
                if f2.dtype is DType.STRING and f2.name in names]
    pf_str = (pq.ParquetFile(f.path, read_dictionary=str_cols)
              if str_cols else pf)
    for rg in groups:
        # the group's span closes before its first yield: the consumer's
        # time between batches is not the scan's
        with _tracing.span("scan.read_group", _tracing.LAYER_TRANSFER) as grp:
            nrows = md.row_group(rg).num_rows
            encoded = {}
            for f2 in data_schema:
                if f2.dtype is DType.STRING or f2.name not in names:
                    continue
                ci = names.index(f2.name)
                at = arrow_schema.field(f2.name).type
                r = read_dict_column(f.path, md, rg, ci, at,
                                     want_runs=device_rle)
                if r is not None:
                    encoded[f2.name] = r
            rest = [n for n in want if n not in encoded]
            plain = None
            if rest:
                with _tracing.span("scan.arrow_read",
                                   _tracing.LAYER_TRANSFER) as sp:
                    plain = pf_str.read_row_group(rg, columns=rest)
                    if sp is not None:
                        sp.note(columns=rest, rows=plain.num_rows,
                                bytes=plain.nbytes)
            with _tracing.span("scan.unify", _tracing.LAYER_TRANSFER) as sp:
                out = _group_tables(encoded, plain, want, nrows, unifier,
                                    batch_rows, data_schema,
                                    partition_schema, f.partition_values)
                if sp is not None:
                    sp.note(columns=len(want), batches=len(out))
            if grp is not None:
                grp.note(row_group=rg, rows=nrows, columns=len(want))
        yield from out


def _group_tables(encoded, plain, want, nrows: int, unifier,
                  batch_rows: int, data_schema: Schema,
                  partition_schema: Schema,
                  partition_values) -> List[pa.Table]:
    """One row group's columns -> its batch_rows-bounded tables: every
    dictionary remapped through the scan's unifier, the group split where a
    mixed-encoding column's dictionary prefix ends, each segment sliced
    (zero-copy)."""
    from spark_rapids_tpu.columnar.encoding import with_dict_tokens
    cols = {}       # name -> (prefix_or_whole, tail_or_None, split_row)
    tokens = {}
    for n in want:
        if n in encoded:
            r = encoded[n]
            prefix = r.prefix
            if isinstance(prefix, pa.DictionaryArray):
                prefix, tokens[n] = unifier.unify(n, prefix)
            cols[n] = (prefix, r.tail, len(prefix))
        else:
            c = plain.column(n)
            if isinstance(c, pa.ChunkedArray):
                # combine_chunks on a ChunkedArray yields an Array
                # (also for the 0-chunk empty-file case)
                c = (c.chunk(0) if c.num_chunks == 1
                     else c.combine_chunks())
            if isinstance(c, pa.DictionaryArray) and len(c.dictionary):
                c, tokens[n] = unifier.unify(n, c)
            cols[n] = (c, None, nrows)
    # segment boundaries: a mixed-encoding column splits the row group
    # where its dictionary prefix ends (only the tail is decoded)
    bounds = sorted({0, nrows} | {sr for _, tail, sr in cols.values()
                                  if tail is not None})
    out = []
    for s, e in zip(bounds, bounds[1:]):
        seg_cols, fields = [], []
        for n in want:
            prefix, tail, split = cols[n]
            a = (prefix.slice(s, e - s) if e <= split
                 else tail.slice(s - split, e - s))
            seg_cols.append(a)
            fields.append(pa.field(n, a.type))
        table = pa.table(seg_cols, schema=pa.schema(fields))
        table = with_dict_tokens(table, tokens)
        for start in range(0, e - s, batch_rows):
            t = table.slice(start, min(batch_rows, e - s - start))
            t = evolve_schema(t, data_schema)
            out.append(append_partition_columns(t, partition_schema,
                                                partition_values))
    return out


def _rebase_legacy_datetimes(t: pa.Table) -> pa.Table:
    """Julian->Gregorian correction for every date/timestamp column of a
    legacy-calendar file's batch (host-side, before any upload)."""
    import numpy as np

    from spark_rapids_tpu.io.rebase import (julian_to_gregorian_days,
                                            julian_to_gregorian_micros)
    for i, field in enumerate(t.schema):
        typ = field.type
        if pa.types.is_date32(typ):
            rebase, vt, width = julian_to_gregorian_days, pa.int32(), np.int32
            col = t.column(i).combine_chunks()
        elif pa.types.is_timestamp(typ):
            rebase, vt, width = julian_to_gregorian_micros, pa.int64(), \
                np.int64
            # normalize to micros (Spark's storage unit) before the math
            col = t.column(i).combine_chunks().cast(
                pa.timestamp("us", typ.tz))
        else:
            continue
        raw = col.cast(vt)
        # fill nulls BEFORE to_numpy: a nullable int column converts to
        # float64 otherwise, silently rounding |micros| > 2^53 (any
        # pre-1582 timestamp) before the rebase ever runs
        ints = raw.fill_null(0).to_numpy(zero_copy_only=False)
        fixed = rebase(ints).astype(width)
        new = pa.Array.from_pandas(fixed, mask=np.asarray(col.is_null()),
                                   type=vt).cast(col.type).cast(typ)
        t = t.set_column(i, field, new)
    return t


class _ParquetScanBase(LeafExec):
    """Shared scan logic (GpuParquetScanBase analog). ``output`` is the full
    read schema including partition columns."""

    def __init__(self, files: Tuple[PartitionedFile, ...], schema: Schema,
                 partition_schema: Schema = Schema([]),
                 filters: Tuple[Expression, ...] = (),
                 max_batch_rows: int = 1 << 20,
                 max_batch_bytes: int = 1 << 31):
        from spark_rapids_tpu.io.datasource import scan_data_schema
        super().__init__(schema)
        self.files = files
        self.partition_schema = partition_schema
        self.data_schema = scan_data_schema(schema, partition_schema)
        self.filters = filters
        self.max_batch_rows = max_batch_rows
        self.max_batch_bytes = max_batch_bytes

    def size_estimate(self):
        from spark_rapids_tpu.io.datasource import file_scan_size_estimate
        return file_scan_size_estimate(self.files)

    @property
    def paths(self) -> Tuple[str, ...]:
        return tuple(f.path for f in self.files)

    #: how many scan tasks split the file list (FilePartition planning knob);
    #: 1 = the whole scan runs in partition 0
    scan_partitions: int = 1

    #: marks execs whose input is a partitioned file list that shard-local
    #: mesh reads can split (GpuParquetScan's per-task partition readers)
    is_file_scan = True

    @property
    def num_partitions(self) -> int:
        return self.scan_partitions

    def file_row_counts(self) -> Optional[List[int]]:
        """Exact per-file row counts after row-group pruning, from footer
        metadata only (no data read) — sizes shard-local mesh reads."""
        return [clipped_groups(f.path, tuple(self.filters))[1]
                for f in self.files]

    def row_group_units(self) -> List[Tuple[int, int, int]]:
        """The scan's splittable work units at ROW-GROUP granularity:
        (file_index, row_group, exact_rows) per statistics-clipped group,
        from footer metadata only. This is what the mesh planner balances
        across shards AT PLAN TIME (the FilePartition split-packing role,
        one level finer than whole files), so a single huge file still
        spreads over the mesh."""
        units: List[Tuple[int, int, int]] = []
        for fi, f in enumerate(self.files):
            groups, _, group_rows = clipped_groups(f.path,
                                                   tuple(self.filters))
            units.extend((fi, rg, rows)
                         for rg, rows in zip(groups, group_rows))
        return units

    def iter_tables_for_units(self, units: Sequence[Tuple[int, int]]
                              ) -> Iterator[pa.Table]:
        """Read only the given (file_index, row_group) units — one shard's
        slice of the plan-time assignment. File order (and group order
        within a file) is preserved so shard-major row order is
        deterministic."""
        unifier = None
        if self.device_dict:
            from spark_rapids_tpu.columnar.encoding import DictionaryUnifier
            unifier = DictionaryUnifier()
        by_file: dict = {}
        for fi, rg in units:
            by_file.setdefault(fi, []).append(rg)
        for fi in sorted(by_file):
            f = self.files[fi]
            for t in _iter_file_tables(
                    f, self.data_schema, self.partition_schema, self.filters,
                    self.max_batch_rows, self.max_batch_bytes,
                    device_dict=self.device_dict,
                    device_rle=self.device_rle, unifier=unifier,
                    groups=sorted(by_file[fi])):
                yield fill_file_meta(t, f, self.output)

    #: TPU scans flip this on (per conf) so fixed-width columns arrive
    #: dictionary-encoded and decode on device
    device_dict = False
    #: with device_dict: keep RLE-dominant chunks as run pairs and expand
    #: in HBM instead of shipping per-row indices
    device_rle = False

    def iter_tables_for_files(self, files: Sequence[PartitionedFile]
                              ) -> Iterator[pa.Table]:
        # ONE dictionary unifier per scan pass: every file/row group's
        # dictionaries remap into a shared prefix-compatible dictionary per
        # column, so batch concatenation keeps the encoded form
        unifier = None
        if self.device_dict:
            from spark_rapids_tpu.columnar.encoding import DictionaryUnifier
            unifier = DictionaryUnifier()
        for f in files:
            for t in _iter_file_tables(
                    f, self.data_schema, self.partition_schema, self.filters,
                    self.max_batch_rows, self.max_batch_bytes,
                    device_dict=self.device_dict,
                    device_rle=self.device_rle, unifier=unifier):
                yield fill_file_meta(t, f, self.output)

    def _iter_arrow(self, ctx: ExecContext) -> Iterator[pa.Table]:
        if ctx.partition_id >= self.scan_partitions:
            return
        yield from self.iter_tables_for_files(
            assigned_files(self.files, ctx.partition_id,
                           self.scan_partitions))


class CpuParquetScanExec(_ParquetScanBase):
    def execute(self, ctx: ExecContext) -> Iterator[HostBatch]:
        for t in self._iter_arrow(ctx):
            b = HostBatch.from_arrow(t, ctx.string_max_bytes)
            self.count_output(b.num_rows)
            yield b


class TpuParquetScanExec(_ParquetScanBase):
    """Host-staged read + single upload per batch into bucketed device
    buffers. Cold scans PIPELINE: a producer thread decodes/stage-uploads
    the next chunks while the consumer computes on the current one
    (device_put is asynchronous, so chunk k+1's host decode overlaps chunk
    k's transfer and compute — the bufferTime/gpuDecodeTime overlap of
    GpuParquetScan.scala:342-478)."""

    is_device = True

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        import os as _os

        from spark_rapids_tpu import config as _cfg
        from spark_rapids_tpu.columnar.transfer import upload_table_conf
        self.device_dict = ctx.conf.get(_cfg.PARQUET_DEVICE_DICT)
        self.device_rle = (self.device_dict
                           and ctx.conf.get(_cfg.PARQUET_DEVICE_RLE))
        depth = ctx.conf.get(_cfg.SCAN_PREFETCH_BATCHES)
        if (_os.cpu_count() or 1) < 2:
            # decode-ahead needs a spare core: on a single-core host the
            # producer thread only contends with the consumer (measured 18%
            # SLOWER on the 1-core bench machine)
            depth = 0
        if depth <= 0:
            for t in self._iter_arrow(ctx):
                b = upload_table_conf(t, ctx.string_max_bytes, ctx.conf,
                                      device=ctx.device)
                self.count_output(b.num_rows)
                yield b
            return
        import queue
        import threading
        from spark_rapids_tpu.execs.pipeline import _put_abortable
        q: "queue.Queue" = queue.Queue(maxsize=depth)
        stop = threading.Event()
        smax = ctx.string_max_bytes

        def put(item) -> bool:
            # a full queue is the consumer holding the scan back: a span
            # only where the put blocks
            if _tracing.TRACER.on and q.full():
                with _tracing.span("scan.backpressure",
                                   _tracing.LAYER_TRANSFER):
                    return _put_abortable(q, item, stop)
            return _put_abortable(q, item, stop)

        def produce() -> None:
            # rebind the owning query thread-locally (the PipelinedExec
            # producer discipline): program-cache attribution AND the
            # tracing spans this thread records (chunk uploads) carry the
            # query id, so per-query trace exports include the prefetched
            # scan's transfer spans
            from spark_rapids_tpu.serving.lifecycle import bind_query
            with bind_query(ctx.query), _tracing.adopt(spawning_span):
                try:
                    for t in self._iter_arrow(ctx):
                        # staging + device_put happen HERE, ahead of the
                        # consumer; the upload is already in flight when
                        # the consumer dequeues the batch. ctx.device
                        # rides along so multi-device placement doesn't
                        # silently default.
                        b = upload_table_conf(t, smax, ctx.conf,
                                              device=ctx.device)
                        if not put(("b", b)):
                            return  # consumer abandoned the scan early
                except BaseException as e:  # noqa: BLE001 - reraised below
                    put(("e", e))
                    return
                put(("end", None))

        spawning_span = _tracing.current() if _tracing.TRACER.on else None
        worker = threading.Thread(target=produce, daemon=True,
                                  name="parquet-scan-prefetch")
        worker.start()
        try:
            while True:
                # what the query's own thread stands still for
                with _tracing.span("scan.wait", _tracing.LAYER_TRANSFER):
                    kind, val = q.get()
                if kind == "end":
                    break
                if kind == "e":
                    raise val
                self.count_output(val.num_rows)
                yield val
        finally:
            # early exit (LimitExec closing the generator), consumer error,
            # or normal end: unblock a producer stuck on a full queue and
            # reap the thread instead of leaking it with device batches
            stop.set()
            while worker.is_alive():
                try:
                    q.get_nowait()
                except queue.Empty:
                    worker.join(0.05)


def write_parquet(table: pa.Table, path: str, compression: str = "snappy") -> None:
    """Single-file columnar parquet write (GpuParquetWriter analog)."""
    pq.write_table(table, path, compression=compression)
