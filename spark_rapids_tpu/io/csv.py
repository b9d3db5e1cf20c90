"""CSV scan (reference: GpuCSVScan / GpuBatchScanExec.scala, 507 LoC).

The reference gates CSV options strictly (GpuCSVScan.tagSupport:87-199) and does
host line-chunking before device parse; here pyarrow's CSV reader performs the
host parse and the TPU side receives uploaded batches. Option gating mirrors the
reference's strictness: unsupported options fall back at tag time.
"""
from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

import pyarrow as pa
import pyarrow.csv as pacsv

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import Schema
from spark_rapids_tpu.columnar.host import HostBatch
from spark_rapids_tpu.execs.base import ExecContext, LeafExec

SUPPORTED_OPTIONS = {"header", "sep", "delimiter", "nullValue"}


def _read_options(options: Dict[str, str]):
    header = options.get("header", "false").lower() in ("true", "1")
    sep = options.get("sep", options.get("delimiter", ","))
    read = pacsv.ReadOptions(autogenerate_column_names=not header)
    parse = pacsv.ParseOptions(delimiter=sep)
    null_values = [options.get("nullValue", "")] + ["", "null"]
    convert = pacsv.ConvertOptions(null_values=null_values,
                                   strings_can_be_null=True)
    return read, parse, convert


def infer_csv_schema(path: str, options: Dict[str, str]) -> Schema:
    """Schema from the first parsed block only — no full-file read."""
    read, parse, convert = _read_options(options)
    with pacsv.open_csv(path, read_options=read, parse_options=parse,
                        convert_options=convert) as reader:
        return Schema.from_pa(reader.schema)


def _read_table(path: str, schema: Schema, options: Dict[str, str]) -> pa.Table:
    read, parse, convert = _read_options(options)
    convert = pacsv.ConvertOptions(
        null_values=convert.null_values, strings_can_be_null=True,
        column_types={f.name: f.dtype.pa_type() for f in schema},
        # the scan's columns only: pruning may have narrowed the schema
        include_columns=schema.names())
    t = pacsv.read_csv(path, read_options=read, parse_options=parse,
                       convert_options=convert)
    return t.cast(schema.to_pa())


class _CsvScanBase(LeafExec):
    def __init__(self, files, schema: Schema, options: Dict[str, str],
                 partition_schema: Schema = Schema([])):
        from spark_rapids_tpu.io.datasource import scan_data_schema
        super().__init__(schema)
        self.files = tuple(files)
        self.options = options
        self.partition_schema = partition_schema
        self.data_schema = scan_data_schema(schema, partition_schema)

    def size_estimate(self):
        from spark_rapids_tpu.io.datasource import file_scan_size_estimate
        return file_scan_size_estimate(self.files)

    @property
    def paths(self) -> Tuple[str, ...]:
        return tuple(f.path for f in self.files)

    scan_partitions: int = 1

    is_file_scan = True

    @property
    def num_partitions(self) -> int:
        return self.scan_partitions

    def file_row_counts(self):
        """CSV has no row-count metadata; shard-local mesh reads fall back
        to the read-then-scatter path."""
        return None

    def iter_tables_for_files(self, files):
        from spark_rapids_tpu.io.datasource import (append_partition_columns,
                                                    fill_file_meta)
        for pf in files:
            t = _read_table(pf.path, self.data_schema, self.options)
            t = append_partition_columns(t, self.partition_schema,
                                         pf.partition_values)
            yield fill_file_meta(t, pf, self.output)

    def _iter_arrow(self, ctx: ExecContext):
        from spark_rapids_tpu.io.datasource import assigned_files
        if ctx.partition_id >= self.scan_partitions:
            return
        yield from self.iter_tables_for_files(
            assigned_files(self.files, ctx.partition_id,
                           self.scan_partitions))


class CpuCsvScanExec(_CsvScanBase):
    def execute(self, ctx: ExecContext) -> Iterator[HostBatch]:
        for t in self._iter_arrow(ctx):
            b = HostBatch.from_arrow(t, ctx.string_max_bytes)
            self.count_output(b.num_rows)
            yield b


class TpuCsvScanExec(_CsvScanBase):
    is_device = True

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for t in self._iter_arrow(ctx):
            b = DeviceBatch.from_arrow(t, ctx.string_max_bytes)
            self.count_output(b.num_rows)
            yield b
