"""Distributed physical operators over a device mesh.

The TPU-native replacement for the reference's distributed execution stack:
where spark-rapids runs one task per GPU and moves batches between executors
through the UCX shuffle (RapidsShuffleInternalManager.scala:194 wiring the
accelerated shuffle into query execution, GpuShuffleExchangeExec partitioning
on device), this engine runs every operator as ONE SPMD program over a
``jax.sharding.Mesh``:

- a partition is a mesh shard (MeshBatch, parallel/mesh_batch.py);
- a shuffle exchange is a single compiled ``all_to_all`` over ICI
  (no host round trip, no serialization, no bounce buffers);
- a broadcast exchange is buffer replication across the mesh (XLA
  all-gather), the GpuBroadcastExchangeExec role;
- aggregation is partial-per-shard, then all-gather + replicated merge for
  small groupings or a key-hash repartition + per-shard merge for large ones
  (aggregate.scala Partial/Final modes over GpuHashPartitioning), with the
  output staying mesh-sharded.

Dynamic output sizes (filter/join cardinality) cross the SPMD boundary as
per-shard row-count vectors — one tiny host sync per operator, amortized over
the whole mesh, never per batch per device.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import DType, Schema, bucket_capacity
from spark_rapids_tpu.execs.base import ExecContext, PhysicalExec
from spark_rapids_tpu.execs.evaluator import (colv_to_column, output_schema,
                                             reference_ordinals)
from spark_rapids_tpu.execs.tpu_execs import _PROGRAM_CACHE
from spark_rapids_tpu.serving.program_cache import named_jit
from spark_rapids_tpu.exprs.core import (ColV, EvalCtx, Expression, flat_len,
                                         flatten_colvs, unflatten_colvs)
from spark_rapids_tpu.exprs.misc import Alias, SortOrder
from spark_rapids_tpu.ops import batch_kernels as bk
from spark_rapids_tpu.ops import join as jk
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.parallel.mesh_batch import (MeshBatch, flatten_mesh,
                                                  gather_mesh, mesh_columns,
                                                  replicate_device_batch,
                                                  scatter_arrow,
                                                  scatter_device_batch)

from spark_rapids_tpu.utils import tracing as _tracing

_SAMPLE_PER_SHARD = 512

#: per-process log of mesh exchange sizings (count pre-pass results): the
#: MapOutputStatistics analog, consumed by skew/capacity tests and debugging.
#: Each entry is the dict a traced run records as the ``args`` of the
#: exchange's ``mesh.exchange`` span (``exchange_stats``)
EXCHANGE_STATS: list = []


def _shard_jit(mesh: Mesh, key: Tuple, builder, in_specs, out_specs):
    """Cached jit(shard_map(...)) keyed like the single-chip program cache.

    The inner key carries everything ``make`` observes beyond the caller's
    key (R016): the active shim's identity — a provider swap must not serve
    the old backend's shard_map program — the mesh, and both sharding-spec
    tuples, so two callers sharing (mesh, key) but sharding differently
    never share a compiled program. The shim is resolved here, once, not
    re-read inside the cached builder."""
    from spark_rapids_tpu import shims
    shim = shims.get()

    def make(shim=shim):
        return shim.shard_map(builder(), mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False)
    # named by the operator's own key, not by the "mesh" that wraps it
    return _PROGRAM_CACHE.get_or_build(
        ("mesh", type(shim).__name__, mesh, key, in_specs, out_specs),
        lambda: named_jit(key[0], make()))


def _specs(n: int, spec=P(DATA_AXIS)) -> Tuple:
    return tuple(spec for _ in range(n))


def _shard_ectx(colvs, cap: int, smax: int) -> EvalCtx:
    """EvalCtx for a shard_map body: the shard index IS the partition id, so
    partition-dependent expressions (spark_partition_id,
    monotonically_increasing_id, rand's per-partition stream) produce
    distinct per-shard values instead of n_dev identical copies."""
    ectx = EvalCtx(jnp, colvs, cap, smax)
    ectx.partition_id = jax.lax.axis_index(DATA_AXIS).astype(np.int32)
    return ectx


class MeshExec(PhysicalExec):
    """Base for mesh-sharded operators. One host-side partition; the
    parallelism lives in the mesh."""

    is_device = True
    is_mesh = True

    #: mesh plans never consume static size estimates: every mesh exchange
    #: and join/aggregate strategy switch counts OBSERVED per-shard sizes
    #: before its program compiles (sql.mesh.aggRepartitionThreshold,
    #: adaptive broadcast), and the out-of-core layer is single-process
    #: scope (per-shard grace is a ROADMAP follow-up)
    size_estimate_none_reason = ("mesh operators decide from observed "
                                 "per-shard sizes at run time")

    def __init__(self, children, output: Schema, mesh: Mesh):
        super().__init__(children, output)
        self.mesh = mesh
        #: declared output placement: rows partitioned over the mesh data
        #: axis. Set at CONSTRUCTION (i.e. at plan time, by mesh_rewrite) so
        #: the plan carries where every batch lives; boundary execs
        #: (gather, writes) override.
        self.placement = NamedSharding(mesh, P(DATA_AXIS))

    @property
    def num_partitions(self) -> int:
        return 1

    def _one_child_batch(self, ctx: ExecContext, i: int = 0) -> MeshBatch:
        batches = list(self.children[i].execute(ctx))
        assert len(batches) == 1, (
            f"mesh subtree produced {len(batches)} batches")
        return batches[0]


# ------------------------------------------------------------------ transitions
class MeshScatterExec(MeshExec):
    """Host rows -> mesh-sharded batch (the upload + partition step: the
    HostToDeviceExec role fused with the initial even distribution the
    reference gets from Spark's input partitioning).

    Directly over an in-memory scan the scattered batch is kept across
    actions by the scan cache, as HostToDeviceExec keeps its upload: keyed
    by the mesh too, each shard charged to the device that holds it."""

    def __init__(self, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), child.output, mesh)

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.execs.cpu_execs import CpuLocalScanExec
        child = self.children[0]
        cached = "hit"
        with _tracing.span("mesh.scatter", _tracing.LAYER_SHUFFLE) as sp:
            if (isinstance(child, CpuLocalScanExec)
                    and ctx.conf.get(cfg.SCAN_CACHE_ENABLED)):
                from spark_rapids_tpu.memory.scan_cache import (
                    derived_budget, get_cache)
                smax = ctx.string_max_bytes

                def build():
                    nonlocal cached
                    cached = "built"
                    return scatter_arrow(child.table, self.mesh, smax)
                # per-key latch: concurrent queries missing on the same
                # table share ONE scatter instead of each paying the link
                mb = get_cache(derived_budget(ctx.conf)).get_or_put(
                    child.table, smax, build,
                    cancel_check=ctx.check_cancelled, mesh=self.mesh)
                child.count_output(mb.num_rows)
            else:
                cached = "built"
                mb = self._scatter_child(ctx)
            if sp is not None:
                sp.note(rows=mb.num_rows, bytes=mb.device_size_bytes,
                        shards=mb.n_dev, cached=cached)
        self.count_output(mb.num_rows)
        yield mb

    def _scatter_child(self, ctx: ExecContext) -> MeshBatch:
        """Whatever the host child yields, over all its partitions."""
        import pyarrow as pa
        child = self.children[0]
        tables = []
        for p in range(child.num_partitions):
            cctx = ExecContext(ctx.conf, partition_id=p,
                               num_partitions=child.num_partitions,
                               device_manager=ctx.device_manager,
                               cleanups=ctx.cleanups)
            for hb in child.execute(cctx):
                tables.append(hb if isinstance(hb, pa.Table) else hb.to_arrow())
        if not tables:
            table = self.output.to_pa().empty_table()
        elif len(tables) == 1:
            table = tables[0]
        else:
            table = pa.concat_tables(tables)
        return scatter_arrow(table, self.mesh, ctx.string_max_bytes)


@dataclass(frozen=True)
class ScanShardAssignment:
    """Plan-time scan split: which (file_index, row_group) units each mesh
    shard reads, with exact per-shard row totals from footer metadata. The
    FilePartition split-packing role at row-group granularity — computed by
    the PLANNER (plan/mesh_rewrite.plan_scan_shards), not at execute time,
    so the plan itself says where every row lands."""

    #: per shard: ordered (file_index, row_group) units
    units: Tuple[Tuple[Tuple[int, int], ...], ...]
    #: per shard: exact row totals (statistics-clipped footer counts)
    rows: Tuple[int, ...]

    @property
    def num_rows(self) -> int:
        return sum(self.rows)


def plan_scan_shards(scan, mesh: Mesh, conf) -> Optional[ScanShardAssignment]:
    """Balance the scan's row-group units over the mesh shards at PLAN time
    (greedy LPT on exact metadata row counts). None when the format has no
    row-group granularity or the conf keeps the whole-file path."""
    from spark_rapids_tpu import config as cfg
    if conf is None or conf.get(cfg.MESH_SCAN_ASSIGNMENT) != "rowgroup":
        return None
    units_fn = getattr(scan, "row_group_units", None)
    if units_fn is None or not getattr(scan, "files", None):
        return None
    try:
        units = units_fn()
    except OSError:
        return None       # unreadable footer: the execute-time path decides
    n_dev = int(mesh.devices.size)
    order = sorted(range(len(units)), key=lambda i: -units[i][2])
    loads = [0] * n_dev
    assign: List[List[int]] = [[] for _ in range(n_dev)]
    for i in order:
        d = int(np.argmin(loads))
        assign[d].append(i)
        loads[d] += units[i][2]
    shard_units, shard_rows = [], []
    for lst in assign:
        lst.sort()    # preserve (file, group) plan order within a shard
        shard_units.append(tuple((units[i][0], units[i][1]) for i in lst))
        shard_rows.append(sum(units[i][2] for i in lst))
    return ScanShardAssignment(tuple(shard_units), tuple(shard_rows))


class MeshFileScatterExec(MeshExec):
    """Shard-local distributed scan: the scan's splits are assigned to
    shards, each shard's rows are read and uploaded straight to that shard's
    device, and the sharded global arrays are assembled without EVER
    materializing the whole table on one host buffer — the per-task
    partition readers of GpuParquetScan.scala (:151,291), with a mesh shard
    as the task.

    With a plan-time ``ScanShardAssignment`` (parquet; row-group
    granularity, sql.mesh.scan.shardAssignment=rowgroup) each shard's upload
    rides the chunked overlapped transfer pipeline (columnar/transfer.py)
    directly onto its owning device. Otherwise files are split at execute
    time by exact metadata row counts; formats without row-count metadata
    (CSV) fall back to read-everything-then-scatter.

    Host working set = one shard's rows."""

    def __init__(self, scan: PhysicalExec, mesh: Mesh,
                 assignment: Optional[ScanShardAssignment] = None):
        super().__init__((scan,), scan.output, mesh)
        self.assignment = assignment

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        import pyarrow as pa
        scan = self.children[0]
        if self.assignment is not None:
            mb = _scatter_assigned_shards(scan, self.assignment, self.mesh,
                                          ctx)
        else:
            counts = scan.file_row_counts() if scan.files else None
            if counts is None:
                # no metadata counts: read all, scatter (the generic path)
                tables = list(scan.iter_tables_for_files(scan.files))
                table = (pa.concat_tables(tables) if tables
                         else self.output.to_pa().empty_table())
                mb = scatter_arrow(table, self.mesh, ctx.string_max_bytes)
            else:
                mb = _scatter_file_shards(scan, counts, self.mesh,
                                          ctx.string_max_bytes)
        scan.count_output(mb.num_rows)
        self.count_output(mb.num_rows)
        yield mb


def _assign_files_to_shards(counts: Sequence[int], n_dev: int) -> List[List[int]]:
    """Greedy LPT: biggest file to the least-loaded shard (the balanced
    FilePartition planning the reference gets from Spark's split packing)."""
    order = sorted(range(len(counts)), key=lambda i: -counts[i])
    loads = [0] * n_dev
    assign: List[List[int]] = [[] for _ in range(n_dev)]
    for i in order:
        d = int(np.argmin(loads))
        assign[d].append(i)
        loads[d] += counts[i]
    for lst in assign:
        lst.sort()  # preserve file order within a shard
    return assign


def _assemble_mesh_batch(schema: Schema, shard_cols: List[List], rows,
                         mesh: Mesh, local_cap: int) -> MeshBatch:
    """Per-shard (data, validity, lengths) device arrays -> one MeshBatch:
    pad each shard to the common local capacity ON ITS DEVICE, equalize
    adaptive string widths, then assemble the global data-axis arrays with
    ``make_array_from_single_device_arrays`` — zero extra data movement.
    ``shard_cols[ci][d]`` is shard d's triple for column ci; arrays already
    at ``local_cap`` pass through untouched. The single assembly tail shared
    by every mesh scan path."""
    n_dev = int(mesh.devices.size)

    def pad_rows(a):
        n = a.shape[0]
        if n == local_cap:
            return a
        if n > local_cap:
            return a[:local_cap]
        return jnp.concatenate(
            [a, jnp.zeros((local_cap - n,) + a.shape[1:], a.dtype)])

    from spark_rapids_tpu.columnar.column import DeviceColumn as _DC
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    cols: List[_DC] = []
    for ci, f in enumerate(schema):
        parts = shard_cols[ci]
        datas = [p[0] for p in parts]
        if datas[0].ndim == 2:
            w = max(d.shape[1] for d in datas)
            datas = [jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                     if d.shape[1] < w else d for d in datas]
        datas = [pad_rows(a) for a in datas]
        valids = [pad_rows(p[1]) for p in parts]
        lens = ([pad_rows(p[2]) for p in parts]
                if parts[0][2] is not None else None)
        gshape = (n_dev * local_cap,) + datas[0].shape[1:]
        data = jax.make_array_from_single_device_arrays(
            gshape, sharding, datas)
        validity = jax.make_array_from_single_device_arrays(
            (n_dev * local_cap,), sharding, valids)
        lengths = None
        if lens is not None:
            lengths = jax.make_array_from_single_device_arrays(
                (n_dev * local_cap,), sharding, lens)
        cols.append(_DC(f.dtype, data, validity, lengths))
    return MeshBatch(schema, tuple(cols), rows, mesh)


def _scatter_file_shards(scan, counts: Sequence[int], mesh: Mesh,
                         smax: int) -> MeshBatch:
    from spark_rapids_tpu.parallel.mesh_batch import staged_column_arrays
    import pyarrow as pa
    schema = scan.output
    n_dev = int(mesh.devices.size)
    assign = _assign_files_to_shards(counts, n_dev)
    shard_rows = [sum(counts[i] for i in lst) for lst in assign]
    local_cap = max(bucket_capacity(max(shard_rows, default=0)), 1)
    devices = list(mesh.devices.flat)
    rows = np.zeros(n_dev, dtype=np.int32)
    # per column: list of per-device (data, validity, lengths) device arrays
    shard_cols: List[List] = [[] for _ in schema]
    for d in range(n_dev):
        files = [scan.files[i] for i in assign[d]]
        tables = list(scan.iter_tables_for_files(files)) if files else []
        if tables:
            table = (tables[0] if len(tables) == 1
                     else pa.concat_tables(tables)).combine_chunks()
        else:
            table = schema.to_pa().empty_table()
        n = table.num_rows
        if n != shard_rows[d]:
            # loud even under python -O: the local-capacity pad would
            # otherwise silently truncate or zero-pad live rows
            raise RuntimeError(
                f"shard {d} read {n} rows but metadata said "
                f"{shard_rows[d]} (stale file metadata?)")
        rows[d] = n
        for ci, f in enumerate(schema):
            data, validity, lengths = staged_column_arrays(
                f.dtype, table.column(ci), smax)
            pdata = np.zeros((local_cap,) + data.shape[1:], dtype=data.dtype)
            pdata[:n] = data
            pvalid = np.zeros(local_cap, dtype=bool)
            pvalid[:n] = validity
            plen = None
            if lengths is not None:
                plen = np.zeros(local_cap, dtype=np.int32)
                plen[:n] = lengths
            up = jax.device_put(
                (pdata, pvalid) + ((plen,) if plen is not None else ()),
                devices[d])
            shard_cols[ci].append(
                (up[0], up[1], up[2] if plen is not None else None))
        del table, tables  # free this shard's host copy before the next
    return _assemble_mesh_batch(schema, shard_cols, rows, mesh, local_cap)


def _scatter_assigned_shards(scan, assign: ScanShardAssignment, mesh: Mesh,
                             ctx: ExecContext) -> MeshBatch:
    """Execute a plan-time shard assignment: per shard, read its row groups,
    upload through the chunked overlapped pipeline (PR 3) LANDING DIRECTLY
    on the owning device (SingleDeviceSharding placement), then assemble the
    global data-axis arrays from the per-device buffers with
    ``make_array_from_single_device_arrays`` — zero extra data movement, no
    whole-table host buffer."""
    from jax.sharding import SingleDeviceSharding
    from spark_rapids_tpu import config as _cfg
    from spark_rapids_tpu.columnar.transfer import upload_table_conf
    if hasattr(scan, "device_dict"):
        # the assigned path uploads through DeviceBatch.from_arrow, which
        # handles encoded forms — mesh scans get the compressed link too
        scan.device_dict = ctx.conf.get(_cfg.PARQUET_DEVICE_DICT)
        scan.device_rle = (scan.device_dict
                           and ctx.conf.get(_cfg.PARQUET_DEVICE_RLE))
    schema = scan.output
    n_dev = int(mesh.devices.size)
    devices = list(mesh.devices.flat)
    local_cap = max(bucket_capacity(max(assign.rows, default=0)), 1)
    rows = np.zeros(n_dev, dtype=np.int32)
    shard_batches: List[DeviceBatch] = []
    from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
    for d in range(n_dev):
        place = SingleDeviceSharding(devices[d])
        # upload each unit table SEPARATELY (a shard's row groups may carry
        # different encodings — dictionary vs REE vs plain — which cannot
        # concatenate as host arrow tables), then combine ON THE DEVICE via
        # the shared concat program. PR 3 pipeline per table, landing
        # straight on the owning device; no u64 bits siblings — the mesh
        # exchange is an all_to_all, never the Pallas byte-packing kernel
        # those siblings exist for, so shipping them would waste
        # 8 B/row/DOUBLE-column of link bandwidth.
        parts = [upload_table_conf(t, ctx.string_max_bytes, ctx.conf,
                                   device=place, with_bits=False)
                 for t in (scan.iter_tables_for_units(assign.units[d])
                           if assign.units[d] else ())]
        if parts:
            db = concat_device_batches(parts, schema, ctx.string_max_bytes)
        else:
            db = upload_table_conf(schema.to_pa().empty_table(),
                                   ctx.string_max_bytes, ctx.conf,
                                   device=place, with_bits=False)
        if db.num_rows != assign.rows[d]:
            # must fail loudly even under python -O: a mismatch means the
            # file changed since plan time, and the capacity pad below
            # would otherwise silently truncate or zero-pad live rows
            raise RuntimeError(
                f"shard {d} read {db.num_rows} rows but the plan-time "
                f"assignment said {assign.rows[d]} (stale file metadata?)")
        rows[d] = db.num_rows
        shard_batches.append(db)
        del parts    # free this shard's intermediate batches
    shard_cols = [[(b.columns[ci].data, b.columns[ci].validity,
                    b.columns[ci].lengths) for b in shard_batches]
                  for ci in range(len(schema))]
    return _assemble_mesh_batch(schema, shard_cols, rows, mesh, local_cap)


class MeshFromDeviceExec(MeshExec):
    """Single-device batches -> mesh batch (scatter), the entry point for a
    small single-device intermediate (e.g. an aggregation result) joining a
    distributed pipeline."""

    def __init__(self, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), child.output, mesh)

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
        db = concat_device_batches(list(self.children[0].execute(ctx)),
                                   self.output, ctx.string_max_bytes)
        mb = scatter_device_batch(db, self.mesh)
        self.count_output(mb.num_rows)
        yield mb


class MeshGatherExec(MeshExec):
    """Mesh batch -> one single-device batch (shard-major order), the
    boundary back to single-device execution (collect, unsupported ops)."""

    is_mesh = False  # consumers see a plain DeviceBatch

    def __init__(self, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), child.output, mesh)
        self.placement = None    # gathered output: process default device

    def execute(self, ctx: ExecContext) -> Iterator[DeviceBatch]:
        for mb in self.children[0].execute(ctx):
            db = gather_mesh(mb)
            self.count_output(db.num_rows)
            yield db


# ------------------------------------------------------------------ row-parallel
class MeshProjectExec(MeshExec):
    def __init__(self, exprs: Tuple[Expression, ...], child: PhysicalExec,
                 mesh: Mesh):
        super().__init__((child,), output_schema(exprs), mesh)
        self.exprs = exprs

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        ordinals = reference_ordinals(self.exprs)
        for mb in self.children[0].execute(ctx):
            if ordinals is not None:
                # plain references select the shards' columns: no program
                out = MeshBatch(self.output,
                                tuple(mb.columns[i] for i in ordinals),
                                mb.rows_per_shard, self.mesh)
                self.count_output(out.num_rows)
                yield out
                continue
            cap = mb.local_capacity
            schema = self.children[0].output
            smax = ctx.string_max_bytes
            key = ("mproject", self.exprs, schema, cap, smax)

            def build(exprs=self.exprs, schema=schema, cap=cap, smax=smax):
                def fn(*flat):
                    colvs = unflatten_colvs(schema, flat)
                    ectx = _shard_ectx(colvs, cap, smax)
                    outs = []
                    for e in exprs:
                        v = e.eval(ectx)
                        data, validity, lengths = colv_to_column(v, jnp, cap,
                                                                 smax)
                        outs.append(data)
                        outs.append(validity)
                        if v.dtype is DType.STRING:
                            outs.append(lengths)
                    return tuple(outs)
                return fn

            nout = flat_len(self.output)
            fn = _shard_jit(self.mesh, key, build,
                            _specs(flat_len(schema)), _specs(nout))
            res = fn(*flatten_mesh(mb))
            out = MeshBatch(self.output, mesh_columns(self.output, res),
                            mb.rows_per_shard, self.mesh)
            self.count_output(out.num_rows)
            yield out


class MeshFilterExec(MeshExec):
    def __init__(self, condition: Expression, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), child.output, mesh)
        self.condition = condition

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        for mb in self.children[0].execute(ctx):
            cap = mb.local_capacity
            schema = self.output
            smax = ctx.string_max_bytes
            key = ("mfilter", self.condition, schema, cap, smax)

            def build(cond=self.condition, schema=schema, cap=cap, smax=smax):
                def fn(rows, *flat):
                    colvs = unflatten_colvs(schema, flat)
                    ectx = _shard_ectx(colvs, cap, smax)
                    pred = cond.eval(ectx)
                    alive = jnp.arange(cap, dtype=np.int32) < rows[0]
                    keep = jnp.logical_and(pred.data, pred.validity)
                    if keep.ndim == 0:
                        keep = jnp.broadcast_to(keep, (cap,))
                    keep = jnp.logical_and(keep, alive)
                    out_cols, n = bk.compact(jnp, keep, colvs, rows[0])
                    return (n[None].astype(np.int32),) + tuple(
                        flatten_colvs(out_cols))
                return fn

            nflat = flat_len(schema)
            fn = _shard_jit(self.mesh, key, build,
                            (P(DATA_AXIS),) + _specs(nflat),
                            (P(DATA_AXIS),) + _specs(nflat))
            res = fn(mb.rows_dev(), *flatten_mesh(mb))
            rows = np.asarray(res[0]).astype(np.int32)
            out = MeshBatch(schema, mesh_columns(schema, res[1:]), rows,
                            self.mesh)
            out = _maybe_shrink(out)
            self.count_output(out.num_rows)
            yield out


def _maybe_shrink(mb: MeshBatch) -> MeshBatch:
    """Re-bucket the local capacity after a selective op (the _to_batch shrink
    analog): all shards share one static shape, so the bucket follows the
    LARGEST shard."""
    max_rows = int(mb.rows_per_shard.max(initial=0))
    new_cap = max(bucket_capacity(max_rows), 1)
    cap = mb.local_capacity
    if new_cap >= cap:
        return mb
    key = ("mshrink", mb.mesh, mb.schema, cap, new_cap,
           tuple(c.data.shape[1:] for c in mb.columns))

    def build(cap=cap, new_cap=new_cap):
        def fn(*flat):
            return tuple(a[:new_cap] for a in flat)
        return fn

    n = len(flatten_mesh(mb))
    fn = _shard_jit(mb.mesh, key, build, _specs(n), _specs(n))
    res = fn(*flatten_mesh(mb))
    return MeshBatch(mb.schema, mesh_columns(mb.schema, res),
                     mb.rows_per_shard, mb.mesh)


# ------------------------------------------------------------------ repartition
def _mesh_repartition(mb: MeshBatch, op_key: Tuple, pid_builder,
                      extra_flat: Tuple = (), n_extra: int = 0,
                      smax: int = 256) -> MeshBatch:
    """Generic ICI repartition: two programs (count, exchange).

    ``pid_builder(colvs, ectx)`` returns int32[local_cap] destination shards.
    The count pre-pass sizes the per-(source,dest) chunk so the exchange can
    NEVER clamp rows away (the skew-overflow guard the VERDICT called for):
    chunk capacity is the bucketed max over the actual counts matrix.
    Extra (replicated) inputs — e.g. range bounds — ride along as ``extra_flat``
    with ``n_extra`` flat slots.

    Relationship to shuffle/ici.py build_ici_repartition: same exchange
    kernel shape (stable argsort by pid, fixed-capacity chunks, all_to_all,
    compaction), different overflow strategy — ici.py takes caller-computed
    pids and returns a clamp flag for its retry driver; this one fuses the
    pid computation into the program and pre-sizes the chunk so overflow is
    impossible. A kernel-level fix in one belongs in the other too.
    """
    mesh, n_dev, cap = mb.mesh, mb.n_dev, mb.local_capacity
    schema = mb.schema
    nflat = flat_len(schema)
    rows = mb.rows_dev()
    # self-sufficient key: everything the traced exchange observes beyond
    # op_key rides in the key itself instead of relying on every caller's
    # op_key discipline (R016 — schema/cap/n_dev/smax specialize the trace)
    base_key = op_key + (schema, cap, n_dev, smax, n_extra)

    def build_count():
        def fn(rows, *args):
            extra = args[:n_extra]
            colvs = unflatten_colvs(schema, args[n_extra:])
            ectx = _shard_ectx(colvs, cap, smax)
            live = jnp.arange(cap, dtype=np.int32) < rows[0]
            pid = jnp.where(live, pid_builder(colvs, ectx, extra), n_dev)
            counts = jnp.sum(
                pid[None, :] == jnp.arange(n_dev, dtype=np.int32)[:, None],
                axis=1, dtype=np.int32)
            return counts
        return fn

    def build_exchange(chunk_cap, out_cap):
        def fn(rows, *args):
            extra = args[:n_extra]
            colvs = unflatten_colvs(schema, args[n_extra:])
            ectx = _shard_ectx(colvs, cap, smax)
            live = jnp.arange(cap, dtype=np.int32) < rows[0]
            pid = jnp.where(live, pid_builder(colvs, ectx, extra), n_dev)
            order = jnp.argsort(pid, stable=True)
            sorted_pid = pid[order]
            counts = jnp.sum(
                sorted_pid[None, :]
                == jnp.arange(n_dev, dtype=np.int32)[:, None],
                axis=1, dtype=np.int32)
            starts = jnp.concatenate(
                [jnp.zeros((1,), np.int32),
                 jnp.cumsum(counts)[:-1].astype(np.int32)])
            offs = jnp.arange(chunk_cap, dtype=np.int32)[None, :]
            idx = jnp.clip(starts[:, None] + offs, 0, cap - 1)
            within = offs < counts[:, None]
            gidx = order[idx]

            def a2a(x):
                return jax.lax.all_to_all(x, DATA_AXIS, split_axis=0,
                                          concat_axis=0, tiled=True)

            recv_counts = a2a(counts)
            recv_live = (jnp.arange(chunk_cap, dtype=np.int32)[None, :]
                         < recv_counts[:, None]).reshape(n_dev * chunk_cap)
            corder = jnp.argsort(~recv_live, stable=True)[:out_cap]
            total = jnp.sum(recv_counts).astype(np.int32)
            outs = [total[None]]
            for v in colvs:
                data = a2a(v.data[gidx])
                flat_shape = (n_dev * chunk_cap,) + data.shape[2:]
                outs.append(data.reshape(flat_shape)[corder])
                validity = a2a(v.validity[gidx] & within)
                outs.append(validity.reshape(n_dev * chunk_cap)[corder])
                if v.lengths is not None:
                    lens = a2a(jnp.where(within, v.lengths[gidx], 0))
                    outs.append(lens.reshape(n_dev * chunk_cap)[corder])
            return tuple(outs)
        return fn

    fnc = _shard_jit(mesh, base_key + ("count",), build_count,
                     (P(DATA_AXIS),) + _specs(n_extra, P()) + _specs(nflat),
                     P(DATA_AXIS))
    # the two child spans run from the program's call to the host's read of
    # its result, a read the sizing and the row counts need anyway
    with _tracing.span("mesh.exchange", _tracing.LAYER_SHUFFLE) as exchange:
        with _tracing.span("mesh.exchange.count", _tracing.LAYER_SHUFFLE):
            cmat = np.asarray(
                fnc(rows, *extra_flat, *flatten_mesh(mb))).reshape(
                    n_dev, n_dev)
        # observability: the count pre-pass result that sized this exchange
        # (the MapOutputStatistics role — skew/capacity-growth tests assert
        # on it)
        stats = exchange_stats(op_key[0], cmat, mb.row_bytes, cap)
        chunk_cap, out_cap = stats["chunk_cap"], stats["out_cap"]
        EXCHANGE_STATS.append(stats)
        if len(EXCHANGE_STATS) > 256:
            del EXCHANGE_STATS[:128]
        if exchange is not None:
            exchange.note(**stats)
        fne = _shard_jit(
            mesh, base_key + ("exchange", chunk_cap, out_cap),
            functools.partial(build_exchange, chunk_cap, out_cap),
            (P(DATA_AXIS),) + _specs(n_extra, P()) + _specs(nflat),
            (P(DATA_AXIS),) + _specs(nflat))
        with _tracing.span("mesh.exchange.move", _tracing.LAYER_SHUFFLE):
            res = fne(rows, *extra_flat, *flatten_mesh(mb))
            new_rows = np.asarray(res[0]).astype(np.int32)
    assert int(new_rows.sum()) == mb.num_rows, (
        f"mesh repartition lost rows: {new_rows.sum()} != {mb.num_rows}")
    return MeshBatch(schema, mesh_columns(schema, res[1:]), new_rows, mesh)


def exchange_stats(op: str, cmat: np.ndarray, row_bytes: int,
                   in_cap: int) -> dict:
    """What one repartition moves, from its count matrix (``cmat[s, d]``:
    live rows of shard s whose destination is shard d): the capacities the
    exchange is sized with, and the ``args`` of its ``mesh.exchange`` span.

    ``bytes`` is the least that must cross between devices (the rows off
    the diagonal, at the row's bytes); ``wire_bytes`` what ``all_to_all``
    ships for them: every (source, destination) pair of different shards a
    chunk of ``chunk_cap`` rows, live or padding; ``max_shard_bytes`` the
    most of ``bytes`` that any one shard sends or receives."""
    n_dev = cmat.shape[0]
    cmat = cmat.astype(np.int64)
    recv = cmat.sum(axis=0)
    stay = np.diagonal(cmat)
    sent_off, recv_off = cmat.sum(axis=1) - stay, recv - stay
    chunk_cap = max(bucket_capacity(int(cmat.max(initial=0))), 1)
    return {
        "op": op, "rows": int(cmat.sum()),
        "moved_rows": int(sent_off.sum()),
        "bytes": int(sent_off.sum()) * row_bytes,
        "wire_bytes": n_dev * (n_dev - 1) * chunk_cap * row_bytes,
        "max_shard_bytes": int(max(sent_off.max(initial=0),
                                   recv_off.max(initial=0))) * row_bytes,
        "chunk_cap": chunk_cap,
        "out_cap": max(bucket_capacity(int(recv.max(initial=0))), 1),
        "in_cap": in_cap, "recv_max": int(recv.max(initial=0)),
        "recv_min": int(recv.min())}


def _hash_pid_builder(keys: Tuple[Expression, ...], n_dev: int):
    from spark_rapids_tpu.execs.exchange_execs import hash_partition_ids

    def pid(colvs, ectx, extra):
        kvs = [e.eval(ectx) for e in keys]
        return hash_partition_ids(jnp, kvs, ectx.capacity, n_dev)
    return pid


class MeshShuffleExchangeExec(MeshExec):
    """Explicit repartition over the mesh (the GpuShuffleExchangeExec +
    accelerated-shuffle composition, collapsed into one ICI all_to_all)."""

    def __init__(self, partitioning, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), child.output, mesh)
        self.partitioning = partitioning

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        from spark_rapids_tpu.execs.exchange_execs import (HashPartitioning,
                                                           RangePartitioning,
                                                           RoundRobinPartitioning)
        part = self.partitioning
        n_dev = int(self.mesh.devices.size)
        for mb in self.children[0].execute(ctx):
            if isinstance(part, RangePartitioning):
                out = _range_repartition(mb, part.orders,
                                         ctx.string_max_bytes)
                self.count_output(out.num_rows)
                yield out
                continue
            if isinstance(part, HashPartitioning):
                builder = _hash_pid_builder(part.keys, n_dev)
            elif isinstance(part, RoundRobinPartitioning):
                def builder(colvs, ectx, extra, n_dev=n_dev):
                    i = jax.lax.axis_index(DATA_AXIS).astype(np.int32)
                    return ((jnp.arange(ectx.capacity, dtype=np.int32) + i)
                            % np.int32(n_dev))
            else:
                raise NotImplementedError(
                    f"mesh exchange for {type(part).__name__}")
            out = _mesh_repartition(
                mb, ("mexchange", part, mb.schema, mb.local_capacity),
                builder, smax=ctx.string_max_bytes)
            self.count_output(out.num_rows)
            yield out


# ------------------------------------------------------------------ expand
class MeshExpandExec(MeshExec):
    """Expand (rollup/cube/grouping sets) per shard: every projection list
    evaluates against the shard's rows and the results stack locally —
    no cross-shard movement at all (GpuExpandExec.scala runs the same
    projections per task; here a task is a shard). Output order per shard is
    projection-major, matching the single-device exec's batch-per-projection
    order."""

    def __init__(self, projections, child: PhysicalExec, output: Schema,
                 mesh: Mesh):
        super().__init__((child,), output, mesh)
        self.projections = projections

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        mb = self._one_child_batch(ctx)
        cap = mb.local_capacity
        schema = self.children[0].output
        smax = ctx.string_max_bytes
        nproj = len(self.projections)
        max_rows = int(mb.rows_per_shard.max(initial=0))
        # never above nproj*cap (the stacked array length): key and shape
        # must agree for the compile-cache bucketing to work
        out_cap = max(min(bucket_capacity(nproj * max_rows), nproj * cap), 1)
        key = ("mexpand", self.projections, schema, cap, out_cap, smax)

        def build(projs=self.projections, schema=schema, cap=cap,
                  out_cap=out_cap, smax=smax):
            def fn(rows, *flat):
                colvs = unflatten_colvs(schema, flat)
                ectx = _shard_ectx(colvs, cap, smax)
                live = jnp.arange(cap, dtype=np.int32) < rows[0]
                # per projection: one (data, validity, lengths) per out column
                parts = [[colv_to_column(e.eval(ectx), jnp, cap, smax)
                          for e in plist] for plist in projs]
                glive = jnp.tile(live, len(projs))
                order = jnp.argsort(~glive, stable=True)[:out_cap]
                res = []
                for ci in range(len(parts[0])):
                    datas = [p[ci][0] for p in parts]
                    if datas[0].ndim == 2:  # strings: pad to the max width
                        w = max(d.shape[1] for d in datas)
                        datas = [jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                                 for d in datas]
                    res.append(jnp.concatenate(datas)[order])
                    res.append(jnp.concatenate(
                        [p[ci][1] for p in parts])[order])
                    if parts[0][ci][2] is not None:
                        res.append(jnp.concatenate(
                            [p[ci][2] for p in parts])[order])
                n = (rows[0] * np.int32(len(projs))).astype(np.int32)
                return (n[None],) + tuple(res)
            return fn

        nout = flat_len(self.output)
        fn = _shard_jit(self.mesh, key, build,
                        (P(DATA_AXIS),) + _specs(flat_len(schema)),
                        (P(DATA_AXIS),) + _specs(nout))
        res = fn(mb.rows_dev(), *flatten_mesh(mb))
        rows = np.asarray(res[0]).astype(np.int32)
        out = MeshBatch(self.output, mesh_columns(self.output, res[1:]),
                        rows, self.mesh)
        self.count_output(out.num_rows)
        yield out


class MeshGenerateExec(MeshExpandExec):
    """Explode/posexplode per shard — the generate-as-expand lowering
    (GpuGenerateExec.scala), sharded."""


# ------------------------------------------------------------------ window
class MeshWindowExec(MeshExec):
    """Distributed window: hash-repartition by the window partition keys so
    every partition group lands whole on one shard, then evaluate the shared
    sorted-window kernel per shard (GpuWindowExec.scala distributed by
    Spark's required child distribution — ClusteredDistribution(part_keys) —
    which is exactly a key-hash exchange)."""

    def __init__(self, wexprs: Tuple[Expression, ...], child: PhysicalExec,
                 mesh: Mesh):
        from spark_rapids_tpu.execs.window_execs import window_output_schema
        super().__init__((child,), window_output_schema(child.output, wexprs),
                         mesh)
        self.wexprs = wexprs

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        from spark_rapids_tpu.execs.window_execs import evaluate_window
        mb = self._one_child_batch(ctx)
        n_dev = mb.n_dev
        smax = ctx.string_max_bytes
        first = (self.wexprs[0].c if isinstance(self.wexprs[0], Alias)
                 else self.wexprs[0])
        part_exprs = tuple(first.part_keys)
        assert part_exprs, "unpartitioned window must gather (rewrite bug)"
        if n_dev > 1:
            mb = _mesh_repartition(
                mb, ("mwindow_part", part_exprs, mb.schema,
                     mb.local_capacity),
                _hash_pid_builder(part_exprs, n_dev), smax=smax)
        cap = mb.local_capacity
        schema = self.children[0].output
        key = ("mwindow", self.wexprs, schema, cap, smax)

        def build(wexprs=self.wexprs, schema=schema, cap=cap, smax=smax):
            def fn(rows, *flat):
                colvs = unflatten_colvs(schema, flat)
                out = evaluate_window(jnp, colvs, wexprs, rows[0], cap, smax)
                return tuple(flatten_colvs(out))
            return fn

        nout = flat_len(self.output)
        fn = _shard_jit(self.mesh, key, build,
                        (P(DATA_AXIS),) + _specs(flat_len(schema)),
                        _specs(nout))
        res = fn(mb.rows_dev(), *flatten_mesh(mb))
        out = MeshBatch(self.output, mesh_columns(self.output, res),
                        mb.rows_per_shard, self.mesh)
        self.count_output(out.num_rows)
        yield out


# ------------------------------------------------------------------ writes
class MeshWriteFilesExec(MeshExec):
    """Distributed file write: each shard's rows download and encode as one
    writer task (one part file per shard, like one file per Spark task —
    GpuDataWritingCommandExec.scala:94 / GpuFileFormatWriter), sharing the
    single commit protocol. No gather: per-shard host staging only."""

    def __init__(self, spec, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), Schema([]), mesh)
        self.spec = spec
        self.placement = None    # produces no batches
        from spark_rapids_tpu.io.writer import WriteStats
        self.stats = WriteStats()

    def execute(self, ctx: ExecContext):
        import time
        from spark_rapids_tpu.io.write_exec import (make_task_writer,
                                                    total_output_bytes)
        from spark_rapids_tpu.io.writer import (DynamicPartitionDataWriter,
                                                FileCommitProtocol,
                                                WriteStats,
                                                resolve_save_mode)
        t0 = time.perf_counter()
        self.stats = WriteStats()
        if resolve_save_mode(self.spec.path, self.spec.mode) is None:
            return
        mb = self._one_child_batch(ctx)
        committer = FileCommitProtocol(self.spec.path)
        committer.setup_job()
        child_schema = self.children[0].output
        partitions_seen = set()
        try:
            for d, table in enumerate(_shard_tables(mb)):
                writer = make_task_writer(self.spec, child_schema, committer,
                                          d)
                if table.num_rows:
                    writer.write(table)
                writer.close()
                self.stats.num_files += writer.files_written
                self.stats.num_rows += writer.rows_written
                if isinstance(writer, DynamicPartitionDataWriter):
                    partitions_seen |= writer.partitions_seen
        except Exception:
            committer.abort_job()
            raise
        committer.commit_job()
        self.stats.num_partitions = len(partitions_seen)
        self.stats.num_bytes = total_output_bytes(self.spec.path)
        self.stats.write_time_s += time.perf_counter() - t0
        return
        yield  # pragma: no cover — generator


def _shard_tables(mb: MeshBatch):
    """Per-shard arrow tables, pulling ONE shard's buffers to host at a time
    (per-task download; never the whole mesh batch)."""
    from spark_rapids_tpu.execs.cpu_execs import _colvs_to_host
    dev_order = {d: i for i, d in enumerate(mb.mesh.devices.flat)}
    for d in range(mb.n_dev):
        n = int(mb.rows_per_shard[d])
        cols = []
        for c in mb.columns:
            parts = {}
            for nm, arr in (("data", c.data), ("validity", c.validity),
                            ("lengths", c.lengths)):
                if arr is None:
                    parts[nm] = None
                    continue
                shard = next(s for s in arr.addressable_shards
                             if dev_order[s.device] == d)
                parts[nm] = np.asarray(shard.data)
            cols.append(ColV(c.dtype, parts["data"], parts["validity"],
                             parts["lengths"]))
        yield _colvs_to_host(mb.schema, cols, n).to_arrow()


# ------------------------------------------------------------------ aggregate
class MeshHashAggregateExec(MeshExec):
    """Distributed aggregation, mesh in -> mesh out (post-agg subtrees stay
    distributed). Three stages:

    1. Per-shard partial aggregation (Partial mode, aggregate.scala) with the
       same grouping-mode escalation as the single-device exec: sort-free
       one-hot -> hash-ordered -> exact lexsort, each re-run only on a flagged
       collision/overflow (ORed across the mesh).
    2. One host sync of the per-shard partial group counts picks the merge
       strategy.
    3a. Small groupings: all-gather the partials over ICI, merge replicated
        (Final mode), and each shard keeps a contiguous slice of the merged
        groups — the output is already evenly mesh-sharded.
    3b. Large groupings (total partials > sql.mesh.aggRepartitionThreshold):
        hash-repartition the PARTIAL key+buffer rows by key over ICI
        (all_to_all) so equal keys collocate, then each shard merges only its
        own key range — the reference's partial/final split over a hash
        exchange (aggregate.scala:227 + GpuHashPartitioning), which scales to
        arbitrary group cardinality with no replicated blowup.
    """

    def __init__(self, grouping: Tuple[Expression, ...],
                 aggregates: Tuple[Expression, ...], child: PhysicalExec,
                 output: Schema, mesh: Mesh,
                 pre_filter: Optional[Expression] = None):
        super().__init__((child,), output, mesh)
        self.grouping = grouping
        self.aggregates = aggregates
        self.pre_filter = pre_filter

    def _partial_schema(self, fns) -> Schema:
        from spark_rapids_tpu.columnar.dtypes import Field
        fields = [Field(f"_k{i}", e.dtype(), e.nullable())
                  for i, e in enumerate(self.grouping)]
        for fi, fn in enumerate(fns):
            for bi, spec in enumerate(fn.buffer_specs()):
                fields.append(Field(f"_b{fi}_{bi}", spec.dtype, True))
        return Schema(fields)

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        from spark_rapids_tpu.ops.aggregate import (group_aggregate,
                                                    grouping_modes,
                                                    reduce_form)
        from spark_rapids_tpu import config as cfg
        mb = self._one_child_batch(ctx)
        cap = mb.local_capacity
        schema = self.children[0].output
        smax = ctx.string_max_bytes
        n_dev = mb.n_dev
        fns = tuple(a.c if isinstance(a, Alias) else a
                    for a in self.aggregates)
        pschema = self._partial_schema(fns)
        npartial = flat_len(pschema)
        key = ("magg", self.grouping, fns, self.pre_filter, schema, cap, smax)
        in_specs = (P(DATA_AXIS),) + _specs(flat_len(schema))

        # ---- stage 1: per-shard partial aggregation (escalating modes) ----
        def build_partial(mode):
            def make(keys_=self.grouping, fns=fns, schema=schema, cap=cap,
                     smax=smax, pre=self.pre_filter, mode=mode):
                def fn(rows, *flat):
                    colvs = unflatten_colvs(schema, flat)
                    ectx = _shard_ectx(colvs, cap, smax)
                    mask = None
                    if pre is not None:
                        p = pre.eval(ectx)
                        mask = jnp.logical_and(p.data, p.validity)
                        if mask.ndim == 0:
                            mask = jnp.broadcast_to(mask, (cap,))
                    res = group_aggregate(
                        jnp, ectx, keys_, fns, rows[0], cap, evaluate=False,
                        grouping=mode, extra_mask=mask)
                    key_cols, buf_cols, ng = res[:3]
                    out = (ng[None].astype(np.int32),) + tuple(
                        flatten_colvs(list(key_cols) + list(buf_cols)))
                    if mode in ("hash", "onehot"):
                        # any shard's collision poisons the whole result:
                        # OR across the mesh, replicated to every device
                        bad = jax.lax.psum(res[3].astype(np.int32),
                                           DATA_AXIS) > 0
                        out = out + (bad,)
                    return out
                return fn
            return make

        modes = (grouping_modes(self.grouping, fns) if self.grouping
                 else ["sort"])
        for mode in modes:
            fast = mode in ("hash", "onehot")
            fn = _shard_jit(
                self.mesh, key + ("partial", mode), build_partial(mode),
                in_specs,
                (P(DATA_AXIS),) + _specs(npartial) + ((P(),) if fast else ()))
            # one span per attempted mode, as the single-device aggregate's:
            # the program's call to the host's read of the flag or, on the
            # attempt that is kept, of the shards' partial group counts
            # (``groups`` is their sum); both reads were here before the span
            with _tracing.span("agg.attempt", _tracing.LAYER_EXEC) as attempt:
                res = fn(mb.rows_dev(), *flatten_mesh(mb))
                # justified sync: the mesh-wide collision flag decides
                # whether this grouping mode's result stands or the next
                # mode runs — one scalar per attempted mode
                flagged = fast and bool(res[-1])  # tpu-lint: disable=R002
                if not flagged:
                    ng = np.asarray(res[0]).astype(np.int32)
                    total = int(ng.sum())
                if attempt is not None:
                    attempt.note(mode=mode, flagged=flagged, capacity=cap,
                                 keys=len(self.grouping),
                                 reduce=reduce_form(mode, cap),
                                 **({} if flagged else {"groups": total}))
            if not flagged:
                break
        partial = MeshBatch(
            pschema, mesh_columns(pschema, res[1:-1] if fast else res[1:]),
            ng, self.mesh)

        threshold = ctx.conf.get(cfg.MESH_AGG_REPARTITION_THRESHOLD)
        if self.grouping and total > threshold:
            out = self._merge_repartitioned(partial, fns, smax)
        else:
            out = self._merge_all_gather(partial, fns, total, smax)
        self.count_output(out.num_rows)
        yield out

    # ---- stage 3a: all-gather + replicated merge + slice ------------------
    def _merge_all_gather(self, partial: MeshBatch, fns, total: int,
                          smax: int) -> MeshBatch:
        from spark_rapids_tpu.ops.aggregate import merge_aggregate
        n_dev = partial.n_dev
        pcap = partial.local_capacity
        pschema = partial.schema
        nkeys = len(self.grouping)
        # `total` (sum of per-shard partial counts) upper-bounds the merged
        # group count, so `per` is a safe static slice stride; the true
        # merged total comes back from the program and trims rows_per_shard
        per = -(-total // n_dev) if total else 0
        out_cap = max(bucket_capacity(per), 1)
        # n_dev is keyed: the merge gathers pcap * n_dev rows, so meshes
        # of different device counts must not share a program (R016)
        key = ("magg_merge_ag", self.grouping, fns, pschema, pcap, out_cap,
               smax, per, n_dev)

        def build(fns=fns, pschema=pschema, pcap=pcap, out_cap=out_cap,
                  nkeys=nkeys, n_dev=n_dev, per=per):
            def fn(rows, *flat):
                colvs = unflatten_colvs(pschema, flat)
                galive = jax.lax.all_gather(
                    jnp.arange(pcap, dtype=np.int32) < rows[0], DATA_AXIS,
                    tiled=True)
                g = [_gather_colv(v) for v in colvs]
                out_keys, out_res, merged_n = merge_aggregate(
                    jnp, g[:nkeys], g[nkeys:], fns, galive, pcap * n_dev)
                d = jax.lax.axis_index(DATA_AXIS).astype(np.int32)
                idx = jnp.clip(d * np.int32(per)
                               + jnp.arange(out_cap, dtype=np.int32),
                               0, pcap * n_dev - 1)
                outs = [merged_n.astype(np.int32)]
                for v in out_keys + out_res:
                    outs.append(v.data[idx])
                    outs.append(v.validity[idx])
                    if v.lengths is not None:
                        outs.append(v.lengths[idx])
                return tuple(outs)
            return fn

        nout = flat_len(self.output)
        fn = _shard_jit(self.mesh, key, build,
                        (P(DATA_AXIS),) + _specs(flat_len(pschema)),
                        (P(),) + _specs(nout))
        res = fn(partial.rows_dev(), *flatten_mesh(partial))
        merged_total = int(res[0])
        rows = np.asarray([max(0, min(per, merged_total - d * per))
                           for d in range(n_dev)], dtype=np.int32)
        return MeshBatch(self.output, mesh_columns(self.output, res[1:]),
                         rows, self.mesh)

    # ---- stage 3b: hash repartition partials + per-shard merge ------------
    def _merge_repartitioned(self, partial: MeshBatch, fns,
                             smax: int) -> MeshBatch:
        from spark_rapids_tpu.ops.aggregate import merge_aggregate
        from spark_rapids_tpu.exprs.core import BoundReference
        n_dev = partial.n_dev
        pschema = partial.schema
        nkeys = len(self.grouping)
        key_refs = tuple(
            BoundReference(i, f.dtype, f.nullable)
            for i, f in enumerate(pschema.fields[:nkeys]))
        partial = _mesh_repartition(
            partial, ("magg_part", key_refs, pschema,
                      partial.local_capacity),
            _hash_pid_builder(key_refs, n_dev), smax=smax)
        pcap = partial.local_capacity
        key = ("magg_merge_part", self.grouping, fns, pschema, pcap, smax)

        def build(fns=fns, pschema=pschema, pcap=pcap, nkeys=nkeys):
            def fn(rows, *flat):
                colvs = unflatten_colvs(pschema, flat)
                alive_n = rows[0]
                out_keys, out_res, ng = merge_aggregate(
                    jnp, colvs[:nkeys], colvs[nkeys:], fns, alive_n, pcap)
                outs = [ng[None].astype(np.int32)]
                for v in out_keys + out_res:
                    outs.extend(flatten_colvs([v]))
                return tuple(outs)
            return fn

        nout = flat_len(self.output)
        fn = _shard_jit(self.mesh, key, build,
                        (P(DATA_AXIS),) + _specs(flat_len(pschema)),
                        (P(DATA_AXIS),) + _specs(nout))
        res = fn(partial.rows_dev(), *flatten_mesh(partial))
        rows = np.asarray(res[0]).astype(np.int32)
        out = MeshBatch(self.output, mesh_columns(self.output, res[1:]),
                        rows, self.mesh)
        return _maybe_shrink(out)


def _mesh_batch_bytes(mb: MeshBatch) -> int:
    """Actual data bytes of the LIVE rows (per-row width x true row count) —
    the MapOutputStatistics role for runtime join adaptivity."""
    return int(mb.num_rows) * mb.row_bytes


def _gather_colv(v: ColV) -> ColV:
    data = jax.lax.all_gather(v.data, DATA_AXIS, tiled=True)
    validity = jax.lax.all_gather(v.validity, DATA_AXIS, tiled=True)
    lengths = (jax.lax.all_gather(v.lengths, DATA_AXIS, tiled=True)
               if v.lengths is not None else None)
    return ColV(v.dtype, data, validity, lengths)


# ------------------------------------------------------------------ joins
class MeshHashJoinBase(MeshExec):
    def __init__(self, left: PhysicalExec, right: PhysicalExec, how: str,
                 left_keys, right_keys, output: Schema, mesh: Mesh,
                 condition: Optional[Expression] = None,
                 build_side: str = "right"):
        super().__init__((left, right), output, mesh)
        self.how = how
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.condition = condition
        self.build_side = build_side

    @property
    def includes_right_columns(self) -> bool:
        return self.how not in ("left_semi", "left_anti")

    def _local_join(self, ctx: ExecContext, lb_flat, rb_flat, l_rows, r_rows,
                    lschema: Schema, rschema: Schema, S: int, B: int,
                    r_replicated: bool, l_replicated: bool = False
                    ) -> MeshBatch:
        """Per-shard two-phase join under shard_map. A ``*_replicated`` side
        is a broadcast build (same rows on every shard); the other side is
        sharded, so each of its rows is evaluated on exactly one shard and
        the per-shard outputs union to the full join."""
        mesh = self.mesh
        smax = ctx.string_max_bytes
        lspec = P() if l_replicated else P(DATA_AXIS)
        rspec = P() if r_replicated else P(DATA_AXIS)
        nl, nr = flat_len(lschema), flat_len(rschema)
        key1 = ("mjoin_size", self.how, self.left_keys, self.right_keys,
                lschema, rschema, S, B, smax, r_replicated, l_replicated)

        def build1(how=self.how, lkeys=self.left_keys, rkeys=self.right_keys,
                   lschema=lschema, rschema=rschema, S=S, B=B, smax=smax):
            def fn(l_rows, r_rows, *flat):
                l_cols = unflatten_colvs(lschema, flat[:nl])
                r_cols = unflatten_colvs(rschema, flat[nl:])
                l_alive = jnp.arange(S, dtype=np.int32) < l_rows[0]
                r_alive = jnp.arange(B, dtype=np.int32) < r_rows[0]
                lectx = _shard_ectx(l_cols, S, smax)
                rectx = _shard_ectx(r_cols, B, smax)
                lk = [e.eval(lectx) for e in lkeys]
                rk = [e.eval(rectx) for e in rkeys]
                sized = jk.join_size(jnp, lk, rk, l_alive, r_alive, how)
                return (sized["emit_counts"], sized["emit_offsets"],
                        sized["total"][None], sized["border"],
                        sized["start_b"], sized["matches_l"])
            return fn

        fn1 = _shard_jit(mesh, key1, build1,
                         (lspec, rspec) + _specs(nl, lspec)
                         + _specs(nr, rspec),
                         _specs(6))
        res1 = fn1(l_rows, r_rows, *lb_flat, *rb_flat)
        totals = np.asarray(res1[2]).astype(np.int64)
        out_cap = max(bucket_capacity(int(totals.max(initial=0))), 1)

        key2 = ("mjoin_gather", self.how, lschema, rschema, S, B, out_cap,
                self.condition, self.includes_right_columns, smax,
                r_replicated, l_replicated)

        def build2(how=self.how, lschema=lschema, rschema=rschema, S=S, B=B,
                   out_cap=out_cap, cond=self.condition,
                   inc_right=self.includes_right_columns, smax=smax):
            def fn(emit_counts, emit_offsets, total, border, start_b,
                   matches_l, *flat):
                l_cols = unflatten_colvs(lschema, flat[:nl])
                r_cols = unflatten_colvs(rschema, flat[nl:])
                sized = dict(emit_counts=emit_counts,
                             emit_offsets=emit_offsets, total=total[0],
                             border=border, start_b=start_b,
                             matches_l=matches_l)
                lrow, lvalid, rrow, rvalid, _ = jk.join_gather(
                    jnp, sized, S, B, out_cap, how)
                r_out = r_cols if inc_right else []
                out_cols = jk.gather_join_output(jnp, l_cols, r_out, lrow,
                                                 lvalid, rrow, rvalid)
                n = total[0]
                if cond is not None:
                    ectx = EvalCtx(jnp, out_cols, out_cap, smax)
                    pred = cond.eval(ectx)
                    keep = jnp.logical_and(
                        jnp.logical_and(pred.data, pred.validity),
                        jnp.arange(out_cap, dtype=np.int64) < n)
                    out_cols, n = bk.compact(jnp, keep, out_cols, n)
                return (n[None].astype(np.int32),) + tuple(
                    flatten_colvs(out_cols))
            return fn

        nout = flat_len(self.output)
        fn2 = _shard_jit(mesh, key2, build2,
                         _specs(6) + _specs(nl, lspec) + _specs(nr, rspec),
                         (P(DATA_AXIS),) + _specs(nout))
        res2 = fn2(*res1, *lb_flat, *rb_flat)
        rows = np.asarray(res2[0]).astype(np.int32)
        out = MeshBatch(self.output, mesh_columns(self.output, res2[1:]),
                        rows, mesh)
        return _maybe_shrink(out)

    def _broadcast_join(self, ctx: ExecContext, stream: MeshBatch,
                        db: DeviceBatch, bi: int) -> MeshBatch:
        """Replicate the single-device build batch ``db`` across the mesh
        (side ``bi``) and join against the sharded stream — the one
        broadcast-join call convention, shared by the planned broadcast exec
        and the adaptive switch."""
        from spark_rapids_tpu.execs.tpu_execs import _flatten
        rep = replicate_device_batch(db, self.mesh)
        rep_rows = jax.device_put(
            np.asarray([db.num_rows], dtype=np.int32),
            NamedSharding(self.mesh, P()))
        if bi == 1:
            return self._local_join(
                ctx, flatten_mesh(stream), _flatten(rep),
                stream.rows_dev(), rep_rows,
                self.children[0].output, self.children[1].output,
                stream.local_capacity, db.capacity, r_replicated=True)
        return self._local_join(
            ctx, _flatten(rep), flatten_mesh(stream),
            rep_rows, stream.rows_dev(),
            self.children[0].output, self.children[1].output,
            db.capacity, stream.local_capacity,
            r_replicated=False, l_replicated=True)


class MeshShuffledHashJoinExec(MeshHashJoinBase):
    """Shuffled equi-join: both sides hash-repartitioned by join key over the
    mesh (one all_to_all each), then joined per shard (the
    GpuShuffledHashJoinExec + RapidsCachingWriter/Reader path, with the whole
    exchange riding ICI).

    Adaptive (sql.adaptive.enabled): the join sees both sides' TRUE
    materialized sizes before any exchange compiles — when a legal build
    side lands under broadcastJoinThreshold, the join switches to the
    broadcast form (replicate the small side, zero stream movement), the
    GpuCustomShuffleReaderExec + DynamicJoinSelection payoff without a
    host-side re-planning pass."""

    #: set by execute() when AQE switched this join to broadcast (plan
    #: introspection for tests/explain)
    adapted_broadcast = False

    def _adaptive_broadcast(self, ctx: ExecContext, lb: MeshBatch,
                            rb: MeshBatch) -> Optional[MeshBatch]:
        from spark_rapids_tpu import config as cfg_
        if not ctx.conf.get(cfg_.ADAPTIVE_ENABLED):
            return None
        from spark_rapids_tpu.execs.join_execs import legal_broadcast_sides
        threshold = ctx.conf.get(cfg_.BROADCAST_JOIN_THRESHOLD)
        for bi in legal_broadcast_sides(self.how):
            bb = (lb, rb)[bi]
            if _mesh_batch_bytes(bb) > threshold:
                continue
            stream = (lb, rb)[1 - bi]
            out = self._broadcast_join(ctx, stream, gather_mesh(bb), bi)
            self.adapted_broadcast = True
            return out
        return None

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        n_dev = int(self.mesh.devices.size)
        lb = self._one_child_batch(ctx, 0)
        rb = self._one_child_batch(ctx, 1)
        smax = ctx.string_max_bytes
        adapted = self._adaptive_broadcast(ctx, lb, rb)
        if adapted is not None:
            self.count_output(adapted.num_rows)
            yield adapted
            return
        lb = _mesh_repartition(
            lb, ("mjoin_lpart", tuple(self.left_keys), lb.schema,
                 lb.local_capacity),
            _hash_pid_builder(tuple(self.left_keys), n_dev), smax=smax)
        rb = _mesh_repartition(
            rb, ("mjoin_rpart", tuple(self.right_keys), rb.schema,
                 rb.local_capacity),
            _hash_pid_builder(tuple(self.right_keys), n_dev), smax=smax)
        out = self._local_join(ctx, flatten_mesh(lb), flatten_mesh(rb),
                               lb.rows_dev(), rb.rows_dev(),
                               self.children[0].output,
                               self.children[1].output,
                               lb.local_capacity, rb.local_capacity,
                               r_replicated=False)
        self.count_output(out.num_rows)
        yield out


class MeshBroadcastHashJoinExec(MeshHashJoinBase):
    """Broadcast equi-join: the build side (per ``build_side``, already
    materialized to a single batch by its BroadcastExchange) is replicated
    across the mesh; the stream side stays sharded — no stream movement at
    all (GpuBroadcastHashJoinExec analog)."""

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
        bi = 0 if self.build_side == "left" else 1
        si = 1 - bi
        stream = self._one_child_batch(ctx, si)
        build_batches = list(self.children[bi].execute(ctx))
        db = concat_device_batches(build_batches, self.children[bi].output,
                                   ctx.string_max_bytes)
        out = self._broadcast_join(ctx, stream, db, bi)
        self.count_output(out.num_rows)
        yield out


# ------------------------------------------------------------------ sort
class MeshSortExec(MeshExec):
    """Global sort: sample-based range repartition over ICI (ascending shard
    index = ascending key range), then one local sort per shard. Shard-major
    gather order IS the global sort order (GpuSortExec + GpuRangePartitioning
    composition)."""

    def __init__(self, orders: Tuple[SortOrder, ...], child: PhysicalExec,
                 mesh: Mesh, pre_partitioned: bool = False):
        super().__init__((child,), child.output, mesh)
        self.orders = orders
        #: child is already range-partitioned on these orders (an explicit
        #: RangePartitioning exchange below) — skip the redundant repartition
        self.pre_partitioned = pre_partitioned

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        mb = self._one_child_batch(ctx)
        smax = ctx.string_max_bytes
        schema = self.output
        if not self.pre_partitioned:
            mb = _range_repartition(mb, self.orders, smax)
        cap = mb.local_capacity
        key = ("msort", self.orders, schema, cap, smax)

        def build(orders=self.orders, schema=schema, cap=cap, smax=smax):
            def fn(rows, *flat):
                colvs = unflatten_colvs(schema, flat)
                ectx = EvalCtx(jnp, colvs, cap, smax)
                alive = bk.alive_mask(jnp, cap, rows[0])
                passes = [jnp.logical_not(alive).astype(np.int8)]
                for o in orders:
                    passes.extend(bk._key_passes(jnp, o.child.eval(ectx),
                                                 o.ascending, o.nulls_first))
                out_cols, _ = bk.sort_colvs(jnp, passes, colvs)
                return tuple(flatten_colvs(out_cols))
            return fn

        nflat = flat_len(schema)
        fn = _shard_jit(self.mesh, key, build,
                        (P(DATA_AXIS),) + _specs(nflat), _specs(nflat))
        res = fn(mb.rows_dev(), *flatten_mesh(mb))
        out = MeshBatch(schema, mesh_columns(schema, res), mb.rows_per_shard,
                        self.mesh)
        self.count_output(out.num_rows)
        yield out

def _mesh_sampled_bounds(mb: MeshBatch, orders, smax: int):
    """Evaluate the order keys per shard, pull an evenly spaced sample to
    the host, derive n_dev-1 range bounds (SamplingUtils role)."""
    from spark_rapids_tpu.execs.exchange_execs import _sample_bounds
    cap = mb.local_capacity
    schema = mb.schema
    k = min(_SAMPLE_PER_SHARD, cap)
    key = ("msort_sample", orders, schema, cap, k, smax)

    def build(orders=orders, schema=schema, cap=cap, k=k, smax=smax):
        def fn(rows, *flat):
            colvs = unflatten_colvs(schema, flat)
            ectx = EvalCtx(jnp, colvs, cap, smax)
            keys = [o.child.eval(ectx) for o in orders]
            idx = jnp.asarray(
                np.linspace(0, cap - 1, k).astype(np.int32))
            alive = idx < rows[0]
            outs = [alive]
            for v in keys:
                v = bk.as_column(jnp, v, cap)
                outs.extend(flatten_colvs([bk.take_colv(jnp, v, idx)]))
            return tuple(outs)
        return fn

    n_keys_flat = sum(3 if o.child.dtype() is DType.STRING else 2
                      for o in orders)
    fn = _shard_jit(mb.mesh, key, build,
                    (P(DATA_AXIS),) + _specs(flat_len(schema)),
                    _specs(1 + n_keys_flat))
    res = [np.asarray(a) for a in fn(mb.rows_dev(), *flatten_mesh(mb))]
    alive = res[0]
    if not alive.any():
        return None
    keys = []
    i = 1
    for o in orders:
        dt = o.child.dtype()
        if dt is DType.STRING:
            keys.append(ColV(dt, res[i][alive], res[i + 1][alive],
                             res[i + 2][alive]))
            i += 3
        else:
            keys.append(ColV(dt, res[i][alive], res[i + 1][alive]))
            i += 2
    return _sample_bounds(orders, [keys], mb.n_dev)


def _range_repartition(mb: MeshBatch, orders, smax: int) -> MeshBatch:
    """Sample-based range repartition over ICI: ascending shard index =
    ascending key range (GpuRangePartitioning + GpuRangePartitioner role).
    No-op on a single-device mesh or an empty batch."""
    from spark_rapids_tpu.execs.exchange_execs import range_partition_ids
    orders = tuple(orders)
    if not mb.num_rows or mb.n_dev < 2:
        return mb
    bounds = _mesh_sampled_bounds(mb, orders, smax)
    if bounds is None:
        return mb
    bflat = []
    for v in bounds:
        for a in flatten_colvs([v]):
            bflat.append(jax.device_put(
                np.asarray(a), NamedSharding(mb.mesh, P())))
    nb = len(bflat)
    bschema = tuple(v.dtype for v in bounds)
    nbound = bounds[0].validity.shape[0]

    def pid(colvs, ectx, extra, orders=orders, bschema=bschema):
        bnd = []
        i = 0
        for dt in bschema:
            if dt is DType.STRING:
                bnd.append(ColV(dt, extra[i], extra[i + 1], extra[i + 2]))
                i += 3
            else:
                bnd.append(ColV(dt, extra[i], extra[i + 1]))
                i += 2
        row_keys = [o.child.eval(ectx) for o in orders]
        return range_partition_ids(jnp, orders, row_keys, bnd,
                                   ectx.capacity)

    return _mesh_repartition(
        mb, ("msort_part", orders, mb.schema, mb.local_capacity, nbound),
        pid, extra_flat=tuple(bflat), n_extra=nb, smax=smax)


# ------------------------------------------------------------------ limit/union
class MeshLimitExec(MeshExec):
    """Global limit over shard-major order: per-shard take counts are plain
    host arithmetic over the row-count vector; no device work at all."""

    def __init__(self, n: int, child: PhysicalExec, mesh: Mesh):
        super().__init__((child,), child.output, mesh)
        self.n = n

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        remaining = self.n
        for mb in self.children[0].execute(ctx):
            take = np.zeros_like(mb.rows_per_shard)
            left = remaining
            for d in range(mb.n_dev):
                t = min(left, int(mb.rows_per_shard[d]))
                take[d] = t
                left -= t
            remaining = left
            out = MeshBatch(mb.schema, mb.columns, take, mb.mesh)
            out = _maybe_shrink(out)
            self.count_output(out.num_rows)
            yield out
            if remaining <= 0:
                break


class MeshUnionExec(MeshExec):
    """Per-shard concatenation of two mesh batches (no data movement across
    shards; shard-major order = left rows then right rows per shard)."""

    def __init__(self, left: PhysicalExec, right: PhysicalExec, mesh: Mesh):
        super().__init__((left, right), left.output, mesh)

    def execute(self, ctx: ExecContext) -> Iterator[MeshBatch]:
        lb = self._one_child_batch(ctx, 0)
        rb = self._one_child_batch(ctx, 1)
        capL, capR = lb.local_capacity, rb.local_capacity
        rows = lb.rows_per_shard + rb.rows_per_shard
        out_cap = max(bucket_capacity(int(rows.max(initial=0))), 1)
        schema = self.output
        key = ("munion", schema, capL, capR, out_cap,
               tuple(c.data.shape[1:] for c in lb.columns),
               tuple(c.data.shape[1:] for c in rb.columns))

        def build(schema=schema, capL=capL, capR=capR, out_cap=out_cap):
            def fn(l_rows, r_rows, *flat):
                nl = flat_len(schema)
                l_cols = unflatten_colvs(schema, flat[:nl])
                r_cols = unflatten_colvs(schema, flat[nl:])
                liveL = jnp.arange(capL, dtype=np.int32) < l_rows[0]
                liveR = jnp.arange(capR, dtype=np.int32) < r_rows[0]
                live = jnp.concatenate([liveL, liveR])
                order = jnp.argsort(~live, stable=True)[:out_cap]
                outs = []
                for lv, rv in zip(l_cols, r_cols):
                    merged = jk._concat_colv(jnp, lv, rv)
                    outs.extend(flatten_colvs(
                        [bk.take_colv(jnp, merged, order)]))
                return tuple(outs)
            return fn

        nflat = flat_len(schema)
        fn = _shard_jit(self.mesh, key, build,
                        (P(DATA_AXIS), P(DATA_AXIS)) + _specs(2 * nflat),
                        _specs(nflat))
        res = fn(lb.rows_dev(), rb.rows_dev(), *flatten_mesh(lb),
                 *flatten_mesh(rb))
        out = MeshBatch(schema, mesh_columns(schema, res), rows, self.mesh)
        self.count_output(out.num_rows)
        yield out
