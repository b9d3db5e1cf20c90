"""Fused partition-reorder kernel: the accelerated map side of the device
shuffle (GpuPartitioning.scala:44-75 contiguousSplit + Table.partition role).

The sort path reorders a batch with a global variadic sort. This module
does it in ONE streaming HBM pass with a Pallas kernel:

  pack     columns -> one (rows, L) byte matrix (XLA; u32 bitcasts fuse into
           the concatenate — f64 uses upload-time bit siblings or an exact
           three-float32 expansion, see below)
  kernel   per 512-row window: partition ranks from a constant triangular
           int8 matrix batched across the group in one wide MXU dot, then a
           stacked one-hot int8 dot spreads the window's rows into
           per-partition segments appended to quota-padded per-(group,
           partition) staging blocks
  pieces   per (group, partition) quota block + live-count sidecars;
           `consolidate` block-gathers each partition's full 8-row blocks
           plus a tiny row-gather of the per-group remainders into one
           ordinary DeviceBatch (shuffles do not promise intra-partition
           row order)

Backend constraints discovered by probing (docs/perf-notes.md):
cumsum/sort/gather do not lower in Mosaic TC kernels; the X64 rewriter
cannot lower any 64-bit-element bitcast (f64->u64, i64->u32, signbit,
frexp); f64 ARITHMETIC is ~49-bit sloppy while f64 STORAGE is true 64-bit;
u64->f64 bitcast (the decode direction) works; unaligned uint8 dynamic
stores crash Mosaic (int32 ones do not). The design routes around each:
integers split to u32 by exact shifts, doubles ride as upload-time u64 bit
siblings (decode is the working bitcast direction) or as an exact hi/mid/lo
float32 expansion validated by an in-program flag, and segment appends use
32-aligned stores with a blended boundary tile.

Fallback: any overflow (quota or per-window) or f64-expansion inexactness
flags the batch back to the sort path — correctness never depends on the
fast path applying.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401 - jax setup
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.columnar.dtypes import DType, Schema, bucket_capacity
from spark_rapids_tpu.serving.program_cache import (_Program,
                                                    global_program_cache,
                                                    named_jit)

W = 512                    #: window rows (one spread dot per window)
GROUP_WINDOWS = 64         #: windows per group (one output piece set each)
BLOCK = 8                  #: consolidation block rows
MAX_PARTS = 32             #: wider fan-outs fall back to the sort path


# ------------------------------------------------------------------ pack spec
@dataclass(frozen=True)
class _ColPlan:
    dtype: DType
    kind: str          # u32x1 | u32x2 | f64bits | f64split3 | u8 | string
    lane: int          # first byte lane of the data bytes
    nbytes: int        # data byte lanes
    smax: int = 0      # string byte width


@dataclass(frozen=True)
class PackSpec:
    """Byte-matrix layout for one batch schema: per-column data lanes, then
    one validity byte lane per column (order: all data, then validities)."""
    plans: Tuple[_ColPlan, ...]
    lanes: int

    @staticmethod
    def for_batch(batch: DeviceBatch) -> Optional["PackSpec"]:
        plans: List[_ColPlan] = []
        lane = 0
        for f, c in zip(batch.schema, batch.columns):
            dt = f.dtype
            if dt is DType.STRING:
                smax = c.data.shape[1]
                plans.append(_ColPlan(dt, "string", lane, smax + 4, smax))
                lane += smax + 4
            elif dt is DType.DOUBLE:
                if getattr(c, "bits", None) is not None:
                    plans.append(_ColPlan(dt, "f64bits", lane, 8))
                    lane += 8
                else:
                    plans.append(_ColPlan(dt, "f64split3", lane, 12))
                    lane += 12
            elif dt in (DType.LONG, DType.TIMESTAMP):
                plans.append(_ColPlan(dt, "u32x2", lane, 8))
                lane += 8
            elif dt in (DType.INT, DType.DATE, DType.FLOAT):
                plans.append(_ColPlan(dt, "u32x1", lane, 4))
                lane += 4
            elif dt in (DType.BOOLEAN, DType.BYTE):
                plans.append(_ColPlan(dt, "u8", lane, 1))
                lane += 1
            elif dt is DType.SHORT:
                plans.append(_ColPlan(dt, "u32x1", lane, 4))
                lane += 4
            else:
                return None                       # NULL etc: sort path
        return PackSpec(tuple(plans), lane + len(plans))


def _u32_bytes(a) -> "jax.Array":
    return jax.lax.bitcast_convert_type(a.astype(jnp.uint32), jnp.uint8)


def _split3(x):
    """Exact three-float32 expansion of device f64 (device arithmetic holds
    ~48 significand bits, so hi+mid+lo is exact for every device-COMPUTED
    value; `ok` is False for full-precision host-uploaded doubles, which
    carry bit siblings instead)."""
    hi = x.astype(jnp.float32)
    r1 = x - hi.astype(jnp.float64)
    mid = r1.astype(jnp.float32)
    lo = (r1 - mid.astype(jnp.float64)).astype(jnp.float32)
    rec = (hi.astype(jnp.float64) + mid.astype(jnp.float64)) \
        + lo.astype(jnp.float64)
    ok = jnp.all(jnp.where(jnp.isnan(x), jnp.isnan(rec), rec == x))
    return hi, mid, lo, ok


def pack_matrix(spec: PackSpec, batch_cols: Sequence, validities: Sequence):
    """Columns -> ((rows, L) u8 matrix, exactness_ok scalar). Runs inside
    the caller's jit; every bitcast/shift fuses into the one concatenate."""
    pieces = []
    ok = jnp.bool_(True)
    for plan, c in zip(spec.plans, batch_cols):
        if plan.kind == "string":
            pieces.append(c.data)
            pieces.append(_u32_bytes(c.lengths))
        elif plan.kind == "f64bits":
            bits = c.bits
            pieces.append(_u32_bytes(bits & np.uint64(0xFFFFFFFF)))
            pieces.append(_u32_bytes(bits >> np.uint64(32)))
        elif plan.kind == "f64split3":
            hi, mid, lo, good = _split3(c.data)
            ok = jnp.logical_and(ok, good)
            for part in (hi, mid, lo):
                pieces.append(_u32_bytes(
                    jax.lax.bitcast_convert_type(part, jnp.uint32)))
        elif plan.kind == "u32x2":
            x = c.data.astype(jnp.int64)
            pieces.append(_u32_bytes(x & np.int64(0xFFFFFFFF)))
            pieces.append(_u32_bytes(jnp.right_shift(x, np.int64(32))))
        elif plan.kind == "u32x1":
            if c.data.dtype == jnp.float32:
                pieces.append(_u32_bytes(
                    jax.lax.bitcast_convert_type(c.data, jnp.uint32)))
            else:
                pieces.append(_u32_bytes(c.data.astype(jnp.int64)
                                         & np.int64(0xFFFFFFFF)))
        elif plan.kind == "u8":
            pieces.append(c.data.astype(jnp.uint8)[:, None])
        else:
            raise AssertionError(plan.kind)
    for v in validities:
        pieces.append(v.astype(jnp.uint8)[:, None])
    return jnp.concatenate(pieces, axis=1), ok


def unpack_columns(spec: PackSpec, schema: Schema, mat) -> List[DeviceColumn]:
    """(rows, L) u8 matrix -> DeviceColumns (decode side; u64->f64 bitcast
    is the direction this backend supports)."""
    def u32(lane):
        # arithmetic byte assembly, NOT bitcast_convert_type: bitcasting a
        # lane SLICE of a u8 matrix has been seen to zero low nibbles
        # (pack's u32->u8 direction is fine and stays a bitcast)
        b = [mat[:, lane + k].astype(jnp.uint32) for k in range(4)]
        return (b[0] | (b[1] << np.uint32(8)) | (b[2] << np.uint32(16))
                | (b[3] << np.uint32(24)))

    def u64(lane):
        lo = u32(lane).astype(jnp.uint64)
        hi = u32(lane + 4).astype(jnp.uint64)
        return lo | (hi << np.uint64(32))

    nvals = len(spec.plans)
    cols: List[DeviceColumn] = []
    for i, (plan, f) in enumerate(zip(spec.plans, schema)):
        validity = mat[:, spec.lanes - nvals + i] != 0
        if plan.kind == "string":
            data = mat[:, plan.lane:plan.lane + plan.smax]
            lengths = u32(plan.lane + plan.smax).astype(jnp.int32)
            cols.append(DeviceColumn(f.dtype, data, validity, lengths))
            continue
        if plan.kind == "f64bits":
            data = jax.lax.bitcast_convert_type(u64(plan.lane), jnp.float64)
        elif plan.kind == "f64split3":
            hi = jax.lax.bitcast_convert_type(u32(plan.lane), jnp.float32)
            mid = jax.lax.bitcast_convert_type(u32(plan.lane + 4),
                                               jnp.float32)
            lo = jax.lax.bitcast_convert_type(u32(plan.lane + 8),
                                              jnp.float32)
            data = (hi.astype(jnp.float64) + mid.astype(jnp.float64)) \
                + lo.astype(jnp.float64)
        elif plan.kind == "u32x2":
            data = u64(plan.lane).astype(jnp.int64)
            if f.dtype is DType.TIMESTAMP:
                data = data.astype(jnp.int64)
        elif plan.kind == "u32x1":
            raw = u32(plan.lane)
            if f.dtype is DType.FLOAT:
                data = jax.lax.bitcast_convert_type(raw, jnp.float32)
            else:
                data = raw.astype(jnp.int32)
        elif plan.kind == "u8":
            raw = mat[:, plan.lane]
            data = (raw != 0) if f.dtype is DType.BOOLEAN \
                else raw.astype(jnp.int8)
        else:
            raise AssertionError(plan.kind)
        if plan.kind == "f64bits":
            col = DeviceColumn(f.dtype, data, validity)
            object.__setattr__(col, "bits", u64(plan.lane))
            cols.append(col)
        else:
            cols.append(DeviceColumn(f.dtype, data, validity))
    return cols


# ------------------------------------------------------------------ geometry
@dataclass(frozen=True)
class KernelGeom:
    cap: int          # padded row count = groups * G * W
    groups: int
    G: int
    n: int
    q_w: int          # per-window per-partition segment bound
    quota: int        # per-(group, partition) piece rows
    L: int
    #: doublings of the per-window bound this plan was asked for: the kernel
    #: runs `split_widening` threw away before it. Not part of the geometry
    #: (two plans that clamp to the same bound are one program)
    widen: int = field(default=0, compare=False)

    @staticmethod
    def plan(rows: int, n: int, L: int, widen: int = 0) -> "KernelGeom":
        """``widen`` doubles the per-window segment bound that many times
        (capped at W, where no window can overflow): clustered keys put
        more than 2x the even share of one window into one partition. The
        quota grows by the same rows, so its headroom stays the base's."""
        G = min(GROUP_WINDOWS, max(1, math.ceil(rows / W)))
        gw = G * W
        groups = max(1, math.ceil(rows / gw))
        cap = groups * gw
        base = (min(W, max(64, 2 * math.ceil(W / n))) + 7) // 8 * 8
        q_w = min(W, base << widen)
        seg = q_w + 32
        quota = max(seg + 32,
                    math.ceil(1.25 * gw / n) + q_w - base)
        quota = (quota + 511) // 512 * 512
        return KernelGeom(cap, groups, G, n, q_w, quota, L, widen)


def padded_lanes(L: int) -> int:
    """Staging-buffer lane width: 128-multiple so the DMA consolidation can
    copy pieces whole (Mosaic lane tiling) without a separate pad pass."""
    return -(-L // 128) * 128


def kernel_vmem_bytes(geom: KernelGeom) -> int:
    """VMEM the reorder kernel's operands hold in one grid step: Pallas
    double-buffers every blocked operand (lanes tile to 128), plus the
    running-count scratch. The (n, 1, quota, L) staging block dominates —
    ~1.25 * G * W * padded_lanes(L) bytes whatever the fan-out."""
    Lp = padded_lanes(geom.L)
    n_pad = (geom.n + 7) // 8 * 8
    blocks = (geom.n * geom.quota * Lp          # out: u8 staging block
              + W * Lp                          # data: one u8 window
              + geom.G * W * 4                  # pids: i32, whole group
              + n_pad * 128 * 4)                # stats: i32
    return 2 * blocks + geom.G * n_pad * W * 4


def _vmem_limit_bytes() -> int:
    """Scoped-VMEM ceiling requested for the reorder kernel: Mosaic's
    default (16 MiB on v5e) is below what wide schemas or a 32-way fan-out
    need, and nothing else runs beside the custom call, so ask for three
    quarters of the core's VMEM."""
    return pltpu.get_tpu_info().vmem_capacity_bytes * 3 // 4


def _make_kernel(geom: KernelGeom):
    G, n, q_w, quota, L = (geom.G, geom.n, geom.q_w, geom.quota, geom.L)
    wn = geom.cap // W
    groups = geom.groups
    seg_rows = q_w + 32
    # Mosaic requires dynamic-slice offsets in dim 0 provably 8-aligned:
    # wg * n is only provable when n is a multiple of 8, so the per-window
    # running-count matrix pads its partition rows (pids never reach the
    # padding, so the extra rows stay zero and drop out of the rank sum)
    n_pad = (n + 7) // 8 * 8

    def kernel(pid_ref, data_ref, out_ref, cnt_ref, run_ref, cs_ref):
        # 2D grid (group, window-in-group): index maps stay arithmetic-free
        # (any jnp arithmetic on grid indices under jax_enable_x64 either
        # recurses in dtype promotion or fails Mosaic legalization)
        wg = pl.program_id(1)

        @pl.when(wg == np.int32(0))
        def _prepass():
            # inclusive running per-partition counts for EVERY window of the
            # group in one wide dot (a narrow n-lane dot per window would
            # waste the MXU's 128 output lanes); cumsum does not lower
            r_i = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
            c_i = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
            tri = (c_i <= r_i).astype(jnp.int8)
            pids = pid_ref[0]                       # (G, W)
            jj = jax.lax.broadcasted_iota(jnp.int32, (G, n_pad, W), 1)
            m = (pids[:, None, :] == jj).astype(jnp.int8)
            m2 = m.reshape(G * n_pad, W)
            cs = jax.lax.dot_general(m2, tri, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.int32)
            cs_ref[:] = cs
            for j in range(n):
                # pinned: a weak 0 traces as int64 under jax_enable_x64 and
                # the interpret-mode ref store rejects the dtype mismatch
                run_ref[j] = jnp.int32(0)
            cnt_ref[...] = jnp.zeros((1, n, 128), jnp.int32)

        p = pid_ref[0, wg, :]
        d8 = data_ref[0].astype(jnp.int8)
        # (n_pad, W) inclusive counts; offset wg*n_pad is 8-aligned
        cs_w = cs_ref[pl.ds(wg * np.int32(n_pad), n_pad), :]
        rank = jnp.sum(jnp.where(p[None, :] ==
                                 jax.lax.broadcasted_iota(
                                     jnp.int32, (n_pad, W), 0),
                                 cs_w, np.int32(0)),
                       axis=0, dtype=jnp.int32) - np.int32(1)
        base_max = np.int32((quota - seg_rows) // 32 * 32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (n * seg_rows, W), 0)
        stack = jnp.full((W,), -1, jnp.int32)
        bases, offs, cnts = [], [], []
        for j in range(n):
            run = run_ref[j]
            base = jnp.minimum((run // np.int32(32)) * np.int32(32),
                               base_max)
            off = run - base
            bases.append(base)
            offs.append(off)
            cnts.append(cs_w[j, W - 1])
            stack = jnp.where(p == np.int32(j),
                              rank + off + np.int32(j * seg_rows), stack)
        oh = (rows == stack[None, :]).astype(jnp.int8)
        segs = jax.lax.dot_general(oh, d8, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        segs = (segs & 255).astype(jnp.uint8)

        wovf = jnp.int32(0)       # a window's segment bound overflowed
        qovf = jnp.int32(0)       # a (group, partition) quota overflowed
        for j in range(n):
            seg = segs[j * seg_rows:(j + 1) * seg_rows, :]
            # u8 dynamic stores must be 32-aligned on this backend: write at
            # the aligned floor (the one-hot already shifted rows by the
            # residue) and blend the first tile with rows appended earlier
            bb = pl.multiple_of(bases[j], 32)
            old = out_ref[j, 0, pl.ds(bb, 32), :]
            head = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0) < offs[j]
            seg = jnp.concatenate(
                [jnp.where(head, old, seg[:32]), seg[32:]], axis=0)
            out_ref[j, 0, pl.ds(bb, seg_rows), :] = seg
            wovf = jnp.where(cnts[j] > np.int32(q_w), jnp.int32(1), wovf)
            qovf = jnp.where(
                run_ref[j] + cnts[j] > np.int32(quota - seg_rows),
                jnp.int32(1), qovf)
            run_ref[j] = run_ref[j] + cnts[j]

        # stats lanes: 0 = live count, 1 = window overflow, 2 = quota overflow
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n, 128), 2)
        flags = jnp.where(lane == np.int32(1), wovf,
                          jnp.where(lane == np.int32(2), qovf, np.int32(0)))

        @pl.when(wg == np.int32(G - 1))
        def _publish():
            counts = jnp.stack([run_ref[j] for j in range(n)])
            stats = jnp.where(lane == np.int32(0), counts[None, :, None],
                              flags)
            cnt_ref[...] = jnp.maximum(stats, cnt_ref[...])

        @pl.when(jnp.logical_and(wovf + qovf > np.int32(0),
                                 wg < np.int32(G - 1)))
        def _early_ovf():
            cnt_ref[...] = jnp.maximum(cnt_ref[...], flags)

    out_shapes = (
        jax.ShapeDtypeStruct((n, groups, quota, L), jnp.uint8),
        jax.ShapeDtypeStruct((groups, n, 128), jnp.int32),
    )
    # index-map literals pinned to int32: weak-typed 0s trace as int64
    # under jax_enable_x64 and the Mosaic func.return cannot legalize them
    z = np.int32(0)
    grid = (groups, G)
    in_specs = [
        pl.BlockSpec((1, G, W), lambda g, wg: (g, z, z),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, W, L), lambda g, wg: (g, wg, z),
                     memory_space=pltpu.VMEM),
    ]
    out_specs = (
        pl.BlockSpec((n, 1, quota, L), lambda g, wg: (z, g, z, z),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, n, 128), lambda g, wg: (g, z, z),
                     memory_space=pltpu.VMEM),
    )

    def run(pid2d, data, interpret=False):
        mosaic = {} if interpret else {
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit_bytes())}
        return pl.pallas_call(
            kernel, out_shape=out_shapes, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.SMEM((n,), jnp.int32),
                            pltpu.VMEM((G * n_pad, W), jnp.int32)],
            interpret=interpret, **mosaic,
        )(pid2d.reshape(groups, G, W),
          data.reshape(groups, G * W, L))
    return run


# ------------------------------------------------------------------ driver
def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


_PROGRAMS: dict = {}


def reorder_program(spec: PackSpec, geom: KernelGeom, cap: int,
                    interpret: bool):
    """The cached pack+kernel jit: fn(num_rows, pids, *flat) ->
    (out, summary). ``flat`` is `_deflate` order."""
    key = ("pkern", spec, geom, cap, interpret)
    fn = _PROGRAMS.get(key)
    if fn is not None:
        return fn
    kern = _make_kernel(geom)

    def fn(num_rows, pids, *flat):
        cols = _reflate(spec, flat)
        mat, ok = _pack(spec, cols)
        # materialize the packed matrix as-is before it feeds the Pallas
        # custom call: XLA fusing the bitcast/concatenate chain into the
        # operand has been seen to zero low nibbles of some lanes
        mat = jax.lax.optimization_barrier(mat)
        cap_in = mat.shape[0]
        live = jnp.arange(cap_in, dtype=jnp.int32) < num_rows
        pids2 = jnp.where(live, pids, np.int32(-1))
        pad = geom.cap - cap_in
        if pad:
            mat = jnp.concatenate(
                [mat, jnp.zeros((pad, geom.L), jnp.uint8)], axis=0)
            pids2 = jnp.concatenate(
                [pids2, jnp.full((pad,), -1, jnp.int32)])
        out, stats = kern(pids2.reshape(geom.cap // W, W), mat,
                          interpret=interpret)
        # one SMALL host download serves counts + overflows + pack-ok: a
        # compact summary vector [ok, counts(groups*n), window_ovf,
        # quota_ovf] instead of the padded stats block
        counts = stats[:, :, 0].reshape(-1)
        summary = jnp.concatenate(
            [ok.astype(jnp.int32)[None], counts,
             jnp.max(stats[:, :, 1])[None], jnp.max(stats[:, :, 2])[None]])
        return out, summary

    fn = named_jit(key[0], fn)
    _PROGRAMS[key] = fn
    return fn


def split_widening(batch: DeviceBatch, n: int, interpret: bool, run):
    """Drive one batch through the reorder, widening the per-window bound
    while only that overflows. ``run(spec, geom) -> (out, summary)`` runs
    the pack+kernel program for a geometry. Every batch starts at the
    narrowest bound: a run that overflows a window is thrown away and the
    next runs at twice the bound (``geom.widen`` of the result counts them,
    the ``exchange.split`` span's ``widenings``). Returns (out, stats_host,
    spec, geom), or None when the fan-out, the schema, the kernel's VMEM
    footprint (compiled for the chip), an inexact f64 expansion or a quota
    overflow puts the batch outside the fast path (caller falls back to
    the sort path). Shared by the standalone entry below and the engine's
    fused pids+pack+kernel program (execs/exchange_execs.py)."""
    if n < 2 or n > MAX_PARTS:
        return None
    spec = PackSpec.for_batch(batch)
    if spec is None:
        return None
    for widen in range(4):     # base << 3 reaches W from any base >= 64
        geom = KernelGeom.plan(batch.capacity, n, spec.lanes, widen)
        if not interpret and kernel_vmem_bytes(geom) > _vmem_limit_bytes():
            return None
        out, summary = run(spec, geom)
        summary = np.asarray(summary)      # ONE small host round trip
        ok, counts = summary[0], summary[1:-2]
        window_ovf, quota_ovf = summary[-2], summary[-1]
        if not ok or quota_ovf > 0:
            return None
        if window_ovf == 0:
            stats_host = np.zeros((geom.groups, geom.n, 2), np.int32)
            stats_host[:, :, 0] = counts.reshape(geom.groups, geom.n)
            return out, stats_host, spec, geom
    return None


def split_batch_kernel(batch: DeviceBatch, pids, n: int,
                       interpret: Optional[bool] = None):
    """Run pack+kernel for one batch with precomputed pids; see
    `split_widening` for the result."""
    if interpret is None:
        interpret = _use_interpret()

    def run(spec, geom):
        fn = reorder_program(spec, geom, batch.capacity, interpret)
        return fn(np.int32(batch.num_rows), pids, *_deflate(spec, batch))
    return split_widening(batch, n, interpret, run)


def _deflate(spec: PackSpec, batch: DeviceBatch) -> List:
    flat: List = []
    for plan, c in zip(spec.plans, batch.columns):
        if plan.kind == "f64bits":
            flat.append(c.bits)
        else:
            flat.append(c.data)
        flat.append(c.validity)
        if plan.kind == "string":
            flat.append(c.lengths)
    return flat


class _PackCol:
    __slots__ = ("data", "bits", "validity", "lengths")

    def __init__(self, data, bits, validity, lengths):
        self.data = data
        self.bits = bits
        self.validity = validity
        self.lengths = lengths


def _reflate(spec: PackSpec, flat) -> List[_PackCol]:
    cols = []
    i = 0
    for plan in spec.plans:
        main = flat[i]
        validity = flat[i + 1]
        i += 2
        lengths = None
        if plan.kind == "string":
            lengths = flat[i]
            i += 1
        if plan.kind == "f64bits":
            cols.append(_PackCol(None, main, validity, lengths))
        else:
            cols.append(_PackCol(main, None, validity, lengths))
    return cols


def _pack(spec: PackSpec, cols: Sequence[_PackCol]):
    return pack_matrix(spec, cols, [c.validity for c in cols])


def consolidate_all(out, stats_host: np.ndarray, spec: PackSpec,
                    schema: Schema, geom: KernelGeom
                    ) -> Optional[List[Optional[DeviceBatch]]]:
    """EVERY partition's quota-padded pieces -> per-partition DeviceBatches
    via ONE pipelined-DMA compaction (round-4 perf-notes "next lever"):

    - grid (group, partition), partition innermost: consecutive steps hit
      DISJOINT destination slices, so n DMA copies ride in flight at once;
      a per-partition semaphore orders the only overlapping pair — group
      g's copy overwrites group g-1's padding tail within one partition.
    - remainder rows (< BLOCK per group; a few hundred rows total) are
      pre-gathered into a packed block and DMA'd at the 8-aligned full-
      block boundary as the grid's final step, so the compact is COMPLETE
      when the program returns.
    - the unpack then reads the materialized pallas output directly — no
      optimization barrier, no second full materialization (the barrier in
      `consolidate` keeps a take() gather from fusing into the lane
      extraction; a pallas output has no such fusion).

    TPU-only (DMA semantics); returns None to send the caller down the
    per-partition `consolidate` path (CPU tests, interpret mode)."""
    if jax.default_backend() != "tpu":
        return None
    counts = stats_host[:, :, 0].astype(np.int64)       # [groups, n]
    totals = counts.sum(axis=0)                         # [n]
    if totals.max(initial=0) == 0:
        return [None] * geom.n
    prefix8, nb8, ridx, ri_cap, dst_rows = dma_index_plan(counts, geom)

    key = ("pdma", spec, geom, ri_cap, dst_rows)
    fn = _PROGRAMS.get(key)
    if fn is None:
        fn = named_jit(key[0], _build_dma_compact(spec, geom, ri_cap,
                                                  dst_rows))
        _PROGRAMS[key] = fn
    compact = fn(jnp.asarray(prefix8), jnp.asarray(nb8),
                 jnp.asarray(ridx), out)

    batches: List[Optional[DeviceBatch]] = []
    for j in range(geom.n):
        total = int(totals[j])
        if total == 0:
            batches.append(None)
            continue
        bucket = int(bucket_capacity(total))
        ukey = ("pdma-unpack", spec, geom.L, bucket, dst_rows,
                tuple(f.dtype for f in schema))
        ufn = _PROGRAMS.get(ukey)
        if ufn is None:
            def build(bucket=bucket):
                def f(compact_j):
                    # the compact is a materialized pallas output: unpack
                    # reads it directly, no optimization barrier needed
                    return _flatten_unpacked(
                        unpack_columns(spec, schema, compact_j[:bucket]))
                return f
            ufn = named_jit(ukey[0], build())
            _PROGRAMS[ukey] = ufn
        batches.append(_res_to_batch(spec, schema, ufn(compact[j]), total))
    return batches


def dma_index_plan(counts: np.ndarray, geom: KernelGeom):
    """Pure host-side index math for the DMA consolidation (testable off-
    TPU): counts [groups, n] -> (prefix8 [n, groups] 8-aligned destination
    offsets of each group's full-block run, nb8 [n] total full-block rows,
    ridx [n, ri_cap] remainder-row source indices into the flattened
    groups*quota staging rows, ri_cap, dst_rows)."""
    n, groups, quota = geom.n, geom.groups, geom.quota
    totals = counts.sum(axis=0)
    nb = counts // BLOCK
    rem = counts - nb * BLOCK
    nb8 = (nb.sum(axis=0) * BLOCK).astype(np.int32)
    prefix8 = np.zeros((n, groups), np.int32)
    prefix8[:, 1:] = np.cumsum((nb.T * BLOCK)[:, :-1], axis=1)
    ri_cap = int(bucket_capacity(max(1, int(rem.sum(axis=0).max()))))
    ridx = np.zeros((n, ri_cap), np.int32)
    for j in range(n):
        rj = rem[:, j]
        rem_tot = int(rj.sum())
        rgid = np.repeat(np.arange(groups), rj)
        rwithin = np.arange(rem_tot) - np.repeat(np.cumsum(rj) - rj, rj)
        ridx[j, :rem_tot] = (rgid * quota + nb[:, j][rgid] * BLOCK
                             + rwithin).astype(np.int32)
    dst_rows = int(bucket_capacity(int(totals.max()))) + max(quota, ri_cap)
    return prefix8, nb8, ridx, ri_cap, dst_rows


def _build_dma_compact(spec: PackSpec, geom: KernelGeom, ri_cap: int,
                       dst_rows: int):
    """The jitted remainder-gather + pipelined-DMA program builder. Pays
    ONE pad pass to 128 lanes before the DMA (Mosaic lane tiling): padding
    the reorder kernel's staging output instead was tried and REGRESSED
    suite exchanges up to 6x — narrow schemas (L ~ 20) amplified every
    kernel write and consolidation read by Lp/L (round-5 perf-notes)."""
    n, groups, quota, L = geom.n, geom.groups, geom.quota, geom.L
    Lp = padded_lanes(L)

    def compact_fn(prefix8, nb8, ridx, out_arr):
        # pre-gather the (tiny) per-partition remainder rows into one
        # packed block the kernel can DMA whole
        flat = out_arr.reshape(n, groups * quota, L)
        rrows = jnp.take_along_axis(flat, ridx[:, :, None].astype(jnp.int32),
                                    axis=1)
        if Lp != L:
            rrows = jnp.pad(rrows, ((0, 0), (0, 0), (0, Lp - L)))
            src = jnp.pad(out_arr, ((0, 0), (0, 0), (0, 0), (0, Lp - L)))
        else:
            src = out_arr

        def kernel(prefix_ref, nb8_ref, src_ref, rem_ref, dst_ref, sems):
            g = pl.program_id(0)
            j = pl.program_id(1)

            def piece_copy(gv):
                off = pl.multiple_of(prefix_ref[j, gv], 8)
                return pltpu.make_async_copy(
                    src_ref.at[j, gv],
                    dst_ref.at[j, pl.ds(off, quota), :],
                    sems.at[j])

            @pl.when(g == np.int32(0))
            def _first():
                piece_copy(np.int32(0)).start()

            @pl.when(jnp.logical_and(g > np.int32(0),
                                     g < np.int32(groups)))
            def _mid():
                # wait the previous copy of THIS partition before starting
                # the next: group g overwrites g-1's padding tail. Copies
                # of the other n-1 partitions stay in flight meanwhile.
                piece_copy(g - np.int32(1)).wait()
                piece_copy(g).start()

            @pl.when(g == np.int32(groups))
            def _tail():
                piece_copy(np.int32(groups - 1)).wait()
                off8 = pl.multiple_of(nb8_ref[j], 8)
                rc = pltpu.make_async_copy(
                    rem_ref.at[j],
                    dst_ref.at[j, pl.ds(off8, ri_cap), :],
                    sems.at[j])
                rc.start()
                rc.wait()

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(groups + 1, n),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))])
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((n, dst_rows, Lp), jnp.uint8),
            grid_spec=grid_spec)(prefix8, nb8, src, rrows)
    return compact_fn


def consolidate(out, stats_host: np.ndarray, j: int, spec: PackSpec,
                schema: Schema, geom: KernelGeom) -> Optional[DeviceBatch]:
    """Partition j's quota-padded pieces -> ONE DeviceBatch: block-gather of
    every full 8-row block plus a row-gather of per-group remainders
    (shuffle makes no intra-partition order promise). Returns None for an
    empty partition.

    The program is SHAPE-STABLE: gather index vectors are padded to
    power-of-two buckets and the partition index rides as data, so one
    compiled program serves every partition of every exchange with this
    geometry — per-exchange counts only change the (tiny) index uploads."""
    counts = stats_host[:, j, 0].astype(np.int64)
    total = int(counts.sum())
    if total == 0:
        return None
    quota = geom.quota
    nb = counts // BLOCK
    rem = counts - nb * BLOCK
    qb = quota // BLOCK
    # vectorized index build: block b of group g -> flat block g*qb + b;
    # remainder row r of group g -> flat row g*quota + nb[g]*BLOCK + r
    nb_tot = int(nb.sum())
    gid = np.repeat(np.arange(len(nb)), nb)
    within = np.arange(nb_tot) - np.repeat(np.cumsum(nb) - nb, nb)
    block_idx = (gid * qb + within).astype(np.int32)
    rem_tot = int(rem.sum())
    rgid = np.repeat(np.arange(len(rem)), rem)
    rwithin = np.arange(rem_tot) - np.repeat(np.cumsum(rem) - rem, rem)
    rem_idx = (rgid * quota + nb[rgid] * BLOCK + rwithin).astype(np.int32)

    bucket = bucket_capacity(total)
    bi_cap = bucket_capacity(max(1, nb_tot))
    ri_cap = bucket_capacity(max(1, rem_tot))
    # pad with repeats of slot 0: the gathered garbage rows land beyond the
    # live prefix of the bucketed matrix (positional aliveness masks them)
    bi = np.zeros(bi_cap, np.int32)
    bi[:nb_tot] = block_idx
    ri = np.zeros(ri_cap, np.int32)
    ri[:rem_tot] = rem_idx

    key = ("pconsol", spec, geom, bi_cap, ri_cap, bucket)
    fn = _PROGRAMS.get(key)
    if fn is None:
        def build(bi_cap=bi_cap, ri_cap=ri_cap, bucket=bucket):
            def f(out_arr, jv, nb8, bidx, ridx):
                x = jax.lax.dynamic_index_in_dim(
                    out_arr, jv, axis=0, keepdims=False)
                x = x.reshape(geom.groups * geom.quota, geom.L)
                xb = x.reshape(geom.groups * geom.quota // BLOCK,
                               BLOCK * geom.L)
                full = jnp.take(xb, bidx, axis=0).reshape(
                    bi_cap * BLOCK, geom.L)
                rows = jnp.take(x, ridx, axis=0)
                # contiguity under bucketed index shapes: write the padded
                # full-block region first, then the remainder rows AT the
                # live boundary (nb8 = true full-block rows) — remainder
                # data overwrites the block padding, its own padding tail
                # lands beyond the live prefix
                work = jnp.zeros((bucket + bi_cap * BLOCK + ri_cap,
                                  geom.L), jnp.uint8)
                work = jax.lax.dynamic_update_slice(
                    work, full, (np.int32(0), np.int32(0)))
                work = jax.lax.dynamic_update_slice(
                    work, rows, (nb8, np.int32(0)))
                mat = work[:bucket]
                # materialize before decoding: the gather fused into the
                # lane extraction has been seen to corrupt lanes
                mat = jax.lax.optimization_barrier(mat)
                return _flatten_unpacked(unpack_columns(spec, schema, mat))
            return named_jit(key[0], f)
        # this module's own dict keeps it (no hit or miss of the program
        # cache), the cache's wrapper gives its calls their program.pconsol
        # span and its first call to compile_s, as every cached program's
        fn = _Program(build(), global_program_cache())
        _PROGRAMS[key] = fn

    res = fn(out, np.int32(j), np.int32(nb_tot * BLOCK),
             jnp.asarray(bi), jnp.asarray(ri))
    return _res_to_batch(spec, schema, res, total)


def _flatten_unpacked(cols) -> tuple:
    """DeviceColumns -> the flat jit-output tuple (one layout, shared by
    every consolidation program)."""
    out_flat = []
    for c in cols:
        out_flat.append(c.data)
        out_flat.append(c.validity)
        if c.lengths is not None:
            out_flat.append(c.lengths)
        b = getattr(c, "bits", None)
        if b is not None:
            out_flat.append(b)
    return tuple(out_flat)


def _res_to_batch(spec: PackSpec, schema: Schema, res,
                  total: int) -> DeviceBatch:
    """Flat jit-output tuple -> DeviceBatch (inverse of _flatten_unpacked,
    driven by the same plan kinds)."""
    cols: List[DeviceColumn] = []
    i = 0
    for plan, f in zip(spec.plans, schema):
        data = res[i]
        validity = res[i + 1]
        i += 2
        lengths = None
        if plan.kind == "string":
            lengths = res[i]
            i += 1
        bits = None
        if plan.kind == "f64bits":
            bits = res[i]
            i += 1
        cols.append(DeviceColumn(f.dtype, data, validity, lengths, bits))
    return DeviceBatch(schema, tuple(cols), total)
