"""Probe: DMA-based exchange consolidation vs the take()-based gather.

Round-4 finding (docs/perf-notes.md): the full exchange is bound by
consolidation at ~3.2 GB/s — far under HBM bandwidth — because XLA lowers
the 8-row block gather + byte-matrix unpack tile-inefficiently. The
quota-padded kernel output is PER-(group, partition) CONTIGUOUS (live
prefix per block), so compaction is expressible as ~groups sequential
quota-sized DMA copies per partition with dynamic destination offsets:
each copy lands at the running total and OVERWRITES the previous copy's
padding tail (TPU grid steps execute in order).

Run on the real chip:  python experiments/consolidate_probe.py
"""
import sys
import time

sys.path.insert(0, ".")

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_tpu.benchmarks.tpch import gen_lineitem
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import bucket_capacity
from spark_rapids_tpu.shuffle import partition_kernel as pk


def dma_compact(out, prefix8_np, geom, dst_rows):
    """out [n, groups, quota, L] -> [n, dst_rows, Lp]: every group's FULL
    8-row blocks land at 8-aligned running offsets (Mosaic sublane tiling
    requires it); each quota-sized copy's tail (remainders + padding) is
    overwritten by the next group's copy — TPU grid steps run in order.
    Remainder rows (<8 per group) are re-attached by the caller with the
    cheap row-gather. prefix8_np: int32 [n, groups] exclusive cumsum of
    8*floor(counts/8)."""
    n, groups, quota, L = (geom.n, geom.groups, geom.quota, geom.L)
    Lp = -(-L // 128) * 128
    if Lp != L:
        out = jnp.pad(out, ((0, 0), (0, 0), (0, 0), (0, Lp - L)))

    def kernel(prefix_ref, src_ref, dst_ref, sem):
        j = pl.program_id(0)
        g = pl.program_id(1)
        off = pl.multiple_of(prefix_ref[j, g], 8)
        dma = pltpu.make_async_copy(
            src_ref.at[j, g],
            dst_ref.at[j, pl.ds(off, quota), :],
            sem)
        dma.start()
        dma.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n, groups),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())])
    fn = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, dst_rows, Lp), jnp.uint8),
        grid_spec=grid_spec)
    return fn(prefix8_np, out)


def main():
    print("backend:", jax.default_backend())
    table = gen_lineitem(scale=1.0, seed=42)
    batch = DeviceBatch.from_arrow(table, 16)
    jax.block_until_ready(batch.columns[0].data)
    n = 8
    spec = pk.PackSpec.for_batch(batch)
    geom = pk.KernelGeom.plan(batch.capacity, n, spec.lanes)
    rng = np.random.default_rng(3)
    pids = jnp.asarray(rng.integers(0, n, batch.capacity).astype(np.int32))
    res = pk.split_batch_kernel(batch, pids, n, interpret=False)
    assert res is not None
    out, stats, spec, geom = res
    jax.block_until_ready(out)
    counts = stats[:, :, 0].astype(np.int64)          # [groups, n]
    totals = counts.sum(axis=0)
    gb = sum(c.data.size * c.data.dtype.itemsize + c.validity.size
             + (c.lengths.size * 4 if c.lengths is not None else 0)
             for c in batch.columns) / 1e9
    print(f"payload {gb:.2f} GB, totals {totals}")

    # ---- baseline: take()-based consolidate, all 8 partitions ----------------
    for it in range(3):
        t0 = time.perf_counter()
        subs = [pk.consolidate(out, stats, j, spec, batch.schema, geom)
                for j in range(n)]
        jax.block_until_ready([c.data for s in subs if s for c in s.columns])
        dt = time.perf_counter() - t0
        print(f"take-consolidate iter {it}: {dt:.3f}s -> {gb/dt:.2f} GB/s")

    # ---- DMA compaction + remainder gather + unpack --------------------------
    nb = (counts // pk.BLOCK)                          # [groups, n]
    prefix8 = np.zeros((n, geom.groups), np.int32)
    prefix8[:, 1:] = np.cumsum(nb.T * pk.BLOCK, axis=1)[:, :-1].astype(np.int32)
    nb8 = (nb.sum(axis=0) * pk.BLOCK).astype(np.int32)        # [n]
    rem = counts - nb * pk.BLOCK
    dst_rows = int(bucket_capacity(int(totals.max())) + geom.quota)
    Lp = -(-geom.L // 128) * 128
    quota = geom.quota

    ri_cap = int(bucket_capacity(max(1, int(rem.sum(axis=0).max()))))
    ridx = np.zeros((n, ri_cap), np.int32)
    for j in range(n):
        rj = rem[:, j]
        rem_tot = int(rj.sum())
        rgid = np.repeat(np.arange(len(rj)), rj)
        rwithin = np.arange(rem_tot) - np.repeat(np.cumsum(rj) - rj, rj)
        ridx[j, :rem_tot] = (rgid * quota + nb[:, j][rgid] * pk.BLOCK
                             + rwithin).astype(np.int32)

    @jax.jit
    def finish_and_unpack(compact, out_arr, ridx_dev, nb8_dev):
        outs = []
        for j in range(n):
            x = out_arr[j].reshape(geom.groups * quota, geom.L)
            rows = jnp.take(x, ridx_dev[j], axis=0)
            rows = jnp.pad(rows, ((0, 0), (0, Lp - geom.L)))
            cj = jax.lax.dynamic_update_slice(
                compact[j], rows, (nb8_dev[j], np.int32(0)))
            mat = jax.lax.optimization_barrier(cj[:, :geom.L])
            for c in pk.unpack_columns(spec, batch.schema, mat):
                outs.append(c.data)
                outs.append(c.validity)
                if c.lengths is not None:
                    outs.append(c.lengths)
                b = getattr(c, "bits", None)
                if b is not None:
                    outs.append(b)
        return tuple(outs)

    ridx_dev = jnp.asarray(ridx)
    nb8_dev = jnp.asarray(nb8)
    for it in range(3):
        t0 = time.perf_counter()
        compact = dma_compact(out, prefix8, geom, dst_rows)
        jax.block_until_ready(compact)
        t1 = time.perf_counter()
        cols = finish_and_unpack(compact, out, ridx_dev, nb8_dev)
        jax.block_until_ready(cols)
        t2 = time.perf_counter()
        print(f"dma iter {it}: compact {t1-t0:.3f}s finish+unpack {t2-t1:.3f}s "
              f"total {t2-t0:.3f}s -> {gb/(t2-t0):.2f} GB/s")

    # ---- correctness: per-partition row multisets match take-consolidate -----
    subs = [pk.consolidate(out, stats, j, spec, batch.schema, geom)
            for j in range(n)]
    compact = dma_compact(out, prefix8, geom, dst_rows)
    cols = finish_and_unpack(compact, out, ridx_dev, nb8_dev)
    # rebuild per-partition matrices host-side for comparison
    per_part = len(cols) // n
    import numpy as _np
    for j in range(n):
        total = int(totals[j])
        want = _np.asarray(
            pk.pack_matrix(spec, _as_packcols(subs[j]),
                           [c.validity for c in subs[j].columns])[0])[:total]
        got_mat = _np.asarray(jax.lax.dynamic_update_slice(
            compact[j],
            jnp.pad(jnp.take(out[j].reshape(geom.groups * quota, geom.L),
                             ridx_dev[j], axis=0),
                    ((0, 0), (0, Lp - geom.L))),
            (nb8_dev[j], np.int32(0))))[:total, :geom.L]
        want = _np.ascontiguousarray(want)
        got_mat = _np.ascontiguousarray(got_mat)
        a = _np.sort(want.view([("", want.dtype)] * want.shape[1]).ravel())
        b = _np.sort(got_mat.view([("", got_mat.dtype)] * got_mat.shape[1]).ravel())
        if not _np.array_equal(a, b):
            print(f"partition {j}: MISMATCH ({total} rows)")
            return
    print("correctness OK (row multisets match per partition)")


def _as_packcols(batch):
    cols = []
    for c in batch.columns:
        cols.append(pk._PackCol(c.data, getattr(c, "bits", None),
                                c.validity, c.lengths))
    return cols


if __name__ == "__main__":
    main()


def probe_i32_gather(out, stats, spec, geom, schema, gb):
    """Variant C: the same block gather on an int32 VIEW of the byte matrix
    (4x fewer lanes, native element width) — isolates whether u8 take() is
    the tile-inefficiency."""
    import jax
    n = geom.n
    counts_all = stats[:, :, 0].astype(np.int64)
    quota, qb = geom.quota, geom.quota // pk.BLOCK
    L4 = geom.L // 4 if geom.L % 4 == 0 else None
    for tag, view_l in (("u8", geom.L), ("i32", L4)):
        if view_l is None:
            print("L not 4-divisible; skipping i32 view")
            continue

        @jax.jit
        def gather_all(out_arr, bidx_all, tag=tag, view_l=view_l):
            outs = []
            for j in range(n):
                x = out_arr[j].reshape(geom.groups * quota, geom.L)
                if tag == "i32":
                    x = jax.lax.bitcast_convert_type(
                        x.reshape(geom.groups * quota, view_l, 4), jnp.int32)
                xb = x.reshape(geom.groups * quota // pk.BLOCK,
                               pk.BLOCK * view_l)
                outs.append(jnp.take(xb, bidx_all[j], axis=0))
            return tuple(outs)

        nb = counts_all // pk.BLOCK
        bi_cap = int(pk.bucket_capacity(int(nb.sum(axis=0).max())))
        bidx_all = np.zeros((n, bi_cap), np.int32)
        for j in range(n):
            nbj = nb[:, j]
            nb_tot = int(nbj.sum())
            gid = np.repeat(np.arange(len(nbj)), nbj)
            within = np.arange(nb_tot) - np.repeat(np.cumsum(nbj) - nbj, nbj)
            bidx_all[j, :nb_tot] = (gid * qb + within).astype(np.int32)
        bidx_dev = jnp.asarray(bidx_all)
        r = gather_all(out, bidx_dev)
        jax.block_until_ready(r)
        best = None
        for it in range(3):
            t0 = time.perf_counter()
            r = gather_all(out, bidx_dev)
            jax.block_until_ready(r)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        print(f"block-gather[{tag}]: {best:.3f}s -> {gb/best:.2f} GB/s")
