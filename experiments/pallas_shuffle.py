"""Fused partition-reorder Pallas kernel prototype (round 4).

One HBM pass: read the packed byte matrix window by window, spread each
window's rows into per-partition segments in VMEM, append segments into a
per-(group, partition) quota-padded staging block that Pallas DMAs out as
the output block — no second compaction pass. Output layout:

    out[(n, groups, Q_G, L)]   partition j's pieces = out[j, g] with
    counts[(groups, n)]        live rows [0, counts[g, j]) per piece
    overflow[(groups,)]        any quota overflow -> caller falls back

Spread variants measured against each other:
  gather  — idx_j = searchsorted(cumsum(pid==j), 1..q_w)  then d[idx_j, :]
  onehot  — int8 one-hot (q_w, W) @ (W, L) on the MXU

Usage:
  JAX_PLATFORMS=cpu python experiments/pallas_shuffle.py check   # interpret
  python experiments/pallas_shuffle.py bench gather|onehot [W G]
"""
import builtins
import functools
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

print = functools.partial(builtins.print, flush=True)

N_PARTS = 8


def make_kernel(cap, L, W, G, q_w, quota, variant):
    del variant                 # one lowerable strategy: MXU one-hot
    groups = cap // (W * G)
    wn = cap // W
    seg_rows = q_w + 32

    def kernel(pid_ref, data_ref, out_ref, cnt_ref, run_ref, cs_ref):
        w = pl.program_id(0)
        wg = w % G              # window index within its group

        # ---- group prepass: ranks for ALL G windows in ONE wide MXU dot
        # (tri @ one-hot pids -> inclusive running counts; a narrow 8-lane
        # dot per window would waste 94% of the MXU's 128 output lanes)
        @pl.when(wg == 0)
        def _prepass():
            r_i = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
            c_i = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
            tri = (c_i <= r_i).astype(jnp.int8)
            pids = pid_ref[:]                       # (G, W)
            jj = jax.lax.broadcasted_iota(jnp.int32, (G, N_PARTS, W), 1)
            m = (pids[:, None, :] == jj).astype(jnp.int8)
            m2 = m.reshape(G * N_PARTS, W)          # leading-dim flatten only
            # (G*n, W) running counts: row g*n+j holds window g's inclusive
            # prefix counts for partition j (transposed so the per-window
            # slice below is a SUBLANE slice — lane-dim dynamic slices need
            # 128-alignment this layout cannot give)
            cs = jax.lax.dot_general(m2, tri, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.int32)
            cs_ref[:] = cs
            for j in range(N_PARTS):
                run_ref[j] = 0

        # ---- spread this window: stacked one-hots, one MXU dot
        p = pid_ref[wg, :]
        d8 = data_ref[:].astype(jnp.int8)
        cs_w = cs_ref[pl.ds(wg * N_PARTS, N_PARTS), :]      # (n, W) incl
        rank = jnp.sum(jnp.where(p[None, :] ==
                                 jax.lax.broadcasted_iota(
                                     jnp.int32, (N_PARTS, W), 0),
                                 cs_w, 0), axis=0) - 1
        base_max = (quota - seg_rows) // 32 * 32
        rows = jax.lax.broadcasted_iota(
            jnp.int32, (N_PARTS * seg_rows, W), 0)
        stack = None
        bases, offs, cnts = [], [], []
        for j in range(N_PARTS):
            run = run_ref[j]
            base = jnp.minimum((run // 32) * 32, base_max)
            off = run - base
            bases.append(base)
            offs.append(off)
            cnts.append(cs_w[j, W - 1])
            rj = jnp.where(p == j, rank + off + j * seg_rows, -1)
            stack = rj if stack is None else jnp.where(p == j, rj, stack)
        oh = (rows == stack[None, :]).astype(jnp.int8)
        segs = jax.lax.dot_general(oh, d8, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        segs = (segs & 255).astype(jnp.uint8)

        ovf = jnp.int32(0)
        for j in range(N_PARTS):
            seg = segs[j * seg_rows:(j + 1) * seg_rows, :]
            bb = pl.multiple_of(bases[j], 32)
            old = out_ref[j, 0, pl.ds(bb, 32), :]
            head = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0) < offs[j]
            seg = jnp.concatenate(
                [jnp.where(head, old, seg[:32]), seg[32:]], axis=0)
            out_ref[j, 0, pl.ds(bb, seg_rows), :] = seg
            over = jnp.logical_or(cnts[j] > q_w,
                                  run_ref[j] + cnts[j] > quota - seg_rows)
            ovf = jnp.where(over, jnp.int32(1), ovf)
            run_ref[j] = run_ref[j] + cnts[j]

        # ---- publish counts/overflow at group end (the stats lane block)
        @pl.when(wg == G - 1)
        def _publish():
            counts = jnp.stack([run_ref[j] for j in range(N_PARTS)])
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, N_PARTS, 128), 2)
            prev = cnt_ref[...]
            stats = jnp.where(lane == 0, counts[None, :, None],
                              jnp.where(lane == 1, ovf, 0))
            # overflow may have been raised by earlier windows of the group
            stats = jnp.where(lane == 1, jnp.maximum(stats, prev), stats)
            cnt_ref[...] = stats

        @pl.when(jnp.logical_and(wg < G - 1, wg == 0))
        def _clear_stats():
            cnt_ref[...] = jnp.zeros((1, N_PARTS, 128), jnp.int32)

        @pl.when(jnp.logical_and(ovf > 0, wg < G - 1))
        def _early_ovf():
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, N_PARTS, 128), 2)
            cnt_ref[...] = jnp.maximum(
                cnt_ref[...], jnp.where(lane == 1, 1, 0))

    out_shapes = (
        jax.ShapeDtypeStruct((N_PARTS, groups, quota, L), jnp.uint8),
        jax.ShapeDtypeStruct((groups, N_PARTS, 128), jnp.int32),
    )
    grid = (wn,)
    in_specs = [
        pl.BlockSpec((G, W), lambda w: (w // G, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((W, L), lambda w: (w, 0), memory_space=pltpu.VMEM),
    ]
    out_specs = (
        pl.BlockSpec((N_PARTS, 1, quota, L), lambda w: (0, w // G, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, N_PARTS, 128), lambda w: (w // G, 0, 0),
                     memory_space=pltpu.VMEM),
    )

    def run(pid, data, interpret=False):
        return pl.pallas_call(
            kernel, out_shape=out_shapes, grid=grid,
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[pltpu.SMEM((N_PARTS,), jnp.int32),
                            pltpu.VMEM((G * N_PARTS, W), jnp.int32)],
            interpret=interpret,
        )(pid.reshape(wn, W), data)
    return run


def _ref_impl(pid, data, G, W, quota):
    """numpy reference: per-group partition-major compaction."""
    cap, L = data.shape
    groups = cap // (W * G)
    out = np.zeros((N_PARTS, groups, quota, L), np.uint8)
    cnt = np.zeros((groups, N_PARTS), np.int32)
    for g in range(groups):
        lo, hi = g * G * W, (g + 1) * G * W
        for j in range(N_PARTS):
            rows = data[lo:hi][pid[lo:hi] == j]
            cnt[g, j] = len(rows)
            out[j, g, :len(rows)] = rows
    return out, cnt


def check():
    cap, L, W, G = 4096, 16, 256, 4
    q_w, quota = 96, 320
    rng = np.random.default_rng(0)
    pid = rng.integers(0, N_PARTS, cap).astype(np.int32)
    data = rng.integers(0, 256, (cap, L)).astype(np.uint8)
    ref_out, ref_cnt = _ref_impl(pid, data, G, W, quota)
    for variant in ("onehot",):
        run = make_kernel(cap, L, W, G, q_w, quota, variant)
        out, stats = run(jnp.asarray(pid), jnp.asarray(data),
                         interpret=True)
        out, stats = map(np.asarray, (out, stats))
        cnt, ovf = stats[:, :, 0], stats[:, :, 1]
        assert (ovf == 0).all(), f"{variant}: unexpected overflow"
        assert (cnt == ref_cnt).all(), f"{variant}: counts differ"
        for g in range(cnt.shape[0]):
            for j in range(N_PARTS):
                c = ref_cnt[g, j]
                assert (out[j, g, :c] == ref_out[j, g, :c]).all(), \
                    f"{variant}: data differs at group {g} part {j}"
        print(f"{variant}: OK")


def bench(variant, W=1024, G=16):
    cap, L = 8 * 1024 * 1024, 112
    q_w = W // N_PARTS * 2              # 2x per-window slack
    quota = int(G * W // N_PARTS * 1.25)  # 1.25x per-group quota
    quota = (quota + 511) // 512 * 512

    @jax.jit
    def gen():
        i = jnp.arange(cap, dtype=jnp.uint32)
        h = (i * np.uint32(0x85EBCA6B)) ^ (i >> np.uint32(13))
        pid = (h % np.uint32(N_PARTS)).astype(jnp.int32)
        col = jnp.arange(L, dtype=jnp.uint32)[None, :]
        data = ((i[:, None] * np.uint32(2654435761) + col)
                & np.uint32(0xFF)).astype(jnp.uint8)
        return pid, data

    pid, data = gen()
    jax.block_until_ready((pid, data))
    run = jax.jit(make_kernel(cap, L, W, G, q_w, quota, variant))
    out = run(pid, data)
    np.asarray(out[1])                      # compile + completion barrier
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        out = run(pid, data)
    np.asarray(out[1])
    dt = (time.perf_counter() - t0) / iters
    gb = cap * L / 1e9
    ovf = int(np.asarray(out[1])[:, :, 1].max())
    print(f"pallas[{variant},W={W},G={G}]: {dt*1e3:.1f} ms  "
          f"{gb/dt:.2f} GB/s  (quota={quota}, ovf={ovf})")


if __name__ == "__main__":
    if sys.argv[1] == "check":
        check()
    else:
        variant = sys.argv[2]
        args = [int(a) for a in sys.argv[3:]]
        bench(variant, *args)
