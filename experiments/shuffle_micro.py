"""Microbenchmarks for the shuffle reorder redesign (round 4).

Measures the primitives that bound any partition-reorder design on this
chip, so the kernel architecture is chosen from data:

  copy      — pure HBM streaming bound (elementwise copy of the batch)
  sortg     — global variadic sort (the round-3 kernel's cost model)
  sortw     — windowed sort: lax.sort over (windows, W) batch dims
  gather    — row gather rate vs row width (the 75M rows/s claim)
  bgather   — block gather: (cap/B, B*L) reshaped row gather
  cumsum    — windowed rank computation (n one-hot cumsums over pids)
  taw       — take_along_axis within windows (3D row-granular spread)

Usage: python experiments/shuffle_micro.py copy sortg sortw ...
"""
import builtins
import functools
import sys
import time

print = functools.partial(builtins.print, flush=True)

import numpy as np

from spark_rapids_tpu import device as _device  # noqa: F401
import jax
import jax.numpy as jnp


def sync(x):
    return jax.block_until_ready(x)


def timeit(fn, *args, iters=5):
    res = sync(fn(*args))          # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        res = fn(*args)
    sync(res)
    return (time.perf_counter() - t0) / iters


CAP = 8 * 1024 * 1024          # rows, the Q1 bucket
N_OPS = 10                     # u64 payload operands ≈ 640 MB batch


def make_payloads(k=N_OPS, cap=CAP):
    # generated ON DEVICE: a 640 MB host->device upload would dominate the
    # benchmark setup
    @jax.jit
    def gen():
        i = jnp.arange(cap, dtype=jnp.uint64)
        return tuple((i * np.uint64(0x9E3779B97F4A7C15) + np.uint64(j))
                     for j in range(k))
    return list(sync(gen()))


def make_pids(cap=CAP, n=8):
    @jax.jit
    def gen():
        i = jnp.arange(cap, dtype=jnp.uint32)
        h = (i * np.uint32(0x85EBCA6B)) ^ (i >> np.uint32(13))
        return (h % np.uint32(n)).astype(jnp.int32)
    return sync(gen())


def bench_copy():
    ps = make_payloads()

    @jax.jit
    def f(*ops):
        return tuple(o + np.uint64(1) for o in ops)

    dt = timeit(f, *ps)
    gb = N_OPS * CAP * 8 / 1e9
    print(f"copy: {dt*1e3:.1f} ms  {gb/dt:.1f} GB/s (r+w {2*gb/dt:.1f})")


def bench_sortg():
    ps = make_payloads()
    pid = make_pids()

    @jax.jit
    def f(k, *ops):
        return jax.lax.sort((k,) + ops, num_keys=1, is_stable=True)

    dt = timeit(f, pid, *ps)
    gb = N_OPS * CAP * 8 / 1e9
    print(f"sortg[{N_OPS} ops]: {dt*1e3:.1f} ms  {gb/dt:.2f} GB/s payload")

    @jax.jit
    def f1(k, o):
        return jax.lax.sort((k, o), num_keys=1, is_stable=True)

    dt1 = timeit(f1, pid, ps[0])
    print(f"sortg[1 op]: {dt1*1e3:.1f} ms")


def bench_sortw():
    ps = make_payloads()
    pid = make_pids()
    for W in (512, 2048, 8192, 65536):
        wn = CAP // W
        k2 = pid.reshape(wn, W)
        ops2 = tuple(p.reshape(wn, W) for p in ps)

        @jax.jit
        def f(k, *ops):
            return jax.lax.sort((k,) + ops, num_keys=1, is_stable=True,
                                dimension=1)

        dt = timeit(f, k2, *ops2)
        gb = N_OPS * CAP * 8 / 1e9
        print(f"sortw[W={W}]: {dt*1e3:.1f} ms  {gb/dt:.2f} GB/s payload")


def _device_matrix(rows, L):
    @jax.jit
    def gen():
        i = jnp.arange(rows, dtype=jnp.uint32)[:, None]
        j = jnp.arange(L, dtype=jnp.uint32)[None, :]
        return (i * np.uint32(2654435761) + j).astype(jnp.int32)
    return sync(gen())


def _device_perm(n):
    """Pseudo-random permutation on device: sort random keys, carry iota."""
    @jax.jit
    def gen():
        i = jnp.arange(n, dtype=jnp.uint32)
        key = i * np.uint32(0x9E3779B9) ^ (i >> np.uint32(16))
        _, perm = jax.lax.sort((key, i.astype(jnp.int32)), num_keys=1)
        return perm
    return sync(gen())


def bench_gather():
    for L in (8, 32, 128, 256):
        rows = CAP // 8                 # 1M rows to keep it quick
        m = _device_matrix(rows, L)
        idx = _device_perm(rows)

        @jax.jit
        def f(mm, ii):
            return jnp.take(mm, ii, axis=0)

        dt = timeit(f, m, idx)
        print(f"gather[L={L}]: {dt*1e3:.1f} ms  {rows/dt/1e6:.1f} Mrows/s  "
              f"{rows*L*4/dt/1e9:.1f} GB/s")


def bench_bgather():
    L = 28                      # i32 lanes per row (Q1-ish)
    for B in (8, 16, 32):
        blocks = CAP // B
        m = _device_matrix(blocks, B * L)
        idx = _device_perm(blocks)

        @jax.jit
        def f(mm, ii):
            return jnp.take(mm, ii, axis=0)

        dt = timeit(f, m, idx)
        print(f"bgather[B={B}]: {dt*1e3:.1f} ms  {blocks/dt/1e6:.1f} "
              f"Mblk/s  {CAP*L*4/dt/1e9:.1f} GB/s")


def bench_cumsum2():
    """Packed ranks: 8 per-pid running counts in TWO i64 cumsums (16-bit
    lanes, counts < W <= 65536) instead of 8 separate i32 cumsums."""
    pid = make_pids()
    for W in (512, 2048):
        wn = CAP // W
        p2 = pid.reshape(wn, W)

        @jax.jit
        def f(p):
            lane = (p % 4).astype(jnp.int64) * np.int64(16)
            one = jnp.left_shift(np.int64(1), lane)
            w0 = jnp.where(p < 4, one, np.int64(0))
            w1 = jnp.where(p >= 4, one, np.int64(0))
            c0 = jnp.cumsum(w0, axis=1)
            c1 = jnp.cumsum(w1, axis=1)
            sel = jnp.where(p < 4, c0, c1)
            rank = (jnp.right_shift(sel, lane) & np.int64(0xFFFF)) - 1
            return rank.astype(jnp.int32), c0[:, -1], c1[:, -1]

        dt = timeit(f, p2)
        print(f"cumsum2[W={W}]: {dt*1e3:.1f} ms")


def bench_cumsum():
    pid = make_pids()
    n = 8
    for W in (512, 2048, 8192):
        wn = CAP // W
        p2 = pid.reshape(wn, W)

        @jax.jit
        def f(p):
            rank = jnp.zeros_like(p)
            counts = []
            for j in range(n):
                oh = (p == j).astype(jnp.int32)
                cs = jnp.cumsum(oh, axis=1)
                rank = jnp.where(p == j, cs - 1, rank)
                counts.append(cs[:, -1])
            return rank, jnp.stack(counts, axis=1)

        dt = timeit(f, p2)
        print(f"cumsum[W={W}]: {dt*1e3:.1f} ms")


def bench_bgu64():
    """Per-operand u64 block gather: (cap/B, B) u64 rows (B u64 = 2B i32
    lanes) — if tile-efficient at B>=64, the merge phase needs NO stacking
    pass."""
    for B in (16, 32, 64, 128):
        blocks = CAP // B

        @jax.jit
        def gen(B=B, blocks=blocks):
            i = jnp.arange(blocks, dtype=jnp.uint64)[:, None]
            j = jnp.arange(B, dtype=jnp.uint64)[None, :]
            return i * np.uint64(0x9E3779B97F4A7C15) + j
        m = sync(gen())
        idx = _device_perm(blocks)

        @jax.jit
        def f(mm, ii):
            return jnp.take(mm, ii, axis=0)

        dt = timeit(f, m, idx)
        print(f"bgu64[B={B}]: {dt*1e3:.1f} ms  {blocks/dt/1e6:.2f} Mblk/s  "
              f"{CAP*8/dt/1e9:.1f} GB/s/operand")


def bench_taw10():
    """Windowed take_along_axis applied to 10 u64 operands with ONE shared
    per-window permutation (the sort-free spread candidate)."""
    ops = make_payloads()
    for W in (512, 2048):
        wn = CAP // W
        ops2 = tuple(o.reshape(wn, W) for o in ops)

        @jax.jit
        def gen_idx(wn=wn, W=W):
            i = jnp.arange(W, dtype=jnp.uint32)[None, :]
            w = jnp.arange(wn, dtype=jnp.uint32)[:, None]
            key = (i * np.uint32(0x9E3779B9) + w * np.uint32(40503)) \
                & np.uint32(0xFFFFFF)
            _, perm = jax.lax.sort(
                (key, jnp.broadcast_to(i.astype(jnp.int32), (wn, W))),
                num_keys=1, dimension=1)
            return perm
        idx = sync(gen_idx())

        @jax.jit
        def f(ii, *ops):
            return tuple(jnp.take_along_axis(o, ii, axis=1) for o in ops)

        dt = timeit(f, idx, *ops2)
        gb = N_OPS * CAP * 8 / 1e9
        print(f"taw10[W={W}]: {dt*1e3:.1f} ms  {gb/dt:.2f} GB/s")


def bench_taw():
    L = 28
    for W in (512, 2048):
        wn = CAP // W
        m = sync(jax.jit(lambda: _device_matrix(CAP, L).reshape(wn, W, L))())

        @jax.jit
        def gen_idx():
            i = jnp.arange(W, dtype=jnp.uint32)[None, :]
            w = jnp.arange(wn, dtype=jnp.uint32)[:, None]
            key = (i * np.uint32(0x9E3779B9) + w * np.uint32(40503)) \
                & np.uint32(0xFFFFFF)
            _, perm = jax.lax.sort(
                (key, jnp.broadcast_to(i.astype(jnp.int32), (wn, W))),
                num_keys=1, dimension=1)
            return perm
        idx = sync(gen_idx())

        @jax.jit
        def f(mm, ii):
            return jnp.take_along_axis(mm, ii[:, :, None], axis=1)

        dt = timeit(f, m, idx)
        print(f"taw[W={W}]: {dt*1e3:.1f} ms  {CAP/dt/1e6:.1f} Mrows/s")


def main():
    which = sys.argv[1:] or ["copy", "sortg"]
    for name in which:
        globals()[f"bench_{name}"]()


if __name__ == "__main__":
    main()
