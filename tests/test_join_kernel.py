"""The join kernel against a nested-loop oracle, and its lowered structure.

Both engines run ``ops/join.py``, so the whole-engine tests
(``test_joins.py``) compare the kernel with itself. Here ``join_size`` ->
``join_gather`` is held, row for row, to a brute-force join written below
that imports nothing of ``ops/``: stream-major output, a stream row's
matches by build row index, unmatched build rows last in row order, nulls
never match, NaN matches NaN, -0.0 matches 0.0.
"""
import collections
import math
import re

import numpy as np
import pytest

import spark_rapids_tpu.device  # noqa: F401  (x64 on)
import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.dtypes import DType
from spark_rapids_tpu.exprs.core import ColV
from spark_rapids_tpu.ops import join as jk

KINDS = ("inner", "left", "right", "full", "left_semi", "left_anti", "cross")
S, B = 13, 17          # one pair of capacities: eager jax compiles per shape


# ---------------------------------------------------------------------------
# the oracle: python values, two loops
# ---------------------------------------------------------------------------
def _same(a, b):
    if isinstance(a, float) and isinstance(b, float):
        return (math.isnan(a) and math.isnan(b)) or a == b
    return a == b


def _rows_match(lrow, rrow):
    return all(a is not None and b is not None and _same(a, b)
               for a, b in zip(lrow, rrow))


def oracle(lrows, rrows, l_alive, r_alive, how):
    """[(stream row or None, build row or None)] in the contract's order."""
    out = []
    matched_b = set()
    for i, lrow in enumerate(lrows):
        if not l_alive[i]:
            continue
        if how == "cross":
            out.extend((i, j) for j in range(len(rrows)) if r_alive[j])
            continue
        hits = [j for j, rrow in enumerate(rrows)
                if r_alive[j] and _rows_match(lrow, rrow)]
        matched_b.update(hits)
        if how == "left_semi":
            out.extend([(i, None)] if hits else [])
        elif how == "left_anti":
            out.extend([] if hits else [(i, None)])
        else:
            out.extend((i, j) for j in hits)
            if not hits and how in ("left", "full"):
                out.append((i, None))
    if how in ("right", "full"):
        out.extend((None, j) for j in range(len(rrows))
                   if r_alive[j] and j not in matched_b)
    return out


# ---------------------------------------------------------------------------
# the cases: per key column (dtype, left values, right values); None = null
# ---------------------------------------------------------------------------
def _cycle(pool, n, step=1, start=0):
    return [pool[(start + i * step) % len(pool)] for i in range(n)]


_LONGS = [7, -3, None, 2 ** 40 + 1, 7, 0, -3, 2 ** 40 + 1, 5, None, -2 ** 62]
_INTS = [1, 2, None, -5, 2 ** 31 - 1, -2 ** 31, 2, 1, 9]
_DOUBLES = [float("nan"), -0.0, 0.0, 1.5, None, float("inf"),
            float("-inf"), -1.5, float("nan"), 1.5]
_STR_L = [b"", b"a", b"ab", None, b"abcde", b"b", b"ab", b"a\x00"]
_STR_R = [b"ab", b"abcdefghij", b"", b"abcdefghiJ", b"a", None, b"abcde",
          b"abcdefghij", b"a\x00"]
_ALL = [True] * 64
_SCATTERED_L = [i % 4 != 1 for i in range(64)]
_SCATTERED_R = [i % 3 != 2 for i in range(64)]
_NONE = [False] * 64

# name -> (key columns [(dtype, width_l, width_r, left, right)], l_alive, r_alive)
CASES = {
    "long": ([(DType.LONG, 0, 0, _cycle(_LONGS, S), _cycle(_LONGS, B, 3))],
             _ALL, _ALL),
    "int": ([(DType.INT, 0, 0, _cycle(_INTS, S), _cycle(_INTS, B, 2, 1))],
            _ALL, _ALL),
    "double_nan_zero": ([(DType.DOUBLE, 0, 0, _cycle(_DOUBLES, S),
                          _cycle(_DOUBLES, B, 3, 2))], _ALL, _ALL),
    "string_unequal_widths": ([(DType.STRING, 5, 12, _cycle(_STR_L, S),
                                _cycle(_STR_R, B))], _ALL, _ALL),
    "long_and_string": ([(DType.LONG, 0, 0, _cycle([1, 2, None], S),
                          _cycle([2, 1, 1, None], B)),
                         (DType.STRING, 5, 12, _cycle(_STR_L, S, 3),
                          _cycle(_STR_R, B, 2))], _ALL, _ALL),
    "all_null": ([(DType.LONG, 0, 0, [None] * S, [None] * B)], _ALL, _ALL),
    "heavy_duplicates": ([(DType.LONG, 0, 0, _cycle([4, 9], S),
                           _cycle([9, 9, 4], B))], _ALL, _ALL),
    "empty_build": ([(DType.LONG, 0, 0, _cycle(_LONGS, S),
                      _cycle(_LONGS, B, 3))], _ALL, _NONE),
    "empty_stream": ([(DType.INT, 0, 0, _cycle(_INTS, S),
                       _cycle(_INTS, B, 2, 1))], _NONE, _ALL),
    # the dead rows hold keys that live rows hold too
    "dead_rows_both_sides": ([(DType.LONG, 0, 0, _cycle([4, 9, None, 5], S),
                               _cycle([9, 5, 4, 4, None], B))],
                             _SCATTERED_L, _SCATTERED_R),
}


def _colv(xp, dtype, width, values):
    n = len(values)
    validity = np.array([v is not None for v in values])
    if dtype is DType.STRING:
        data = np.zeros((n, width), dtype=np.uint8)
        lengths = np.zeros(n, dtype=np.int32)
        for i, v in enumerate(values):
            if v is not None:
                data[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
                lengths[i] = len(v)
        return ColV(dtype, xp.asarray(data), xp.asarray(validity),
                    xp.asarray(lengths))
    # a null's slot holds a value that live keys hold too
    filler = next((v for v in values if v is not None), 0)
    data = np.array([filler if v is None else v for v in values],
                    dtype=dtype.np_dtype())
    return ColV(dtype, xp.asarray(data), xp.asarray(validity))


@pytest.mark.parametrize("xp", [np, jnp], ids=["numpy", "jax"])
@pytest.mark.parametrize("how", KINDS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_nested_loop_oracle(case, how, xp):
    cols, l_alive, r_alive = CASES[case]
    l_alive, r_alive = l_alive[:S], r_alive[:B]
    l_keys = [_colv(xp, dt, wl, lv) for dt, wl, _, lv, _ in cols]
    r_keys = [_colv(xp, dt, wr, rv) for dt, _, wr, _, rv in cols]
    lrows = list(zip(*[lv for _, _, _, lv, _ in cols]))
    rrows = list(zip(*[rv for _, _, _, _, rv in cols]))
    want = oracle(lrows, rrows, l_alive, r_alive, how)

    sized = jk.join_size(xp, l_keys, r_keys, xp.asarray(np.array(l_alive)),
                         xp.asarray(np.array(r_alive)), how)
    assert "sgid" not in sized
    total = int(sized["total"])
    assert total == len(want)
    # a capacity above the total, as the device path's bucket is
    lrow, lvalid, rrow, rvalid, _ = jk.join_gather(
        xp, sized, S, B, total + 3, how)
    lrow, lvalid, rrow, rvalid = (np.asarray(a) for a in
                                  (lrow, lvalid, rrow, rvalid))
    got = [(int(lrow[p]) if lvalid[p] else None,
            int(rrow[p]) if rvalid[p] else None) for p in range(total)]
    assert got == want
    assert not lvalid[total:].any() and not rvalid[total:].any()


def test_the_oracle_itself():
    """The oracle on a join small enough to write down."""
    lrows, rrows = [(1,), (None,), (2,), (1,)], [(1,), (3,), (1,), (None,)]
    alive = [True] * 4
    assert oracle(lrows, rrows, alive, alive, "inner") == [
        (0, 0), (0, 2), (3, 0), (3, 2)]
    assert oracle(lrows, rrows, alive, alive, "full") == [
        (0, 0), (0, 2), (1, None), (2, None), (3, 0), (3, 2),
        (None, 1), (None, 3)]
    assert oracle(lrows, rrows, alive, alive, "left_anti") == [
        (1, None), (2, None)]
    assert oracle([(float("nan"),), (-0.0,)], [(0.0,), (float("nan"),)],
                  [True] * 2, [True] * 2, "inner") == [(0, 1), (1, 0)]


# ---------------------------------------------------------------------------
# structure: what the lowered kernel holds, where no chip is
# ---------------------------------------------------------------------------
def _lowered(how, s=256, b=4096):
    def fn(lk, lv, rk, rv, ln, rn):
        sized = jk.join_size(
            jnp, [ColV(DType.LONG, lk, lv)], [ColV(DType.LONG, rk, rv)],
            jnp.arange(s, dtype=np.int32) < ln,
            jnp.arange(b, dtype=np.int32) < rn, how)
        return tuple(sized[k] for k in sorted(sized))
    return jax.jit(fn).lower(
        jax.ShapeDtypeStruct((s,), np.int64), jax.ShapeDtypeStruct((s,), bool),
        jax.ShapeDtypeStruct((b,), np.int64), jax.ShapeDtypeStruct((b,), bool),
        jax.ShapeDtypeStruct((), np.int32), jax.ShapeDtypeStruct((), np.int32))


@pytest.mark.parametrize("how,sorts", [
    ("inner", 2), ("left", 2), ("left_semi", 2), ("left_anti", 2),
    ("right", 3), ("full", 3)])
def test_join_size_lowers_to_sorts_and_scans_only(how, sorts):
    """One LONG key, S = 256, B = 4,096: two sorts of the union (a third, of
    the build side alone, where right/full need matched_b per build row) and
    no gather or scatter at all. Before PR 31: five sorts and 17 gathers of
    S+B rows after CSE, 1.86 s of Q3's 2.1 s on a v5e."""
    low = _lowered(how)
    ops = collections.Counter(re.findall(
        r"stablehlo\.(sort|gather|scatter|dynamic_gather|while)\b",
        low.as_text()))
    assert ops == {"sort": sorts}, ops
    hlo = low.compile().as_text()
    compiled = collections.Counter(re.findall(
        r"[ )](sort|gather|scatter)\(", hlo))
    assert compiled == {"sort": sorts}, compiled
    # 64-bit words are emulated on the TPU: the LONG key rides the first
    # sort, every position, count and row index is 32 bits wide
    sort_types = [ln.split(" sort(")[0] for ln in hlo.splitlines()
                  if " sort(" in ln]
    assert sum(t.count("64[") for t in sort_types) == 1, sort_types


@pytest.mark.parametrize("width", [8, 64])
def test_a_string_key_costs_the_same_sorts_at_any_width(width):
    """A STRING key is ranked to one int32 word by a loop of two narrow
    sorts a chunk (``_string_rank``), so the program holds four sorts and a
    loop whatever the key's width: a sort keyed by every chunk at once did
    not finish compiling for the TPU in 25 minutes at 32 bytes."""
    s, b = 256, 1024

    def fn(ld, lv, ll, rd, rv, rl):
        sized = jk.join_size(
            jnp, [ColV(DType.STRING, ld, lv, ll)],
            [ColV(DType.STRING, rd, rv, rl)],
            jnp.ones(s, dtype=bool), jnp.ones(b, dtype=bool), "inner")
        return tuple(sized[k] for k in sorted(sized))
    sd = jax.ShapeDtypeStruct
    text = jax.jit(fn).lower(
        sd((s, width), np.uint8), sd((s,), bool), sd((s,), np.int32),
        sd((b, width), np.uint8), sd((b,), bool), sd((b,), np.int32)
    ).as_text()
    ops = collections.Counter(re.findall(
        r"stablehlo\.(sort|gather|scatter|dynamic_gather|while)\b", text))
    assert ops == {"sort": 4, "while": 1}, ops
