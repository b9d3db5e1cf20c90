"""The aggregate's sorted-segment reduction, device path against the numpy
path (the semantics' definition) of ``group_aggregate`` / ``merge_aggregate``
at capacities where ``bk.SortedSegmentStacker`` reduces by segmented scans
and one compaction sort (a multiple of 512 rows, 2,048 at least), with
groups of 1-7 rows as Q18's and with groups of 64 rows and more; under
2,048 rows the plain form stands. And what the lowered programs of Q18's
first aggregate hold: no scatter and no gather at all."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar.dtypes import DType
from spark_rapids_tpu.exprs import Count, First, Last, Max, Min, Sum
from spark_rapids_tpu.exprs.core import BoundReference, ColV, EvalCtx
from spark_rapids_tpu.ops import aggregate
from spark_rapids_tpu.ops import batch_kernels as bk

CAP = 4096
WIDTH = 16
MODES = ("sort", "hash")


def _strings(values):
    data = np.zeros((len(values), WIDTH), np.uint8)
    lengths = np.zeros(len(values), np.int32)
    for i, v in enumerate(values):
        raw = v.encode()
        data[i, :len(raw)] = np.frombuffer(raw, np.uint8)
        lengths[i] = len(raw)
    return data, lengths


def _col(dtype, values, valid=None, cap=CAP):
    """A column of ``cap`` rows, the given values first."""
    n = len(values)
    validity = np.zeros(cap, bool)
    validity[:n] = True if valid is None else valid
    if dtype is DType.STRING:
        data = np.zeros((cap, WIDTH), np.uint8)
        lengths = np.zeros(cap, np.int32)
        data[:n], lengths[:n] = _strings(values)
        return ColV(dtype, data, validity, lengths)
    data = np.zeros(cap, dtype.np_dtype())
    data[:n] = values
    return ColV(dtype, data, validity)


def _ref(i, col):
    return BoundReference(i, col.dtype, True)


def _arrays(cols):
    return [a for c in cols for a in (c.data, c.validity, c.lengths)
            if a is not None]


def _colvs(like, flat):
    out, i = [], 0
    for c in like:
        k = 2 if c.lengths is None else 3
        out.append(ColV(c.dtype, *flat[i:i + k]))
        i += k
    return out


def _both(cols, keys, fns, n, mode, extra_mask=None, cap=CAP):
    """(numpy result, device result) of one aggregation, each (key columns,
    result columns, groups, flag or None)."""
    def run(xp, flat, mask):
        ectx = EvalCtx(xp, _colvs(cols, flat), cap, WIDTH)
        res = aggregate.group_aggregate(
            xp, ectx, keys, fns, n, cap, grouping=mode, extra_mask=mask)
        flag = res[3] if mode == "hash" else None
        return list(res[0]), list(res[1]), res[2], flag

    flat = _arrays(cols)
    host = run(np, flat, extra_mask)
    nk, nr = len(host[0]), len(host[1])

    def prog(mask, *flat):
        ks, rs, ng, flag = run(jnp, flat, mask)
        return (tuple(_arrays(ks)), tuple(_arrays(rs)), ng, flag)

    ks, rs, ng, flag = jax.jit(prog)(extra_mask, *flat)
    dev = (_colvs(host[0], [np.asarray(a) for a in ks]),
           _colvs(host[1], [np.asarray(a) for a in rs]), int(ng),
           None if flag is None else bool(flag))
    assert len(dev[0]) == nk and len(dev[1]) == nr
    return (host[0], host[1], int(host[2]),
            None if host[3] is None else bool(host[3])), dev


def _group_order(key_cols, n):
    """The rows of the first ``n`` groups ordered by their key: in ``hash``
    mode the groups come in the order of a hash that numpy and XLA need not
    round alike for a double."""
    def cell(k, i):
        if not np.asarray(k.validity)[i]:
            return (0, "")
        v = np.asarray(k.data)[i]
        if k.dtype is DType.STRING:
            return (1, bytes(v[:np.asarray(k.lengths)[i]]).hex())
        if k.dtype.is_floating:
            return (2, "") if np.isnan(v) else (1, float(v) + 0.0)
        return (1, int(v))
    rows = [tuple(cell(k, i) for k in key_cols) for i in range(n)]
    return np.asarray(sorted(range(n), key=rows.__getitem__), dtype=np.int64)


def _same_column(a: ColV, b: ColV, ia, ib, exact=False):
    av, bv = np.asarray(a.validity)[ia], np.asarray(b.validity)[ib]
    assert (av == bv).all()
    ad, bd = np.asarray(a.data)[ia][av], np.asarray(b.data)[ib][av]
    if a.dtype is DType.STRING:
        assert (np.asarray(a.lengths)[ia][av]
                == np.asarray(b.lengths)[ib][av]).all()
        assert (ad == bd).all()
    elif a.dtype.is_floating and not exact:
        np.testing.assert_allclose(ad, bd, rtol=1e-12, atol=0, equal_nan=True)
    else:
        np.testing.assert_array_equal(ad, bd)
        if a.dtype.is_floating:
            assert (np.signbit(ad) == np.signbit(bd)).all()


def _same(host, dev, exact=False):
    assert dev[2] == host[2]
    assert dev[3] == host[3]
    ia, ib = _group_order(host[0], host[2]), _group_order(dev[0], dev[2])
    for a, b in zip(host[0] + host[1], dev[0] + dev[1]):
        _same_column(a, b, ia, ib, exact)
        # nothing past the groups is valid
        assert not np.asarray(b.validity)[host[2]:].any()


def _dense_keys(rng, n, lo=1, hi=8):
    """Group ids of ``n`` rows in groups of lo..hi-1 rows, shuffled."""
    sizes = rng.integers(lo, hi, n)
    ids = np.repeat(np.arange(n), sizes)[:n]
    return rng.permutation(ids)


@pytest.fixture
def rng():
    return np.random.default_rng(33)


# ---------------------------------------------------------------------------
# every reduction kind, dense groups, both grouping modes
# ---------------------------------------------------------------------------
def _numeric_case(rng, n):
    ids = _dense_keys(rng, n)
    ints = rng.integers(-10**12, 10**12, n)
    floats = rng.normal(size=n) * 1e3
    floats[rng.random(n) < 0.1] = np.nan
    valid = rng.random(n) > 0.2
    return ids, ints, floats, valid


CASES = {
    "int_sum_and_count": lambda c: (Sum(_ref(1, c[1])), Count(_ref(1, c[1]))),
    "float_sum": lambda c: (Sum(_ref(2, c[2])),),
    "int_min_max": lambda c: (Min(_ref(1, c[1])), Max(_ref(1, c[1]))),
    "float_min_max_with_nan": lambda c: (Min(_ref(2, c[2])),
                                         Max(_ref(2, c[2]))),
    "bool_min_max": lambda c: (Min(_ref(4, c[4])), Max(_ref(4, c[4]))),
    "first_last": lambda c: (First(_ref(2, c[2]), False),
                             Last(_ref(1, c[1]), False)),
    "first_last_ignore_nulls": lambda c: (First(_ref(2, c[2]), True),
                                          Last(_ref(1, c[1]), True)),
    "first_last_of_strings": lambda c: (First(_ref(3, c[3]), True),
                                        Last(_ref(3, c[3]), False)),
    "string_min_max": lambda c: (Min(_ref(3, c[3])), Max(_ref(3, c[3]))),
    "all_together": lambda c: (Sum(_ref(2, c[2])), Count(_ref(1, c[1])),
                               Min(_ref(3, c[3])), Max(_ref(2, c[2])),
                               First(_ref(1, c[1]), True)),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_groups_reduce_as_the_numpy_path(rng, case, mode):
    n = 3500
    ids, ints, floats, valid = _numeric_case(rng, n)
    words = [f"w{v % 97:03d}" + "x" * int(v % 5) for v in ints]
    cols = [_col(DType.LONG, ids * 7 - 1000), _col(DType.LONG, ints, valid),
            _col(DType.DOUBLE, floats, valid), _col(DType.STRING, words, valid),
            _col(DType.BOOLEAN, ints % 3 == 0, valid)]
    host, dev = _both(cols, (_ref(0, cols[0]),), CASES[case](cols), n, mode)
    assert host[2] == len(np.unique(ids)) > CAP // 8
    _same(host, dev)


KEYS = {
    "int64": lambda c: (_ref(0, c[0]),),
    "string": lambda c: (_ref(1, c[1]),),
    "double": lambda c: (_ref(2, c[2]),),
    "string_double_int64": lambda c: (_ref(1, c[1]), _ref(2, c[2]),
                                      _ref(0, c[0])),
}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("key", sorted(KEYS))
def test_dense_groups_by_an_int64_a_string_and_a_double_key(rng, key, mode):
    """Null keys are one group, NaN keys another; the key that comes back
    is the group's first sorted row's."""
    n = 3000
    ids = _dense_keys(rng, n)
    key_valid = ids % 11 != 0
    doubles = ids * 0.25
    doubles[ids % 13 == 0] = np.nan
    cols = [_col(DType.LONG, ids - 500, key_valid),
            _col(DType.STRING, [f"k{v:05d}" for v in ids], key_valid),
            _col(DType.DOUBLE, doubles, key_valid),
            _col(DType.DOUBLE, rng.normal(size=n))]
    fns = (Sum(_ref(3, cols[3])), Count(_ref(3, cols[3])))
    host, dev = _both(cols, KEYS[key](cols), fns, n, mode)
    assert host[2] > CAP // 8
    _same(host, dev)


@pytest.mark.parametrize("mode", MODES)
def test_dead_rows_and_an_extra_mask_join_no_group(rng, mode):
    n = 3000
    ids = _dense_keys(rng, n)
    vals = rng.integers(1, 51, n).astype(np.float64)
    # live values past the row count, and a mask that takes whole groups
    # and single rows out
    cols = [_col(DType.LONG, np.r_[ids, np.arange(CAP - n)]),
            _col(DType.DOUBLE, np.r_[vals, np.full(CAP - n, 1e9)])]
    mask = np.ones(CAP, bool)
    mask[:n] = (ids % 5 != 0) & (rng.random(n) > 0.1)
    fns = (Sum(_ref(1, cols[1])), Count(_ref(1, cols[1])))
    host, dev = _both(cols, (_ref(0, cols[0]),), fns, n, mode,
                      extra_mask=mask)
    kept = np.unique(ids[mask[:n]])
    assert host[2] == len(kept)
    _same(host, dev, exact=True)
    sums = np.asarray(dev[1][0].data)[:dev[2]]
    order = np.argsort(np.asarray(dev[0][0].data)[:dev[2]])
    np.testing.assert_array_equal(
        sums[order], [vals[mask[:n] & (ids == k)].sum() for k in kept])


# ---------------------------------------------------------------------------
# a float sum adds its own group's rows and nothing else
# ---------------------------------------------------------------------------
def _neighbours(rng, big):
    """Groups of four rows: every third one cancels to exactly 0.0, its
    neighbours hold ``big``."""
    groups = 800
    ids = np.repeat(np.arange(groups), 4)
    vals = np.tile([big, 1.0, 2.0, 3.0], groups)
    quiet = np.arange(groups) % 3 == 1
    # exact in any order of addition, and lost beside 1e15 (ulp 0.125)
    vals.reshape(groups, 4)[quiet] = [0.5 + 2.0**-20, 0.25, -0.5 - 2.0**-20,
                                      -0.25]
    order = rng.permutation(len(ids))
    return ids[order], vals[order], quiet


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("big", [1e15, np.inf, -np.inf])
def test_a_group_that_cancels_is_exactly_zero_whatever_lies_beside_it(
        rng, big, mode):
    """A difference of running totals would leave residue of the 1e15 next
    door in the group that sums to 0.0 (and flip ``HAVING sum(x) > 0``), and
    turn it into NaN beside an inf."""
    ids, vals, quiet = _neighbours(rng, big)
    cols = [_col(DType.LONG, ids), _col(DType.DOUBLE, vals)]
    host, dev = _both(cols, (_ref(0, cols[0]),), (Sum(_ref(1, cols[1])),),
                      len(ids), mode)
    _same(host, dev)
    keys = np.asarray(dev[0][0].data)[:dev[2]]
    sums = np.asarray(dev[1][0].data)[:dev[2]]
    assert (sums[quiet[keys]] == 0.0).all()
    assert not (sums[quiet[keys]] > 0).any()
    assert (sums[~quiet[keys]] == big + 6.0).all()


@pytest.mark.parametrize("mode", MODES)
def test_the_key_of_a_group_is_its_first_rows(rng, mode):
    """-0.0 and 0.0 are one group; the representative is the first row in
    the input's order, as the numpy path picks it."""
    n = 3000
    ids = _dense_keys(rng, n).astype(np.float64)
    zero = ids % 2 == 0
    ids[zero] = np.where(rng.random(zero.sum()) < 0.5, -0.0, 0.0)
    cols = [_col(DType.DOUBLE, ids), _col(DType.LONG, np.arange(n))]
    host, dev = _both(cols, (_ref(0, cols[0]),),
                      (Count(_ref(1, cols[1])), First(_ref(1, cols[1]), False)),
                      n, mode)
    _same(host, dev, exact=True)
    keys = np.asarray(dev[0][0].data)[:dev[2]]
    (z,) = np.flatnonzero(keys == 0)
    first = np.flatnonzero(ids == 0)[0]
    assert np.signbit(keys[z]) == np.signbit(ids[first])
    assert np.asarray(dev[1][1].data)[z] == first


# ---------------------------------------------------------------------------
# the flags, other group sizes, other capacities
# ---------------------------------------------------------------------------
def test_more_groups_than_group_cap_are_still_flagged(rng, monkeypatch):
    monkeypatch.setattr(aggregate, "GROUP_CAP", 512)
    n = 3500
    ids = _dense_keys(rng, n)
    cols = [_col(DType.LONG, ids), _col(DType.DOUBLE, rng.normal(size=n))]
    host, dev = _both(cols, (_ref(0, cols[0]),), (Sum(_ref(1, cols[1])),),
                      n, "hash")
    assert host[2] == dev[2] == len(np.unique(ids)) > 512
    assert host[3] is True and dev[3] is True
    assert dev[1][0].data.shape == (512,)
    # the first 512 groups are whole all the same (an int64 key hashes
    # alike on both paths: one order)
    first = np.arange(511)
    for a, b in zip(host[0] + host[1], dev[0] + dev[1]):
        _same_column(a, b, first, first)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("rows", [64, 700])
def test_groups_of_many_rows_reduce_the_same_way(rng, rows, mode):
    """Groups that span whole blocks of the scan, and blocks without a
    group's end: the carries. (Before PR 33 such groups took a block-local
    one-hot form; on a v5e the scans were no slower on any shape.)"""
    n = 4000
    ids = rng.permutation(np.arange(n) // rows)
    vals = rng.normal(size=n)
    cols = [_col(DType.LONG, ids), _col(DType.DOUBLE, vals),
            _col(DType.STRING, [f"s{v % 89:02d}" for v in range(n)])]
    fns = (Sum(_ref(1, cols[1])), Count(_ref(1, cols[1])),
           Min(_ref(2, cols[2])), Last(_ref(1, cols[1]), True))
    host, dev = _both(cols, (_ref(0, cols[0]),), fns, n, mode)
    assert host[2] == -(-n // rows)
    _same(host, dev)


@pytest.mark.parametrize("mode", MODES)
def test_without_keys_there_is_one_group_and_nothing_to_sort(rng, mode):
    n = 3000
    cols = [_col(DType.DOUBLE, rng.normal(size=n), rng.random(n) > 0.3)]
    fns = (Sum(_ref(0, cols[0])), Count(_ref(0, cols[0])),
           Max(_ref(0, cols[0])))
    host, dev = _both(cols, (), fns, n, mode)
    _same(host, dev)
    text = jax.jit(lambda d, v: aggregate.group_aggregate(
        jnp, EvalCtx(jnp, [ColV(DType.DOUBLE, d, v)], CAP, WIDTH), (), fns,
        n, CAP)[1][0].data).lower(cols[0].data, cols[0].validity).as_text()
    assert not re.search(r"stablehlo\.(sort|scatter|gather|case|if)\b", text)


@pytest.mark.parametrize("cap", [512, 1536, 2304])
def test_small_and_odd_capacities_keep_the_plain_form(rng, cap):
    n = cap - 100
    ids = _dense_keys(rng, n)
    cols = [_col(DType.LONG, ids, cap=cap),
            _col(DType.DOUBLE, rng.normal(size=n), cap=cap)]
    fns = (Sum(_ref(1, cols[1])), Min(_ref(1, cols[1])))
    host, dev = _both(cols, (_ref(0, cols[0]),), fns, n, "sort", cap=cap)
    _same(host, dev)
    assert aggregate.reduce_form("sort", cap) == "plain"


def test_what_a_span_says_of_the_form():
    assert aggregate.reduce_form("onehot", CAP) == "onehot"
    assert [aggregate.reduce_form(m, CAP) for m in MODES] == ["scan"] * 2
    assert aggregate.reduce_form("hash", 8_388_608) == "scan"
    assert aggregate.reduce_form("hash", 2048 - 512) == "plain"


# ---------------------------------------------------------------------------
# the final stage over partial buffers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows", [3, 100])
def test_merge_aggregate_reduces_partials_the_same_way(rng, rows):
    n = 3600
    ids = rng.permutation(np.arange(n) // rows)
    keys = [_col(DType.LONG, ids, ids % 17 != 0)]
    bufs = [_col(DType.DOUBLE, rng.normal(size=n), rng.random(n) > 0.1),
            _col(DType.LONG, rng.integers(0, 9, n)),
            _col(DType.DOUBLE, rng.normal(size=n), rng.random(n) > 0.5)]
    fns = (Sum(BoundReference(0, DType.DOUBLE, True)),
           Count(BoundReference(0, DType.DOUBLE, True)),
           Max(BoundReference(0, DType.DOUBLE, True)))
    alive = np.arange(CAP) < n
    host = aggregate.merge_aggregate(np, keys, bufs, fns, alive, CAP)

    def prog(*flat):
        ks, rs, ng = aggregate.merge_aggregate(
            jnp, _colvs(keys, flat[:2]), _colvs(bufs, flat[2:]), fns,
            flat[-1], CAP)
        return tuple(_arrays(ks)), tuple(_arrays(rs)), ng

    ks, rs, ng = jax.jit(prog)(*_arrays(keys), *_arrays(bufs), alive)
    assert int(ng) == int(host[2])
    rows = np.arange(int(ng))
    for a, b in zip(list(host[0]) + list(host[1]),
                    _colvs(host[0], ks) + _colvs(host[1], rs)):
        _same_column(a, b, rows, rows)


# ---------------------------------------------------------------------------
# the scan itself
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [7, 512, 2048, 3 * 2048, 512 * 512 * 4])
def test_segmented_scan_leaves_each_segments_reduction_on_its_first_row(
        rng, n):
    ends = rng.random(n) < 0.3
    if n > 100_000:
        # a segment over many blocks, and blocks without an end
        ends[1000:200_000] = False
    ends[-1] = True
    ints = rng.integers(-100, 100, n).astype(np.int64)
    floats = rng.normal(size=n)
    got = jax.jit(lambda e, a, b, c: bk.segmented_scan(
        jnp, e, ["sum", "min", "max"], [a, b, c]))(ends, ints, floats, ints)
    seg = np.r_[0, np.cumsum(ends)[:-1]]
    first = np.flatnonzero(np.r_[True, ends[:-1]])
    np.testing.assert_array_equal(np.asarray(got[0])[first],
                                  np.add.reduceat(ints, first))
    np.testing.assert_array_equal(np.asarray(got[1])[first],
                                  np.minimum.reduceat(floats, first))
    np.testing.assert_array_equal(np.asarray(got[2])[first],
                                  np.maximum.reduceat(ints, first))
    # every row, not only the first: the reduction from it to the end
    if n <= 2048:
        for i in range(n):
            j = np.flatnonzero(seg == seg[i])[-1] + 1
            assert np.asarray(got[0])[i] == ints[i:j].sum()


# ---------------------------------------------------------------------------
# structure: what the lowered programs of Q18's first aggregate hold
# ---------------------------------------------------------------------------
def _lowered_first_aggregate(mode, cap):
    """sum(l_quantity) group by l_orderkey, as the exec builds it."""
    keys = (BoundReference(0, DType.LONG, True),)
    fns = (Sum(BoundReference(1, DType.DOUBLE, True)),)

    def fn(num_rows, kd, kv, qd, qv):
        ectx = EvalCtx(jnp, [ColV(DType.LONG, kd, kv),
                             ColV(DType.DOUBLE, qd, qv)], cap, 64)
        res = aggregate.group_aggregate(jnp, ectx, keys, fns, num_rows, cap,
                                        grouping=mode)
        return (tuple(_arrays(res[0])), tuple(_arrays(res[1])),
                tuple(res[2:]))

    sd = jax.ShapeDtypeStruct
    return jax.jit(fn).lower(
        sd((), np.int32), sd((cap,), np.int64), sd((cap,), bool),
        sd((cap,), np.float64), sd((cap,), bool))


@pytest.mark.parametrize("cap", [CAP, 4 * aggregate.GROUP_CAP])
@pytest.mark.parametrize("mode", MODES)
def test_q18s_first_aggregate_lowers_to_sorts_and_scans_only(mode, cap):
    """Before PR 33 the program held a scatter of all rows for every (kind,
    dtype) bucket behind a ``cond`` that Q18's four-row groups always took,
    a gather of the keys at capacity, and in ``hash`` mode two binary
    searches: 2.3 s of Q18's 3.7 on a v5e. Now: the key sort and the one
    compaction sort, nothing that scatters, gathers, branches or loops."""
    low = _lowered_first_aggregate(mode, cap)
    ops = collections.Counter(re.findall(
        r"stablehlo\.(sort|gather|scatter|dynamic_gather|while|case|if)\b",
        low.as_text()))
    assert ops == {"sort": 2}, ops
    if cap == CAP:
        compiled = collections.Counter(re.findall(
            r"[ )](sort|gather|scatter|conditional|while)\(",
            low.compile().as_text()))
        assert compiled == {"sort": 2}, compiled
