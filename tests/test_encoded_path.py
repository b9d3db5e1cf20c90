"""Compressed columnar data path: encoded upload bit-identity, device RLE
expansion, mixed-encoding parquet chunks, dictionary unification, the
encoded-domain filter/group-by/join rewrites, the lz4 shuffle codec, and
codec negotiation."""
import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.api import TpuSession
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar import encoding as ce
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.columnar.dtypes import DType, Schema
from spark_rapids_tpu.config import TpuConf
from spark_rapids_tpu.execs.base import ExecContext
from spark_rapids_tpu.execs.tpu_execs import concat_device_batches
from spark_rapids_tpu.io.datasource import PartitionedFile
from spark_rapids_tpu.io.parquet import TpuParquetScanExec
from spark_rapids_tpu.io.parquet_pages import (index_runs, read_dict_column,
                                               rle_bp_decode, run_count)
from spark_rapids_tpu.testing import assert_tables_equal
from spark_rapids_tpu.utils import metrics as um
from spark_rapids_tpu.utils import tracing


def _write(table: pa.Table, tmp_path, name="t.parquet", **kw) -> str:
    path = str(tmp_path / name)
    pq.write_table(table, path, **kw)
    return path


def _scan_batches(path, schema, conf=None):
    scan = TpuParquetScanExec((PartitionedFile(path),), schema)
    ctx = ExecContext(conf or TpuConf({}), partition_id=0, num_partitions=1)
    return list(scan.execute(ctx))


def _roundtrip(path, table, conf=None):
    batches = _scan_batches(path, Schema.from_pa(table.schema), conf)
    return pa.concat_tables(b.to_arrow() for b in batches), batches


# ------------------------------------------------------- upload bit-identity
def _encoded_vs_decoded_table():
    rng = np.random.default_rng(7)
    n = 5000
    return pa.table({
        # dictionary forms of every flavor the issue names
        "dict_str": pa.array(np.array(["aa", "bb", "cc"])[
            rng.integers(0, 3, n)]).dictionary_encode(),
        "dict_i64": pa.array(rng.integers(0, 9, n),
                             pa.int64()).dictionary_encode(),
        "dict_f64": pa.array(np.round(rng.uniform(0, 1, n), 2),
                             pa.float64()).dictionary_encode(),
        "nulls": pa.array([None if v % 11 == 0 else int(v)
                           for v in rng.integers(0, 6, n)],
                          pa.int64()).dictionary_encode(),
        "plain_f64": pa.array(rng.uniform(size=n) * 1e9),
    })


def test_encoded_upload_bit_identical_to_decoded():
    """Dictionary (string/int/double), null-bearing, and DOUBLE
    bits-sibling columns: the encoded upload must be bit-identical to the
    decoded single-shot upload of the same rows."""
    t = _encoded_vs_decoded_table()
    enc = DeviceBatch.from_arrow(t, 16)
    decoded_t = pa.table({f.name: (t.column(f.name).combine_chunks()
                                   .cast(f.type.value_type)
                                   if pa.types.is_dictionary(f.type)
                                   else t.column(f.name))
                          for f in t.schema})
    dec = DeviceBatch.from_arrow(decoded_t, 16)
    n = t.num_rows
    for ci, (a, b) in enumerate(zip(enc.columns, dec.columns)):
        valid = np.asarray(a.validity[:n])
        assert np.array_equal(valid, np.asarray(b.validity[:n])), ci
        # data at INVALID rows is garbage by contract (the encoded path
        # points null indices at dict slot 0, the decoded path stages 0)
        assert np.array_equal(np.asarray(a.data[:n])[valid],
                              np.asarray(b.data[:n])[valid]), ci
        assert (a.bits is None) == (b.bits is None), ci
        if a.bits is not None:
            assert np.array_equal(np.asarray(a.bits[:n])[valid],
                                  np.asarray(b.bits[:n])[valid]), ci
    # the f64 bits sibling survived the encoded path
    assert enc.column_by_name("dict_f64").bits is not None
    # encodings retained for unique dictionaries
    assert enc.column_by_name("dict_str").encoding is not None
    assert enc.column_by_name("dict_str").encoding.lengths is not None
    assert enc.column_by_name("plain_f64").encoding is None


def test_ree_upload_bit_identical_and_double_bits():
    ends = pa.array(np.array([100, 228, 412, 500], np.int32))
    vals = pa.array([1.5, -0.0, float("nan"), 3.75], pa.float64())
    ree = pa.RunEndEncodedArray.from_arrays(ends, vals)
    t = pa.table({"x": ree})
    plain = pa.table({"x": ce.ree_to_plain(ree)})
    a = DeviceBatch.from_arrow(t, 16).columns[0]
    b = DeviceBatch.from_arrow(plain, 16).columns[0]
    assert np.array_equal(np.asarray(a.bits[:500]), np.asarray(b.bits[:500]))
    assert np.array_equal(np.asarray(a.data[:500]), np.asarray(b.data[:500]),
                          equal_nan=True)
    # slicing an REE table stays encoded and exact (NaN == NaN comparison:
    # pa.Table.equals is NaN-strict)
    s = t.slice(150, 300)
    sa = DeviceBatch.from_arrow(s, 16)
    assert_tables_equal(plain.slice(150, 300), sa.to_arrow())


# ------------------------------------------- the run-end expansion program
def _ree(num_runs, seed=0, values=None, max_run=20):
    rng = np.random.default_rng(seed)
    ends = np.cumsum(rng.integers(1, max_run, num_runs)).astype(np.int32)
    if values is None:
        values = pa.array(rng.integers(-1000, 1000, num_runs), pa.int64())
    return pa.RunEndEncodedArray.from_arrays(pa.array(ends), values)


def _expected_rows(ree, capacity):
    """Every row of the capacity, padding included, by np.searchsorted:
    (run index, live mask) over the slice-relative run list."""
    ends, _ = ce.ree_staged(ree)
    idx = np.minimum(np.searchsorted(ends, np.arange(capacity),
                                     side="right"), len(ends) - 1)
    return idx, np.arange(capacity) < len(ree)


def _assert_expanded(ree, col, capacity):
    idx, live = _expected_rows(ree, capacity)
    _, vals = ce.ree_staged(ree)
    n = len(ree)
    data = np.asarray(col.data)
    assert data.shape == (capacity,)
    plain = np.asarray(ce.ree_to_plain(ree).fill_null(0))
    np_vals = np.asarray(vals.fill_null(0))
    # live rows as the host decode; capacity padding clamps to the last run
    assert np.array_equal(data[:n], plain, equal_nan=True)
    assert np.array_equal(data, np_vals[idx], equal_nan=True)
    valid = live if vals.null_count == 0 else np.logical_and(
        np.asarray(vals.is_valid())[idx], live)
    assert np.array_equal(np.asarray(col.validity), valid)
    return idx


@pytest.mark.parametrize("num_runs", [1, 2, 7, 8, 9, 1000, 65536])
def test_ree_expansion_equals_searchsorted(num_runs):
    """Run counts on both sides of the padding buckets' edges (8 | 9) up to
    65,536: the cached program's rows equal ``ree_to_plain`` and
    ``np.searchsorted`` row for row, the capacity padding included."""
    ree = _ree(num_runs, seed=num_runs, max_run=4 if num_runs > 1000 else 20)
    b = DeviceBatch.from_arrow(pa.table({"x": ree}))
    _assert_expanded(ree, b.columns[0], b.capacity)
    assert b.columns[0].encoding is None
    ends, vals = ce.ree_staged(ree)
    pends, (pvals,) = ce.pad_runs(ends, b.capacity, (np.asarray(vals),))
    assert len(pends) == len(pvals) == ce.dict_bucket(num_runs) >= num_runs
    assert (pends[num_runs:] == b.capacity).all()


@pytest.mark.parametrize("offset,length", [(1, 40), (37, 500), (5, 1),
                                           (0, 128), (200, 128)])
def test_ree_expansion_of_a_slice(offset, length):
    """A sliced REE array (``offset > 0``, a one-row slice, and a slice
    that fills its capacity exactly, whose last run end is dropped by the
    scatter) expands as its host decode."""
    ree = _ree(128, seed=offset, max_run=16).slice(offset, length)
    b = DeviceBatch.from_arrow(pa.table({"x": ree}))
    assert b.num_rows == length
    _assert_expanded(ree, b.columns[0], b.capacity)


def test_ree_expansion_of_doubles_keeps_their_bits():
    """DOUBLE runs: data and the uint64 ``bits`` sibling identical to the
    staged values' bits row for row, -0.0 and NaN included."""
    vals = pa.array([1.5, -0.0, float("nan"), 0.0, -np.inf, 3.75, -0.0,
                     float("nan"), 2.0 ** -1074], pa.float64())
    ree = _ree(9, seed=3, values=vals)
    col = DeviceBatch.from_arrow(pa.table({"x": ree})).columns[0]
    idx = _assert_expanded(ree, col, col.capacity)
    assert np.array_equal(np.asarray(col.bits),
                          np.asarray(vals).view(np.uint64)[idx])
    assert np.array_equal(np.asarray(col.data).view(np.uint64),
                          np.asarray(vals).view(np.uint64)[idx])


@pytest.mark.parametrize("null_runs", [[0], [3, 4], [8]])
def test_ree_expansion_of_null_runs(null_runs):
    """Run values with nulls: a row's validity is its run's, AND-ed with
    the live mask, so capacity padding over a valid last run stays
    invalid."""
    raw = list(range(9))
    vals = pa.array([None if i in null_runs else v for i, v in
                     enumerate(raw)], pa.int32())
    ree = _ree(9, seed=11, values=vals)
    col = DeviceBatch.from_arrow(pa.table({"x": ree})).columns[0]
    _assert_expanded(ree, col, col.capacity)
    assert not np.asarray(col.validity)[len(ree):].any()


def test_ree_expansion_is_one_cached_program(monkeypatch):
    """A second upload of the same shape (another run count of the bucket,
    another row count of the capacity) is a program-cache hit of the one
    ``ree_expand`` program, and no ``searchsorted`` is traced."""
    import jax.numpy as jnp
    from spark_rapids_tpu.serving.program_cache import global_program_cache

    def refuse(*a, **k):
        raise AssertionError("searchsorted on the upload path")
    monkeypatch.setattr(jnp, "searchsorted", refuse)
    cache = global_program_cache()
    def table(ends):
        return pa.table({"x": pa.RunEndEncodedArray.from_arrays(
            pa.array(np.array(ends, np.int32)),
            pa.array(np.arange(len(ends), dtype=np.int64)))})
    first = table([20, 40, 60, 80, 100])
    second = table([3, 9, 30, 31, 70, 90, 120])
    assert ce.dict_bucket(5) == ce.dict_bucket(7)
    DeviceBatch.from_arrow(first)
    before = cache.snapshot_counters()
    b = DeviceBatch.from_arrow(second)
    after = cache.snapshot_counters()
    assert (after["hits"] - before["hits"],
            after["misses"] - before["misses"]) == (1, 0)
    _assert_expanded(second.column(0).chunk(0), b.columns[0], b.capacity)


def test_upload_metrics_count_encoded_vs_decoded_bytes():
    t = _encoded_vs_decoded_table()
    before = um.TRANSFER_METRICS.snapshot()
    DeviceBatch.from_arrow(t, 16)
    after = um.TRANSFER_METRICS.snapshot()
    enc = after[um.TRANSFER_ENCODED_BYTES] - before[um.TRANSFER_ENCODED_BYTES]
    dec = (after[um.TRANSFER_DECODED_EQUIV_BYTES]
           - before[um.TRANSFER_DECODED_EQUIV_BYTES])
    assert 0 < enc < dec          # the encoding shrank the link


# ------------------------------------------------------- the hybrid decoder
def _varint(v):
    out = b""
    while True:
        b7 = v & 0x7F
        v >>= 7
        out += bytes([b7 | (0x80 if v else 0)])
        if not v:
            return out


def _pack(values, bw):
    """LSB-first bit packing, one bit at a time."""
    bits = [(v >> i) & 1 for v in values for i in range(bw)]
    return bytes(sum(bits[j + i] << i for i in range(8) if j + i < len(bits))
                 for j in range(0, len(bits), 8))


def _hybrid(segments, bw):
    """Encode ("rle", value, n) / ("packed", values) segments; a packed
    segment's values are padded with zeros to whole groups of 8."""
    out = b""
    for seg in segments:
        if seg[0] == "rle":
            out += _varint(seg[2] << 1) + seg[1].to_bytes((bw + 7) // 8,
                                                          "little")
        else:
            vals = list(seg[1]) + [0] * (-len(seg[1]) % 8)
            out += _varint((len(vals) // 8) << 1 | 1) + _pack(vals, bw)
    return out


def _reference_decode(buf, bw, count):
    """The RLE/bit-packed hybrid read one bit at a time (the spec's loop)."""
    out, pos = [], 0
    while len(out) < count:
        header = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            header |= (b & 0x7F) << shift
            shift += 7
            if not b & 0x80:
                break
        if header & 1:
            for i in range((header >> 1) * 8):
                out.append(sum(((buf[pos + (i * bw + b) // 8]
                                 >> ((i * bw + b) % 8)) & 1) << b
                               for b in range(bw)))
            pos += (header >> 1) * bw
        else:
            w = (bw + 7) // 8
            out += [int.from_bytes(buf[pos:pos + w], "little")] * (header >> 1)
            pos += w
    return np.array(out[:count], np.uint32).view(np.int32)


def _segments(shape, bw, rng):
    top = 1 << bw
    draw = lambda n: [int(v) for v in rng.integers(0, top, n)]  # noqa: E731
    if shape == "rle":              # a 2-byte varint header among them
        return [("rle", draw(1)[0], 300), ("rle", top - 1, 9),
                ("rle", 0, 5)], 314
    if shape == "packed":           # 70 groups: a 2-byte header
        return [("packed", draw(560)), ("packed", [top - 1] * 8)], 568
    if shape == "alternating":
        return [("packed", draw(16)), ("rle", draw(1)[0], 11),
                ("packed", draw(24)), ("rle", top - 1, 8),
                ("packed", draw(8))], 67
    if shape == "cut_group":        # the count ends inside the last group
        return [("rle", draw(1)[0], 12), ("packed", draw(24))], 12 + 21
    # cut_header: the count ends on a group boundary inside the last
    # header, and is not the stream's whole length
    return [("packed", draw(8)), ("rle", 1 % top, 3),
            ("packed", draw(32))], 8 + 3 + 16


@pytest.mark.parametrize("shape", ["rle", "packed", "alternating",
                                   "cut_group", "cut_header"])
@pytest.mark.parametrize("bw", [0, 1, 2, 3, 4, 6, 7, 8, 12, 16, 20, 25, 26,
                                31, 32])
def test_hybrid_decoder_matches_bit_at_a_time_reference(bw, shape):
    rng = np.random.default_rng(bw * 7 + len(shape))
    segments, count = _segments(shape, bw, rng)
    buf = _hybrid(segments, bw)
    expect = (np.zeros(count, np.int32) if bw == 0
              else _reference_decode(buf, bw, count))
    # decoded in place into a slice of a larger array: nothing beside it
    # moves; through a view of a pyarrow buffer, as a decompressed page
    # arrives (its bytes read as signed)
    final = np.full(count + 6, -7, np.int32)
    lit, rle = rle_bp_decode(memoryview(pa.py_buffer(buf)), bw,
                             final[3:3 + count])
    assert np.array_equal(final[3:3 + count], expect)
    assert (final[:3] == -7).all() and (final[-3:] == -7).all()
    rle_rows = min(count, sum(s[2] for s in segments if s[0] == "rle"))
    assert (lit, rle) == ((0, count) if bw == 0 else (count - rle_rows,
                                                      rle_rows))


def test_hybrid_decoder_on_hand_built_stream_and_its_runs():
    # hand-built hybrid: RLE run of 7 x value 3, then a bit-packed group of
    # 8 (bit width 2), then RLE 5 x value 1
    bw = 2
    packed = np.packbits(
        np.array([[(v >> i) & 1 for i in range(bw)]
                  for v in [0, 1, 2, 3, 0, 1, 2, 3]],
                 np.uint8).reshape(-1), bitorder="little").tobytes()
    stream = (_varint(7 << 1) + bytes([3])          # RLE 7 x 3
              + _varint((1 << 1) | 1) + packed      # bit-packed group of 8
              + _varint(5 << 1) + bytes([1]))       # RLE 5 x 1
    out = np.empty(20, np.int32)
    assert rle_bp_decode(memoryview(stream), bw, out) == (8, 12)
    assert out.tolist() == [3] * 7 + [0, 1, 2, 3] * 2 + [1] * 5
    assert run_count(out) == 1 + 8 + 1      # the RLE runs, 8 literals
    # the runs the `ree` form ships: page boundaries and length-1 runs merge
    idx = np.repeat(np.array([3, 3, 1, 1, 1, 2], np.int32), [2, 5, 1, 1, 3, 4])
    ends, vals = index_runs(idx)
    assert vals.tolist() == [3, 1, 2] and ends.tolist() == [7, 12, 16]
    assert ends.dtype == np.int32 and run_count(idx) == 3
    assert run_count(idx[:0]) == 0


def test_forms_and_values_unchanged_and_a_declined_chunk_stops_at_its_prefix(
        tmp_path, monkeypatch):
    """A pyarrow-written chunk of each form: each takes the form it took
    before the decoder was rewritten, decodes equal to pyarrow's own read,
    and a declined chunk's PLAIN tail is never opened."""
    n = 40000
    rng = np.random.default_rng(5)
    hc_head = rng.integers(0, 1 << 40, n).astype(np.int64)
    hc_head[:2000] = hc_head[0]         # repeated head keeps early pages dict
    t = pa.table({
        "dict": pa.array(rng.integers(1, 51, n).astype(np.float64)),
        "nulls": pa.array(rng.integers(0, 9, n).astype(np.int64),
                          mask=rng.uniform(size=n) < 0.1),
        "sorted": pa.array(np.sort(rng.integers(0, 15, n)).astype(np.int64)),
        "mixed": pa.array(hc_head),
        "declined": pa.array(rng.integers(0, 1 << 40, n).astype(np.int64)),
    })
    path = _write(t, tmp_path, dictionary_pagesize_limit=2048,
                  data_page_size=4096, row_group_size=n)
    pf = pq.ParquetFile(path)
    traced = tracing.Tracer(capacity=4096)
    monkeypatch.setattr(tracing, "TRACER", traced)
    got = {}
    with traced.activate():
        for ci, name in enumerate(t.column_names):
            got[name] = read_dict_column(path, pf.metadata, 0, ci,
                                         t.schema.field(name).type,
                                         want_runs=True)
    spans = {r.args["column"]: r.args for r in traced.since(0)
             if r.name == "scan.chunk_decode"}
    assert {c: a["form"] for c, a in spans.items()} == {
        "dict": "dict", "nulls": "dict", "sorted": "ree", "mixed": "mixed",
        "declined": "declined"}
    assert got["declined"] is None
    for name, r in got.items():
        if r is None:
            continue
        prefix = (ce.ree_to_plain(r.prefix)
                  if pa.types.is_run_end_encoded(r.prefix.type)
                  else r.prefix.cast(t.schema.field(name).type))
        whole = (prefix if r.tail is None
                 else pa.concat_arrays([prefix, r.tail]))
        assert whole.equals(t.column(name).combine_chunks()), name
    for name, a in spans.items():
        assert a["literal_values"] + a["rle_values"] > 0, name
        if a["form"] in ("dict", "ree"):       # every page opened
            assert a["pages_decompressed"] == a["pages"], name
    assert spans["dict"]["literal_values"] + spans["dict"]["rle_values"] == n
    assert spans["sorted"]["rle_values"] > spans["sorted"]["literal_values"]
    assert spans["mixed"]["pages_decompressed"] == spans["mixed"]["pages"]
    d = spans["declined"]
    assert 2 <= d["pages_decompressed"] < d["pages"]
    decompress = [r for r in traced.since(0) if r.name == "scan.decompress"]
    assert len(decompress) == sum(a["pages_decompressed"]
                                  for a in spans.values())
    # and the whole scan reads what pyarrow reads
    out, _ = _roundtrip(path, t)
    assert out.equals(t)


def test_scan_keeps_rle_dominant_column_as_runs(tmp_path):
    n = 30000
    rng = np.random.default_rng(0)
    t = pa.table({"r": pa.array(np.sort(rng.integers(0, 15, n))
                                .astype(np.int64)),
                  "x": pa.array(rng.uniform(size=n))})
    path = _write(t, tmp_path, row_group_size=10000)
    pf = pq.ParquetFile(path)
    r = read_dict_column(path, pf.metadata, 0, 0, pa.int64(),
                         want_runs=True)
    assert pa.types.is_run_end_encoded(r.prefix.type)
    assert len(r.prefix.values) < 40           # runs, not rows
    out, _ = _roundtrip(path, t)
    assert out.equals(t)
    # conf off: still correct, via the dictionary-index form
    out2, batches2 = _roundtrip(path, t, TpuConf(
        {"spark.rapids.tpu.io.parquet.deviceRleExpand.enabled": "false"}))
    assert out2.equals(t)


def test_per_column_fallback_when_encoding_does_not_shrink(tmp_path):
    """A high-cardinality column whose dictionary form is BIGGER than the
    decoded column must fall back to the decoded read."""
    n = 20000
    rng = np.random.default_rng(1)
    t = pa.table({"hc": pa.array(rng.integers(0, 1 << 60, n, dtype=np.int64))})
    path = _write(t, tmp_path)
    pf = pq.ParquetFile(path)
    assert read_dict_column(path, pf.metadata, 0, 0, pa.int64()) is None
    out, batches = _roundtrip(path, t)
    assert out.equals(t)
    assert all(b.columns[0].encoding is None for b in batches)


# ------------------------------------------------- mixed-encoding boundary
def test_mixed_encoding_chunk_keeps_prefix_encoded(tmp_path):
    """The issue's boundary case: a PLAIN fallback mid-chunk must not decode
    the whole chunk on host — the dictionary prefix stays encoded, only the
    tail decodes, and the scan splits the row group at the boundary."""
    n = 50000
    rng = np.random.default_rng(2)
    vals = rng.integers(0, 1 << 40, n).astype(np.int64)
    vals[:2000] = vals[0]          # repeated head keeps early pages dict
    t = pa.table({"m": pa.array(vals),
                  "d": pa.array(rng.integers(0, 5, n).astype(np.int32))})
    path = _write(t, tmp_path, dictionary_pagesize_limit=2048,
                  data_page_size=4096, row_group_size=n)
    pf = pq.ParquetFile(path)
    r = read_dict_column(path, pf.metadata, 0, 0, pa.int64())
    assert r is not None and r.tail is not None
    assert pa.types.is_dictionary(r.prefix.type)    # prefix still encoded
    assert len(r.prefix) + len(r.tail) == n
    rebuilt = pa.concat_arrays([r.prefix.cast(pa.int64()), r.tail])
    assert rebuilt.equals(t.column("m").combine_chunks())
    out, _ = _roundtrip(path, t)
    assert out.equals(t)


# ----------------------------------------------- unification + concat carry
def test_unifier_tokens_make_concat_carry_encoding(tmp_path):
    n = 9000
    rng = np.random.default_rng(3)
    t = pa.table({
        "s": pa.array(np.array(["x", "y", "z", "w"])[rng.integers(0, 4, n)]),
        "k": pa.array(rng.integers(0, 30, n).astype(np.int64))})
    path = _write(t, tmp_path, row_group_size=3000)
    batches = _scan_batches(path, Schema.from_pa(t.schema))
    assert len(batches) >= 3
    for name in ("s", "k"):
        encs = [b.column_by_name(name).encoding for b in batches]
        assert all(e is not None for e in encs), name
        assert len({e.token for e in encs}) == 1, name
    merged = concat_device_batches(batches, batches[0].schema, 16)
    for name in ("s", "k"):
        enc = merged.column_by_name(name).encoding
        assert enc is not None, name
        # invariant: data == take(values, indices) on the live prefix
        col = merged.column_by_name(name)
        got = np.asarray(col.data[:n])
        exp = np.asarray(enc.values)[np.asarray(enc.indices[:n])]
        assert np.array_equal(got, exp), name
    # different dictionary streams (two separate scans) must NOT carry
    other = _scan_batches(path, Schema.from_pa(t.schema))
    mixed = concat_device_batches([batches[0], other[1]],
                                  batches[0].schema, 16)
    assert mixed.column_by_name("s").encoding is None


def test_unifier_remaps_into_prefix_compatible_dictionary():
    u = ce.DictionaryUnifier()
    a = pa.array(["b", "a", "b"]).dictionary_encode()
    b = pa.array(["c", "a"]).dictionary_encode()
    ua, tok_a = u.unify("col", a)
    ub, tok_b = u.unify("col", b)
    assert tok_a == tok_b
    assert ua.to_pylist() == ["b", "a", "b"]
    assert ub.to_pylist() == ["c", "a"]
    # append-only: the first dictionary is a prefix of the second
    assert ub.dictionary.to_pylist()[:len(ua.dictionary)] == \
        ua.dictionary.to_pylist()


# ------------------------------------------------- encoded-domain operators
_Q1_CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
            "spark.rapids.tpu.sql.string.maxBytes": "16",
            "spark.rapids.tpu.sql.scanCache.enabled": "false"}
_DECODED = {"spark.rapids.tpu.sql.encodedDomain.enabled": "false",
            "spark.rapids.tpu.io.parquet.deviceDictDecode.enabled": "false"}


def _lineitem_parquet(tmp_path, n=20000):
    from spark_rapids_tpu.benchmarks.tpch import gen_lineitem
    t = gen_lineitem(scale=n / 6_000_000, seed=11)
    return _write(t, tmp_path, "lineitem.parquet",
                  row_group_size=max(1, t.num_rows // 3)), t


def test_q1_shaped_encoded_domain_equivalence(tmp_path):
    """TPC-H Q1 over parquet: encoded-domain grouping (string keys on
    dictionary indices) + encoded filter must match the decoded path
    bit-for-bit, and must actually run on the encoded domain."""
    from spark_rapids_tpu.benchmarks.tpch import q1
    path, _ = _lineitem_parquet(tmp_path)

    def run(extra):
        sess = TpuSession({**_Q1_CONF, **extra})
        before = um.TRANSFER_METRICS.snapshot()
        out = q1(sess.read.parquet(path)).collect()
        after = um.TRANSFER_METRICS.snapshot()
        ops = (after[um.TRANSFER_ENCODED_DOMAIN_OPS]
               - before[um.TRANSFER_ENCODED_DOMAIN_OPS])
        return out, ops, sess

    enc, enc_ops, sess = run({})
    dec, dec_ops, _ = run(_DECODED)
    assert enc.equals(dec)             # Q1 sorts its output: strict equality
    assert enc_ops >= 1 and dec_ops == 0
    # per-action transfer metrics expose the ratio
    ratio = sess.last_metrics["transfer"]["transfer.compression_ratio"]
    assert 0 < ratio < 1.0


def test_q3_shaped_encoded_domain_join_equivalence(tmp_path):
    """A Q3-shaped plan (filter + equi-join + group-by) over two parquet
    scans: encoded-domain join keys (different dictionary streams, device
    remap) must match the decoded path."""
    rng = np.random.default_rng(5)
    n, m = 15000, 400
    orders = pa.table({
        "o_key": pa.array(rng.integers(0, 300, n).astype(np.int64)),
        "seg": pa.array(np.array(["AUTO", "HOME", "SHIP"])[
            rng.integers(0, 3, n)]),
        "price": pa.array(np.round(rng.uniform(1, 100, n), 2))})
    cust = pa.table({
        "c_key": pa.array(rng.integers(0, 300, m).astype(np.int64)),
        "nation": pa.array(np.array(["US", "DE", "JP", "BR"])[
            rng.integers(0, 4, m)])})
    p1 = _write(orders, tmp_path, "orders.parquet", row_group_size=5000)
    p2 = _write(cust, tmp_path, "cust.parquet")

    def run(extra):
        sess = TpuSession({**_Q1_CONF, **extra})
        o = sess.read.parquet(p1)
        c = sess.read.parquet(p2)
        before = um.TRANSFER_METRICS.snapshot()
        out = (o.filter(F.col("seg") == "AUTO")
                .join(c, [("o_key", "c_key")], how="inner")
                .groupBy("nation")
                .agg(F.sum("price").alias("rev"),
                     F.count().alias("cnt"))
                .sort("nation")).collect()
        after = um.TRANSFER_METRICS.snapshot()
        ops = (after[um.TRANSFER_ENCODED_DOMAIN_OPS]
               - before[um.TRANSFER_ENCODED_DOMAIN_OPS])
        return out, ops

    enc, enc_ops = run({})
    dec, dec_ops = run(_DECODED)
    assert_tables_equal(dec, enc, approx_float=1e-9)
    assert enc_ops >= 1 and dec_ops == 0


def test_join_remap_path_fires_on_scan_joins(tmp_path):
    """Two direct scans with DIFFERENT dictionary streams joined on
    dict-encoded keys: the device remap path itself (not just the filter
    rewrite) must fire and match the decoded join. (In the Q3 shape the
    left filter's compaction drops encodings, so the join there falls back
    per-column — this pins the remap in isolation.)"""
    rng = np.random.default_rng(8)
    n, m = 12000, 300
    left = pa.table({
        "o_key": pa.array(rng.integers(0, 250, n).astype(np.int64)),
        "price": pa.array(np.round(rng.uniform(1, 100, n), 2))})
    right = pa.table({
        "c_key": pa.array(rng.integers(0, 250, m).astype(np.int64)),
        "w": pa.array(rng.integers(0, 9, m).astype(np.int64))})
    p1 = _write(left, tmp_path, "l.parquet", row_group_size=4000)
    p2 = _write(right, tmp_path, "r.parquet")

    def run(extra):
        sess = TpuSession({**_Q1_CONF, **extra})
        before = um.TRANSFER_METRICS.snapshot()
        out = (sess.read.parquet(p1)
               .join(sess.read.parquet(p2), [("o_key", "c_key")],
                     how="inner")
               .agg(F.count().alias("n"),
                    F.sum("price").alias("s"))).collect()
        after = um.TRANSFER_METRICS.snapshot()
        return out, (after[um.TRANSFER_ENCODED_DOMAIN_OPS]
                     - before[um.TRANSFER_ENCODED_DOMAIN_OPS])

    enc, enc_ops = run({})
    dec, dec_ops = run(_DECODED)
    assert enc_ops >= 1 and dec_ops == 0
    assert_tables_equal(dec, enc, approx_float=1e-9)


def test_encoded_filter_with_nulls_matches_decoded(tmp_path):
    rng = np.random.default_rng(6)
    n = 8000
    vals = [None if v % 9 == 0 else ["a", "b", "c"][v % 3]
            for v in rng.integers(0, 90, n)]
    t = pa.table({"s": pa.array(vals), "v": pa.array(np.arange(n))})
    path = _write(t, tmp_path, row_group_size=2000)

    def run(extra):
        sess = TpuSession({**_Q1_CONF, **extra})
        df = sess.read.parquet(path)
        return (df.filter(F.col("s") != "b").agg(
            F.count().alias("c"), F.sum("v").alias("sv"))).collect()

    assert run({}).equals(run(_DECODED))


def test_null_tolerant_predicates_stay_decoded(tmp_path):
    """IsNull / Coalesce produce NON-null verdicts from null inputs, which
    the dictionary-domain gather cannot represent — they must not rewrite
    (regression: `WHERE col IS NULL` returned 0 rows on encoded scans)."""
    vals = ["a", "b", None, "c"] * 2000
    t = pa.table({"s": pa.array(vals), "v": pa.array(np.arange(8000))})
    path = _write(t, tmp_path, row_group_size=2000)

    def run(q, extra):
        sess = TpuSession({**_Q1_CONF, **extra})
        return q(sess.read.parquet(path)).collect()

    for q in (lambda df: df.filter(F.col("s").isNull())
              .agg(F.count().alias("c")),
              lambda df: df.filter(F.col("s").isNotNull())
              .agg(F.count().alias("c")),
              lambda df: df.filter(F.coalesce(F.col("s"), F.lit("b")) == "b")
              .agg(F.count().alias("c"))):
        assert run(q, {}).equals(run(q, _DECODED))
    # null count sanity: isNull really selected the 2000 null rows
    sess = TpuSession(_Q1_CONF)
    got = (sess.read.parquet(path).filter(F.col("s").isNull())
           .agg(F.count().alias("c"))).collect()
    assert got.to_pydict()["c"] == [2000]


def test_unifier_preserves_negative_zero_and_nan_bits(tmp_path):
    """Float dictionaries dedupe by BIT PATTERN: -0.0 survives the unifier
    (regression: Python == collapsed it into +0.0) and equal-bit NaNs
    dedupe instead of growing the dictionary every row group."""
    t = pa.table({"z": pa.array([0.0, -0.0, float("nan"), 1.5] * 2000)})
    path = _write(t, tmp_path, row_group_size=1000)
    batches = _scan_batches(path, Schema.from_pa(t.schema),
                            TpuConf({}))
    out = pa.concat_tables(b.to_arrow() for b in batches)
    assert_tables_equal(t, out)
    neg = sum(1 for v in out["z"].to_pylist()
              if v == 0.0 and str(v).startswith("-"))
    assert neg == 2000
    # a dictionary whose values are distinct by BITS but equal by value
    # (-0.0 vs 0.0) is rightly rejected for index-domain execution
    assert all(b.columns[0].encoding is None for b in batches)
    # the unifier itself: bit-pattern keys keep -0.0 and dedupe equal NaNs
    u = ce.DictionaryUnifier()
    d = pa.array(np.array([0.0, -0.0, np.nan, 1.5])).dictionary_encode()
    u1, tok1 = u.unify("z", d)
    u2, tok2 = u.unify("z", d)
    assert tok1 == tok2
    assert len(u2.dictionary) == 4           # no growth on re-unify
    bits = np.asarray(u2.dictionary).view(np.uint64)
    assert len(set(bits.tolist())) == 4      # -0.0 and NaN bits intact


def test_dict_bucket_keeps_jit_shapes_stable(tmp_path):
    """A dictionary growing a few entries per row group must NOT change the
    encoding's padded shape each batch (jit cache keys include EncSpec.k —
    per-batch growth would recompile every encoded-domain program)."""
    rng = np.random.default_rng(9)
    parts = [np.array([f"v{j}" for j in rng.integers(0, 3 + 2 * i, 4000)])
             for i in range(4)]
    t = pa.table({"s": pa.array(np.concatenate(parts))})
    path = _write(t, tmp_path, row_group_size=4000)
    batches = _scan_batches(path, Schema.from_pa(t.schema))
    ks = [b.columns[0].encoding.k for b in batches]
    reals = [b.columns[0].encoding.k_real for b in batches]
    assert reals == sorted(reals) and reals[-1] > reals[0]  # it DID grow
    assert len(set(ks)) <= 2, ks      # but padded shapes stayed bucketed
    out = pa.concat_tables(b.to_arrow() for b in batches)
    assert out.equals(t)


def test_planner_pass_marks_only_reachable_operators(tmp_path):
    from spark_rapids_tpu.plan.encoded import count_encoded_domain
    path, _ = _lineitem_parquet(tmp_path, n=4000)
    from spark_rapids_tpu.benchmarks.tpch import q1
    sess = TpuSession(_Q1_CONF)
    q1(sess.read.parquet(path)).collect()
    assert count_encoded_domain(sess.last_plan) >= 1
    sess_off = TpuSession({**_Q1_CONF,
                           "spark.rapids.tpu.sql.encodedDomain.enabled":
                               "false"})
    q1(sess_off.read.parquet(path)).collect()
    assert count_encoded_domain(sess_off.last_plan) == 0


# ----------------------------------------------------------- lz4 + shuffle
def test_lz4_block_roundtrip_and_vectors():
    from spark_rapids_tpu.shuffle import lz4
    rng = np.random.default_rng(0)
    cases = [b"", b"a", b"abcd", b"a" * 29, os.urandom(10_000),
             bytes(rng.integers(0, 4, 50_000, dtype=np.uint8)),
             b"hello world " * 4000, bytes(10_000),
             os.urandom(13) + b"X" * 300 + os.urandom(7)]
    for c in cases:
        assert lz4.decompress(lz4.compress(c), len(c)) == c
    # spec vector: 5 literals + overlapping match (offset 5, len 10) + tail
    blk = (bytes([0x56]) + b"hello" + (5).to_bytes(2, "little")
           + bytes([0x50]) + b"hello")
    assert lz4.decompress(blk, 20) == b"hello" * 4
    with pytest.raises(ValueError):
        lz4.decompress(blk, 21)        # wrong size must not pass silently


def test_codec_registry_single_lookup_and_errors():
    from spark_rapids_tpu.shuffle.codec import (available_codecs,
                                                codec_available, get_codec)
    assert {"copy", "none", "zlib", "lz4"} <= set(available_codecs())
    with pytest.raises(ValueError, match="unknown shuffle codec"):
        get_codec("snappy")
    c = get_codec("lz4")
    buf = b"the quick brown fox " * 512
    assert c.decompress(c.compress(buf), len(buf)) == buf
    assert codec_available("definitely-not-a-codec") is False


def test_zlib_level_conf_reaches_codec():
    from spark_rapids_tpu.shuffle.codec import get_codec
    conf = TpuConf({"spark.rapids.tpu.shuffle.compression.zlib.level": "9"})
    assert get_codec("zlib", conf).level == 9
    assert get_codec("zlib").level == 1
    with pytest.raises(ValueError, match="zlib.level"):
        TpuConf({"spark.rapids.tpu.shuffle.compression.zlib.level": "11"})


def test_client_rejects_unknown_codec_early(tmp_path):
    from spark_rapids_tpu.shuffle.inprocess import _Fabric
    from spark_rapids_tpu.shuffle.manager import ShuffleEnv
    _Fabric.reset()
    try:
        env = ShuffleEnv("exec-0", TpuConf(
            {"spark.rapids.tpu.shuffle.compression.codec": "snappy"}),
            disk_dir=str(tmp_path / "e0"))
        env2 = ShuffleEnv("exec-1", TpuConf({}),
                          disk_dir=str(tmp_path / "e1"))
        with pytest.raises(ValueError, match="unknown shuffle codec"):
            env.client_for("exec-1")
    finally:
        _Fabric.reset()


def test_shuffle_lz4_fetch_and_negotiation(tmp_path):
    """lz4-compressed fetch returns exact rows; a codec-less peer
    negotiates the transfer down to copy (counted) instead of failing."""
    from spark_rapids_tpu.shuffle.inprocess import _Fabric
    from spark_rapids_tpu.shuffle.manager import ShuffleEnv, ShuffleManager
    from spark_rapids_tpu.utils import metrics as mt
    from tests.test_shuffle import (collect_partition, sample_table,
                                    write_partitioned)
    conf = TpuConf({"spark.rapids.tpu.shuffle.compression.codec": "lz4",
                    "spark.rapids.tpu.shuffle.bounceBuffers.size": 1024})
    mgr = ShuffleManager()
    t = sample_table(800, seed=1)
    expected = t.take(list(range(0, 800, 2)))
    _Fabric.reset()
    try:
        e0 = ShuffleEnv("exec-0", conf, disk_dir=str(tmp_path / "a0"))
        e1 = ShuffleEnv("exec-1", conf, disk_dir=str(tmp_path / "a1"))
        sid, _ = mgr.register_shuffle(2)
        write_partitioned(mgr, e1, sid, 0, t, 2)
        got = collect_partition(mgr, e0, sid, 0)
        assert got.sort_by("f").equals(expected.sort_by("f"))

        # negotiation: the serving peer supports only copy
        e1.server.supported_codecs = {"copy"}
        sid2, _ = mgr.register_shuffle(2)
        write_partitioned(mgr, e1, sid2, 0, t, 2)
        got2 = collect_partition(mgr, e0, sid2, 0)
        assert got2.sort_by("f").equals(expected.sort_by("f"))
        assert e1.metrics[mt.SHUFFLE_CODEC_FALLBACKS].value >= 1
    finally:
        _Fabric.reset()


def test_lz4_corrupt_frame_checksum_retry(tmp_path):
    """The PR 2 fault matrix composes with compression: a corrupted
    lz4-compressed frame is caught by the on-wire checksum BEFORE
    decompression and the retry succeeds."""
    from spark_rapids_tpu.shuffle.inprocess import _Fabric
    from spark_rapids_tpu.utils import metrics as mt
    from tests.test_shuffle import (collect_partition, sample_table,
                                    write_partitioned)
    from tests.test_shuffle_faults import fault_cluster
    _Fabric.reset()
    try:
        mgr, e0, e1 = fault_cluster(
            tmp_path, plan="corrupt_frame:after=2",
            extra={"spark.rapids.tpu.shuffle.compression.codec": "lz4"})
        sid, _ = mgr.register_shuffle(1)
        t = sample_table(700, seed=3)
        write_partitioned(mgr, e1, sid, 0, t, 1)
        got = collect_partition(mgr, e0, sid, 0)
        assert sorted(got["f"].to_pylist()) == sorted(t["f"].to_pylist())
        assert e0.metrics[mt.SHUFFLE_CHECKSUM_FAILURES].value >= 1
        assert e0.metrics[mt.SHUFFLE_TRANSFER_RETRIES].value >= 1
    finally:
        _Fabric.reset()
