"""The least-bytes count for Q1, Q6 and Q3 against hand counts."""
import pyarrow as pa

from benchmark import manifest
from benchmark.datagen import gen_tables
from benchmark.least_bytes import least_bytes, referenced


def _tables():
    return gen_tables(["customer", "orders", "lineitem"], 0.01, 3)


def test_q6_reads_four_columns_of_lineitem():
    t = _tables()
    n = t["lineitem"].num_rows
    result = pa.table({"revenue": [1.0]})
    cols = referenced(manifest.query_sql("q6"), t)
    assert cols == {"lineitem": ["l_quantity", "l_extendedprice",
                                 "l_discount", "l_shipdate"]}
    # three doubles and a date32 per row, one double out
    assert least_bytes(manifest.query_sql("q6"), t, result) == n * (3 * 8 + 4) + 8


def test_q1_reads_seven_columns_strings_at_their_utf8_length():
    t = _tables()
    n = t["lineitem"].num_rows
    result = pa.table({"l_returnflag": ["A", "N"], "count_order": [1, 2]})
    cols = referenced(manifest.query_sql("q1"), t)["lineitem"]
    assert cols == ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                    "l_returnflag", "l_linestatus", "l_shipdate"]
    # four doubles, two one-letter strings, one date32; result 2 letters + 2 int64
    assert least_bytes(manifest.query_sql("q1"), t, result) == \
        n * (4 * 8 + 1 + 1 + 4) + 2 + 16


def test_q3_reads_three_tables():
    t = _tables()
    result = pa.table({"l_orderkey": pa.array([1], pa.int64())})
    cols = referenced(manifest.query_sql("q3"), t)
    assert cols == {
        "customer": ["c_custkey", "c_mktsegment"],
        "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
        "lineitem": ["l_orderkey", "l_extendedprice", "l_discount",
                     "l_shipdate"]}
    seg = sum(len(s) for s in t["customer"]["c_mktsegment"].to_pylist())
    want = (t["customer"].num_rows * 8 + seg
            + t["orders"].num_rows * (8 + 8 + 4 + 4)
            + t["lineitem"].num_rows * (8 + 8 + 8 + 4) + 8)
    assert least_bytes(manifest.query_sql("q3"), t, result) == want
