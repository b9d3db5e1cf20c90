"""TPC-H Q18 (large volume customer), DataFrame form, validation parameter
QUANTITY = 300. The benchmark's copy of the program's ``tpch_queries.q18``:
the ``in (select ... having ...)`` of the text is a left-semi join."""
from spark_rapids_tpu.api import functions as F

col = F.col


def build(t, quantity=300):
    big = (t["lineitem"].groupBy(col("l_orderkey").alias("big_orderkey"))
           .agg(F.sum("l_quantity").alias("big_qty"))
           .filter(col("big_qty") > quantity))
    return (t["customer"]
            .join(t["orders"], [("c_custkey", "o_custkey")])
            .join(big, [("o_orderkey", "big_orderkey")], "left_semi")
            .join(t["lineitem"], [("o_orderkey", "l_orderkey")])
            .groupBy("c_name", "c_custkey", "o_orderkey", "o_orderdate",
                     "o_totalprice")
            .agg(F.sum("l_quantity").alias("sum_qty"))
            .sort(col("o_totalprice").desc(), "o_orderdate")
            .limit(100))
