"""TPC-H Q3 (shipping priority), DataFrame form, validation parameters
SEGMENT = BUILDING, DATE = 1995-03-15. The benchmark's copy of the program's
``tpch_queries.q3``."""
import datetime

from spark_rapids_tpu.api import functions as F

col, lit = F.col, F.lit


def build(t):
    cutoff = lit(datetime.date(1995, 3, 15))
    revenue = col("l_extendedprice") * (1 - col("l_discount"))
    return (t["customer"].filter(col("c_mktsegment") == "BUILDING")
            .join(t["orders"].filter(col("o_orderdate") < cutoff),
                  [("c_custkey", "o_custkey")])
            .join(t["lineitem"].filter(col("l_shipdate") > cutoff),
                  [("o_orderkey", "l_orderkey")])
            .groupBy("l_orderkey", "o_orderdate", "o_shippriority")
            .agg(F.sum(revenue).alias("revenue"))
            .select("l_orderkey", "revenue", "o_orderdate", "o_shippriority")
            .sort(col("revenue").desc(), "o_orderdate")
            .limit(10))
