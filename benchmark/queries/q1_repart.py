"""TPC-H Q1 (pricing summary report) read from a ``lineitem`` that the
session hash-partitioned by its join key: ``queries/q1.py``'s ``build`` with
``t["lineitem"]`` replaced by ``t["lineitem"].repartition(8, "l_orderkey")``
(Spark's ``Dataset.repartition(numPartitions, cols)``, which plans a
``ShuffleExchangeExec`` over ``HashPartitioning``) and nothing else changed."""
import datetime

from spark_rapids_tpu.api import functions as F

col, lit = F.col, F.lit

PARTITIONS = 8


def build(t):
    revenue = col("l_extendedprice") * (1 - col("l_discount"))
    charge = revenue * (1 + col("l_tax"))
    return (t["lineitem"].repartition(PARTITIONS, "l_orderkey")
            .filter(col("l_shipdate") <= lit(datetime.date(1998, 9, 2)))
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base_price"),
                 F.sum(revenue).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count().alias("count_order"))
            .sort("l_returnflag", "l_linestatus"))
