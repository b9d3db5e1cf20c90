"""TPC-H-like benchmark data + queries (reference:
integration_tests/src/main/scala/.../tpch/ — "Like" queries over generated data;
doubles instead of decimals, exactly like the reference's TpchLike schema since
v0 has no decimal support).

The generator is a deterministic, vectorized dbgen-alike for the lineitem table
(the table Q1/Q6 need); scale factor 1.0 ~ 6M rows.
"""
from __future__ import annotations

import datetime

import numpy as np
import pyarrow as pa

from spark_rapids_tpu.api import TpuSession, functions as F
from spark_rapids_tpu.api.dataframe import DataFrame

_FLAGS = np.array(["A", "N", "R"])
_STATUS = np.array(["F", "O"])
_EPOCH_1992 = (datetime.date(1992, 1, 1) - datetime.date(1970, 1, 1)).days


def gen_lineitem(scale: float = 0.01, seed: int = 0) -> pa.Table:
    n = int(6_000_000 * scale)
    rng = np.random.default_rng(seed)
    quantity = rng.integers(1, 51, n).astype(np.float64)
    extendedprice = np.round(rng.uniform(900, 105000, n), 2)
    discount = np.round(rng.uniform(0.0, 0.1, n), 2)
    tax = np.round(rng.uniform(0.0, 0.08, n), 2)
    flag_idx = rng.integers(0, 3, n)
    status_idx = rng.integers(0, 2, n)
    shipdate = (_EPOCH_1992 + rng.integers(0, 2526, n)).astype(np.int32)
    orderkey = rng.integers(1, max(int(n / 4), 2), n).astype(np.int64)
    return pa.table({
        "l_orderkey": pa.array(orderkey),
        "l_quantity": pa.array(quantity),
        "l_extendedprice": pa.array(extendedprice),
        "l_discount": pa.array(discount),
        "l_tax": pa.array(tax),
        "l_returnflag": pa.array(_FLAGS[flag_idx]),
        "l_linestatus": pa.array(_STATUS[status_idx]),
        "l_shipdate": pa.array(shipdate, type=pa.date32()),
    })


def q1(lineitem: DataFrame) -> DataFrame:
    """TPC-H Q1: pricing summary report."""
    cutoff = datetime.date(1998, 9, 2)
    disc_price = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    charge = disc_price * (1 + F.col("l_tax"))
    return (lineitem
            .filter(F.col("l_shipdate") <= F.lit(cutoff))
            .groupBy("l_returnflag", "l_linestatus")
            .agg(F.sum("l_quantity").alias("sum_qty"),
                 F.sum("l_extendedprice").alias("sum_base_price"),
                 F.sum(disc_price).alias("sum_disc_price"),
                 F.sum(charge).alias("sum_charge"),
                 F.avg("l_quantity").alias("avg_qty"),
                 F.avg("l_extendedprice").alias("avg_price"),
                 F.avg("l_discount").alias("avg_disc"),
                 F.count().alias("count_order"))
            .sort("l_returnflag", "l_linestatus"))


def q6(lineitem: DataFrame) -> DataFrame:
    """TPC-H Q6: forecasting revenue change."""
    lo = datetime.date(1994, 1, 1)
    hi = datetime.date(1995, 1, 1)
    return (lineitem
            .filter((F.col("l_shipdate") >= F.lit(lo))
                    & (F.col("l_shipdate") < F.lit(hi))
                    & (F.col("l_discount") >= 0.05)
                    & (F.col("l_discount") <= 0.07)
                    & (F.col("l_quantity") < 24))
            .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                 .alias("revenue")))


# default confs + float aggregation, for the tests that run these queries
BENCH_CONF = {
    # float sums are required by TPC-H aggregates (same switch the reference
    # flips for benchmarks: spark.rapids.sql.variableFloatAgg.enabled)
    "spark.rapids.tpu.sql.variableFloatAgg.enabled": "true",
    "spark.rapids.tpu.sql.incompatibleOps.enabled": "true",
}
