"""TPC-H Q6 (forecasting revenue change), DataFrame form, validation
parameters DATE = 1994-01-01, DISCOUNT = 0.06, QUANTITY = 24. The benchmark's
copy of the program's ``tpch_queries.q6``."""
import datetime

from spark_rapids_tpu.api import functions as F

col, lit = F.col, F.lit


def build(t):
    return (t["lineitem"]
            .filter((col("l_shipdate") >= lit(datetime.date(1994, 1, 1)))
                    & (col("l_shipdate") < lit(datetime.date(1995, 1, 1)))
                    & (col("l_discount") >= 0.05)
                    & (col("l_discount") <= 0.07)
                    & (col("l_quantity") < 24))
            .agg(F.sum(col("l_extendedprice") * col("l_discount"))
                 .alias("revenue")))
