"""Plain reference of TPC-H Q3 (see queries/q3.sql)."""
import numpy as np
import pyarrow as pa

from benchmark.reference.common import codes, column, days, floats

EXACT = ("l_orderkey", "o_orderdate", "o_shippriority")
#: see reference/q1.py; readings in PERF.md section 2
REL_GAP_LIMIT = 1e-10


def answer(tables, precision="float64"):
    cust, orders, li = (tables[n] for n in ("customer", "orders", "lineitem"))
    cutoff = days(1995, 3, 15)
    seg, segs = codes(cust, "c_mktsegment")
    building = column(cust, "c_custkey")[seg == segs.index("BUILDING")]
    okey = column(orders, "o_orderkey")
    odate = column(orders, "o_orderdate")
    oprio = column(orders, "o_shippriority")
    order_ok = (odate < cutoff) & np.isin(column(orders, "o_custkey"),
                                          building)
    # orders' keys are dense from 1: a lookup by key is an index
    slot = np.full(int(okey.max()) + 1, -1, np.int64)
    slot[okey[order_ok]] = np.flatnonzero(order_ok)
    lkey = column(li, "l_orderkey")
    keep = (column(li, "l_shipdate") > cutoff) & (slot[lkey] >= 0)
    price = floats(li, "l_extendedprice", precision)[keep]
    disc = floats(li, "l_discount", precision)[keep]
    # lineitem is clustered by l_orderkey: a group is a run of rows
    keys, first = np.unique(lkey[keep], return_index=True)
    revenue = np.add.reduceat(price * (price.dtype.type(1) - disc), first)
    revenue = revenue.astype(np.float64)
    dates = odate[slot[keys]]
    top = np.lexsort((dates, -revenue))[:10]
    return pa.table({
        "l_orderkey": keys[top],
        "revenue": revenue[top],
        "o_orderdate": pa.array(dates[top], type=pa.int32()).cast(
            pa.date32()),
        "o_shippriority": oprio[slot[keys]][top],
    })
