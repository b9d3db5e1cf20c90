"""Plain reference of TPC-H Q6 (see queries/q6.sql)."""
import pyarrow as pa

from benchmark.reference.common import column, days, floats, total

EXACT = ()
#: see reference/q1.py; readings in PERF.md section 2
REL_GAP_LIMIT = 1e-10


def answer(tables, precision="float64"):
    li = tables["lineitem"]
    ship = column(li, "l_shipdate")
    qty = floats(li, "l_quantity", precision)
    price = floats(li, "l_extendedprice", precision)
    disc = floats(li, "l_discount", precision)
    t = disc.dtype.type
    keep = ((ship >= days(1994, 1, 1)) & (ship < days(1995, 1, 1))
            & (disc >= t(0.05)) & (disc <= t(0.07)) & (qty < t(24)))
    return pa.table({"revenue": [total(price[keep] * disc[keep], precision)]})
