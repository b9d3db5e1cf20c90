"""Plain reference of TPC-H Q1 (see queries/q1.sql)."""
import numpy as np
import pyarrow as pa

from benchmark.reference.common import codes, column, days, floats, total

#: columns compared exactly; the others are float columns compared by
#: relative gap. Row order is part of the answer (``order by``).
EXACT = ("l_returnflag", "l_linestatus", "count_order")
#: widest relative gap of a float cell that still counts as the same answer.
#: Readings (my chip runs, PR 24) are in PERF.md section 2.
REL_GAP_LIMIT = 1e-10


def answer(tables, precision="float64"):
    li = tables["lineitem"]
    keep = column(li, "l_shipdate") <= days(1998, 9, 2)
    flag, flags = codes(li, "l_returnflag")
    status, statuses = codes(li, "l_linestatus")
    group = flag * len(statuses) + status
    qty = floats(li, "l_quantity", precision)
    price = floats(li, "l_extendedprice", precision)
    disc = floats(li, "l_discount", precision)
    tax = floats(li, "l_tax", precision)
    one = price.dtype.type(1)
    disc_price = price * (one - disc)
    charge = disc_price * (one + tax)
    rows = []
    for g in np.unique(group[keep]):
        sel = keep & (group == g)
        n = int(sel.sum())
        sums = [total(v[sel], precision)
                for v in (qty, price, disc_price, charge, disc)]
        rows.append((flags[g // len(statuses)], statuses[g % len(statuses)],
                     sums[0], sums[1], sums[2], sums[3],
                     sums[0] / n, sums[1] / n, sums[4] / n, n))
    rows.sort(key=lambda r: (r[0], r[1]))
    names = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
             "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
             "avg_disc", "count_order"]
    return pa.table({n: [r[i] for r in rows] for i, n in enumerate(names)})
