"""Plain reference of TPC-H Q18 (see queries/q18.sql).

What the comparison can and cannot show here. Four of the five grouping
columns are keys and a date: they are passed through, never computed, and
must come back exactly, in the order the text asks for (``EXACT``;
``o_orderkey`` is unique in an answer, so it pins the order of the rows).

The fifth, ``o_totalprice``, is a double that is passed through as well, and
the issue that asked for this cell wanted it back bit for bit. On the TPU it
cannot be: XLA holds a float64 there as a pair of float32 (``X64SplitLow`` /
``X64SplitHigh`` / ``X64Combine`` in every compiled program; some 48 bits of
mantissa), so a double is altered in its last four or five bits by the upload
alone, before any operator touches it (the parent's run on the chip, PR 32:
63 of 69 prices came back changed, by at most 2**-48 of themselves; on the CPU
backend all come back bit for bit). It is therefore compared as the float
columns of the other queries are, by its relative gap under ``REL_GAP_LIMIT``:
the program reads some 1e-15 on the chip, float32 in its place reads some
3e-8 (850.00 to 560,000.00, to the cent, does not survive float32), and 1e-10
lies between the two with room on both sides. PERF.md section 2 has the
readings, section 7 what an exact pass-through would take.

``l_quantity`` holds the integers 1..50 and an order has at most seven lines:
every ``sum_qty`` is an integer of at most 350 and exact in float32 as well
as in float64, so on that column the limit cannot tell the two apart; the
float32 control fails by ``o_totalprice``. A wrong group, a missed ``HAVING``
or a lost line shows as a wrong row or a ``sum_qty`` off by a whole number.

``order by o_totalprice desc, o_orderdate`` leaves rows tied on both in any
order; the reference puts them by ``o_orderkey``. ``tied_rows`` counts such
ties among the rows that decide an answer: with ``o_totalprice`` uniform over
5.6e7 values and about a hundred rows it is one seed in some 10**4, and
PERF.md gives the count over the seeds that were run."""
import numpy as np
import pyarrow as pa

from benchmark.reference.common import column, floats

EXACT = ("c_name", "c_custkey", "o_orderkey", "o_orderdate")
#: of ``o_totalprice`` and ``sum_qty``; see reference/q1.py, and the note
#: above on what it shows
REL_GAP_LIMIT = 1e-10
LIMIT = 100


def ranked(tables, precision, quantity):
    """Every row of the answer before ``limit``, in its order."""
    cust, orders, li = (tables[n] for n in ("customer", "orders", "lineitem"))
    lkey = column(li, "l_orderkey")
    # lineitem is clustered by l_orderkey: a group is a run of rows
    first = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    keys = lkey[first]
    if not np.all(keys[1:] > keys[:-1]):
        raise ValueError("lineitem is not clustered by l_orderkey")
    sums = np.add.reduceat(floats(li, "l_quantity", precision), first)
    # the subquery: group by l_orderkey having sum(l_quantity) > quantity
    big = sums > quantity
    okey = column(orders, "o_orderkey")
    ckey = column(cust, "c_custkey")
    # customers' keys are dense from 1: a lookup by key is an index
    cslot = np.full(int(ckey.max()) + 1, -1, np.int64)
    cslot[ckey] = np.arange(len(ckey))
    ocust = column(orders, "o_custkey")
    rows = np.flatnonzero(np.isin(okey, keys[big]) & (cslot[ocust] >= 0))
    # o_orderkey and c_custkey are unique: a group of the outer query is one
    # order, and its sum runs over that order's lines again
    sum_qty = sums[np.searchsorted(keys, okey[rows])].astype(np.float64)
    price = floats(orders, "o_totalprice", precision)[rows].astype(np.float64)
    date = column(orders, "o_orderdate")[rows]
    order = np.lexsort((okey[rows], date, -price))
    rows = rows[order]
    names = cust.column("c_name").combine_chunks().take(
        pa.array(cslot[ocust[rows]]))
    return pa.table({
        "c_name": names,
        "c_custkey": ocust[rows],
        "o_orderkey": okey[rows],
        "o_orderdate": pa.array(date[order], type=pa.int32()).cast(
            pa.date32()),
        "o_totalprice": price[order],
        "sum_qty": sum_qty[order],
    })


def answer(tables, precision="float64", quantity=300):
    return ranked(tables, precision, quantity).slice(0, LIMIT)


def tied_rows(tables, quantity=300):
    """Rows among the answer's, and the first one cut off, that tie with
    their predecessor on ``(o_totalprice, o_orderdate)``."""
    top = ranked(tables, "float64", quantity).slice(0, LIMIT + 1)
    price = column(top, "o_totalprice")
    date = column(top, "o_orderdate")
    return int(np.sum((price[1:] == price[:-1]) & (date[1:] == date[:-1])))
