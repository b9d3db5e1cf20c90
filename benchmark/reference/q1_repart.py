"""Plain reference of TPC-H Q1 over ``lineitem.repartition(8, "l_orderkey")``
(see queries/q1_repart.sql): Q1's plain numpy answer on the whole table.

That is the whole semantics. A repartition permutes the table's rows among
partitions and changes none of them, and Q1 is a function of the multiset of
rows: the groups, their counts, their sums and averages do not depend on the
order or the placement of the rows, and ``order by`` fixes the order of the
four that come back. So the answer over the repartitioned table is the answer
over the table, and the reference computes nothing of the partitioning.

What the comparison shows of the exchange: ``count_order`` is exact, so a row
the exchange lost or doubled is a mismatch, and it moves every sum of its
group by a whole row's worth, some 1e-6 of a sum over 1.5 M rows, four orders
of magnitude over ``REL_GAP_LIMIT``; a value the pack or the consolidation
altered shows in the sum of its column. What it cannot show is which partition
a row landed in: that is compared row for row, partition by partition, against
the CPU engine by ``tests/test_exchange_cell.py`` at SF0.01 and by
``chip_smoke.py``'s exchange leg at SF1 on the chip.

The limits are Q1's: the program's float64 sums in another order (here the
order the exchange leaves) stay some 1e-14 from the reference, the float32
control reads some 1e-7 (PERF.md section 2 has the readings)."""
from benchmark.reference.q1 import EXACT, REL_GAP_LIMIT, answer

__all__ = ["EXACT", "REL_GAP_LIMIT", "answer"]
