"""The comparison that decides ``correct``.

Every answer the timed path returned in the window is compared with the
plain reference of its query on the same tables: exact columns cell by cell
(their differences are counted, limit 0), float columns by the widest
relative gap (limit: the reference module's ``REL_GAP_LIMIT``). Besides, no
operator but the scan may have been planned on the CPU engine (limit 0).
Nothing here imports the program.
"""
import importlib

import numpy as np
import pyarrow as pa

#: the gap reported where two answers cannot be compared cell by cell (a
#: finite number, so that the result line stays plain JSON)
GAP_WHEN_INCOMPARABLE = 1e300


def load_reference(query_id):
    return importlib.import_module(f"benchmark.reference.{query_id}")


def compare(got, ref, exact):
    """(exact cells that differ, widest relative gap of a float cell).

    A wrong shape (columns, row count) cannot be compared cell by cell: it
    counts as one mismatch per reference cell and an infinite gap."""
    if (got.column_names != ref.column_names
            or got.num_rows != ref.num_rows):
        return max(ref.num_rows * ref.num_columns, 1), GAP_WHEN_INCOMPARABLE
    mismatches, gap = 0, 0.0
    for name in ref.column_names:
        g = got.column(name).combine_chunks()
        r = ref.column(name).combine_chunks()
        if name in exact:
            if not pa.types.is_floating(r.type):
                g = g.cast(r.type)
            mismatches += sum(a != b for a, b in
                              zip(g.to_pylist(), r.to_pylist()))
            continue
        if g.null_count or r.null_count:
            return max(ref.num_rows * ref.num_columns, 1), GAP_WHEN_INCOMPARABLE
        gv = np.asarray(g.cast(pa.float64()).to_numpy(zero_copy_only=False))
        rv = np.asarray(r.cast(pa.float64()).to_numpy(zero_copy_only=False))
        with np.errstate(invalid="ignore", divide="ignore"):
            rel = np.abs(gv - rv) / np.maximum(np.abs(rv), 1e-300)
        rel = np.where(np.isfinite(rel), rel, GAP_WHEN_INCOMPARABLE)
        gap = max(gap, float(rel.max(initial=0.0)))
    return mismatches, gap


def judge(answers, tables, cpu_execs, unanswered=0, failed=0):
    """``answers``: {query id: [tables the timed path returned]};
    ``unanswered``: requests that never came back; ``failed``: those that
    raised or were shed, an answer that never comes too. Returns (correct,
    numbers) where numbers is the list the run prints: each a short name
    with its number and its limit."""
    numbers = []
    for qid in sorted(answers):
        ref_mod = load_reference(qid)
        ref = ref_mod.answer(tables)
        worst_miss, worst_gap = 0, 0.0
        for got in answers[qid]:
            miss, gap = compare(got, ref, ref_mod.EXACT)
            worst_miss, worst_gap = max(worst_miss, miss), max(worst_gap, gap)
        numbers.append({"name": f"{qid}.answers", "value": len(answers[qid]),
                        "limit": 1, "holds": "at_least"})
        numbers.append({"name": f"{qid}.exact_mismatch", "value": worst_miss,
                        "limit": 0, "holds": "at_most"})
        numbers.append({"name": f"{qid}.rel_gap", "value": worst_gap,
                        "limit": ref_mod.REL_GAP_LIMIT, "holds": "at_most"})
    numbers.append({"name": "cpu_execs", "value": cpu_execs, "limit": 0,
                    "holds": "at_most"})
    numbers.append({"name": "unanswered", "value": unanswered, "limit": 0,
                    "holds": "at_most"})
    numbers.append({"name": "failed", "value": failed, "limit": 0,
                    "holds": "at_most"})
    return all(holds(n) for n in numbers), numbers


def holds(number):
    if number["holds"] == "at_least":
        return number["value"] >= number["limit"]
    return number["value"] <= number["limit"]


def control_gaps(tables, query_ids):
    """The control: the reference in float32 put in the program's place.
    Returns {query id: (exact mismatches, relative gap)}; the comparison is
    sound only if this fails a limit for every query."""
    out = {}
    for qid in query_ids:
        ref_mod = load_reference(qid)
        out[qid] = compare(ref_mod.answer(tables, "float32"),
                           ref_mod.answer(tables), ref_mod.EXACT)
    return out
