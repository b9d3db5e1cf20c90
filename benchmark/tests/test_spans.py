"""spans.py and the nine ``program_span`` metrics over a hand-built ring:
per-query sums, the last-N selection behind warm-up's roots, per-request
medians, None where the ring wrapped or the program has no such spans."""
import itertools
import types

import pytest

from benchmark import manifest, readers, spans

MS, S = 1_000_000, 1_000_000_000


class Ring:
    """Records as the program's ring would hold them: sequence numbers in
    the order of recording (a parent closes after its children)."""

    def __init__(self):
        self.records, self._ids = [], itertools.count(1)

    def add(self, name, dur_ns, parent=None, self_ns=None):
        rec = types.SimpleNamespace(
            name=name, dur_ns=dur_ns, span_id=next(self._ids),
            parent_id=parent.span_id if parent else None,
            self_ns=dur_ns if self_ns is None else self_ns, seq=None)
        self.records.append(rec)
        return rec

    def close(self):
        """Number the records: children before their parent."""
        depth = {}
        by_id = {r.span_id: r for r in self.records}
        for r in self.records:
            d, p = 0, r
            while p.parent_id is not None:
                p, d = by_id[p.parent_id], d + 1
            depth[r.span_id] = d
        root_of = {}
        for r in self.records:
            p = r
            while p.parent_id is not None:
                p = by_id[p.parent_id]
            root_of[r.span_id] = p.span_id
        order = sorted(self.records, key=lambda r: (
            root_of[r.span_id], -depth[r.span_id], r.span_id))
        for seq, r in enumerate(order):
            r.seq = seq
        return sorted(self.records, key=lambda r: r.seq)


def collect_query(ring, k):
    """One embedded query's tree; ``k`` scales its durations."""
    root = ring.add("query", 1000 * MS * k, self_ns=2 * MS * k)
    ring.add("plan", 10 * MS * k, root)
    action = ring.add("action", 900 * MS * k, root, self_ns=3 * MS * k)
    ring.add("serving.admission_wait", 1 * MS, action)
    scan = ring.add("HostToDeviceExec", 800 * MS * k, action)
    upload = ring.add("transfer.upload", 790 * MS * k, scan)
    for _ in range(2):
        ring.add("upload.stage", 300 * MS * k, upload)
        ring.add("upload.wait", 90 * MS * k, upload)
    ring.add("upload.assemble", 5 * MS, upload)
    agg = ring.add("TpuHashAggregateExec", 50 * MS, action)
    ring.add("program.agg", 4 * MS * k, agg)
    ring.add("program.sort", 2 * MS * k, action)
    ring.add("download.wait", 6 * MS * k, action)
    ring.add("download.to_arrow", 1 * MS * k, action)
    ring.add("result.concat", 1 * MS, root)
    return root


def served_request(ring, admission_ms, cache_wait_ms, uploads_s):
    root = ring.add("query", 10 * S)
    ring.add("serving.queue_wait", 1 * MS, root)
    action = ring.add("action", 9 * S, root)
    ring.add("serving.admission_wait", admission_ms * MS, action)
    scan = ring.add("HostToDeviceExec", 8 * S, action)
    if cache_wait_ms:
        ring.add("scan_cache.wait", cache_wait_ms * MS, scan)
    for u in uploads_s:
        ring.add("transfer.upload", int(u * S), scan)
    return root


def read(name, records, dropped, queries, monkeypatch):
    monkeypatch.setattr(spans, "_ring", lambda: (records, dropped))
    return readers.read(name, manifest.metric_file(name),
                        {"queries": queries})


def test_per_query_sums_over_the_last_n_roots(monkeypatch):
    ring = Ring()
    for _ in range(3):
        collect_query(ring, 7)          # warm-up: never read
    collect_query(ring, 1)
    collect_query(ring, 3)
    records = ring.close()
    got = {n: read(n, records, 0, 2, monkeypatch) for n in (
        "plan_ms_per_query.collect", "upload_stage_s_per_query.collect",
        "upload_wait_s_per_query.collect", "download_ms_per_query.collect",
        "program_call_ms_per_query.collect",
        "unattributed_ms_per_query.collect")}
    assert got == pytest.approx({
        "plan_ms_per_query.collect": (10 + 30) / 2,
        "upload_stage_s_per_query.collect": (0.6 + 1.8) / 2,
        "upload_wait_s_per_query.collect": (0.18 + 0.54) / 2,
        "download_ms_per_query.collect": (7 + 21) / 2,
        "program_call_ms_per_query.collect": (6 + 18) / 2,
        "unattributed_ms_per_query.collect": (5 + 15) / 2})
    # the whole window: all five when the run made five queries
    assert read("plan_ms_per_query.collect", records, 0, 5,
                monkeypatch) == pytest.approx((3 * 70 + 10 + 30) / 5)


def test_per_request_medians(monkeypatch):
    ring = Ring()
    served_request(ring, 500, 0, [9.0])             # warm-up
    served_request(ring, 1, 0, [3.0])
    served_request(ring, 3, 2900, [3.1])            # latched, then again
    served_request(ring, 2, 0, [1.5, 1.7])          # two tables
    records = ring.close()
    assert read("admission_wait_p50_ms.served", records, 0, 3,
                monkeypatch) == pytest.approx(2.0)
    assert read("scan_cache_wait_p50_ms.served", records, 0, 3,
                monkeypatch) == pytest.approx(0.0)
    assert read("upload_s_p50.served", records, 0, 3,
                monkeypatch) == pytest.approx(3.1)


def test_a_tree_is_followed_by_parent_not_by_order():
    """Two requests in flight interleave in the ring; each tree still
    holds its own spans only."""
    ring = Ring()
    a = ring.add("query", 10 * S)
    b = ring.add("query", 10 * S)
    ring.add("transfer.upload", 3 * S, ring.add("action", 9 * S, a))
    ring.add("transfer.upload", 5 * S, ring.add("action", 9 * S, b))
    records = ring.records
    for seq, r in enumerate(sorted(records, key=lambda r: (
            r.name == "query", r.span_id))):        # roots last
        r.seq = seq
    trees = spans.trees_of(records, 0, 2)
    assert [[r.name for r in t] for t in trees] == [
        ["query", "action", "transfer.upload"]] * 2
    assert [t[2].dur_ns for t in trees] == [3 * S, 5 * S]


def test_none_where_the_ring_wrapped_into_the_window():
    ring = Ring()
    collect_query(ring, 7)
    collect_query(ring, 1)
    collect_query(ring, 3)
    records = ring.close()
    warm_up_root = [r for r in records if r.name == "query"][0]
    first_of_window = warm_up_root.seq + 1
    # all of warm-up lost but its root, the last before the window: whole
    held = [r for r in records if r.seq >= warm_up_root.seq]
    assert len(spans.trees_of(held, warm_up_root.seq, 2)) == 2
    # one record of the window's first query gone: refused
    held = [r for r in records if r.seq > first_of_window]
    assert spans.trees_of(held, first_of_window + 1, 2) is None
    # that root gone too: nothing says where the window's first tree
    # began, refused
    held = [r for r in records if r.seq >= first_of_window]
    assert spans.trees_of(held, first_of_window, 2) is None
    # fewer roots than queries, or no query at all
    assert spans.trees_of(records, 0, 4) is None
    assert spans.trees_of(records, 0, 0) is None


def test_nothing_to_read_is_none_not_an_error(monkeypatch):
    """A program from before these spans: no ``dropped`` on its tracer, or
    no ``query`` root in its ring."""
    from spark_rapids_tpu.utils import tracing
    old = types.SimpleNamespace(since=lambda mark: [])
    monkeypatch.setattr(tracing, "TRACER", old)
    assert spans._ring() is None
    ring = Ring()
    ring.add("transfer.upload", 3 * S, ring.add("HostToDeviceExec", 4 * S))
    records = ring.close()
    for m in manifest.load()["per_layer"]:
        if m["source"] == "program_span" and m["name"] != \
                "queue_wait_p50_ms.served":
            assert read(m["name"], records, 0, 2, monkeypatch) is None


def test_the_program_ring_reads_through(monkeypatch):
    """The real tracer's records carry what spans.py reads."""
    from spark_rapids_tpu.utils import tracing
    t = tracing.Tracer(capacity=64)
    monkeypatch.setattr(tracing, "TRACER", t)
    with t.activate():
        for _ in range(3):
            with t.span("query", "query", profile=False):
                with t.span("plan", "plan", profile=False):
                    pass
    ctx = {"queries": 2}
    value = spans.per_query(ctx, ("plan",), 1e-6)
    assert value is not None and value >= 0
    assert [len(tree) for tree in spans.trees(ctx)] == [2, 2]
    small = tracing.Tracer(capacity=16)
    monkeypatch.setattr(tracing, "TRACER", small)
    with small.activate():
        for _ in range(9):
            with small.span("query", "query", profile=False):
                with small.span("plan", "plan", profile=False):
                    pass
    assert small.dropped == 2
    assert spans.per_query({"queries": 7}, ("plan",), 1e-6) is not None
    assert spans.per_query({"queries": 8}, ("plan",), 1e-6) is None


def test_the_manifest_holds_the_nine():
    mf = manifest.load()
    manifest.validate(mf)
    by_name = {m["name"]: m for m in mf["per_layer"]}
    nine = [by_name[name] for name in (
        "plan_ms_per_query.collect", "upload_stage_s_per_query.collect",
        "upload_wait_s_per_query.collect", "download_ms_per_query.collect",
        "program_call_ms_per_query.collect",
        "unattributed_ms_per_query.collect", "admission_wait_p50_ms.served",
        "scan_cache_wait_p50_ms.served", "upload_s_p50.served")]
    assert all(m["source"] == "program_span" and m["better"] == "lower"
               for m in nine)
