"""The bounded open loop (``"loop": "open_overload"``): requests sent when
they are due, whatever the server is doing, as in ``harness.open_window``,
but from a fixed set of threads however many requests the window holds, so
that a load above the server's knee can be offered for a whole window.

- The schedule is ``loadgen.open_schedule``'s (the file's weights exactly,
  its ``arrival_seed``); each request's tenant is drawn from the file's
  ``tenants`` shares, exactly, by a stream of the same ``arrival_seed``.
- The calling thread sends: at each due time it submits the SQL text with
  ``QueryServiceClient.submit``, which returns a handle at once.
- Collectors resolve the handles: for each tenant as many threads as the
  server runs queries at once (its scheduler's ``max_concurrent``), each
  taking that tenant's oldest unresolved handle. The scheduler
  admits a tenant's requests in their order (fair share between tenants,
  FIFO within one), so the requests running on the server are always among
  the handles the collectors wait on, and an answer is timed when it
  arrives. One queue for all tenants would not do: a small tenant's request
  admitted ahead of the large tenant's backlog would wait, answered, behind
  the handles of that backlog.
- A traced run profiles a slice that starts ``trace_offset_s`` into the
  window, once the backlog has formed, and lasts ``trace_slice_s``. A thread
  of its own starts and closes it: closing blocks for about as long as it
  traced.

Every request of the window is waited for up to ``grace_s`` past its close
and judged; ``completed_qps`` counts only those answered by the close."""
import math
import queue
import random
import sys
import threading
import time
import types

from benchmark import loadgen

#: the most threads the loop holds: the sender, the collectors, the tracer's
MAX_THREADS = 16


def tenant_draw(tenants, n, arrival_seed):
    """``n`` tenant ids in the file's shares exactly (largest remainder),
    shuffled by a stream of ``arrival_seed`` apart from the schedule's."""
    total = sum(t["share"] for t in tenants)
    exact = [n * t["share"] / total for t in tenants]
    counts = [math.floor(x) for x in exact]
    by_remainder = sorted(range(len(tenants)),
                          key=lambda i: counts[i] - exact[i])
    for i in by_remainder[:n - sum(counts)]:
        counts[i] += 1
    ids = [t["id"] for t, c in zip(tenants, counts) for _ in range(c)]
    random.Random(f"tenants:{arrival_seed}").shuffle(ids)
    return ids


def schedule(workload, seconds, rate=None):
    """[(due seconds from window start, query id, tenant)], sorted by due
    time. The run's ``--seed`` is not read: it makes the tables only."""
    workload = dict(workload, rate_per_s=rate or workload["rate_per_s"])
    due = loadgen.open_schedule(workload, None, seconds)
    tenants = tenant_draw(workload["tenants"], len(due),
                          workload.get("arrival_seed", 0))
    return [(d, qid, tenant) for (d, qid), tenant in zip(due, tenants)]


def completed_qps(records, seconds):
    """Requests answered by the window's close, over its seconds."""
    return sum(1 for r in records
               if "table" in r and r["done"] <= seconds) / seconds


def _collect(handles, t0, tracer):
    while True:
        item = handles.get()
        if item is None:
            return
        rec, handle = item
        try:
            table = handle.result()
        except Exception as e:  # a failed query is counted, not fatal
            rec["done"] = time.perf_counter() - t0
            rec["error"] = repr(e)
            continue
        rec["done"] = time.perf_counter() - t0
        rec["queue_wait_s"] = handle.metrics.get("queue_wait_s")
        # last: a record that holds a table is whole, even one read at the
        # deadline while its collector is still at work
        rec["table"] = table
        if tracer:
            tracer.query_done(rec["qid"])


def _trace(tracer, t0, offset_s, slice_s, ended):
    """Start the slice at ``offset_s``, close it ``slice_s`` later or when
    the window has ended, whichever comes first."""
    if ended.wait(t0 + offset_s - time.perf_counter()):
        return
    tracer.start()
    ended.wait(t0 + offset_s + slice_s - time.perf_counter())
    tracer.stop()


def trace_slice(workload, seconds):
    """(offset, length) of the traced slice, both shrunk alike where the
    window is shorter than the file's slice ends (a rehearsal)."""
    offset, length = workload["trace_offset_s"], workload["trace_slice_s"]
    shrink = min(1.0, seconds / (offset + length))
    return offset * shrink, length * shrink


def window(st, seconds, grace_s, tracer=None, rate=None):
    # a tenant's running queries are at most all the server runs at once
    each = st.session.scheduler.max_concurrent
    need = 1 + len(st.workload["tenants"]) * each + (tracer is not None)
    if need > MAX_THREADS:
        raise ValueError(f"{st.cell}: the loop would hold {need} threads, "
                         f"more than {MAX_THREADS}")
    records = [{"due": due, "qid": qid, "tenant": tenant}
               for due, qid, tenant in schedule(st.workload, seconds, rate)]
    per_tenant = {t["id"]: queue.Queue() for t in st.workload["tenants"]}
    before = st.counters.snapshot()
    t0 = time.perf_counter()
    collectors = [threading.Thread(target=_collect, args=(q, t0, tracer),
                                   name=f"overload-collect-{tenant}-{i}",
                                   daemon=True)
                  for tenant, q in per_tenant.items() for i in range(each)]
    ended = threading.Event()
    tracing = tracer and threading.Thread(
        target=_trace, name="overload-trace", daemon=True,
        args=(tracer, t0, *trace_slice(st.workload, seconds), ended))
    for thread in collectors + ([tracing] if tracing else []):
        thread.start()
    peak = threading.active_count()
    for rec in records:
        delay = t0 + rec["due"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        rec["sent"] = time.perf_counter() - t0
        try:
            handle = st.client.submit(st.sql[rec["qid"]], tenant=rec["tenant"],
                                      label=rec["qid"])
        except Exception as e:  # shed or refused: counted in `failed`
            rec["error"] = repr(e)
            rec["done"] = time.perf_counter() - t0
        else:
            per_tenant[rec["tenant"]].put((rec, handle))
        peak = max(peak, threading.active_count())
    deadline = max(t0 + seconds, time.perf_counter()) + grace_s
    for q in per_tenant.values():
        for _ in range(each):
            q.put(None)
    for thread in collectors:
        thread.join(max(deadline - time.perf_counter(), 0.0))
    ended.set()
    if tracing:
        tracing.join()
        tracer.stop()
    answers = {q: [] for q in st.qids}
    failed = unanswered = 0
    for rec in records:
        rec["late_s"] = rec["sent"] - rec["due"]
        if "error" in rec:
            failed += 1
        elif "table" not in rec:
            unanswered += 1
        else:
            answers[rec["qid"]].append(rec["table"])
            rec["latency_s"] = rec["done"] - rec["due"]
    qps = completed_qps(records, seconds)
    answered = len(records) - failed - unanswered
    print(f"benchmark: {len(records)} requests, {answered} answered "
          f"({round(qps * seconds)} by the close), {failed} failed, "
          f"{unanswered} unanswered; the loop held {need} threads, the "
          f"process at most {peak}", file=sys.stderr, flush=True)
    return types.SimpleNamespace(
        answers=answers, attempted=len(records), failed=failed + unanswered,
        unanswered=unanswered, queries=answered, before=before,
        after=st.counters.snapshot(), requests=records,
        loop_threads=need,
        last_done=max((r.get("done", 0.0) for r in records), default=0.0),
        end_to_end={"completed_qps": qps})
