"""Structured query tracing: thread-bound spans in a bounded ring buffer.

The reference plugin is debuggable because every GpuExec carries
``totalTime``/``peakDevMemory``/``bufferTime`` and an NVTX range — you can
say which exec in a 20-node plan ate the wall clock. Our engine only
reported flat per-action counter DELTAS (utils/metrics.py): no per-operator
attribution, no timeline. This module is the missing layer, consumed by
three surfaces:

- **EXPLAIN ANALYZE** — ``PhysicalExec.tree_string(analyze=True)`` /
  ``TpuSession.explain_analyze()`` / ``QueryHandle.explain_analyze()``
  annotate each plan node with observed rows / batches / wall / self time
  (and grace-spill counts), Spark-UI style;
- **Perfetto / Chrome trace-event export** — ``export_chrome()`` writes
  the span window as ``{"traceEvents": [...]}`` JSON that loads in
  ``ui.perfetto.dev`` or ``chrome://tracing``, so overlapped pipelines
  (chunked upload vs compute, streaming D2H) are visually inspectable;
- **serve.stats** — the serving layer's rolling gauge window
  (serving/stats.py) rides the same per-query attribution.

Design constraints (the R002 contract):

- timestamps are ``time.perf_counter_ns`` taken at HOST boundaries that
  already exist — exec ``__next__`` calls, chunk staging returns, async
  D2H resolution, admission wakeups. No new device syncs anywhere: a span
  never calls ``block_until_ready``/``np.asarray`` on device data.
- one span tree per query: every record carries ``span_id`` and the
  ``parent_id`` of the span open on the recording thread (threads the
  program starts for a query ``adopt()`` the spawning span), and the id
  of its query — the bound ``QueryHandle.query_id`` when served, else an
  ordinal from the same counter taken when the root opens.
- one clock: a live ``span()`` also enters a ``jax.profiler``
  ``TraceAnnotation`` named ``<name>#<plan_id>``, so the ring and a
  profile hold the same spans and the profile's are on the device
  trace's clock. Windows between asynchronous boundaries that are only
  known afterwards (``record()`` with explicit timestamps:
  ``shuffle.fetch``, ``serving.queue_wait``, ``serving.preempt_yield``),
  instants and the ``query`` root are in the ring alone.
- disabled mode is near-zero-cost: every hook is gated on one module-bool
  read (``enabled()``); ``span()`` returns a shared no-op context manager
  without allocating.
- the ring buffer is bounded (``trace.maxBufferedSpans``): a long-running
  traced server overwrites its oldest spans instead of growing without
  bound. ``mark()``/``since()`` give an action-scoped window; per-query
  filtering uses the span's query id (bound thread-locally by the serving
  worker via ``serving.lifecycle.bind_query``).

Span layers (``cat``): ``query`` (the root), ``plan`` (plan + rewrite),
``action`` (the device-admitted run), ``exec`` (operator execute
boundaries), ``program`` (calls of cached XLA programs), ``transfer``
(upload stage / wait / assemble, scan-cache waits, downloads), ``shuffle``
(fetch / retry), ``memory`` (grace partition / spill), ``serving``
(lifecycle transitions, queue, admission and preemption waits, wire
frames). docs/observability.md has the table of names.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

#: span layer names every consumer agrees on (docs/observability.md)
LAYER_QUERY = "query"
LAYER_PLAN = "plan"
LAYER_ACTION = "action"
LAYER_EXEC = "exec"
LAYER_PROGRAM = "program"
LAYER_TRANSFER = "transfer"
LAYER_SHUFFLE = "shuffle"
LAYER_MEMORY = "memory"
LAYER_SERVING = "serving"

#: the action span's profiler name: benchmark/reduce.py names an idle gap
#: by it when no finer range covers the gap
ACTION_RANGE = "tpu-sql-action"


def profiler_name(name: str, plan_id: Optional[int]) -> str:
    """``<name>#<plan_id>``, the form benchmark/reduce.py takes for a
    program range. The suffix is the plan node's ordinal (``#0`` for spans
    outside any exec), stable from query to query — never the query id,
    which would splinter a per-name reduction."""
    return f"{name}#{plan_id or 0}"


class SpanRecord:
    """One completed span (or instant event, ``dur_ns == 0``)."""

    __slots__ = ("name", "cat", "ts_ns", "dur_ns", "tid", "query_id",
                 "plan_id", "args", "seq", "span_id", "parent_id", "self_ns")

    def __init__(self, name: str, cat: str, ts_ns: int, dur_ns: int,
                 tid: int, query_id: Optional[int],
                 plan_id: Optional[int], args: Optional[Dict[str, Any]],
                 seq: int = 0, span_id: int = 0,
                 parent_id: Optional[int] = None,
                 self_ns: Optional[int] = None):
        self.name = name
        self.cat = cat
        self.ts_ns = ts_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.query_id = query_id
        self.plan_id = plan_id
        self.args = args
        self.seq = seq
        self.span_id = span_id
        #: the span that was open on the recording thread (None: a root)
        self.parent_id = parent_id
        #: duration minus the live child spans on the same thread
        self.self_ns = dur_ns if self_ns is None else self_ns

    def to_event(self) -> Dict[str, Any]:
        """Chrome trace-event form (``ph: X`` complete events; instants
        use ``ph: i``). Timestamps/durations are microseconds."""
        import os
        ev: Dict[str, Any] = {
            "name": self.name, "cat": self.cat, "pid": os.getpid(),
            "tid": self.tid, "ts": self.ts_ns / 1e3,
        }
        if self.dur_ns > 0:
            ev["ph"] = "X"
            ev["dur"] = self.dur_ns / 1e3
        else:
            ev["ph"] = "i"
            ev["s"] = "t"
        args = dict(self.args or {})
        if self.query_id is not None:
            args["query_id"] = self.query_id
        if self.plan_id is not None:
            args["plan_id"] = self.plan_id
        args["span_id"] = self.span_id
        if self.parent_id is not None:
            args["parent_id"] = self.parent_id
        ev["args"] = args
        return ev


class _NullSpan:
    """Shared no-op context manager returned while tracing is off —
    ``span()`` on the disabled path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()

#: per-thread innermost open frame (``.top``): what a new span's parent,
#: query id and plan id come from
_TLS = threading.local()
_SPAN_IDS = itertools.count(1)


class _Frame:
    """One open span on a thread's stack. ``child_ns`` sums the live child
    spans closed under it, which on one thread nest and never overlap, so
    duration minus ``child_ns`` is the span's self time."""

    __slots__ = ("span_id", "query_id", "plan_id", "child_ns", "_prev")

    def __init__(self, span_id: int, query_id: Optional[int],
                 plan_id: Optional[int]):
        self.span_id = span_id
        self.query_id = query_id
        self.plan_id = plan_id
        self.child_ns = 0
        self._prev = None

    def push(self) -> None:
        self._prev = getattr(_TLS, "top", None)
        _TLS.top = self

    def pop(self, dur_ns: int) -> None:
        _TLS.top = self._prev
        if self._prev is not None:
            self._prev.child_ns += dur_ns

    # adopt(): the frame as a context manager on the adopting thread
    def __enter__(self):
        self.push()
        return self

    def __exit__(self, *exc):
        self.pop(0)
        return False


def current() -> Optional[_Frame]:
    """The innermost span open on this thread (None outside any)."""
    return getattr(_TLS, "top", None)


def adopt(parent: Optional[_Frame]):
    """Context manager for a thread the program starts for a query
    (pipeline producer, scan prefetch): spans recorded on it become
    children of ``parent``, the span that was open where the thread was
    spawned (``current()`` there; None while tracing is off)."""
    if parent is None:
        return _NULL_SPAN
    return _Frame(parent.span_id, parent.query_id, parent.plan_id)


def _profiler_annotation(name: str):
    """A jax.profiler.TraceAnnotation for ``name`` (the named ranges
    TRACE_ENABLED promises — NvtxWithMetrics analog), or None when the
    profiler is unavailable. The one route to the profiler: live spans and
    the per-pull exec ranges both come through here."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            import jax.profiler
            _TRACE_ANNOTATION = jax.profiler.TraceAnnotation
        except Exception:
            _TRACE_ANNOTATION = False
    if _TRACE_ANNOTATION is False:
        return None
    try:
        return _TRACE_ANNOTATION(name)
    except Exception:
        return None


_TRACE_ANNOTATION = None


class _LiveSpan(_Frame):
    """Context manager recording one span on exit, and showing it as a
    profiler range while it is open."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_profile",
                 "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]], plan_id: Optional[int],
                 query_id: Optional[int], profile, t0_ns: Optional[int]):
        super().__init__(0, query_id, plan_id)
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = t0_ns
        self._profile = profile
        self._ann = None

    def __enter__(self):
        self.push()
        parent = self._prev
        if parent is not None:
            if self.plan_id is None:
                self.plan_id = parent.plan_id
            if self.query_id is None:
                self.query_id = parent.query_id
        elif self.query_id is None:
            self.query_id = _root_query_id()
        self.span_id = next(_SPAN_IDS)
        if self._profile:
            self._ann = _profiler_annotation(
                self._profile if isinstance(self._profile, str)
                else profiler_name(self._name, self.plan_id))
            if self._ann is not None:
                self._ann.__enter__()
        if self._t0 is None:
            self._t0 = time.perf_counter_ns()
        return self

    def note(self, **args) -> None:
        """Add args only known once the work is done (row counts, sizes)."""
        self._args = {**(self._args or {}), **args}

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.pop(dur)
        self._tracer._put(SpanRecord(
            self._name, self._cat, self._t0, dur, threading.get_ident(),
            self.query_id, self.plan_id, self._args, span_id=self.span_id,
            parent_id=self._prev.span_id if self._prev is not None else None,
            self_ns=max(dur - self.child_ns, 0)))
        return False


def _lifecycle():
    # lazy, cached: only runs while tracing is ON (never on the hot path)
    global _LIFECYCLE
    if _LIFECYCLE is None:
        from spark_rapids_tpu.serving import lifecycle
        _LIFECYCLE = lifecycle
    return _LIFECYCLE


_LIFECYCLE = None


def _current_query_id() -> Optional[int]:
    q = _lifecycle().current_query()
    return q.query_id if q is not None else None


def _root_query_id() -> int:
    """The id a span tree takes when it opens with no parent: the bound
    query's, else the next of the ids QueryHandles take, so an embedded
    ``collect()`` and a served query never share one."""
    qid = _current_query_id()
    return qid if qid is not None else _lifecycle().next_query_id()


class Tracer:
    """Bounded ring buffer of spans with an activation count.

    ``activate()`` scopes (one per traced action / served query) nest; the
    ring survives across scopes so a server can export a window covering
    many queries. ``mark()``/``since()`` give callers an action-scoped
    slice without copying the whole ring.
    """

    DEFAULT_CAPACITY = 262144

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._capacity = max(16, capacity)
        self._ring: List[Optional[SpanRecord]] = [None] * self._capacity
        self._seq = 0               # monotonically increasing record count
        self._active = 0
        #: records the ring overwrote or a resize let go: a reader
        #: refuses a window that reaches back past them
        self.dropped = 0
        #: the one-field fast path every disabled hook reads
        self.on = False

    # ---- activation --------------------------------------------------------
    def configure(self, capacity: int) -> None:
        """Resize the ring, PRESERVING the newest min(old, new) records —
        the capacity is effectively process-wide (one tracer, many
        sessions), so a session with a different trace.maxBufferedSpans
        must not wipe a just-finished query's exportable spans. Resizes
        are skipped while an activation is live (a shrink could drop part
        of a running action's window)."""
        with self._lock:
            capacity = max(16, int(capacity))
            if capacity == self._capacity or self._active > 0:
                return
            new_ring: List[Optional[SpanRecord]] = [None] * capacity
            lo = max(0, self._seq - min(self._capacity, capacity))
            for i in range(lo, self._seq):
                new_ring[i % capacity] = self._ring[i % self._capacity]
            self.dropped = max(self.dropped, lo)
            self._capacity = capacity
            self._ring = new_ring

    def activate(self):
        """Context manager turning tracing on for the scope (nesting
        counts; ``on`` stays True until the outermost scope exits)."""
        tracer = self

        class _Scope:
            def __enter__(self):
                with tracer._lock:
                    tracer._active += 1
                    tracer.on = True
                return tracer

            def __exit__(self, *exc):
                with tracer._lock:
                    tracer._active -= 1
                    tracer.on = tracer._active > 0
                return False

        return _Scope()

    # ---- recording ---------------------------------------------------------
    def _put(self, rec: SpanRecord) -> None:
        with self._lock:
            rec.seq = self._seq
            self._ring[self._seq % self._capacity] = rec
            self._seq += 1
            self.dropped = max(self.dropped, self._seq - self._capacity)

    def record(self, name: str, cat: str, ts_ns: int, dur_ns: int,
               args: Optional[Dict[str, Any]] = None,
               plan_id: Optional[int] = None,
               query_id: Optional[int] = None) -> None:
        """A span whose two ends are only known afterwards (a window
        between asynchronous boundaries), or an instant: ring only. Its
        parent is the span open on this thread, unless that belongs to
        another query than the one named."""
        if not self.on:
            return
        top = getattr(_TLS, "top", None)
        if top is not None and query_id not in (None, top.query_id):
            top = None
        parent_id = None
        if top is not None:
            parent_id = top.span_id
            query_id = top.query_id
            if plan_id is None:
                plan_id = top.plan_id
        elif query_id is None:
            query_id = _current_query_id()
        self._put(SpanRecord(name, cat, ts_ns, dur_ns, threading.get_ident(),
                             query_id, plan_id, args,
                             span_id=next(_SPAN_IDS), parent_id=parent_id))

    def span(self, name: str, cat: str,
             args: Optional[Dict[str, Any]] = None, *,
             plan_id: Optional[int] = None, query_id: Optional[int] = None,
             profile=True, t0_ns: Optional[int] = None):
        """Timed scope, in the ring and (``profile``: True for
        ``<name>#<plan_id>``, a string for that name, False for none) in
        the profiler's trace; the disabled path returns one shared no-op.
        ``plan_id`` and ``query_id`` default to the enclosing span's;
        ``t0_ns`` backdates the start to a boundary already passed."""
        if not self.on:
            return _NULL_SPAN
        return _LiveSpan(self, name, cat, args, plan_id, query_id, profile,
                         t0_ns)

    def instant(self, name: str, cat: str,
                args: Optional[Dict[str, Any]] = None) -> None:
        if not self.on:
            return
        self.record(name, cat, time.perf_counter_ns(), 0, args)

    # ---- reading -----------------------------------------------------------
    def mark(self) -> int:
        """Current sequence number — pass to ``since()`` for the spans
        recorded after this point (an action-scoped window)."""
        with self._lock:
            return self._seq

    def since(self, mark: int, query_id: Optional[int] = None
              ) -> List[SpanRecord]:
        """Spans recorded at or after ``mark`` (oldest first), optionally
        filtered to one query. Records the ring already overwrote are
        gone — the window is bounded by trace.maxBufferedSpans."""
        with self._lock:
            lo = max(mark, self._seq - self._capacity)
            out = [self._ring[i % self._capacity]
                   for i in range(lo, self._seq)]
        return [r for r in out
                if r is not None
                and (query_id is None or r.query_id == query_id)]

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self._capacity
            self._seq = 0
            self.dropped = 0


#: the process-wide tracer every layer records into
TRACER = Tracer()


def enabled() -> bool:
    return TRACER.on


def span(name: str, cat: str, args: Optional[Dict[str, Any]] = None, *,
         plan_id: Optional[int] = None, query_id: Optional[int] = None,
         profile=True, t0_ns: Optional[int] = None):
    return TRACER.span(name, cat, args, plan_id=plan_id, query_id=query_id,
                       profile=profile, t0_ns=t0_ns)


def instant(name: str, cat: str,
            args: Optional[Dict[str, Any]] = None) -> None:
    TRACER.instant(name, cat, args)


def record(name: str, cat: str, ts_ns: int, dur_ns: int,
           args: Optional[Dict[str, Any]] = None,
           plan_id: Optional[int] = None,
           query_id: Optional[int] = None) -> None:
    TRACER.record(name, cat, ts_ns, dur_ns, args, plan_id, query_id)


# ---------------------------------------------------------------- exec spans
#: per-thread currently-recording exec frame, for self-time attribution:
#: a child exec's __next__ time nested inside its parent's subtracts from
#: the parent's SELF time (the classic profiler discipline). Producer
#: threads (PipelinedExec / prefetch) keep their own stack — cross-thread
#: overlap deliberately does not subtract (it is genuine concurrency).
_EXEC_TLS = threading.local()


class _ExecRecorder:
    """Aggregated observation of one exec node across one execute() call."""

    __slots__ = ("node", "wall_ns", "child_ns", "rows", "batches", "bytes",
                 "t_first")

    def __init__(self, node):
        self.node = node
        self.wall_ns = 0
        self.child_ns = 0
        self.rows = 0
        self.batches = 0
        self.bytes = 0
        self.t_first = 0


def observed_of(node) -> Optional[Dict[str, Any]]:
    """The node's accumulated observation dict (None before any traced
    execution). Keys: rows, batches, bytes, wall_ns, self_ns, partitions,
    plus grace_partitions / grace_depth when the out-of-core path ran."""
    return getattr(node, "_observed", None)


def _accumulate(node, rec: _ExecRecorder) -> None:
    obs = getattr(node, "_observed", None)
    with TRACER._lock:
        if obs is None:
            obs = node._observed = {"rows": 0, "batches": 0, "bytes": 0,
                                    "wall_ns": 0, "self_ns": 0,
                                    "partitions": 0}
        obs["rows"] += rec.rows
        obs["batches"] += rec.batches
        obs["bytes"] += rec.bytes
        obs["wall_ns"] += rec.wall_ns
        obs["self_ns"] += max(rec.wall_ns - rec.child_ns, 0)
        obs["partitions"] += 1


def note_exec_spill(node, partitions: int, depth: int) -> None:
    """Grace layer attribution: this node's input was grace-partitioned
    (EXPLAIN ANALYZE renders it as ``spill=nxd``). Cheap dict stores on
    the already-degraded path — recorded even when span tracing is off so
    analyze output stays truthful about spills. Same lock as
    ``_accumulate``: one plan node's partitions can execute on parallel
    task threads (cluster task slots)."""
    with TRACER._lock:
        obs = getattr(node, "_observed", None)
        if obs is None:
            obs = node._observed = {"rows": 0, "batches": 0, "bytes": 0,
                                    "wall_ns": 0, "self_ns": 0,
                                    "partitions": 0}
        obs["grace_partitions"] = obs.get("grace_partitions", 0) + partitions
        obs["grace_depth"] = max(obs.get("grace_depth", 0), depth)


def trace_exec(node, ctx, raw) -> Iterator:
    """Wrap one exec's ``execute()`` iteration with span recording: each
    ``__next__`` is timed (and shows as a named jax.profiler range), rows/
    batches/bytes are observed from the yielded batches, and ONE span per
    execute() call lands in the ring (ts = first pull, dur = pull window).
    Each pull is a frame on the thread's span stack, so spans opened inside
    it (uploads, program calls, child execs) are this span's children and
    take its plan id. EXPLAIN ANALYZE's self time subtracts nested child
    EXEC pulls on the same thread; the record's ``self_ns`` subtracts every
    live child span.

    A subclass delegating to ``super().execute()`` (FusedAggregateStage ->
    TpuHashAggregate) must not double-record the node: when the CURRENT
    frame already records this node, the raw iterator passes through."""
    cur = getattr(_EXEC_TLS, "rec", None)
    if cur is not None and cur.node is node:
        yield from raw(node, ctx)
        return
    rec = _ExecRecorder(node)
    opener = getattr(_TLS, "top", None)
    qid = opener.query_id if opener is not None else _current_query_id()
    span_id = next(_SPAN_IDS)
    range_name = profiler_name(node.name, node.plan_id)
    self_ns = 0
    it = iter(raw(node, ctx))
    try:
        while True:
            parent = getattr(_EXEC_TLS, "rec", None)
            _EXEC_TLS.rec = rec
            frame = _Frame(span_id, qid, node.plan_id)
            frame.push()
            ann = _profiler_annotation(range_name)
            t0 = time.perf_counter_ns()
            if rec.t_first == 0:
                rec.t_first = t0
            try:
                if ann is not None:
                    with ann:
                        batch = next(it)
                else:
                    batch = next(it)
            except StopIteration:
                return
            finally:
                dt = time.perf_counter_ns() - t0
                rec.wall_ns += dt
                if parent is not None:
                    parent.child_ns += dt
                _EXEC_TLS.rec = parent
                frame.pop(dt)
                self_ns += max(dt - frame.child_ns, 0)
            rec.batches += 1
            n = getattr(batch, "num_rows", None)
            if n is not None:
                rec.rows += int(n)
            rec.bytes += int(getattr(batch, "device_size_bytes", 0) or 0)
            yield batch
    finally:
        close = getattr(it, "close", None)
        if close is not None:
            close()
        _accumulate(node, rec)
        if rec.t_first:
            TRACER._put(SpanRecord(
                node.name, LAYER_EXEC, rec.t_first,
                time.perf_counter_ns() - rec.t_first,
                threading.get_ident(), qid, node.plan_id,
                {"rows": rec.rows, "batches": rec.batches,
                 "bytes": rec.bytes,
                 "busy_ms": round(rec.wall_ns / 1e6, 3),
                 "self_ms": round(max(rec.wall_ns - rec.child_ns, 0) / 1e6,
                                  3),
                 "partition": ctx.partition_id},
                span_id=span_id,
                parent_id=opener.span_id if opener is not None else None,
                self_ns=self_ns))


# ---------------------------------------------------------------- rendering
def _fmt_ms(ns: int) -> str:
    return f"{ns / 1e6:.1f}ms"


def analyze_annotation(node) -> str:
    """The EXPLAIN ANALYZE suffix for one plan node, '' when the node was
    never executed under tracing."""
    obs = getattr(node, "_observed", None)
    if obs is None:
        return ""
    parts = [f"rows={obs['rows']}", f"batches={obs['batches']}"]
    if obs.get("wall_ns"):
        parts.append(f"wall={_fmt_ms(obs['wall_ns'])}")
        parts.append(f"self={_fmt_ms(obs['self_ns'])}")
    if obs.get("bytes"):
        parts.append(f"bytes={obs['bytes']}")
    if obs.get("grace_partitions"):
        parts.append(f"spill={obs['grace_partitions']}p"
                     f"x{obs.get('grace_depth', 1)}d")
    return " (" + ", ".join(parts) + ")"


def export_chrome(records: List[SpanRecord], path: str,
                  metadata: Optional[Dict[str, Any]] = None) -> None:
    """Write ``records`` as Chrome trace-event JSON (loads in Perfetto /
    chrome://tracing). ``metadata`` lands in the top-level ``otherData``."""
    doc = {"traceEvents": [r.to_event() for r in records],
           "displayTimeUnit": "ms"}
    if metadata:
        doc["otherData"] = metadata
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)


def layer_counts(records: List[SpanRecord]) -> Dict[str, int]:
    """Span count per layer — the CI smoke's one-line acceptance check."""
    out: Dict[str, int] = {}
    for r in records:
        out[r.cat] = out.get(r.cat, 0) + 1
    return out
