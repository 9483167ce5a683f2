"""Query-scoped span tracer — the NVTX-range + event-log role.

Reference: NvtxWithMetrics.scala wraps operator work in NVTX ranges nsys
consumes; Spark's event log feeds the history server and the RAPIDS
profiling tool replays it offline (SURVEY §5).  Here one `QueryTracer`
rides the ExecContext through a query: lifecycle phases (plan, compile,
execute, transitions, shuffle) record `Span`s, runtime incidents (OOM
retry, batch split, spill, semaphore wait, whole-plan fallback) record
instant events, and data-movement accounting (H2D/D2H/shuffle/ICI bytes)
accumulates in counters.

Serialization is two-way:
  * a JSONL structured event log per query under
    `spark.rapids.tpu.eventLog.dir` (`query_<id>.jsonl`) — parse it back
    with `read_event_log()`;
  * a Chrome trace-event JSON (`query_<id>.trace.json`) openable in
    perfetto / chrome://tracing.

Tracing is OFF by default (`NULL_TRACER` no-ops keep the disabled path
near-free); enable with `spark.rapids.tpu.trace.enabled` (in-memory, for
`TpuSession.last_query_profile()`) or by setting the event-log dir.

The layer boundaries of `DataFrame.collect()` are the exception: each is
one `CollectSpan`, which is always on.  It writes a `tpu.<name>`
`jax.profiler.TraceAnnotation` (so a profiler trace holds the host's
spans on the device's clock), adds its time to one `ctx.metrics` key,
and records the same span here when a `QueryTracer` is enabled.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..config import EVENT_LOG_DIR, TRACE_ENABLED, TpuConf
from .recorder import FLIGHT_RECORDER
from .registry import DATA_BYTES, RUNTIME_EVENTS, next_query_seq

#: tracer byte-counter key -> always-on registry data-movement channel
_BYTE_CHANNELS = {
    "h2d_bytes": "h2d",
    "d2h_bytes": "d2h",
    "shuffle_bytes_written": "shuffle_write",
    "shuffle_bytes_read": "shuffle_read",
    "ici_exchange_bytes": "ici_exchange",
}


def _publish_instant(name: str, cat: str, attrs: dict,
                     query=None) -> None:
    """Always-on half of every instant: the flight-recorder ring and
    the process registry see the incident whether or not a per-query
    tracer is collecting it."""
    FLIGHT_RECORDER.record("instant", name, cat, attrs, query=query)
    RUNTIME_EVENTS.inc(1, event=name, cat=cat)


def _publish_bytes(key: str, n: int) -> None:
    DATA_BYTES.inc(int(n), channel=_BYTE_CHANNELS.get(key, key))


@dataclasses.dataclass
class Span:
    """One timed range. t0/t1 are time.perf_counter() seconds; `node` is
    the stable plan-node id (`ClassName#preorder`) for operator spans."""
    sid: int
    parent: Optional[int]
    name: str
    cat: str                      # plan | compile | execute | operator |
                                  # transition | shuffle | query
    t0: float
    t1: float
    node: Optional[str] = None
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dur_ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0


@dataclasses.dataclass
class Event:
    """An instant incident (OOM retry, spill, fallback, ...)."""
    name: str
    cat: str
    t: float
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _jsonable(v):
    """Numbers stay numbers (numpy scalars included), everything else
    stringifies — the event log must always serialize."""
    if isinstance(v, bool) or v is None or isinstance(v, (int, float, str)):
        return v
    item = getattr(v, "item", None)
    if item is not None:
        try:
            return item()
        except Exception:                        # noqa: BLE001
            pass
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


class QueryTracer:
    """Span/event/counter collector for ONE query execution.

    Thread-safe: shuffle writer/reader threads and spill workers record
    into the same tracer; parent attribution uses a per-thread span
    stack (a worker thread's spans parent to the root query span)."""

    def __init__(self, query_id: int):
        self.query_id = query_id
        self.enabled = True
        self.spans: List[Span] = []
        self.events: List[Event] = []
        self.counters: Dict[str, float] = {}
        self.meta: Dict[str, Any] = {}
        self.metrics: Optional[dict] = None   # bound to ctx.metrics
        self.wall_start_unix = time.time()
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._next_sid = 0
        self._root_sid: Optional[int] = None

    # -- recording ---------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _parent(self) -> Optional[int]:
        st = self._stack()
        return st[-1] if st else self._root_sid

    def add_span(self, name: str, cat: str, t0: float, t1: float,
                 node: Optional[str] = None, parent: Optional[int] = None,
                 **attrs) -> Span:
        """Record an already-measured range (operator wrappers time
        themselves and report at stream exhaustion)."""
        with self._lock:
            sid = self._next_sid
            self._next_sid += 1
            sp = Span(sid, parent if parent is not None else self._parent(),
                      name, cat, t0, t1, node,
                      {k: _jsonable(v) for k, v in attrs.items()})
            self.spans.append(sp)
        FLIGHT_RECORDER.record(
            "span", name, cat,
            {"dur_ms": round(sp.dur_ms, 3),
             **({"node": node} if node else {})}, query=self.query_id)
        return sp

    def begin(self, name: str, cat: str, node: Optional[str] = None,
              t0: Optional[float] = None, parent: Optional[int] = None,
              **attrs) -> Span:
        """Open a range on the calling thread; spans opened before
        `end()` parent to it.  `t0`/`parent` place a range that started
        before this tracer existed (CollectSpan)."""
        with self._lock:
            sid = self._next_sid
            self._next_sid += 1
        if parent is None:
            parent = self._parent()
        if cat == "query" and self._root_sid is None:
            self._root_sid = sid
        self._stack().append(sid)
        if t0 is None:
            t0 = time.perf_counter()
        return Span(sid, parent, name, cat, t0, t0, node,
                    {k: _jsonable(v) for k, v in attrs.items()})

    def end(self, sp: Span, t1: Optional[float] = None) -> None:
        self._stack().pop()
        sp.t1 = time.perf_counter() if t1 is None else t1
        with self._lock:
            self.spans.append(sp)
        FLIGHT_RECORDER.record(
            "span", sp.name, sp.cat,
            {"dur_ms": round(sp.dur_ms, 3),
             **({"node": sp.node} if sp.node else {})},
            query=self.query_id)

    @contextmanager
    def span(self, name: str, cat: str, node: Optional[str] = None,
             **attrs):
        """Time a range; nested spans parent to it (per-thread)."""
        sp = self.begin(name, cat, node, **attrs)
        try:
            yield
        finally:
            self.end(sp)

    def instant(self, name: str, cat: str, **attrs) -> None:
        with self._lock:
            self.events.append(Event(name, cat, time.perf_counter(),
                                     {k: _jsonable(v)
                                      for k, v in attrs.items()}))
        _publish_instant(name, cat, attrs, query=self.query_id)

    def add_bytes(self, key: str, n: int) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + int(n)
        _publish_bytes(key, n)

    def finish(self, metrics: Optional[dict] = None) -> None:
        """Snapshot the query's final metrics (call after lazy device
        metric coercion so every value is a host number)."""
        if metrics is not None:
            snap = {k: _jsonable(v) for k, v in metrics.items()}
            with self._lock:
                self.metrics = snap

    # -- serialization -----------------------------------------------------
    def _origin(self) -> float:
        ts = [s.t0 for s in self.spans] + [e.t for e in self.events]
        return min(ts) if ts else 0.0

    def to_jsonl_lines(self) -> List[str]:
        """The structured event log: one JSON object per line, starting
        with a query_start header and ending with query_end (metrics +
        counters + meta)."""
        org = self._origin()
        lines = [json.dumps({
            "type": "query_start", "query_id": self.query_id,
            "wall_start_unix": self.wall_start_unix})]
        for s in sorted(self.spans, key=lambda s: s.t0):
            rec = {"type": "span", "id": s.sid, "parent": s.parent,
                   "name": s.name, "cat": s.cat,
                   "t0_ms": round((s.t0 - org) * 1e3, 3),
                   "dur_ms": round(s.dur_ms, 3)}
            if s.node is not None:
                rec["node"] = s.node
            if s.attrs:
                rec["attrs"] = s.attrs
            lines.append(json.dumps(rec))
        for e in self.events:
            rec = {"type": "instant", "name": e.name, "cat": e.cat,
                   "t_ms": round((e.t - org) * 1e3, 3)}
            if e.attrs:
                rec["attrs"] = e.attrs
            lines.append(json.dumps(rec))
        from .registry import REGISTRY
        lines.append(json.dumps(_jsonable({
            "type": "query_end", "query_id": self.query_id,
            "metrics": self.metrics or {}, "counters": self.counters,
            "meta": self.meta,
            # the process metrics-plane snapshot at log-write time, so
            # one event log is post-mortem self-contained
            "registry": REGISTRY.flat()})))
        return lines

    def to_chrome_trace(self) -> dict:
        """Chrome trace-event JSON (ph=X complete events, ph=i instants)
        — open in perfetto.  Operator spans get their own tid so the
        per-node lanes render side by side."""
        org = self._origin()
        tids = {}                # node id -> stable small tid

        def tid_for(s: Span) -> int:
            if s.node is None:
                return 0
            return tids.setdefault(s.node, len(tids) + 1)

        evs = [{"name": "process_name", "ph": "M", "pid": 1, "tid": 0,
                "args": {"name": f"query_{self.query_id}"}}]
        for s in sorted(self.spans, key=lambda s: s.t0):
            evs.append({"name": s.name, "cat": s.cat, "ph": "X",
                        "ts": round((s.t0 - org) * 1e6, 1),
                        "dur": round((s.t1 - s.t0) * 1e6, 1),
                        "pid": 1, "tid": tid_for(s),
                        "args": {**s.attrs,
                                 **({"node": s.node} if s.node else {})}})
        for e in self.events:
            evs.append({"name": e.name, "cat": e.cat, "ph": "i",
                        "ts": round((e.t - org) * 1e6, 1), "pid": 1,
                        "tid": 0, "s": "p", "args": e.attrs})
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def write(self, dir_path: str) -> Dict[str, str]:
        """Write both artifacts under dir_path; returns their paths.

        Collision-proof: query ids are process-unique and monotonic
        (make_tracer allocates under one lock), but several PROCESSES —
        or a process restart — may share one event-log directory, so an
        existing `query_<id>.jsonl` gets a monotonic `-<n>` suffix
        instead of being overwritten (the crash-dump filename rule,
        runtime/failure.py)."""
        os.makedirs(dir_path, exist_ok=True)
        with _WRITE_LOCK:
            base = os.path.join(dir_path, f"query_{self.query_id}")
            n = 0
            while os.path.exists(base + ".jsonl"):
                n += 1
                base = os.path.join(
                    dir_path, f"query_{self.query_id}-{n}")
            jsonl = base + ".jsonl"
            with open(jsonl, "w") as f:
                f.write("\n".join(self.to_jsonl_lines()) + "\n")
        trace = base + ".trace.json"
        with open(trace, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return {"jsonl": jsonl, "chrome_trace": trace}


@dataclasses.dataclass
class EventLog:
    """Parsed form of one query's JSONL event log."""
    query_id: int
    wall_start_unix: float
    spans: List[Span]
    events: List[Event]
    counters: Dict[str, float]
    metrics: Dict[str, Any]
    meta: Dict[str, Any]
    #: metrics-plane snapshot from the query_end record (PR 5)
    registry: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: the final line failed to parse (crash-time logs end mid-write);
    #: spans/events hold the intact prefix
    truncated: bool = False

    def span_tree(self) -> set:
        """Structural fingerprint for round-trip tests: one (id, parent,
        name, cat, node) tuple per span."""
        return {(s.sid, s.parent, s.name, s.cat, s.node)
                for s in self.spans}


def read_event_log(path: str) -> EventLog:
    """Parse a query_<id>.jsonl event log back into spans/events/metrics
    (the profiling tool's input — see scripts/profile_report.py).

    Crash-time logs end mid-write: a final line that fails to JSON-parse
    is tolerated — the intact prefix is returned with `truncated=True`
    instead of surfacing a raw json.JSONDecodeError.  A malformed line
    ANYWHERE ELSE still raises (that is corruption, not truncation)."""
    spans: List[Span] = []
    events: List[Event] = []
    qid, start = 0, 0.0
    counters: Dict[str, float] = {}
    metrics: Dict[str, Any] = {}
    meta: Dict[str, Any] = {}
    registry: Dict[str, Any] = {}
    truncated = False
    with open(path) as f:
        lines = [ln.strip() for ln in f]
    lines = [ln for ln in lines if ln]
    for i, line in enumerate(lines):
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            if i == len(lines) - 1:
                truncated = True
                break
            raise
        typ = rec.get("type")
        if typ == "query_start":
            qid = rec.get("query_id", 0)
            start = rec.get("wall_start_unix", 0.0)
        elif typ == "span":
            t0 = rec.get("t0_ms", 0.0) / 1e3
            spans.append(Span(rec.get("id", len(spans)),
                              rec.get("parent"),
                              rec.get("name", "?"), rec.get("cat", "?"),
                              t0, t0 + rec.get("dur_ms", 0.0) / 1e3,
                              rec.get("node"), rec.get("attrs", {})))
        elif typ == "instant":
            events.append(Event(rec.get("name", "?"), rec.get("cat", "?"),
                                rec.get("t_ms", 0.0) / 1e3,
                                rec.get("attrs", {})))
        elif typ == "query_end":
            counters = rec.get("counters", {})
            metrics = rec.get("metrics", {})
            meta = rec.get("meta", {})
            registry = rec.get("registry", {})
    return EventLog(qid, start, spans, events, counters, metrics, meta,
                    registry=registry, truncated=truncated)


class NullTracer:
    """Disabled-path tracer: span collection is a no-op (no timing, no
    allocation — what keeps default-conf overhead under the <2% budget),
    but instants and byte counters still feed the ALWAYS-ON metrics
    plane (flight recorder + process registry, PR 5): incidents and
    data movement stay visible with tracing off, at the cost of one
    enabled-flag check plus a dict/deque append per event."""

    enabled = False
    metrics: Optional[dict] = None
    meta: Dict[str, Any] = {}
    _null_cm = nullcontext()

    def span(self, name: str, cat: str, node=None, **attrs):
        return self._null_cm

    def add_span(self, *a, **k):
        return None

    def instant(self, name: str, cat: str, **attrs) -> None:
        _publish_instant(name, cat, attrs)

    def add_bytes(self, key: str, n: int) -> None:
        _publish_bytes(key, n)

    def finish(self, *a, **k):
        return None


NULL_TRACER = NullTracer()

_QUERY_ID_LOCK = threading.Lock()
_NEXT_QUERY_ID = 0
_WRITE_LOCK = threading.Lock()

# The ACTIVE tracer: runtime subsystems that have no ExecContext in
# reach (shuffle manager threads, the ICI exchange, the retry/spill
# machinery) report here.  Set for the duration of a query's
# instrumented scope (plan/overrides.py); NULL outside it.
#
# Concurrency (the serving plane runs many instrumented scopes at once):
# the binding is THREAD-LOCAL — each query's own thread (semaphore
# waits, retry ladders, spill chains all run on it) always attributes to
# its own tracer, and one query finishing can no longer null out another
# query's active binding.  Threads with no binding of their own (shared
# shuffle/spill/compile pool workers) fall back to the single active
# tracer when exactly ONE query is in scope process-wide — the
# single-query behavior every existing call site was built on — and to
# NULL_TRACER when several are (ambiguous attribution is dropped, never
# misassigned; the always-on registry still sees those events).
_TLS_ACTIVE = threading.local()
_ACTIVE_LOCK = threading.Lock()
_ACTIVE_SET: dict = {}            # id(tracer) -> tracer, currently in scope
_FALLBACK: object = NULL_TRACER   # the unique in-scope tracer, else NULL


def set_active(tracer) -> None:
    """Bind `tracer` as the calling thread's active tracer
    (NULL_TRACER unbinds).  Balanced bind/unbind pairs per scope keep
    the process-wide fallback exact."""
    global _FALLBACK
    prev = getattr(_TLS_ACTIVE, "tracer", None)
    _TLS_ACTIVE.tracer = tracer
    with _ACTIVE_LOCK:
        if prev is not None and getattr(prev, "enabled", False):
            _ACTIVE_SET.pop(id(prev), None)
        if getattr(tracer, "enabled", False):
            _ACTIVE_SET[id(tracer)] = tracer
        _FALLBACK = (next(iter(_ACTIVE_SET.values()))
                     if len(_ACTIVE_SET) == 1 else NULL_TRACER)


def get_active():
    tracer = getattr(_TLS_ACTIVE, "tracer", None)
    if tracer is not None and tracer is not NULL_TRACER:
        return tracer
    return _FALLBACK


def tracing(conf: TpuConf) -> bool:
    """Tracing is on for this conf: trace.enabled or an event-log dir."""
    return bool(conf.get(TRACE_ENABLED) or conf.get(EVENT_LOG_DIR))


def make_tracer(conf: TpuConf):
    """A real tracer when tracing is on for this conf, else the shared
    NULL_TRACER."""
    if not tracing(conf):
        return NULL_TRACER
    global _NEXT_QUERY_ID
    with _QUERY_ID_LOCK:
        _NEXT_QUERY_ID += 1
        qid = _NEXT_QUERY_ID
    return QueryTracer(qid)


class CollectSpan:
    """One layer boundary of the collect path, always on:

        with CollectSpan(ctx, "prepare", "overhead.prepare_ms"):
            ...

    Entering opens `TraceAnnotation("tpu.<name>", query=<query seq>)`
    where a profiler is live: it lands on the `/host:CPU` plane of the
    trace, on the device's clock.  Leaving adds the span's own time to
    `ctx.metrics[key]`: its duration less that of the spans opened
    inside it, so the keys of one collect add up to the outermost
    span's duration and the counters cannot disagree with the spans.
    A `split` span is a part of its parent, not a category beside it
    (the wait inside a fetch): its own time goes to its key and the
    parent's own time keeps it, so the parts of a span add up to no more
    than its key.  Where the context's tracer is enabled
    the span is recorded there too, parented to the enclosing
    CollectSpan, with its thread CPU time as `cpu_ms`.  Spans are serial
    on the collecting thread.

    Off-CPU time, `overhead.offcpu_ms`: the collecting thread off its
    core while doing host work (a GIL wait, a lock, preemption).  The
    spans' own times partition the outermost span's, so it is the
    outermost span's wall less its thread CPU time (`time.thread_time()`),
    less the same of each `device_wait` span inside it, which blocks on
    the device by design.  Only those spans read the CPU clock (a system
    call: about 6 us on the TPU v5e hosts of PERF.md), and every span
    where a tracer may record it."""

    __slots__ = ("ctx", "name", "key", "cat", "split", "whole_key",
                 "device_wait", "after", "_ann", "_outer", "_span", "_t0",
                 "_t1", "_c0", "_cpu_ms", "_inner_ms")

    def __init__(self, ctx, name: str, key: str, cat: str = "collect",
                 split: bool = False, whole_key: Optional[str] = None,
                 device_wait: bool = False):
        self.ctx = ctx
        self.name = "tpu." + name
        self.key = key
        self.cat = cat
        self.split = split
        #: a second key, given the whole duration (`tpu.collect`, whose
        #: own time is the residual)
        self.whole_key = whole_key
        self.device_wait = device_wait
        #: run once the span has closed and its time is in the metrics
        self.after = None
        self._span = None

    def __enter__(self) -> "CollectSpan":
        ctx = self.ctx
        if not ctx.query_seq:
            ctx.query_seq = next_query_seq()
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self.name, query=ctx.query_seq)
            self._ann.__enter__()
        else:
            self._ann = None
        open_spans = ctx.open_spans
        outer = self._outer = open_spans[-1] if open_spans else None
        open_spans.append(self)
        self._inner_ms = 0.0
        tracer = ctx.tracer
        # the CPU interval lies inside the wall one: off-CPU time >= 0
        self._t0 = time.perf_counter()
        self._c0 = time.thread_time() if (
            outer is None or self.device_wait or tracer.enabled
            or (ctx.early_spans is not None and tracing(ctx.conf))) else None
        if tracer.enabled:
            self._record_begin(tracer)
        return self

    def __exit__(self, et, ev, tb) -> bool:
        ctx = self.ctx
        cpu = self._cpu_ms = None if self._c0 is None else \
            (time.thread_time() - self._c0) * 1e3
        self._t1 = time.perf_counter()
        ms = (self._t1 - self._t0) * 1e3
        ctx.open_spans.pop()
        m = ctx.metrics
        m[self.key] = m.get(self.key, 0.0) + ms - self._inner_ms
        if self.whole_key is not None:
            m[self.whole_key] = m.get(self.whole_key, 0.0) + ms
        outer = self._outer
        # the outermost span adds its off-CPU time, a device wait inside
        # it takes its own back out
        if (outer is None) != self.device_wait:
            off = ms - cpu
            m["overhead.offcpu_ms"] = m.get("overhead.offcpu_ms", 0.0) + \
                (off if outer is None else -off)
        if outer is not None:
            # a split span is transparent: the spans inside it are its
            # parent's children for the parent's own time
            outer._inner_ms += self._inner_ms if self.split else ms
        if self._span is not None:
            if cpu is not None:
                self._span.attrs["cpu_ms"] = round(cpu, 3)
            ctx.tracer.end(self._span, self._t1)
        elif ctx.early_spans is not None:
            ctx.early_spans.append(self)
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        if self.after is not None:
            self.after()
        return False

    def _outer_sid(self) -> Optional[int]:
        outer = self._outer
        return None if outer is None or outer._span is None \
            else outer._span.sid

    def _record_begin(self, tracer) -> None:
        self._span = tracer.begin(self.name, self.cat, t0=self._t0,
                                  parent=self._outer_sid())


def bind_tracer(ctx, tracer) -> Dict[str, int]:
    """Make `tracer` the context's tracer for this query's scope.  The
    spans of the collect path that opened before it existed (the
    enclosing `tpu.collect`, `tpu.plan`, the first piece of
    `tpu.scope_enter`) are handed to it with their own clock readings;
    returns {name: span id} of those that had already closed."""
    ctx.tracer = tracer
    early, ctx.early_spans = ctx.early_spans, None
    closed: Dict[str, int] = {}
    if not tracer.enabled:
        return closed
    for sp in ctx.open_spans:
        sp._record_begin(tracer)
    for sp in early or ():
        span = tracer.add_span(sp.name, sp.cat, sp._t0, sp._t1,
                               parent=sp._outer_sid())
        if sp._cpu_ms is not None:
            span.attrs["cpu_ms"] = round(sp._cpu_ms, 3)
        closed[sp.name] = span.sid
    return closed
