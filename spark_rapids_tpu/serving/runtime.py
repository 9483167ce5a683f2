"""ServingRuntime: many queries, many tenants, one engine.

The single-query path (`DataFrame.collect`) runs plan -> compile ->
upload -> execute strictly in sequence and one query at a time; under
interactive traffic the device idles through every host phase.  This
runtime is the concurrency layer the ROADMAP's "millions of users" item
asks for, built ON TOP of the existing substrate rather than beside it:

  * ADMISSION — a bounded queue (`serving.queueDepth`) with a blocking
    timeout (`serving.admitTimeoutMs`): at the bound, `submit()` applies
    backpressure and then raises `AdmissionTimeout` — load sheds with a
    clean, retryable signal at the door instead of a device OOM halfway
    through a query.  Device-phase overlap is additionally gated by an
    HBM working-set estimate against the memory budget
    (`runtime/memory.py` sizing), so concurrent queries queue for HBM
    instead of betting on the OOM retry ladder.
  * CONF SNAPSHOT — every query's `TpuConf` is captured at admission; a
    mid-flight `TpuSession.set_conf` affects only queries admitted
    after it (TpuConf instances are immutable, `set_conf` swaps them).
  * PHASE OVERLAP — each admitted query runs its pipeline (plan ->
    result-cache probe -> compile -> scan upload -> device execute) on
    a worker thread; compilation routes through the background compile
    service (`runtime/compile_service.py`, keyed by canonical plan
    structure so identical-shape tenants' queries compile once) and XLA
    compiles release the GIL — one query compiles while another holds
    the device, which is where the `bench.py --serving` QPS-over-serial
    win comes from.
  * FAIR SHARE — device-execute grants go through a weighted
    virtual-time scheduler: each tenant accumulates measured device
    microseconds divided by its weight, and the runnable tenant with
    the LEAST virtual time runs next, with a hard starvation bound
    (`serving.starvationBound` consecutive pass-overs forces a grant).
    Per-tenant device time feeds `tpu_serving_tenant_device_us_total`
    from the same integer measurement the ticket records, so registry
    totals and per-ticket sums agree exactly.
  * RESULT CACHE — see serving/cache.py.
  * FAULT ISOLATION (`serving.pool.processes` > 0) — queries execute in
    a SUPERVISED POOL of worker processes (serving/workers.py): each
    worker owns its own TpuSession / MemoryBudget / device slice while
    sharing the persistent compile cache and history store; a crash,
    hang or fatal device error in one worker loses only its in-flight
    queries, which REDRIVE on survivors (serving.redrive.maxAttempts)
    bit-identically.  Admission stays here, in the supervisor, and the
    HBM gate reconciles its estimates against the pool's heartbeat-
    reported DeviceCensus totals — a truthful cross-process picture.
  * DEADLINES (`serving.deadlineMs`, or per-submit) — cooperative
    cancellation at the engine's natural brackets (seam / batch / OOC
    pass / exchange round / spill sweep, ExecContext.checkpoint):
    an expired query raises QueryDeadlineExceeded at the next
    checkpoint and its FULL device reservation releases.
  * GRACEFUL DRAIN — `drain()` stops admitting, lets in-flight queries
    finish (or redrive), checkpoints the history store, and reaps every
    worker process: empty queue, no orphans.

Surfaces: `TpuSession.serving()` -> ServingRuntime;
`runtime.tenant("bi", weight=2.0)` -> TenantSession with
`submit()`/`collect()`; `runtime.stats()` for the live picture; the
`tpu_serving_*` metric families for Prometheus.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

import pyarrow as pa

from ..config import (HBM_BUDGET_BYTES, HBM_BUDGET_FRACTION,
                      SERVING_ADMIT_TIMEOUT_MS,
                      SERVING_ADMIT_WORKING_SET_FACTOR,
                      SERVING_DEADLINE_MS, SERVING_DEVICE_SLOTS,
                      SERVING_POOL_PROCS, SERVING_QUEUE_DEPTH,
                      SERVING_RESULT_CACHE_BYTES, SERVING_STARVATION_BOUND,
                      SERVING_WORKERS, TpuConf)
from ..obs.registry import (SERVING_ADMIT_WAIT_MS, SERVING_DEADLINE_CANCELS,
                            SERVING_DEVICE_BUSY_US, SERVING_QUERIES,
                            SERVING_TENANT_DEVICE_US,
                            SERVING_TENANT_PREDICTED_US)
from ..obs.registry import SERVING_QUEUE_DEPTH as QUEUE_DEPTH_GAUGE
from .cache import ResultCache, result_cache_key


class AdmissionTimeout(RuntimeError):
    """The admission queue stayed at queueDepth past admitTimeoutMs —
    the backpressure signal.  Retryable by construction: nothing was
    admitted, nothing ran."""


class InjectedAdmissionTimeout(AdmissionTimeout):
    """Chaos-harness form (`serving:timeout:...`,  runtime/faults.py)."""


class QueryTicket:
    """One admitted query's handle: state, timings, result."""

    _SEQ_LOCK = threading.Lock()
    _SEQ = 0

    def __init__(self, plan, conf: TpuConf, tenant: str):
        with QueryTicket._SEQ_LOCK:
            QueryTicket._SEQ += 1
            self.id = QueryTicket._SEQ
        self.plan = plan                  # logical plan (DataFrame._plan)
        self.conf = conf                  # admission-time snapshot
        self.tenant = tenant
        self.cache = "bypass"             # hit | miss | store | bypass
        self.plan_kind = None             # "device" | "host" once planned
        #: admission-time cost prediction (obs/estimator.py), or None
        #: when the history plane is off: {device_us, working_set_bytes,
        #: compile_ms, confidence, basis, ...}
        self.predicted: Optional[dict] = None
        #: admitted in OUT-OF-CORE mode: the working-set estimate
        #: exceeded the HBM budget, so instead of running solo (and
        #: serializing the queue) the query executes with the OOC tier
        #: forced and a grant sized to the OOC resident window
        self.ooc = False
        #: per-query deadline (serving.deadlineMs or the submit
        #: override); 0 = none.  Cooperative: enforced at the engine's
        #: checkpoint brackets, not by thread preemption.
        self.deadline_ms = 0.0
        self.redrives = 0                 # worker losses survived (MP mode)
        self.worker = None                # worker id that answered (MP mode)
        #: compact profile summary from the answering worker's
        #: completion frame (MP mode): wall breakdown, hbm, serving
        #: context — folded into the stitched event-log record
        self.worker_profile: Optional[dict] = None
        self.device_us = 0                # measured device-execute micros
        self.skips = 0                    # scheduler pass-overs at grant
        self.admit_wait_ms = 0.0
        self.phases: Dict[str, float] = {}     # phase -> wall seconds
        self.error: Optional[BaseException] = None
        self._table: Optional[pa.Table] = None
        self._done = threading.Event()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = 600.0) -> pa.Table:
        """Block for the result; re-raises the query's failure here."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"serving query #{self.id} (tenant {self.tenant!r}) did "
                f"not finish within {timeout}s")
        if self.error is not None:
            raise self.error
        return self._table

    def _complete(self, table: pa.Table) -> None:
        self._table = table
        self._done.set()

    def _fail(self, exc: BaseException) -> None:
        self.error = exc
        self._done.set()


class _TenantState:
    __slots__ = ("name", "weight", "vtime_us", "skips", "queue",
                 "queries", "device_us")

    def __init__(self, name: str, weight: float):
        self.name = name
        self.weight = float(weight)
        self.vtime_us = 0.0          # device_us / weight, accumulated
        self.skips = 0               # consecutive pass-overs while runnable
        self.queue: List[QueryTicket] = []
        self.queries = 0
        self.device_us = 0


class TenantSession:
    """Per-tenant handle: the unit client code holds.

    `collect()` retries ONE AdmissionTimeout (genuine backpressure and
    the chaos `serving:timeout` site both surface there) — a dashboard
    refresh should survive a momentary full queue without caller retry
    loops; sustained overload still raises."""

    def __init__(self, runtime: "ServingRuntime", name: str):
        self._runtime = runtime
        self.name = name

    def submit(self, df,
               deadline_ms: Optional[float] = None) -> QueryTicket:
        return self._runtime.submit(df, tenant=self.name,
                                    deadline_ms=deadline_ms)

    def collect(self, df, timeout: Optional[float] = 600.0,
                deadline_ms: Optional[float] = None) -> pa.Table:
        try:
            ticket = self.submit(df, deadline_ms=deadline_ms)
        except AdmissionTimeout:
            # one bounded re-admission
            ticket = self.submit(df, deadline_ms=deadline_ms)
        return ticket.result(timeout)


class ServingRuntime:
    def __init__(self, session, conf_overrides: Optional[dict] = None):
        self._session = session
        rconf = session.conf
        if conf_overrides:
            rconf = TpuConf({**rconf._raw, **conf_overrides})
        self._rconf = rconf
        self._overrides = dict(conf_overrides or {})
        # merged-conf cache: ONE TpuConf per session-conf instance, so
        # the fault injector / typed-value caches riding the conf keep
        # stable counters across submits (a fresh merge per submit
        # would reset deterministic nth= chaos triggers)
        self._merged = (None, None)
        self._queue_depth = rconf.get(SERVING_QUEUE_DEPTH)
        self._admit_timeout_s = rconf.get(SERVING_ADMIT_TIMEOUT_MS) / 1e3
        self._deadline_ms = float(rconf.get(SERVING_DEADLINE_MS))
        #: serving.pool.processes > 0 = MULTI-PROCESS mode: queries
        #: execute in the supervised worker pool (serving/workers.py);
        #: the pool itself starts lazily, on the first submit
        self._pool_procs = int(rconf.get(SERVING_POOL_PROCS))
        self._worker_pool = None
        self._device_slots = rconf.get(SERVING_DEVICE_SLOTS)
        if self._pool_procs > 0:
            from .workers import require_shareable_device
            require_shareable_device(self._pool_procs)
            # each worker process owns its own device slice + budget:
            # device phases genuinely run in parallel across processes,
            # so the grant width IS the pool width
            self._device_slots = self._pool_procs
        elif self._device_slots == 0:
            # auto: on an accelerator, the GpuSemaphore sizing
            # (concurrentTpuTasks) — the chip pipelines dispatches and
            # one query's host tail overlaps another's compute.  On the
            # CPU backend "device compute" IS host compute: concurrent
            # XLA CPU programs each size their intra-op pool to all
            # cores and thrash (measured 5x throughput collapse), so
            # device phases serialize and only host phases overlap.
            import jax
            if jax.default_backend() == "cpu":
                self._device_slots = 1
            else:
                from ..config import CONCURRENT_TPU_TASKS
                self._device_slots = rconf.get(CONCURRENT_TPU_TASKS)
        self._starvation_bound = rconf.get(SERVING_STARVATION_BOUND)
        self._ws_factor = rconf.get(SERVING_ADMIT_WORKING_SET_FACTOR)
        self.cache = ResultCache(rconf.get(SERVING_RESULT_CACHE_BYTES))
        self._hbm_limit = self._device_budget_bytes(rconf)
        from concurrent.futures import ThreadPoolExecutor
        self._pool = ThreadPoolExecutor(
            max_workers=rconf.get(SERVING_WORKERS),
            thread_name_prefix="tpu-serving")
        self._cond = threading.Condition()
        self._tenants: Dict[str, _TenantState] = {}
        self._inflight = 0               # admitted, not yet finished
        self._device_active = 0
        self._device_bytes = 0           # working-set estimates admitted
        self._closed = False
        self._draining = False           # drain(): admission closed, in-
                                         # flight queries still finishing
        # -- stats (under _cond) -------------------------------------
        self._t0 = time.perf_counter()
        self._busy_us = 0
        self._max_skips = 0
        self._max_depth = 0
        self._completed = 0
        self._admission_timeouts = 0
        self._ooc_admissions = 0         # oversized queries admitted OOC
        self._deadline_cancels = 0       # deadline/injected cancellations
        #: recent (phase, ticket id, t0, t1) intervals — the overlap
        #: proof stats()["overlap_observed"] is computed from
        self._intervals: List[tuple] = []

    # -- construction helpers ---------------------------------------------
    @staticmethod
    def _device_budget_bytes(conf: TpuConf) -> int:
        """The HBM byte budget device-phase admission schedules against
        (0 = undiscoverable = unlimited) — same sizing rule as
        runtime/memory.py MemoryBudget."""
        limit = conf.get(HBM_BUDGET_BYTES)
        if limit == 0:
            from ..runtime.memory import device_hbm_bytes
            hbm = device_hbm_bytes()
            limit = int(hbm * conf.get(HBM_BUDGET_FRACTION)) if hbm else 0
        return limit

    def tenant(self, name: str, weight: float = 1.0) -> TenantSession:
        """The tenant handle (registers the tenant; weight sticks —
        re-calling with a new weight updates it)."""
        with self._cond:
            st = self._tenants.get(name)
            if st is None:
                self._tenants[name] = _TenantState(name, weight)
            else:
                st.weight = float(weight)
        return TenantSession(self, name)

    # -- admission ---------------------------------------------------------
    def submit(self, df, tenant: str = "default",
               conf: Optional[TpuConf] = None,
               deadline_ms: Optional[float] = None) -> QueryTicket:
        """Admit one query (blocking up to admitTimeoutMs when the queue
        is full) and start its pipeline.  `df` is a DataFrame or a
        logical plan; the session conf is SNAPSHOT here, at admission.
        `deadline_ms` overrides serving.deadlineMs for this query."""
        if self._closed:
            raise RuntimeError("ServingRuntime is closed")
        if self._draining:
            raise RuntimeError("ServingRuntime is draining: admission "
                               "is closed, in-flight queries finishing")
        # the snapshot: TpuConf instances are immutable — grabbing the
        # reference pins this query's behavior against later set_conf
        snap = conf or self._session.conf
        if conf is None and self._overrides:
            with self._cond:
                if self._merged[0] is not snap:
                    self._merged = (
                        snap, TpuConf({**snap._raw, **self._overrides}))
                snap = self._merged[1]
        from ..runtime.faults import get_injector
        injector = get_injector(snap)
        injector.fire("serving", tenant=tenant)
        plan = getattr(df, "_plan", df)
        ticket = QueryTicket(plan, snap, tenant)
        ticket.deadline_ms = float(self._deadline_ms
                                   if deadline_ms is None else deadline_ms)
        if self._pool_procs > 0:
            self._ensure_pool()
        t0 = time.perf_counter()
        deadline = t0 + self._admit_timeout_s
        with self._cond:
            while self._inflight >= self._queue_depth:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    self._admission_timeouts += 1
                    SERVING_QUERIES.inc(tenant=tenant,
                                        status="admission_timeout")
                    raise AdmissionTimeout(
                        f"serving queue at depth {self._queue_depth} for "
                        f"{self._admit_timeout_s:.1f}s (tenant "
                        f"{tenant!r}) — shed load or raise "
                        f"spark.rapids.tpu.serving.queueDepth")
                self._cond.wait(remaining)
            if self._closed:
                raise RuntimeError("ServingRuntime is closed")
            self._inflight += 1
            self._max_depth = max(self._max_depth, self._inflight)
            if tenant not in self._tenants:
                self._tenants[tenant] = _TenantState(tenant, 1.0)
        waited_ms = (time.perf_counter() - t0) * 1e3
        ticket.admit_wait_ms = waited_ms
        SERVING_ADMIT_WAIT_MS.observe(waited_ms)
        QUEUE_DEPTH_GAUGE.set(self._inflight)
        self._pool.submit(self._run, ticket, injector)
        return ticket

    def _ensure_pool(self):
        """The supervised worker pool, started on first demand (worker
        processes each build a full TpuSession — seconds, paid once)."""
        with self._cond:
            pool = self._worker_pool
            if pool is not None:
                return pool
        from .workers import WorkerPool
        pool = WorkerPool(self._rconf, dict(self._rconf._raw),
                          self._pool_procs).start()
        with self._cond:
            if self._worker_pool is None:
                self._worker_pool = pool
                return pool
        pool.close()                     # lost the race: keep the first
        return self._worker_pool

    # -- the per-query pipeline (one worker thread) ------------------------
    def _run(self, ticket: QueryTicket, injector) -> None:
        try:
            out = self._pipeline(ticket, injector)
            ticket._complete(out)
            SERVING_QUERIES.inc(
                tenant=ticket.tenant,
                status="cache_hit" if ticket.cache == "hit" else "ok")
        except BaseException as e:                   # noqa: BLE001
            ticket._fail(e)
            from ..exec.plan import (InjectedDeadlineExceeded,
                                     QueryCancelled, QueryDeadlineExceeded)
            if isinstance(e, QueryDeadlineExceeded):
                reason = ("injected"
                          if isinstance(e, InjectedDeadlineExceeded)
                          else "drain" if isinstance(e, QueryCancelled)
                          else "deadline")
                SERVING_DEADLINE_CANCELS.inc(reason=reason)
                with self._cond:
                    self._deadline_cancels += 1
            SERVING_QUERIES.inc(tenant=ticket.tenant, status="error")
        finally:
            with self._cond:
                self._inflight -= 1
                self._completed += 1
                self._cond.notify_all()
            QUEUE_DEPTH_GAUGE.set(self._inflight)

    def _phase(self, name: str, ticket: QueryTicket):
        runtime = self

        @contextmanager
        def scope():
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                ticket.phases[name] = ticket.phases.get(name, 0.0) \
                    + (t1 - t0)
                with runtime._cond:
                    runtime._intervals.append((name, ticket.id, t0, t1))
                    if len(runtime._intervals) > 4096:
                        del runtime._intervals[:2048]
        return scope()

    def _pipeline(self, ticket: QueryTicket, injector) -> pa.Table:
        from ..plan.overrides import apply_overrides
        with self._phase("plan", ticket):
            q = apply_overrides(ticket.plan, ticket.conf)
        ticket.plan_kind = q.kind
        # admission-time cost prediction: the structure-keyed history
        # oracle (obs/estimator.py) answers BEFORE anything runs; the
        # prediction rides the ticket, the per-tenant predicted-us
        # counter, and (below) the query's tracer/event log — the
        # execution record closes the calibration loop
        try:
            from ..obs.estimator import estimate_query
            ticket.predicted = estimate_query(q)
        except Exception:                            # noqa: BLE001
            ticket.predicted = None      # the oracle must never fail a query
        pred = ticket.predicted
        if pred:
            SERVING_TENANT_PREDICTED_US.inc(int(pred["device_us"]),
                                            tenant=ticket.tenant)
        if self._pool_procs > 0:
            # MULTI-PROCESS mode: the query executes in the supervised
            # worker pool.  The result cache is bypassed (the mp tier
            # trades it for fault isolation — the persistent compile
            # cache still dedupes across workers); admission, fair
            # share and the HBM gate stay here in the supervisor.
            return self._pipeline_mp(ticket, injector, q, pred)
        keyed = None
        if self.cache.cap_bytes and q.kind == "device":
            keyed = result_cache_key(q.root, ticket.conf)
        if keyed is not None:
            hit = self.cache.get(keyed[0], injector)
            if hit is not None:
                ticket.cache = "hit"
                return hit
            ticket.cache = "miss"
        with self._phase("compile", ticket):
            self._compile(q, ticket)
        with self._phase("upload", ticket):
            est_bytes = self._upload(q, ticket)
        est_bytes = self._admit_working_set(ticket, est_bytes, pred)
        with self._device_grant(ticket, est_bytes):
            with self._phase("execute", ticket):
                from ..exec.plan import ExecContext, cancel_scope
                ctx = ExecContext(ticket.conf)
                # cooperative deadline: checked at every checkpoint
                # bracket (seam/batch/OOC/exchange/spill); the clock
                # starts HERE, at the device grant — queue wait does
                # not consume the budget
                ctx.arm_deadline(ticket.deadline_ms)
                if ticket.ooc:
                    ctx.ooc_force = True
                ctx.metrics["serving.tenant"] = ticket.tenant
                # the GLOBAL ticket id: the tracer adopts it
                # (plan/overrides.py), so event-log filenames are
                # keyed the same way in-process and across the pool
                ctx.metrics["serving.query_id"] = ticket.id
                if pred:
                    # stamped pre-collect so the instrumented scope
                    # embeds the prediction in the trace + event log
                    # and the history record calibrates against it
                    ctx.metrics["predicted.device_us"] = \
                        int(pred["device_us"])
                    ctx.metrics["predicted.basis"] = pred["basis"]
                    ctx.metrics["predicted.working_set_bytes"] = \
                        int(pred.get("working_set_bytes") or 0)
                    ctx.metrics["predicted.ws_basis"] = \
                        str(pred.get("ws_basis") or "?")
                    ctx.metrics["predicted.confidence"] = \
                        pred.get("confidence")
                t0 = time.perf_counter()
                with cancel_scope(ctx):
                    out = q.collect(ctx)
                ticket.device_us = int(
                    (time.perf_counter() - t0) * 1e6)
        if keyed is not None and ticket.error is None:
            if self.cache.put(keyed[0], out, keyed[1]):
                ticket.cache = "store"
        return out

    def _admit_working_set(self, ticket: QueryTicket, est_bytes: int,
                           pred: Optional[dict]) -> int:
        """Tighten the heuristic working-set estimate against the
        history oracle, then make the OVERSIZED call (shared by the
        thread and multi-process pipelines)."""
        if pred and pred.get("ws_basis") == "measured" and \
                int(pred.get("working_set_bytes") or 0) > 0:
            # MEASURED-basis working set (memattr query peaks / XLA
            # memory_analysis floors folded through the history plane):
            # it REPLACES the admitWorkingSetFactor x source-bytes
            # heuristic — the gate tightens to what the structure
            # actually held, so more queries overlap without betting
            # on the OOM ladder
            est_bytes = int(pred["working_set_bytes"])
        elif pred and pred["basis"] == "exact_history":
            # reserved-basis history: schedule against the LARGER of
            # the heuristic and the recorded peak (no measured data
            # yet — over-reserve rather than over-commit)
            est_bytes = max(est_bytes,
                            int(pred.get("working_set_bytes") or 0))
        # OVERSIZED working set: instead of waiting for a solo slot
        # (the `_runnable` escape hatch — one big query serializing the
        # whole queue), admit in OUT-OF-CORE mode (ROADMAP 4's last
        # clause): the query runs with the OOC tier forced, its actual
        # resident footprint is the OOC window, and the grant is sized
        # to that window so small-tenant queries keep overlapping it
        if self._hbm_limit > 0 and est_bytes > self._hbm_limit:
            from ..config import OOC_ENABLED, OOC_RESIDENT_FRACTION
            if ticket.conf.get(OOC_ENABLED):
                ticket.ooc = True
                est_bytes = max(
                    int(self._hbm_limit *
                        float(ticket.conf.get(OOC_RESIDENT_FRACTION))), 1)
                with self._cond:
                    self._ooc_admissions += 1
                from ..obs.registry import OOC_ELECTIONS
                OOC_ELECTIONS.inc(op="query", mode="admission")
        return est_bytes

    def _pipeline_mp(self, ticket: QueryTicket, injector, q,
                     pred: Optional[dict]) -> pa.Table:
        """The multi-process tail of the pipeline: size the grant from
        the LOGICAL plan (uploads happen inside whichever worker wins
        the dispatch, against that worker's own budget), then dispatch
        through the pool's redrive loop under a supervisor grant."""
        src_bytes = 0
        if q.kind == "device":
            from ..exec.plan import HostScanExec
            stack, seen = [q.root], set()
            while stack:
                n = stack.pop()
                if id(n) in seen:
                    continue
                seen.add(id(n))
                if isinstance(n, HostScanExec) and \
                        n._source_table is not None:
                    src_bytes += int(n._source_table.nbytes)
                stack.extend(getattr(n, "children", ()))
        est_bytes = self._admit_working_set(
            ticket, int(src_bytes * self._ws_factor), pred)
        pool = self._ensure_pool()
        tracer = self._stitch_tracer(ticket)
        status = "ok"
        try:
            t_g0 = time.perf_counter()
            with self._device_grant(ticket, est_bytes):
                if tracer is not None:
                    tracer.add_span("grant", "serving", t_g0,
                                    time.perf_counter(),
                                    skips=ticket.skips,
                                    est_bytes=est_bytes)
                with self._phase("execute", ticket):
                    out, device_us = pool.execute(ticket, injector,
                                                  ticket.deadline_ms,
                                                  tracer=tracer)
                    ticket.device_us = int(device_us)
            return out
        except BaseException:
            status = "error"
            raise
        finally:
            self._finish_stitch(tracer, ticket, status)

    def _stitch_tracer(self, ticket: QueryTicket):
        """The supervisor-side STITCHED trace: one event-log record per
        pool query, keyed by the global ticket id, spanning admission ->
        grant -> worker execution (-> loss -> redrive) -> completion.
        The answering worker writes its own deep per-query log under
        the SAME id; this record is the cross-process head that names
        every worker the query touched."""
        from ..config import EVENT_LOG_DIR, TRACE_ENABLED
        if not (ticket.conf.get(TRACE_ENABLED)
                or ticket.conf.get(EVENT_LOG_DIR)):
            return None
        from ..obs.tracer import QueryTracer
        tracer = QueryTracer(ticket.id)
        tracer.meta["stitched"] = True
        tracer.meta["tenant"] = ticket.tenant
        if ticket.predicted:
            tracer.meta["prediction"] = {
                k: ticket.predicted.get(k)
                for k in ("device_us", "basis")}
        # admission already happened: replay it as a span so the record
        # covers submit -> grant
        now = time.perf_counter()
        tracer.add_span("admission", "serving",
                        now - ticket.admit_wait_ms / 1e3, now,
                        wait_ms=round(ticket.admit_wait_ms, 3))
        return tracer

    def _finish_stitch(self, tracer, ticket: QueryTicket,
                       status: str) -> None:
        if tracer is None:
            return
        from ..config import EVENT_LOG_DIR
        try:
            tracer.meta["status"] = status
            tracer.meta["redrives"] = ticket.redrives
            tracer.meta["worker"] = ticket.worker
            tracer.meta["workers"] = [
                s.attrs.get("worker") for s in tracer.spans
                if s.cat == "execute"]
            if ticket.worker_profile:
                tracer.meta["worker_profile"] = ticket.worker_profile
            # a root query span over the whole stitched window so
            # QueryProfile/profile_report render it like any trace
            ts = [s.t0 for s in tracer.spans] or [time.perf_counter()]
            with tracer.span("query", "query"):
                pass
            root = tracer.spans[-1]
            root.t0 = min(ts)
            tracer.finish({"serving.tenant": ticket.tenant,
                           "serving.query_id": ticket.id,
                           "serving.redrives": ticket.redrives,
                           "device_us": ticket.device_us})
            log_dir = str(ticket.conf.get(EVENT_LOG_DIR) or "")
            if log_dir:
                tracer.write(log_dir)
        except Exception:                            # noqa: BLE001
            pass          # stitching must never fail a served query

    def _compile(self, q, ticket: QueryTicket) -> None:
        """AOT-compile the whole-plan program through the background
        compile service: dedupe-keyed by canonical plan structure, so N
        tenants submitting the same dashboard shape pay ONE compile;
        injected `compile` chaos faults re-raise here, on the consuming
        thread, where the existing recovery ladders live."""
        if q.kind != "device" or not q._whole_plan_enabled():
            return
        from ..exec.compiled import plan_structure_key
        from ..runtime.compile_service import get_service
        skey = plan_structure_key(q.root, ticket.conf)
        key = ("serving-compile", skey if skey is not None else ticket.id)
        task = get_service(ticket.conf).submit(key, q.prewarm)
        task.wait()
        get_service(ticket.conf).take(key)

    def _upload(self, q, ticket: QueryTicket) -> int:
        """Host-IO phase: push every scan's source table through the
        shared upload cache NOW, outside the device grant, so uploads
        overlap other queries' device execution.  Returns the HBM
        working-set estimate admission schedules with."""
        src_bytes = 0
        if q.kind == "device":
            from ..exec.compiled import _shared_scan_upload
            from ..exec.plan import HostScanExec
            stack, seen = [q.root], set()
            while stack:
                n = stack.pop()
                if id(n) in seen:
                    continue
                seen.add(id(n))
                if isinstance(n, HostScanExec) and \
                        n._source_table is not None:
                    src_bytes += int(n._source_table.nbytes)
                    try:
                        _shared_scan_upload(n, ticket.conf)
                    except Exception:                # noqa: BLE001
                        pass      # the execute path re-tries with retry
                stack.extend(getattr(n, "children", ()))
        return int(src_bytes * self._ws_factor)

    # -- fair-share device scheduling --------------------------------------
    def _runnable(self, st: _TenantState) -> bool:
        """A tenant's head ticket can run now: a device slot argument is
        checked by the caller; here only the HBM-fit gate (a query that
        can never fit runs alone — progress over perfection)."""
        if not st.queue:
            return False
        est = st.queue[0]._grant_est
        if self._hbm_limit <= 0:
            return True
        used = self._device_bytes
        if self._worker_pool is not None:
            # the truthful cross-process picture: the pool's heartbeat-
            # reported DeviceCensus live bytes, reconciled against the
            # supervisor's own reservations — gate on whichever says
            # MORE (estimates can undershoot; the census can lag)
            used = max(used, self._worker_pool.live_bytes())
        if used + est <= self._hbm_limit:
            return True
        return self._device_active == 0      # too big: run it solo

    def _try_grant(self, ticket: QueryTicket) -> bool:
        """Under _cond: grant `ticket` the next device slot iff the
        weighted virtual-time scheduler (with the starvation override)
        picks it right now.  Mutates skip counters exactly once per
        actual grant."""
        if self._device_active >= self._device_slots:
            return False
        runnable = [st for st in self._tenants.values()
                    if self._runnable(st)]
        if not runnable:
            return False
        starving = [st for st in runnable
                    if st.skips >= self._starvation_bound]
        if starving:
            pick = max(starving, key=lambda s: (s.skips, -s.vtime_us))
        else:
            pick = min(runnable, key=lambda s: (s.vtime_us, s.name))
        if pick.queue[0] is not ticket:
            return False
        # commit: this ticket runs — exactly one skip bump per grant
        pick.queue.pop(0)
        ticket.skips = pick.skips
        self._max_skips = max(self._max_skips, pick.skips)
        pick.skips = 0
        for st in runnable:
            if st is not pick and st.queue:
                st.skips += 1
        self._device_active += 1
        self._device_bytes += ticket._grant_est
        return True

    @contextmanager
    def _device_grant(self, ticket: QueryTicket, est_bytes: int):
        ticket._grant_est = int(est_bytes)
        with self._cond:
            st = self._tenants[ticket.tenant]
            st.queue.append(ticket)
            # state changed: a waiter whose tenant just became the
            # scheduler's pick must re-evaluate
            self._cond.notify_all()
            while not self._try_grant(ticket):
                if self._closed:
                    st.queue.remove(ticket)
                    raise RuntimeError("ServingRuntime closed while "
                                       "waiting for a device grant")
                self._cond.wait(0.5)
        try:
            yield
        finally:
            with self._cond:
                st.vtime_us += ticket.device_us / st.weight
                st.queries += 1
                st.device_us += ticket.device_us
                self._busy_us += ticket.device_us
                self._device_active -= 1
                self._device_bytes -= ticket._grant_est
                self._cond.notify_all()
            SERVING_TENANT_DEVICE_US.inc(ticket.device_us,
                                         tenant=ticket.tenant)
            SERVING_DEVICE_BUSY_US.inc(ticket.device_us)

    # -- introspection -----------------------------------------------------
    def stats(self) -> dict:
        with self._cond:
            wall_s = time.perf_counter() - self._t0
            tenants = {st.name: {"weight": st.weight,
                                 "queries": st.queries,
                                 "device_us": st.device_us,
                                 "vtime_us": round(st.vtime_us, 1),
                                 "waiting": len(st.queue)}
                       for st in self._tenants.values()}
            intervals = list(self._intervals)
            busy_us = self._busy_us
            pool = self._worker_pool
            out = {"inflight": self._inflight,
                   "completed": self._completed,
                   "max_queue_depth": self._max_depth,
                   "max_skips": self._max_skips,
                   "admission_timeouts": self._admission_timeouts,
                   "ooc_admissions": self._ooc_admissions,
                   "deadline_cancellations": self._deadline_cancels,
                   "draining": self._draining,
                   "device_slots": self._device_slots,
                   "hbm_limit_bytes": self._hbm_limit,
                   "wall_s": round(wall_s, 3),
                   "device_busy_us": busy_us,
                   "device_utilization": round(
                       busy_us / 1e6 / (wall_s * self._device_slots), 4)
                   if wall_s > 0 else 0.0,
                   "tenants": tenants,
                   "result_cache": self.cache.stats()}
        from ..obs.export import bound_metrics_port
        out["metrics_port"] = bound_metrics_port()
        if pool is not None:
            out["pool"] = pool.stats()
            out["census"] = pool.census()
            # the federated fleet view: per-worker-labeled tpu_fleet_*
            # series folded from worker heartbeats (obs/registry.py)
            from ..obs.registry import FLEET
            fleet = FLEET.flat()
            if fleet:
                out["fleet"] = fleet
        out["overlap_observed"] = _overlap_observed(intervals)
        # oracle trustworthiness: per-basis estimate counts + the
        # prediction-error summary (obs/estimator.py / history plane)
        try:
            from ..obs.estimator import prediction_stats
            out["prediction"] = prediction_stats()
        except Exception:                            # noqa: BLE001
            pass
        return out

    # -- lifecycle ---------------------------------------------------------
    def drain(self, timeout: float = 120.0) -> None:
        """GRACEFUL shutdown: stop admitting (new submits raise), let
        every in-flight query finish — in multi-process mode a query on
        a dying worker still REDRIVES during drain — then checkpoint
        the history store (atomic aggregate rewrite) and reap every
        worker process.  On return: empty queue, no orphans, runtime
        closed.  Unlike close(), grant waiters are NOT aborted."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = time.perf_counter() + timeout
        with self._cond:
            while self._inflight > 0:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    raise TimeoutError(
                        f"drain: {self._inflight} queries still in "
                        f"flight after {timeout}s")
                self._cond.wait(min(remaining, 0.5))
        from ..obs.history import get_store
        store = get_store(self._rconf)
        if store is not None:
            store.checkpoint()
        pool = self._worker_pool
        if pool is not None:
            pool.drain()                 # workers checkpoint + exit 0
            self._worker_pool = None
        self.close()

    def close(self, wait: bool = True) -> None:
        """Stop accepting queries; `wait` drains in-flight ones."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        self._pool.shutdown(wait=wait)
        pool = self._worker_pool
        if pool is not None:
            pool.close()
            self._worker_pool = None

    def __enter__(self) -> "ServingRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _overlap_observed(intervals: List[tuple]) -> bool:
    """True when any host-side phase (plan/compile/upload) of one query
    ran concurrently with another query's device execute — the
    structural proof the pipeline actually overlaps phases."""
    execs = [(t0, t1, tid) for name, tid, t0, t1 in intervals
             if name == "execute"]
    for name, tid, t0, t1 in intervals:
        if name == "execute":
            continue
        for e0, e1, etid in execs:
            if etid != tid and t0 < e1 and e0 < t1:
                return True
    return False
