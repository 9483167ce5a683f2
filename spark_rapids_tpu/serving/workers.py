"""Fault-isolated multi-process serving: the supervised worker pool.

One Python process is one fault domain: a fatal XLA error, a native
crash or a SIGKILL takes down every tenant it hosts, and throughput
cannot scale past one GIL.  The reference engine survives this class of
failure *structurally* — Spark retries tasks and replaces dead
executors; Theseus (PAPERS.md) runs a distributed GPU query platform
whose worker processes are replaceable units behind one admission
plane.  Our queries are read-only and deterministic with oracle-checked
results, so REDRIVE-ON-CRASH is safe by construction.

This module is both sides of that boundary:

  * `WorkerPool` — the SUPERVISOR, embedded in the ServingRuntime when
    `serving.pool.processes` > 0: spawns N worker processes, dispatches
    admitted queries to the least-loaded live worker over an
    authenticated local socket (the plugin/worker.py framing), consumes
    heartbeats (pid, in-flight query, DeviceCensus totals, bound
    metrics port), detects death three ways (connection EOF, process
    exit, heartbeat-miss window) and REDRIVES the dead worker's
    in-flight queries on survivors up to `serving.redrive.maxAttempts`.
    With `serving.pool.restart` it spawns a replacement so the pool
    holds its size.
  * `main()` — the WORKER: builds its own TpuSession (own MemoryBudget,
    own device slice, own metrics plane) from the conf the supervisor
    ships, shares the PERSISTENT compile cache and history store with
    its siblings (topology-keyed dirs; JSONL appends and the aggregate
    summary rewrite are multi-process safe), executes one query per
    request under the full single-query substrate (crash_capture, retry
    ladders, OOC tier), and SELF-TERMINATES after a classified
    FATAL_DEVICE dump — the Plugin.scala executor-self-termination
    contract, with the supervisor as the cluster manager that replaces
    it.

Chaos (`worker:{kill,hang,fatal}:trigger`, runtime/faults.py) fires
SUPERVISOR-side at dispatch so nth= triggers stay deterministic across
the pool; `kill` SIGKILLs the victim the moment its `started` frame
confirms the query is mid-flight, `hang` wedges it (the heartbeat-miss
window detects it), `fatal` arms the in-worker fatal injector.  All
three lose only the victim's in-flight queries, which redrive
bit-identically while other tenants' queries complete uninterrupted.

Graceful drain: the runtime stops admitting, in-flight queries finish
or redrive, then every worker checkpoints the history store (atomic
aggregate rewrite) and exits 0 — no orphan processes, nothing lost.
"""
from __future__ import annotations

import os
import pickle
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from ..config import (SERVING_POOL_HEARTBEAT_MISSES,
                      SERVING_POOL_HEARTBEAT_MS, SERVING_POOL_RESTART,
                      SERVING_REDRIVE_MAX, TpuConf)
from ..plugin.worker import recv_frame, send_frame

_ENV_ID = "SPARK_RAPIDS_TPU_WORKER_ID"
_ENV_ADDR = "SPARK_RAPIDS_TPU_WORKER_ADDR"
_ENV_TOKEN = "SPARK_RAPIDS_TPU_WORKER_TOKEN"

#: worker exit codes: the supervisor's restart accounting reads these
EXIT_DRAINED = 0
EXIT_FATAL = 13


class WorkerLost(RuntimeError):
    """A dispatched query's worker process died before answering
    (crash / SIGKILL / hang-kill / fatal self-termination).  Caught by
    the redrive loop, never by client code."""

    def __init__(self, msg: str, reason: str):
        super().__init__(msg)
        self.reason = reason


class ServingWorkerError(RuntimeError):
    """A query exhausted `serving.redrive.maxAttempts` worker losses —
    the terminal form the ticket fails with."""


def _frame(obj: dict) -> bytes:
    return pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)


def _unframe(data: bytes) -> dict:
    return pickle.loads(data)


# ===========================================================================
# Supervisor side
# ===========================================================================

def require_shareable_device(procs: int) -> None:
    """A TPU chip belongs to ONE process: the supervisor — this process,
    whose session, admission gate and callers all use jax — holds every
    local chip, and a worker process that then needs one fails or hangs.
    So on a TPU the pool refuses to start, at once and by name, instead
    of spawning workers that cannot open the device.  On the CPU backend
    every process has its own device and the pool runs as built."""
    import jax
    if jax.default_backend() != "tpu":
        return
    devs = jax.devices()
    raise RuntimeError(
        f"spark.rapids.tpu.serving.pool.processes={procs} cannot run on a "
        f"TPU: this process (pid {os.getpid()}) holds the chip(s) "
        f"({len(devs)} x {devs[0].device_kind}), a chip belongs to one "
        f"process, and worker processes could not open it. Serve "
        f"in-process (serving.pool.processes=0), one process per chip.")


class _Dispatch:
    """One in-flight query on one worker (supervisor bookkeeping)."""

    __slots__ = ("qid", "event", "reply", "lost", "kill_on_start",
                 "started", "ticket_info")

    def __init__(self, qid: int, kill_on_start: bool = False,
                 ticket_info: Optional[dict] = None):
        self.qid = qid
        self.event = threading.Event()
        self.reply: Optional[dict] = None
        self.lost: Optional[WorkerLost] = None
        self.kill_on_start = kill_on_start
        self.started = threading.Event()
        # the supervisor's view of the dispatched ticket (tenant,
        # attempt, deadline, prediction) — embedded into the WorkerLost
        # forensics dump when the worker dies holding this query
        self.ticket_info = dict(ticket_info or {})


class _WorkerHandle:
    """Supervisor-side state for one worker process."""

    def __init__(self, wid: str, proc: subprocess.Popen):
        self.wid = wid
        self.proc = proc
        self.conn: Optional[socket.socket] = None
        self.send_lock = threading.Lock()
        self.pid: Optional[int] = None
        self.metrics_port: Optional[int] = None
        self.ready = threading.Event()       # hello received, conf sent
        self.alive = False                   # ready and not declared dead
        self.last_hb = time.monotonic()
        self.census: Dict[str, int] = {"live_bytes": 0, "peak_bytes": 0}
        self.inflight: Dict[int, _Dispatch] = {}     # qid -> dispatch
        self.draining = False
        # the worker's last heartbeat-carried flight-recorder snapshot
        # (black box): embedded into the WorkerLost dump on kill/hang —
        # the cases where the victim cannot write its own dump
        self.flight: List[dict] = []

    def send(self, obj: dict) -> None:
        with self.send_lock:
            send_frame(self.conn, _frame(obj))


class WorkerPool:
    """Supervises `procs` worker processes behind the admission front
    (serving/runtime.py owns admission, conf snapshots, fair-share
    grants and tickets; the pool owns dispatch, health, redrive, and
    the cross-process census picture)."""

    def __init__(self, rconf: TpuConf, conf_raw: dict, procs: int):
        self._rconf = rconf
        self._conf_raw = dict(conf_raw)
        self.procs = int(procs)
        self._hb_s = float(rconf.get(SERVING_POOL_HEARTBEAT_MS)) / 1e3
        self._hb_misses = int(rconf.get(SERVING_POOL_HEARTBEAT_MISSES))
        self._restart = bool(rconf.get(SERVING_POOL_RESTART))
        self._redrive_max = int(rconf.get(SERVING_REDRIVE_MAX))
        self._cond = threading.Condition()
        self._workers: Dict[str, _WorkerHandle] = {}
        self._wid_seq = 0
        self._srv: Optional[socket.socket] = None
        self._token = b""
        self._closed = False
        self._draining = False
        self._restarts: Dict[str, int] = {}          # reason -> count
        self._redrives = 0

    # -- lifecycle ---------------------------------------------------------
    def start(self, timeout: float = 120.0) -> "WorkerPool":
        import secrets
        self._token = secrets.token_hex(16).encode()
        self._srv = socket.create_server(("127.0.0.1", 0))
        threading.Thread(target=self._accept_loop, daemon=True,
                         name="tpu-pool-accept").start()
        threading.Thread(target=self._monitor_loop, daemon=True,
                         name="tpu-pool-monitor").start()
        for _ in range(self.procs):
            self._spawn()
        deadline = time.monotonic() + timeout
        with self._cond:
            while self._live_count() < self.procs:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    self.close()
                    raise RuntimeError(
                        f"serving worker pool: only {self._live_count()}"
                        f"/{self.procs} workers came up in {timeout}s")
                self._cond.wait(min(remaining, 0.5))
        return self

    def _spawn(self) -> _WorkerHandle:
        with self._cond:
            self._wid_seq += 1
            wid = f"w{self._wid_seq}"
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env[_ENV_ID] = wid
        env[_ENV_ADDR] = "%s:%d" % self._srv.getsockname()
        env[_ENV_TOKEN] = self._token.decode()
        env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.Popen(
            [sys.executable, "-m", "spark_rapids_tpu.serving.workers"],
            env=env, stdin=subprocess.DEVNULL)
        h = _WorkerHandle(wid, proc)
        with self._cond:
            self._workers[wid] = h
        return h

    def _accept_loop(self) -> None:
        import hmac
        while not self._closed:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            try:
                hello = recv_frame(conn)
                if hello is None:
                    conn.close()
                    continue
                msg = _unframe(hello)
                if not hmac.compare_digest(
                        msg.get("token", "").encode(), self._token):
                    conn.close()
                    continue
                wid = msg["worker_id"]
                with self._cond:
                    h = self._workers.get(wid)
                if h is None:
                    conn.close()
                    continue
                h.conn = conn
                h.pid = msg.get("pid")
                h.metrics_port = msg.get("metrics_port")
                h.send({"op": "conf", "conf": self._conf_raw,
                        "hb_ms": self._hb_s * 1e3})
                h.last_hb = time.monotonic()
                with self._cond:
                    h.alive = True
                    h.ready.set()
                    self._cond.notify_all()
                self._set_live_gauge()
                threading.Thread(target=self._reader_loop, args=(h,),
                                 daemon=True,
                                 name=f"tpu-pool-read-{wid}").start()
            except Exception:                        # noqa: BLE001
                try:
                    conn.close()
                except OSError:
                    pass

    def _reader_loop(self, h: _WorkerHandle) -> None:
        from ..obs.registry import SERVING_WORKER_HEARTBEATS
        while True:
            try:
                data = recv_frame(h.conn)
            except OSError:
                data = None
            if data is None:
                self._declare_dead(h, "crash")
                return
            try:
                msg = _unframe(data)
            except Exception:                        # noqa: BLE001
                self._declare_dead(h, "crash")
                return
            op = msg.get("op")
            if op == "hb":
                h.last_hb = time.monotonic()
                h.census = dict(msg.get("census") or {})
                if msg.get("metrics_port") is not None:
                    h.metrics_port = msg["metrics_port"]
                SERVING_WORKER_HEARTBEATS.inc()
                self._fold_telemetry(h, msg)
            elif op == "started":
                if msg.get("flight"):
                    h.flight = list(msg["flight"])
                d = h.inflight.get(msg.get("qid"))
                if d is not None:
                    d.started.set()
                    if d.kill_on_start:
                        # worker:kill — the victim is now PROVABLY
                        # mid-query; lose the whole process
                        try:
                            h.proc.kill()
                        except OSError:
                            pass
            elif op in ("result", "error"):
                qid = msg.get("qid")
                d = h.inflight.pop(qid, None)
                if op == "error" and \
                        msg.get("classification") == "fatal_device":
                    # the worker wrote its classified dump and is
                    # self-terminating: its query REDRIVES (the dump
                    # names the pid; the redrive conf carries no
                    # injected fatal), exactly like a plain crash
                    if d is not None:
                        d.lost = WorkerLost(
                            f"worker {h.wid} hit a fatal device error "
                            f"(dump: {msg.get('dump_path')})", "fatal")
                        d.event.set()
                    self._declare_dead(h, "fatal")
                    return
                if d is not None:
                    d.reply = msg
                    d.event.set()
                with self._cond:
                    self._cond.notify_all()
            elif op == "drained":
                h.draining = True
                with self._cond:
                    self._cond.notify_all()

    def _monitor_loop(self) -> None:
        while not self._closed:
            time.sleep(self._hb_s)
            now = time.monotonic()
            with self._cond:
                handles = list(self._workers.values())
            for h in handles:
                if not h.alive:
                    continue
                if h.proc.poll() is not None:
                    self._declare_dead(h, "crash")
                elif not h.draining and \
                        now - h.last_hb > self._hb_s * self._hb_misses:
                    # hung: heartbeats stopped but the process lives —
                    # SIGKILL it and treat exactly like a crash
                    try:
                        h.proc.kill()
                    except OSError:
                        pass
                    self._declare_dead(h, "hang")

    def _fold_telemetry(self, h: _WorkerHandle, msg: dict) -> None:
        """Metrics federation + black-box fold of one heartbeat frame.
        The `fleet` chaos site fires here, SUPERVISOR-side, once per
        telemetry-carrying frame: ioerror drops THIS frame whole
        (cumulative-set federation converges on the next beat, the
        in-flight query untouched); fatal writes a classified dump
        naming the site and drops the frame — the supervisor (and the
        pool) survive, telemetry never takes serving down."""
        if msg.get("registry") is None and msg.get("flight") is None:
            return
        from ..obs.registry import (FLEET_FRAMES, fold_fleet_snapshot)
        from ..runtime.faults import get_injector
        try:
            get_injector(self._rconf).fire("fleet", worker=h.wid)
        except OSError:
            FLEET_FRAMES.inc(outcome="dropped")
            return
        except Exception as exc:                     # noqa: BLE001
            from ..runtime.failure import (FATAL_DEVICE, classify,
                                           write_crash_dump)
            if classify(exc) == FATAL_DEVICE:
                try:
                    write_crash_dump(self._rconf, exc)
                except Exception:                    # noqa: BLE001
                    pass
                FLEET_FRAMES.inc(outcome="dropped")
                return
            FLEET_FRAMES.inc(outcome="error")
            return
        try:
            if msg.get("registry") is not None:
                fold_fleet_snapshot(h.wid, msg["registry"])
            if msg.get("flight") is not None:
                h.flight = list(msg["flight"])
            FLEET_FRAMES.inc(outcome="folded")
        except Exception:                            # noqa: BLE001
            # a malformed frame must never kill the reader loop (the
            # worker would be declared dead over telemetry)
            FLEET_FRAMES.inc(outcome="error")

    def _declare_dead(self, h: _WorkerHandle, reason: str) -> None:
        from ..obs.registry import SERVING_WORKER_RESTARTS
        with self._cond:
            if not h.alive and h.ready.is_set():
                return                   # already handled
            h.alive = False
            self._workers.pop(h.wid, None)
            pending = list(h.inflight.values())
            h.inflight.clear()
            # A worker exiting while the pool drains/closes is a CLEAN
            # shutdown racing the reaper, not a loss: no restart count,
            # no black-box dump.
            shutdown = self._draining or self._closed
            if not shutdown:
                self._restarts[reason] = self._restarts.get(reason, 0) + 1
            self._cond.notify_all()
        if not shutdown:
            SERVING_WORKER_RESTARTS.inc(reason=reason)
        self._set_live_gauge()
        # fleet federation: the dead worker's GAUGE series (point-in-
        # time state) die with the process; its counters — cumulative
        # work the fleet did — stay.  A restarted replacement publishes
        # under a fresh worker id.
        try:
            from ..obs.registry import drop_fleet_worker
            drop_fleet_worker(h.wid)
        except Exception:                            # noqa: BLE001
            pass
        # BLACK-BOX forensics: on kill/hang the victim could not write
        # its own dump — embed its last heartbeat-carried flight
        # snapshot + the in-flight ticket state supervisor-side
        if not shutdown:
            try:
                from ..runtime.failure import write_worker_lost_dump
                write_worker_lost_dump(
                    self._rconf, h.wid, h.pid, reason,
                    flight=list(h.flight), census=dict(h.census),
                    inflight=[dict(d.ticket_info, qid=d.qid,
                                   started=d.started.is_set())
                              for d in pending])
            except Exception:                        # noqa: BLE001
                pass              # forensics must never break redrive
        try:
            if h.conn is not None:
                h.conn.close()
        except OSError:
            pass
        for d in pending:
            if d.lost is None:
                d.lost = WorkerLost(
                    f"worker {h.wid} (pid {h.pid}) died mid-query "
                    f"({reason})", reason)
            d.event.set()
        if self._restart and not self._draining and not self._closed:
            self._spawn()

    def _set_live_gauge(self) -> None:
        from ..obs.registry import SERVING_WORKERS_LIVE
        SERVING_WORKERS_LIVE.set(self._live_count())

    def _live_count(self) -> int:
        return sum(1 for h in list(self._workers.values()) if h.alive)

    # -- dispatch ----------------------------------------------------------
    def _pick(self, timeout: float = 60.0) -> _WorkerHandle:
        """The least-loaded live worker (blocks for a restart when the
        whole pool is momentarily down)."""
        deadline = time.monotonic() + timeout
        with self._cond:
            while True:
                live = [h for h in self._workers.values()
                        if h.alive and not h.draining]
                if live:
                    return min(live, key=lambda h: (len(h.inflight),
                                                    h.wid))
                if self._closed or self._draining:
                    raise ServingWorkerError("worker pool is closed")
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise ServingWorkerError(
                        f"no live serving worker within {timeout}s")
                self._cond.wait(min(remaining, 0.5))

    def execute(self, ticket, injector, deadline_ms: float = 0.0,
                tracer=None):
        """Run one admitted query on the pool: dispatch, await, REDRIVE
        on worker loss up to serving.redrive.maxAttempts.  Returns
        (pa.Table, device_us).  Chaos `worker` fires here, supervisor-
        side, once per dispatch.  With a (stitched) tracer, each
        attempt is one `execute@<wid>` span and each loss a
        `worker_lost` instant — the redrive chain renders as retry
        spans naming both workers."""
        from ..obs.registry import SERVING_REDRIVES
        from ..runtime.faults import InjectedWorkerFault
        losses = 0
        pred = dict(ticket.predicted or {})
        while True:
            attempt = losses
            fault_kind = None
            try:
                injector.fire("worker", query=ticket.id,
                              tenant=ticket.tenant)
            except InjectedWorkerFault as f:
                fault_kind = f.kind
            h = self._pick()
            d = _Dispatch(ticket.id,
                          kill_on_start=(fault_kind == "kill"),
                          ticket_info={
                              "tenant": ticket.tenant,
                              "attempt": attempt,
                              "deadline_ms": float(deadline_ms or 0.0),
                              "ooc": bool(ticket.ooc),
                              "predicted_us": int(
                                  pred.get("device_us") or 0)})
            extra = {}
            if fault_kind == "fatal":
                # arm the in-worker fatal injector for THIS dispatch
                # only — the redrive conf is clean
                extra["spark.rapids.tpu.test.injectFatalError"] = "1"
            h.inflight[ticket.id] = d
            t0 = time.perf_counter()
            try:
                h.send({"op": "query", "qid": ticket.id,
                        "plan": ticket.plan, "extra": extra,
                        "deadline_ms": float(deadline_ms or 0.0),
                        "ooc": bool(ticket.ooc),
                        "hang": fault_kind == "hang",
                        # the supervisor's GLOBAL ticket context: the
                        # worker tracer adopts the id (key-exact event
                        # logs) and stamps the serving.* metrics
                        "ctx": {"query_id": ticket.id,
                                "tenant": ticket.tenant,
                                "attempt": attempt,
                                "admit_wait_ms": round(
                                    ticket.admit_wait_ms, 3),
                                "predicted": {
                                    "device_us": pred.get("device_us"),
                                    "basis": pred.get("basis")}}})
            except (OSError, pickle.PicklingError) as e:
                h.inflight.pop(ticket.id, None)
                if isinstance(e, pickle.PicklingError):
                    raise
                d.lost = WorkerLost(f"worker {h.wid} unreachable "
                                    f"at dispatch: {e}", "crash")
                d.event.set()
            while not d.event.wait(0.5):
                pass
            t1 = time.perf_counter()
            if d.lost is None:
                msg = d.reply
                if msg["op"] == "result":
                    ticket.worker = h.wid
                    ticket.worker_profile = msg.get("profile")
                    if tracer is not None and \
                            getattr(tracer, "enabled", False):
                        tracer.add_span(f"execute@{h.wid}", "execute",
                                        t0, t1, worker=h.wid,
                                        attempt=attempt,
                                        device_us=int(
                                            msg.get("device_us") or 0))
                    return msg["table"], int(msg.get("device_us") or 0)
                exc = msg.get("exc")
                if exc is None:
                    exc = RuntimeError(
                        f"[worker {h.wid}] {msg.get('error_class')}: "
                        f"{msg.get('message')}")
                raise exc
            # worker loss: redrive on a survivor, bit-identically —
            # queries are read-only and deterministic
            losses += 1
            ticket.redrives = losses
            SERVING_REDRIVES.inc(reason=d.lost.reason)
            if tracer is not None and getattr(tracer, "enabled", False):
                tracer.add_span(f"execute@{h.wid}", "execute", t0, t1,
                                worker=h.wid, attempt=attempt,
                                lost=d.lost.reason)
                tracer.instant("worker_lost", "serving", worker=h.wid,
                               reason=d.lost.reason, attempt=attempt)
            with self._cond:
                self._redrives += 1
            if losses > self._redrive_max:
                raise ServingWorkerError(
                    f"query #{ticket.id} lost its worker {losses} times "
                    f"(> serving.redrive.maxAttempts="
                    f"{self._redrive_max}); last: {d.lost}") \
                    from d.lost

    # -- the cross-process HBM picture ------------------------------------
    def live_bytes(self) -> int:
        with self._cond:
            return sum(int(h.census.get("live_bytes") or 0)
                       for h in self._workers.values() if h.alive)

    def census(self) -> dict:
        with self._cond:
            per = {h.wid: {"pid": h.pid,
                           "live_bytes": int(
                               h.census.get("live_bytes") or 0),
                           "peak_bytes": int(
                               h.census.get("peak_bytes") or 0)}
                   for h in self._workers.values() if h.alive}
        return {"live_bytes": sum(w["live_bytes"] for w in per.values()),
                "peak_bytes": sum(w["peak_bytes"] for w in per.values()),
                "workers": per}

    def stats(self) -> dict:
        with self._cond:
            now = time.monotonic()
            workers = {h.wid: {"pid": h.pid,
                               "inflight": len(h.inflight),
                               "metrics_port": h.metrics_port,
                               "last_heartbeat_ms": round(
                                   (now - h.last_hb) * 1e3, 1)}
                       for h in self._workers.values() if h.alive}
            return {"processes": self.procs,
                    "live": len(workers),
                    "restarts": dict(self._restarts),
                    "redrives": self._redrives,
                    "workers": workers}

    # -- drain / close -----------------------------------------------------
    def drain(self, timeout: float = 60.0) -> None:
        """Graceful: every worker checkpoints the history store (atomic
        aggregate rewrite) and exits 0; the supervisor reaps them all —
        no orphan processes."""
        with self._cond:
            self._draining = True
            handles = [h for h in self._workers.values() if h.alive]
        for h in handles:
            try:
                h.send({"op": "drain"})
            except OSError:
                pass
        deadline = time.monotonic() + timeout
        for h in handles:
            remaining = max(deadline - time.monotonic(), 0.1)
            try:
                h.proc.wait(remaining)
            except subprocess.TimeoutExpired:
                h.proc.kill()
                h.proc.wait(5.0)
            with self._cond:
                h.alive = False
                self._workers.pop(h.wid, None)
        self._set_live_gauge()
        self.close()

    def close(self) -> None:
        self._closed = True
        with self._cond:
            handles = list(self._workers.values())
            self._workers.clear()
            self._cond.notify_all()
        for h in handles:
            try:
                h.proc.kill()
            except OSError:
                pass
            try:
                h.proc.wait(5.0)
            except Exception:                        # noqa: BLE001
                pass
        if self._srv is not None:
            try:
                self._srv.close()
            except OSError:
                pass
        self._set_live_gauge()


# ===========================================================================
# Worker side
# ===========================================================================

def _worker_heartbeat(conn, send_lock: threading.Lock, hb_s: float,
                      stop: threading.Event, state: dict) -> None:
    from ..obs.export import bound_metrics_port
    from ..obs.memattr import CENSUS
    from ..obs.recorder import FLIGHT_RECORDER, tail_bounded
    from ..obs.registry import REGISTRY
    # First beat goes out IMMEDIATELY: a worker killed early in its
    # first query must already have shipped a black-box snapshot.
    while True:
        msg = {"op": "hb", "pid": os.getpid(),
               "census": CENSUS.totals(),
               "metrics_port": bound_metrics_port(),
               "inflight": state.get("qid")}
        tel = state.get("telemetry")
        if tel:
            k_events, max_bytes = tel
            # federation piggyback: the FULL cumulative registry
            # snapshot (set semantics supervisor-side make a dropped
            # frame self-heal) + the rolling black-box flight tail.
            # Liveness first: trim the flight, then drop it, then drop
            # the registry — the bare heartbeat always goes out.
            msg["registry"] = REGISTRY.snapshot()
            msg["flight"] = tail_bounded(FLIGHT_RECORDER, k_events,
                                         max(max_bytes // 4, 1024))
            if len(_frame(msg)) > max_bytes:
                msg["flight"] = []
                if len(_frame(msg)) > max_bytes:
                    msg.pop("registry")
        try:
            with send_lock:
                send_frame(conn, _frame(msg))
        except OSError:
            # supervisor is gone: a worker must never outlive it
            os._exit(EXIT_DRAINED)
        if stop.wait(hb_s):
            return


def _profile_summary(ctx, device_us: int, wid: str) -> dict:
    """Compact, jsonable span-tree/profile summary the completion frame
    carries home: wall breakdown (overhead.*), memory attribution
    (memory.*), serving/prediction context and the worker's event-log
    path — the supervisor folds it into the stitched record's meta."""
    from ..obs.memattr import CENSUS
    out = {"worker": wid, "pid": os.getpid(), "device_us": device_us,
           "hbm": CENSUS.totals()}
    keep = {}
    for k, v in (ctx.metrics or {}).items():
        if not isinstance(v, (int, float, str, bool)) and v is not None:
            continue
        if k.startswith(("overhead.", "memory.", "serving.",
                         "predicted.", "seg.")):
            keep[k] = v
    out["metrics"] = keep
    logf = ctx.metrics.get("event_log_files")
    if isinstance(logf, dict):
        out["event_log"] = logf.get("jsonl")
    return out


def _run_one(session, base_raw: dict, req: dict) -> dict:
    """Execute one dispatched query under the full single-query
    substrate (crash_capture, retry ladders, OOC tier, history feed)."""
    from ..exec.plan import ExecContext, cancel_scope
    from ..plan.overrides import apply_overrides
    extra = req.get("extra") or {}
    conf = TpuConf({**base_raw, **extra}) if extra else session.conf
    q = apply_overrides(req["plan"], conf)
    ctx = ExecContext(conf)
    ctx.arm_deadline(float(req.get("deadline_ms") or 0.0))
    if req.get("ooc"):
        ctx.ooc_force = True
    wid = os.environ.get(_ENV_ID, "w?")
    dctx = req.get("ctx") or {}
    if dctx:
        # the supervisor's ticket context rides ctx.metrics into the
        # instrumented scope: the tracer adopts the GLOBAL query id
        # (plan/overrides.py — the event log becomes query_<gid>.jsonl,
        # key-exact for stitching) and the serving.* keys land in the
        # trace meta + history record
        if dctx.get("query_id") is not None:
            ctx.metrics["serving.query_id"] = int(dctx["query_id"])
        if dctx.get("tenant"):
            ctx.metrics["serving.tenant"] = str(dctx["tenant"])
        ctx.metrics["serving.worker"] = wid
        ctx.metrics["serving.attempt"] = int(dctx.get("attempt") or 0)
        if dctx.get("admit_wait_ms") is not None:
            ctx.metrics["serving.admit_wait_ms"] = dctx["admit_wait_ms"]
        pred = dctx.get("predicted") or {}
        if pred.get("device_us") is not None:
            ctx.metrics["predicted.device_us"] = int(pred["device_us"])
            ctx.metrics["predicted.basis"] = str(pred.get("basis")
                                                 or "?")
    t0 = time.perf_counter()
    with cancel_scope(ctx):
        out = q.collect(ctx)
    device_us = int((time.perf_counter() - t0) * 1e6)
    tenant = dctx.get("tenant")
    if tenant:
        # publish the SAME integer the supervisor's grant publishes for
        # this ticket, so the fleet's per-worker tenant device-us sums
        # to the supervisor's per-tenant counter EXACTLY (the PR 10
        # hammer invariant, now across the socket)
        from ..obs.registry import SERVING_TENANT_DEVICE_US
        SERVING_TENANT_DEVICE_US.inc(device_us, tenant=str(tenant))
    return {"op": "result", "qid": req["qid"], "table": out,
            "device_us": device_us,
            "profile": _profile_summary(ctx, device_us, wid)}


def main() -> int:
    wid = os.environ.get(_ENV_ID, "w?")
    host, port = os.environ[_ENV_ADDR].rsplit(":", 1)
    token = os.environ.get(_ENV_TOKEN, "")
    conn = socket.create_connection((host, int(port)))
    send_lock = threading.Lock()
    from ..obs.export import bound_metrics_port
    send_frame(conn, _frame({"op": "hello", "token": token,
                             "worker_id": wid, "pid": os.getpid(),
                             "metrics_port": bound_metrics_port()}))
    cfg = _unframe(recv_frame(conn))
    base_raw = dict(cfg["conf"])
    # failure.py registers its conf keys (coredump.path, the fatal
    # injector) at import — they must exist before the shipped conf
    # (already validated supervisor-side) is re-validated here
    from ..runtime.failure import classify          # noqa: F401
    # this worker owns its own session: budget, device slice, metrics
    # plane, and the SHARED persistent compile cache + history store
    from ..session import TpuSession
    session = TpuSession(base_raw)
    state: dict = {"qid": None}
    from ..config import (SERVING_POOL_TELEMETRY_ENABLED,
                          SERVING_POOL_TELEMETRY_FLIGHT_EVENTS,
                          SERVING_POOL_TELEMETRY_MAX_FRAME_BYTES)
    if bool(session.conf.get(SERVING_POOL_TELEMETRY_ENABLED)):
        state["telemetry"] = (
            int(session.conf.get(SERVING_POOL_TELEMETRY_FLIGHT_EVENTS)),
            int(session.conf.get(SERVING_POOL_TELEMETRY_MAX_FRAME_BYTES)))
    stop_hb = threading.Event()
    threading.Thread(target=_worker_heartbeat,
                     args=(conn, send_lock, float(cfg["hb_ms"]) / 1e3,
                           stop_hb, state),
                     daemon=True, name="tpu-worker-hb").start()
    while True:
        try:
            data = recv_frame(conn)
        except OSError:
            # supervisor died mid-frame (SIGKILL'd, crashed): same exit
            # as a clean EOF — a worker never outlives its supervisor
            return EXIT_DRAINED
        if data is None:
            return EXIT_DRAINED            # supervisor closed the pool
        req = _unframe(data)
        op = req.get("op")
        if op == "drain":
            # checkpoint the shared history store (atomic aggregate
            # rewrite) so a restart/deploy loses no folded history
            from ..obs.history import get_store
            store = get_store(session.conf)
            if store is not None:
                store.checkpoint()
            session.close()
            with send_lock:
                send_frame(conn, _frame({"op": "drained"}))
            return EXIT_DRAINED
        if op != "query":
            continue
        state["qid"] = req["qid"]
        started = {"op": "started", "qid": req["qid"],
                   "pid": os.getpid()}
        tel = state.get("telemetry")
        if tel:
            # black-box determinism: a dispatch instant + the current
            # flight tail ride the started frame itself, so a worker
            # killed mid-query — even its FIRST, milliseconds in —
            # always leaves a snapshot naming the query it died on
            from ..obs.recorder import FLIGHT_RECORDER, tail_bounded
            FLIGHT_RECORDER.record(
                "instant", "serving_dispatch", "serving",
                attrs={"qid": req["qid"],
                       "tenant": (req.get("ctx") or {}).get("tenant")},
                query=req["qid"])
            k_events, max_bytes = tel
            started["flight"] = tail_bounded(
                FLIGHT_RECORDER, k_events, max(max_bytes // 4, 1024))
        with send_lock:
            send_frame(conn, _frame(started))
        if req.get("hang"):
            # chaos worker:hang — wedge: heartbeats stop, requests
            # stop; the supervisor's miss window kills this process
            stop_hb.set()
            while True:
                time.sleep(60.0)
        try:
            reply = _run_one(session, base_raw, req)
        except BaseException as exc:                 # noqa: BLE001
            cls = classify(exc)
            reply = {"op": "error", "qid": req["qid"],
                     "classification": cls,
                     "error_class": type(exc).__name__,
                     "message": str(exc),
                     "dump_path": getattr(exc, "dump_path", None)}
            try:
                pickle.dumps(exc)
                reply["exc"] = exc
            except Exception:                        # noqa: BLE001
                pass                  # supervisor rebuilds from message
            with send_lock:
                send_frame(conn, _frame(reply))
            if cls == "fatal_device":
                # executor self-termination (Plugin.scala contract):
                # the dump is written, the error frame is out — exit so
                # the supervisor replaces this process
                conn.close()
                os._exit(EXIT_FATAL)
            state["qid"] = None
            continue
        with send_lock:
            send_frame(conn, _frame(reply))
        state["qid"] = None


if __name__ == "__main__":
    raise SystemExit(main())
