"""Site-addressable deterministic fault injection — the chaos harness.

Reference: the RAPIDS plugin proves its recovery paths by injecting
faults (`spark.rapids.sql.test.injectRetryOOM` via RmmSpark,
GpuCoreDumpHandler drills, SURVEY §5).  This module generalizes that
idea from "one synthetic OOM knob" to a harness where ANY layer that can
fail in production carries a *named injection site*, and a conf spec
(`spark.rapids.tpu.test.faults`) arms deterministic faults at those
sites:

    site:kind:trigger[;site:kind:trigger...]

    spill_read:corrupt:nth=2          # corrupt the 2nd spill block read
    reserve:oom:every=3               # OOM every 3rd budget reservation
    shuffle_fetch:ioerror:p=0.1,seed=7  # 10% of fetches fail (seeded)
    execute:fatal:nth=5               # wedge the device on batch 5

Sites (the layers that can actually fail — see `SITES`):
  reserve, compile, execute, h2d, d2h, spill_write, spill_read,
  shuffle_write, shuffle_fetch, exchange, serving, result_cache,
  history, memattr, ooc, kernel, worker, deadline.

Kinds:
  oom     -> TpuRetryOOM       (the OOM retry ladder owns recovery)
  ioerror -> InjectedIOError   (OSError: the bounded IO retry ladder,
                                runtime/retry.py retry_io, owns recovery)
  corrupt -> flips a payload byte in the on-disk block so the REAL
             checksum verification path detects it (spill_read only)
  fatal   -> InjectedFatalError (classified FATAL_DEVICE: crash dump +
                                 FatalDeviceError, runtime/failure.py);
             at the worker site: the victim worker process dies with a
             classified dump and its queries redrive
  error   -> InjectedQueryError (a plain query error, class QUERY)
  timeout -> serving: the AdmissionTimeout backpressure signal;
             deadline: a synthetic per-query deadline expiry at a
             cancellation checkpoint
  kill    -> (worker only) SIGKILL the victim worker process mid-query
  hang    -> (worker only) wedge the victim worker (heartbeats stop;
             the health monitor kills it past the miss window)

Triggers fire deterministically: `nth=N` fires exactly once on the Nth
hit of the site; `every=N` on every Nth hit; `p=F[,seed=N]` per-hit with
a counter-seeded splitmix64 (NOT python's salted hash — runs reproduce);
`always` on every hit.  Each firing emits a `fault_injected` obs instant
and is appended to the injector's `log`, which crash dumps embed so a
post-mortem shows exactly what chaos did (the injected-fault record).

The disabled path is a no-op: `get_injector(conf)` returns the shared
`NULL_INJECTOR` when the conf has no fault spec, and `fire()` on it does
nothing — call sites never branch.
"""
from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Dict, List, Optional

from ..config import TEST_FAULTS, TpuConf
from .memory import TpuRetryOOM

#: site name -> which layer it interrupts (the registry the coverage
#: lint `scripts/check_fault_sites.py` asserts chaos tests exercise)
SITES: Dict[str, str] = {
    "reserve": "MemoryBudget.reserve admission (runtime/memory.py)",
    "compile": "whole-plan XLA compile (exec/compiled.py) — fires on "
               "the compiling thread, including background segment "
               "compiles on the compile service "
               "(runtime/compile_service.py), whose faults re-raise on "
               "the consuming query thread",
    "execute": "per-batch physical root stream (runtime/failure.py "
               "install_fault_injection)",
    "h2d": "host->device upload transitions",
    "d2h": "device->host fetch transitions",
    "spill_write": "Spillable host->disk block write (runtime/memory.py)",
    "spill_read": "Spillable disk block read-back (runtime/memory.py)",
    "shuffle_write": "shuffle map-output write (exec/exchange.py)",
    "shuffle_fetch": "shuffle reduce-side fetch (exec/exchange.py)",
    "exchange": "mesh/multihost collective exchange (parallel/)",
    "serving": "ServingRuntime admission (serving/runtime.py) — fires "
               "per submit; kind 'timeout' raises the admission-timeout "
               "backpressure signal (TenantSession.collect retries it "
               "once, the bounded-admission recovery rung)",
    "result_cache": "serving plan+result cache read (serving/cache.py) "
                    "— kind 'corrupt' flips a byte in the cached IPC "
                    "payload so the REAL checksum verification detects "
                    "it, drops the entry and recomputes",
    "history": "performance-history store write (obs/history.py) — "
               "fires once per recorded query on the JSONL append "
               "path. Kind 'ioerror' is absorbed by the store itself: "
               "the entry is SKIPPED "
               "(tpu_history_records_total{outcome=io_error}) and the "
               "query's result is untouched — telemetry must never "
               "fail work. 'fatal' propagates through the query's "
               "crash-capture scope as a classified FATAL_DEVICE dump "
               "naming the site",
    "memattr": "memory-attribution census read (obs/memattr.py via "
               "exec/compiled.py) — fires once per profiled segment "
               "dispatch when the plane is armed "
               "(profile.segments + profile.memory). Kind 'ioerror' "
               "is absorbed at the bracket: that dispatch's HBM "
               "sample is SKIPPED (memattr_census_skipped metric) and "
               "the query result is bit-identical — sampling must "
               "never cost work. 'fatal' propagates through the "
               "query's crash-capture scope as a classified "
               "FATAL_DEVICE dump embedding the PARTIAL HBM timeline "
               "collected up to the fault",
    "ooc": "out-of-core tier boundaries (exec/ooc.py via exec/join.py "
           "hash-join spill partitioning, exec/ooc_agg.py spill-"
           "partitioned aggregation, exec/ooc_sort.py merge passes) — "
           "fires once per partition pass / merge pass with the "
           "operator, bucket and depth in the injected-fault record, "
           "AFTER the matching `ooc_state` instant hit the flight "
           "recorder.  Kind 'oom' rides the normal OOM ladder (the "
           "query replays bit-identically — the OOC context is already "
           "forced on the replay); 'fatal' surfaces as a classified "
           "FATAL_DEVICE crash dump whose flight-recorder tail embeds "
           "the OOC bucket state the pass was in",
    "worker": "serving worker-process dispatch (serving/workers.py) — "
              "fires SUPERVISOR-side, once per query dispatched to a "
              "worker process (redrives fire it again), so nth= "
              "triggers stay deterministic across the pool. Kind "
              "'kill' SIGKILLs the victim worker the moment its "
              "'started' frame confirms the query is mid-flight; "
              "'hang' wedges the victim (heartbeats and request "
              "processing stop — the health monitor detects the "
              "missed-heartbeat window and kills it); 'fatal' arms "
              "the in-worker fatal injector so the query dies with a "
              "classified FATAL_DEVICE crash dump and the worker "
              "self-terminates. All three lose only the victim's "
              "in-flight queries, which REDRIVE on a surviving worker "
              "(serving.redrive.maxAttempts) bit-identically",
    "fleet": "observability federation fold (serving/workers.py "
             "_reader_loop): fires SUPERVISOR-side once per heartbeat "
             "frame that carries telemetry (registry snapshot / flight "
             "tail). Kind 'ioerror' drops that ONE frame whole — "
             "cumulative-set federation converges on the next beat and "
             "the in-flight query stays bit-identical; 'fatal' writes "
             "a classified crash dump naming the site and drops the "
             "frame, with the supervisor (and the pool) surviving — "
             "telemetry must never take serving down",
    "deadline": "cooperative cancellation checkpoints (exec/plan.py "
                "ExecContext.checkpoint): the compiled-plan seam "
                "brackets, the per-batch result stream, out-of-core "
                "partition/merge passes, exchange rounds and spill-all "
                "sweeps. Kind 'timeout' injects a synthetic deadline "
                "expiry at the Nth checkpoint — the query cancels "
                "exactly as if serving.deadlineMs had elapsed there, "
                "and the ticket's whole device reservation is released "
                "(DeviceCensus shows zero residual)",
    "kernel": "Encoded-execution dispatch (ops/encodings.py) — fires "
              "each time an operator elects a code-space/narrow-lane "
              "path, with the encoded site in the injected-fault "
              "record. Kind 'oom' is caught by the dispatch gate "
              "itself: the dispatch sheds to the decoded tier "
              "bit-identically "
              "(tpu_encoded_dispatch_total{outcome=oom_shed}); 'fatal' "
              "surfaces as a classified FATAL_DEVICE crash dump whose "
              "injected-fault record names the site",
}

KINDS = ("oom", "ioerror", "corrupt", "fatal", "error", "timeout",
         "kill", "hang")

#: kinds the corrupt action makes sense for: it needs an on-disk block
#: path (spill_read) or an in-memory payload bytearray (result_cache)
#: in the fire() info to flip bytes in
_CORRUPT_SITES = ("spill_read", "result_cache")

#: the timeout kind models admission backpressure (serving) and
#: deadline expiry (the cancellation checkpoints)
_TIMEOUT_SITES = ("serving", "deadline")

#: process-level faults: only the supervised worker pool can SIGKILL or
#: wedge a process, so kill/hang arm only at the worker site — and the
#: worker site accepts only process-level kinds
_WORKER_KINDS = ("kill", "hang", "fatal")

#: the federation fold can lose a frame (ioerror) or dump-and-survive
#: (fatal); nothing else is meaningful for pure telemetry
_FLEET_KINDS = ("ioerror", "fatal")


class InjectedIOError(OSError):
    """Synthetic transient host-IO failure (classified 'io'; the bounded
    IO retry ladder recovers it)."""


class InjectedQueryError(RuntimeError):
    """Synthetic plain query error (classified 'query')."""


class InjectedWorkerFault(Exception):
    """Control-flow signal for `worker:{kill,hang,fatal}` rules: raised
    by fire('worker') SUPERVISOR-side at dispatch; the WorkerPool
    catches it and acts on the victim process (SIGKILL after the
    started frame / wedge the worker / arm the in-worker fatal
    injector).  Never escapes the pool."""

    def __init__(self, kind: str, msg: str):
        super().__init__(msg)
        self.kind = kind


@dataclasses.dataclass
class FaultRule:
    site: str
    kind: str
    nth: Optional[int] = None        # fire once, on the Nth hit
    every: Optional[int] = None      # fire on every Nth hit
    p: Optional[float] = None        # per-hit probability (seeded)
    seed: int = 0
    always: bool = False
    hits: int = 0
    fired: int = 0

    def should_fire(self) -> bool:
        self.hits += 1
        if self.nth is not None:
            return self.hits == self.nth
        if self.every is not None:
            return self.hits % self.every == 0
        if self.p is not None:
            return _splitmix_uniform(self.seed, self.hits) < self.p
        return self.always


def _splitmix_uniform(seed: int, counter: int) -> float:
    """Deterministic per-(seed, counter) uniform in [0, 1) — python's
    `hash` is process-salted and would make p= rules unreproducible."""
    x = (seed * 0x9E3779B97F4A7C15 + counter * 0xBF58476D1CE4E5B9) \
        & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 30
    x = (x * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 27
    x = (x * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    x ^= x >> 31
    return x / 2.0 ** 64


def parse_spec(spec: str) -> List[FaultRule]:
    """`site:kind:trigger[;...]` -> rules.  Raises ValueError on any
    unknown site/kind or malformed trigger (the conf checker surfaces
    this at set time, not at the injection site)."""
    rules: List[FaultRule] = []
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        pieces = part.split(":")
        if len(pieces) != 3:
            raise ValueError(f"fault rule {part!r}: want site:kind:trigger")
        site, kind, trigger = (p.strip() for p in pieces)
        if site not in SITES:
            raise ValueError(f"unknown fault site {site!r} "
                             f"(known: {sorted(SITES)})")
        if kind not in KINDS:
            raise ValueError(f"unknown fault kind {kind!r} "
                             f"(known: {list(KINDS)})")
        if kind == "corrupt" and site not in _CORRUPT_SITES:
            raise ValueError(f"kind 'corrupt' only applies to sites "
                             f"{list(_CORRUPT_SITES)}, got {site!r}")
        if kind == "timeout" and site not in _TIMEOUT_SITES:
            raise ValueError(f"kind 'timeout' only applies to sites "
                             f"{list(_TIMEOUT_SITES)}, got {site!r}")
        if kind in ("kill", "hang") and site != "worker":
            raise ValueError(f"kind {kind!r} only applies to site "
                             f"'worker', got {site!r}")
        if site == "worker" and kind not in _WORKER_KINDS:
            raise ValueError(f"site 'worker' only takes process-level "
                             f"kinds {list(_WORKER_KINDS)}, got {kind!r}")
        if site == "fleet" and kind not in _FLEET_KINDS:
            raise ValueError(f"site 'fleet' only takes telemetry kinds "
                             f"{list(_FLEET_KINDS)}, got {kind!r}")
        rule = FaultRule(site, kind)
        if trigger == "always":
            rule.always = True
        else:
            for kv in trigger.split(","):
                if "=" not in kv:
                    raise ValueError(f"fault trigger {trigger!r}: "
                                     f"want key=value[,key=value]")
                k, v = (x.strip() for x in kv.split("=", 1))
                try:
                    if k == "nth":
                        rule.nth = int(v)
                    elif k == "every":
                        rule.every = int(v)
                    elif k == "p":
                        rule.p = float(v)
                    elif k == "seed":
                        rule.seed = int(v)
                    else:
                        raise ValueError(f"unknown trigger key {k!r}")
                except ValueError as e:
                    raise ValueError(f"fault trigger {trigger!r}: {e}")
            if rule.nth is None and rule.every is None and rule.p is None:
                raise ValueError(f"fault trigger {trigger!r}: need one of "
                                 f"nth=/every=/p=/always")
            if (rule.nth is not None and rule.nth < 1) or \
                    (rule.every is not None and rule.every < 1):
                raise ValueError(f"fault trigger {trigger!r}: counts are "
                                 f"1-based (must be >= 1)")
            if rule.p is not None and not 0.0 <= rule.p <= 1.0:
                raise ValueError(f"fault trigger {trigger!r}: p must be "
                                 f"in [0, 1]")
        rules.append(rule)
    return rules


def check_spec(spec: str) -> Optional[str]:
    """Conf-checker form of parse_spec: error string or None."""
    try:
        parse_spec(spec)
        return None
    except ValueError as e:
        return str(e)


class FaultInjector:
    """Armed injector for one conf's fault spec.  Thread-safe: shuffle
    and spill worker threads hit sites concurrently; hit counters are
    global per rule so `nth=` means the Nth hit process-wide for this
    conf, whichever thread lands it."""

    enabled = True

    def __init__(self, spec: str):
        self.rules = parse_spec(spec)
        self._by_site: Dict[str, List[FaultRule]] = {}
        for r in self.rules:
            self._by_site.setdefault(r.site, []).append(r)
        self._lock = threading.Lock()
        self.log: List[dict] = []        # the injected-fault record

    def has_site(self, site: str) -> bool:
        return site in self._by_site

    def fire(self, site: str, **info) -> None:
        """Evaluate every rule armed at `site`; the first that triggers
        acts (raise / corrupt).  Each firing is logged and emits a
        `fault_injected` obs instant before the fault surfaces."""
        rules = self._by_site.get(site)
        if not rules:
            return
        with self._lock:
            fired = None
            for r in rules:
                if r.should_fire():
                    r.fired += 1
                    fired = r
                    break
            if fired is None:
                return
            rec = {"site": site, "kind": fired.kind, "hit": fired.hits,
                   "ts": time.time()}
            rec.update({k: str(v) for k, v in info.items()
                        if k != "payload"})   # bulk bytes stay out of logs
            if len(self.log) < 256:
                self.log.append(rec)
        from ..obs.registry import FAULTS_INJECTED
        FAULTS_INJECTED.inc(site=site, kind=fired.kind)
        from ..obs.tracer import get_active
        get_active().instant("fault_injected", "chaos", site=site,
                             kind=fired.kind, hit=fired.hits)
        self._act(fired, site, info)

    @staticmethod
    def _act(rule: FaultRule, site: str, info: dict) -> None:
        kind = rule.kind
        msg = (f"injected {kind} at fault site {site!r} "
               f"(hit #{rule.hits}, spark.rapids.tpu.test.faults)")
        if site == "worker":
            # process-level faults (kill/hang/fatal) act on the VICTIM
            # process, not the firing thread: the supervisor catches
            # this and kills/wedges/arms the dispatched worker
            raise InjectedWorkerFault(kind, msg)
        if kind == "timeout" and site == "deadline":
            from ..exec.plan import InjectedDeadlineExceeded
            raise InjectedDeadlineExceeded(msg)
        if kind == "oom":
            raise TpuRetryOOM(msg)
        if kind == "ioerror":
            raise InjectedIOError(msg)
        if kind == "fatal":
            from .failure import InjectedFatalError
            raise InjectedFatalError(msg)
        if kind == "error":
            raise InjectedQueryError(msg)
        if kind == "timeout":
            from ..serving.runtime import InjectedAdmissionTimeout
            raise InjectedAdmissionTimeout(msg)
        if kind == "corrupt":
            payload = info.get("payload")
            if isinstance(payload, bytearray) and payload:
                # in-memory block (serving result cache): flip a payload
                # byte past the Arrow IPC stream header so the REAL
                # checksum verification path detects the damage
                off = min(64, len(payload) - 1)
                payload[off] ^= 0xFF
                return
            path = info.get("path")
            if path and os.path.exists(path):
                _corrupt_block(path)
            return
        raise AssertionError(f"unhandled fault kind {kind}")


def _corrupt_block(path: str) -> None:
    """Flip one payload byte past the 24-byte block header so the REAL
    checksum verification (native/spillio) detects the damage — the
    chaos suite exercises detection, not a simulation of it."""
    size = os.path.getsize(path)
    off = 24 + 8 if size > 32 else max(size - 1, 0)
    with open(path, "r+b") as f:
        f.seek(off)
        b = f.read(1)
        f.seek(off)
        f.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")


class NullInjector:
    """Disabled path: every call is a no-op (the NULL_TRACER pattern)."""

    enabled = False
    log: tuple = ()

    def has_site(self, site: str) -> bool:
        return False

    def fire(self, site: str, **info) -> None:
        return None


NULL_INJECTOR = NullInjector()


def get_injector(conf: TpuConf):
    """The injector armed for this conf (cached on the conf instance so
    hit counters are stable for the conf's lifetime), or NULL_INJECTOR
    when no fault spec is set."""
    inj = getattr(conf, "_fault_injector", None)
    if inj is None:
        spec = str(conf.get(TEST_FAULTS) or "")
        inj = FaultInjector(spec) if spec.strip() else NULL_INJECTOR
        conf._fault_injector = inj
    return inj


# The ACTIVE injector: sites with no conf in reach (the mesh/multihost
# exchange collectives) report here.  Installed for the duration of a
# query's instrumented scope (plan/overrides.py), mirroring the active
# tracer — and like it, the binding is THREAD-LOCAL with a
# single-active-scope process fallback, so concurrent queries (the
# serving plane) cannot arm each other's chaos rules or disarm a still-
# running query's injector at scope exit.
_TLS_ACTIVE = threading.local()
_ACTIVE_LOCK = threading.Lock()
_ACTIVE_SET: dict = {}           # id(injector) -> injector, in scope
_FALLBACK: object = NULL_INJECTOR


def set_active(injector) -> None:
    global _FALLBACK
    prev = getattr(_TLS_ACTIVE, "injector", None)
    _TLS_ACTIVE.injector = injector
    with _ACTIVE_LOCK:
        if prev is not None and getattr(prev, "enabled", False):
            _ACTIVE_SET.pop(id(prev), None)
        if getattr(injector, "enabled", False):
            _ACTIVE_SET[id(injector)] = injector
        _FALLBACK = (next(iter(_ACTIVE_SET.values()))
                     if len(_ACTIVE_SET) == 1 else NULL_INJECTOR)


def get_active_injector():
    inj = getattr(_TLS_ACTIVE, "injector", None)
    if inj is not None and inj is not NULL_INJECTOR:
        return inj
    return _FALLBACK


def fire_active(site: str, **info) -> None:
    """Fire `site` on the active injector (conf-less call sites)."""
    get_active_injector().fire(site, **info)
