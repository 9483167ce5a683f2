"""Failure detection + device crash capture — the GpuCoreDumpHandler /
executor-self-termination role.

Reference (SURVEY §5): the executor plugin classifies CUDA errors and
self-terminates on fatal ones so Spark replaces the executor
(Plugin.scala:566-575, logGpuDebugInfoAndExit); GpuCoreDumpHandler
(GpuCoreDumpHandler.scala:38) streams GPU core dumps to a distributed FS
and notifies the driver; `CudaFatalException` gets distinct retry
handling (RmmRapidsRetryIterator).

TPU translation:
- `classify(exc)`: RETRYABLE (RESOURCE_EXHAUSTED / budget OOM — the
  retry ladder owns these), FATAL_DEVICE (XLA internal errors, device
  halt, data loss — the chip or its runtime is wedged; the hosting
  process must exit so the cluster manager replaces it), QUERY (plain
  python/user errors — fail the query, keep the executor).
- `crash_capture(conf, ctx)`: context manager that, on FATAL_DEVICE,
  writes a crash-dump JSON (exception, device info, memory budget
  counters, query metrics, backend platform/version) to
  `spark.rapids.tpu.coredump.path` before re-raising wrapped in
  FatalDeviceError — the analogue of streaming the core dump out before
  the executor dies.  PhysicalQuery.collect installs it when the conf
  is set.
- fault injection: `spark.rapids.tpu.test.injectFatalError` (internal)
  raises a synthetic fatal error after N device batches, testing the
  capture path the way injectRetryOOM tests the retry path.
"""
from __future__ import annotations

import itertools
import json
import os
import time
import traceback
from contextlib import contextmanager
from typing import Optional

from ..config import TpuConf, conf as _conf, _positive
from .memory import CorruptBlockError, is_oom_error

COREDUMP_PATH = _conf(
    "spark.rapids.tpu.coredump.path", "",
    "Directory for device crash dumps (GpuCoreDumpHandler role). Empty "
    "disables capture.")

INJECT_FATAL = _conf(
    "spark.rapids.tpu.test.injectFatalError", 0,
    "Test-only: raise a synthetic fatal device error after this many "
    "device batches (0 = off).", internal=True,
    checker=lambda v: None if v >= 0 else "must be >= 0")

RETRYABLE = "retryable"
FATAL_DEVICE = "fatal_device"
QUERY = "query"
IO = "io"                      # transient host IO — the retry.io ladder
CORRUPTION = "corruption"      # checksummed block failed verification:
                               # data loss, fail the query cleanly

_FATAL_MARKERS = (
    "INTERNAL:", "DATA_LOSS", "device halted", "Device halted",
    "FAILED_PRECONDITION: The program continuator has halted",
    "XLA:TPU compile permanent error", "tpu driver",
)


_DUMP_SEQ = itertools.count()


class FatalDeviceError(RuntimeError):
    """The device/runtime is wedged; the hosting process should exit
    (the CudaFatalException analogue)."""

    def __init__(self, msg: str, dump_path: Optional[str] = None):
        super().__init__(msg)
        self.dump_path = dump_path


class InjectedFatalError(Exception):
    """Synthetic fatal error from the fault-injection conf."""


def classify(exc: BaseException) -> str:
    if isinstance(exc, (FatalDeviceError, InjectedFatalError)):
        return FATAL_DEVICE
    if isinstance(exc, CorruptBlockError):
        return CORRUPTION
    if is_oom_error(exc):
        return RETRYABLE
    s = str(exc)
    mod = type(exc).__module__ or ""
    from_device_runtime = ("jax" in mod
                           or "XlaRuntimeError" in type(exc).__name__)
    if from_device_runtime and any(m in s for m in _FATAL_MARKERS):
        return FATAL_DEVICE
    if isinstance(exc, OSError):
        return IO
    return QUERY


def write_crash_dump(conf: TpuConf, exc: BaseException,
                     ctx=None) -> Optional[str]:
    """Serialize diagnostic state next to the dying executor (the
    core-dump stream-out). Returns the dump path."""
    dump_dir = conf.get(COREDUMP_PATH)
    if not dump_dir:
        return None
    os.makedirs(dump_dir, exist_ok=True)
    # the flight recorder FIRST: its tail must show what the runtime
    # was doing up to the fault — the fault's own instant is the last
    # event, and nothing the dump writer does below may append past it
    from ..obs.recorder import FLIGHT_RECORDER
    from ..obs.registry import CRASH_DUMPS, REGISTRY
    flight_tail = FLIGHT_RECORDER.tail()
    info = {
        "ts": time.time(),
        "pid": os.getpid(),
        # the supervised serving pool stamps each worker process's id
        # into its environment: a post-mortem maps dump -> pool slot
        "worker_id": os.environ.get("SPARK_RAPIDS_TPU_WORKER_ID"),
        "exception": repr(exc),
        "traceback": traceback.format_exception(
            type(exc), exc, exc.__traceback__),
        "classification": classify(exc),
        "flight_recorder": flight_tail,
        "metrics_registry": REGISTRY.flat(),
    }
    CRASH_DUMPS.inc()
    try:
        import jax
        d = jax.devices()[0]
        info["device"] = {"kind": d.device_kind,
                          "platform": d.platform,
                          "id": d.id}
        info["jax_version"] = jax.__version__
        stats = d.memory_stats() or {}
        info["memory_stats"] = {k: v for k, v in stats.items()
                                if isinstance(v, (int, float))}
    except Exception as e:                       # noqa: BLE001
        info["device"] = f"unavailable: {e!r}"
    if ctx is not None:
        info["query_metrics"] = dict(getattr(ctx, "metrics", {}))
        budget = getattr(ctx, "_budget", None)
        if budget is not None:
            info["memory_budget"] = dict(getattr(budget, "metrics", {}))
            info["memory_budget"]["naked_live"] = int(
                getattr(budget, "naked_live", 0) or 0)
    # spill/OOM forensics (obs/memattr.py): the HBM-timeline tail —
    # which node-id ranges owned the memory pressure in the window
    # before the fault — rides the dump when the plane was armed
    from ..obs import memattr
    rec = getattr(ctx, "_memattr", None) if ctx is not None else None
    if rec is None:
        rec = memattr.get_active_recorder()
    if rec is not None:
        info["hbm_timeline"] = rec.timeline(tail=64)
        info["hbm_summary"] = rec.summary()
    info["hbm_census"] = memattr.CENSUS.totals()
    # the injected-fault record: when chaos is armed, a post-mortem must
    # show exactly which synthetic faults fired before the crash
    from .faults import get_active_injector, get_injector
    for inj in (get_active_injector(), get_injector(conf)):
        if getattr(inj, "log", None):
            info["injected_faults"] = list(inj.log)
            break
    # the pid keeps CONCURRENT WORKER PROCESSES sharing one dump dir
    # from colliding; the process-monotonic -<seq> suffix keeps two
    # same-second failures in ONE process from overwriting each other
    path = os.path.join(dump_dir,
                        f"tpu-coredump-{os.getpid()}-{int(time.time())}"
                        f"-{next(_DUMP_SEQ)}.json")
    with open(path, "w") as f:
        json.dump(info, f, indent=2, default=str)
    return path


def write_worker_lost_dump(conf: TpuConf, worker_id: str, pid,
                           reason: str, flight=None, census=None,
                           inflight=None) -> Optional[str]:
    """BLACK-BOX forensics for a worker that died by kill/hang — the
    cases where no in-worker dump is possible.  The supervisor writes
    this from the victim's last heartbeat-carried flight-recorder
    snapshot plus the in-flight ticket state it was holding, so a
    post-mortem sees what the worker was doing right up to its last
    beat even though the process never got to say goodbye."""
    dump_dir = conf.get(COREDUMP_PATH)
    if not dump_dir:
        return None
    os.makedirs(dump_dir, exist_ok=True)
    info = {
        "ts": time.time(),
        "type": "worker_lost",
        "supervisor_pid": os.getpid(),
        "worker_id": worker_id,
        "worker_pid": pid,
        "reason": reason,
        # the victim's black box: its last-known flight-recorder tail
        # (heartbeat telemetry) — NOT this process's recorder
        "flight_recorder": list(flight or ()),
        "hbm_census": dict(census or {}),
        # the tickets that were mid-flight on the victim (they redrive)
        "inflight_tickets": list(inflight or ()),
        "metrics_registry": None,
    }
    try:
        from ..obs.registry import FLEET
        fleet = {k: v for k, v in FLEET.flat().items()
                 if f"worker={worker_id}" in k}
        info["metrics_registry"] = fleet or None
    except Exception:                            # noqa: BLE001
        pass
    from .faults import get_active_injector, get_injector
    for inj in (get_active_injector(), get_injector(conf)):
        if getattr(inj, "log", None):
            info["injected_faults"] = list(inj.log)
            break
    path = os.path.join(dump_dir,
                        f"tpu-workerlost-{worker_id}-{int(time.time())}"
                        f"-{next(_DUMP_SEQ)}.json")
    try:
        with open(path, "w") as f:
            json.dump(info, f, indent=2, default=str)
    except OSError:
        return None                  # forensics must never break redrive
    return path


@contextmanager
def crash_capture(conf: TpuConf, ctx=None):
    """On a fatal device error: capture the dump, re-raise as
    FatalDeviceError so the hosting process can self-terminate (the
    Plugin.scala:569-575 contract: Spark replaces the executor)."""
    try:
        yield
    except BaseException as exc:                 # noqa: BLE001
        if classify(exc) == FATAL_DEVICE and \
                not isinstance(exc, FatalDeviceError):
            path = write_crash_dump(conf, exc, ctx)
            raise FatalDeviceError(
                f"fatal device error: {exc!r}"
                + (f" (crash dump: {path})" if path else ""),
                dump_path=path) from exc
        raise


def install_fault_injection(root, conf: TpuConf) -> None:
    """Wrap a physical root's execute stream with the per-batch fault
    sites: the legacy batch-count fatal injector (injectRetryOOM's
    sibling) and the chaos harness's `execute` site (runtime/faults.py),
    which fires once per device batch the root emits."""
    from .faults import get_injector
    chaos = get_injector(conf)
    thr = int(conf.get(INJECT_FATAL))
    if (not thr and not chaos.has_site("execute")) or \
            getattr(root, "_fatal_injected", False):
        return
    inj = FatalInjector(conf)
    orig = root.execute

    def wrapped(ctx):
        for b in orig(ctx):
            inj.tick()
            chaos.fire("execute")
            yield b

    root.execute = wrapped
    root._fatal_injected = True


def faults_armed(conf: TpuConf) -> bool:
    """Whether this conf arms any fault site: the chaos harness's rules
    or the batch-count fatal injector, whose counter lives on the root
    of the plan it was installed on (a plan collected again would go on
    counting where a plan made anew starts from zero)."""
    from .faults import get_injector
    return bool(get_injector(conf).enabled or int(conf.get(INJECT_FATAL)))


class FatalInjector:
    """Counts device batches; raises at the configured threshold."""

    def __init__(self, conf: TpuConf):
        self.threshold = int(conf.get(INJECT_FATAL))
        self.count = 0

    def tick(self):
        if not self.threshold:
            return
        self.count += 1
        if self.count >= self.threshold:
            self.threshold = 0      # fire once
            raise InjectedFatalError(
                "injected fatal device error "
                "(spark.rapids.tpu.test.injectFatalError)")
