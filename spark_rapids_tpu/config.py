"""Typed configuration system for the TPU-native engine.

Plays the role of the reference's RapidsConf (sql-plugin/.../RapidsConf.scala:
3156 LoC, 225 `spark.rapids.*` entries): a registry of typed, documented
config entries with defaults, validated setters, `startup_only`/`internal`
markers and markdown doc generation (`python -m spark_rapids_tpu.config`
mirrors RapidsConf.main writing docs/configs.md).

Keys use the `spark.rapids.tpu.*` prefix.  Per-operator enable keys are
generated automatically from rule names by the plan-rewrite engine
(`spark.rapids.tpu.sql.expression.Abs=false` pattern, reference
RapidsMeta.scala:301-316).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: Dict[str, "ConfEntry"] = {}


def _parse_bool(raw: Any) -> bool:
    if isinstance(raw, bool):
        return raw
    return str(raw).strip().lower() in ("true", "1", "yes")


@dataclasses.dataclass
class ConfEntry:
    key: str
    default: Any
    doc: str
    conf_type: type
    checker: Optional[Callable[[Any], Optional[str]]] = None
    internal: bool = False
    startup_only: bool = False
    commonly_used: bool = False

    def convert(self, raw: Any) -> Any:
        if self.conf_type is bool:
            val = _parse_bool(raw)
        elif self.conf_type is int:
            val = int(str(raw).strip())
        elif self.conf_type is float:
            val = float(str(raw).strip())
        else:
            val = str(raw)
        if self.checker is not None:
            err = self.checker(val)
            if err:
                raise ValueError(f"{self.key}: {err}")
        return val


def _register(entry: ConfEntry) -> ConfEntry:
    if entry.key in _REGISTRY:
        raise ValueError(f"duplicate conf key {entry.key}")
    _REGISTRY[entry.key] = entry
    return entry


def conf(key, default, doc, conf_type=None, checker=None, internal=False,
         startup_only=False, commonly_used=False) -> ConfEntry:
    if conf_type is None:
        conf_type = type(default) if default is not None else str
    return _register(ConfEntry(key, default, doc, conf_type, checker,
                               internal, startup_only, commonly_used))


def _enum_checker(*allowed):
    def check(v):
        if str(v).upper() not in allowed:
            return f"must be one of {allowed}, got {v}"
        return None
    return check


def _non_negative(v):
    return None if v >= 0 else "must be >= 0"


def _positive(v):
    return None if v > 0 else "must be positive"


# --------------------------------------------------------------------------
# Core entries (subset mirroring the commonly-used reference entries; grows).
# --------------------------------------------------------------------------

SQL_ENABLED = conf(
    "spark.rapids.tpu.sql.enabled", True,
    "Master kill-switch: when false, no operator is placed on the TPU.",
    commonly_used=True)

EXPLAIN = conf(
    "spark.rapids.tpu.sql.explain", "NONE",
    "Explain mode: NONE, ALL (log every placement decision), or NOT_ON_TPU "
    "(log only operators that fell back to CPU with their reasons).",
    checker=_enum_checker("NONE", "ALL", "NOT_ON_TPU"), commonly_used=True)

MODE = conf(
    "spark.rapids.tpu.sql.mode", "executeOnTPU",
    "executeOnTPU runs supported operators on the TPU; explainOnly runs the "
    "whole planning pipeline (tagging + reasons) but executes fully on CPU.",
    checker=_enum_checker("EXECUTEONTPU", "EXPLAINONLY"))

BATCH_SIZE_ROWS = conf(
    "spark.rapids.tpu.sql.batchSizeRows", 1 << 22,
    "Target maximum rows per device batch (reference batchSizeBytes analogue; "
    "rows rather than bytes because XLA static shapes are row-bucketed).",
    checker=_positive, commonly_used=True)

BATCH_SIZE_BYTES = conf(
    "spark.rapids.tpu.sql.batchSizeBytes", 1 << 30,
    "Target maximum bytes per device batch when coalescing host batches.",
    checker=_positive)

WHOLE_PLAN_COMPILE = conf(
    "spark.rapids.tpu.sql.compile.wholePlan", "AUTO",
    "Compile an entire device plan into ONE XLA program (tracing is the "
    "whole-plan analogue of the reference's cudf AST compiled "
    "expressions). AUTO enables it on the TPU backend only (CPU test "
    "meshes keep the eager batch engine); ON/OFF force it. Plans that "
    "need host-side decisions (sized join expansion, out-of-core sort) "
    "automatically fall back to the eager engine.",
    checker=_enum_checker("AUTO", "ON", "OFF"), commonly_used=True)

PYTHON_WORKER_CONCURRENCY = conf(
    "spark.rapids.tpu.python.concurrentPythonWorkers", 4,
    "Concurrent pandas-UDF worker processes (the reference's "
    "spark.rapids.python.concurrentPythonWorkers / "
    "PythonWorkerSemaphore role).", checker=_positive)

STRING_TRANSFORM_DEVICE_MIN = conf(
    "spark.rapids.tpu.sql.string.transformDeviceMinUnique", 8192,
    "Dictionary size above which string transforms (upper/lower/trim/"
    "substring) rewrite their byte tensors ON DEVICE (one packed-range "
    "kernel + one fetch) instead of the per-entry host loop. Small "
    "dictionaries stay host-side (kernel+fetch overhead dominates).",
    checker=_positive)

SESSION_TIMEZONE = conf(
    "spark.sql.session.timeZone", "UTC",
    "Session timezone for timestamp field extraction, truncation and "
    "date<->timestamp casts. Non-UTC zones convert on device through a "
    "precomputed IANA transition table (ops/timezone.py — the "
    "GpuTimeZoneDB role).", commonly_used=True)

MESH_ENABLED = conf(
    "spark.rapids.tpu.sql.mesh.enabled", False,
    "Execute device plans SPMD over ALL addressable chips: leaf scans "
    "shard row-wise across a jax.sharding.Mesh and the whole-plan XLA "
    "program is GSPMD-partitioned, with cross-chip exchanges (groupby, "
    "sort, join) riding ICI collectives inserted by XLA. The "
    "multi-chip execution fabric (reference RapidsShuffleManager/UCX "
    "role). Requires >=2 addressable devices; single-device sessions "
    "ignore it.", commonly_used=True)

MESH_DEVICES = conf(
    "spark.rapids.tpu.sql.mesh.devices", 0,
    "Number of mesh devices for SPMD execution (0 = all addressable).",
    checker=lambda v: None if v >= 0 else "must be >= 0")

CONCURRENT_TPU_TASKS = conf(
    "spark.rapids.tpu.sql.concurrentTpuTasks", 2,
    "Number of concurrent tasks allowed to hold device memory at once "
    "(reference GpuSemaphore concurrentGpuTasks default 2).",
    checker=_positive, commonly_used=True)

BUCKET_MIN_ROWS = conf(
    "spark.rapids.tpu.sql.shape.minBucketRows", 1024,
    "Smallest static-shape row bucket. Device batches are padded up to a "
    "bounded geometric set of row capacities so XLA's jit cache stays small.",
    checker=_positive, internal=True)

BUCKET_GROWTH = conf(
    "spark.rapids.tpu.sql.shape.bucketGrowth", 4,
    "Geometric growth factor between static-shape row buckets.",
    checker=lambda v: None if v >= 2 else "must be >= 2", internal=True)

ANSI_ENABLED = conf(
    "spark.rapids.tpu.sql.ansi.enabled", False,
    "ANSI mode: overflow/invalid-cast raise instead of returning null.")

IMPROVED_FLOAT_OPS = conf(
    "spark.rapids.tpu.sql.variableFloatAgg.enabled", True,
    "Allow floating-point aggregations whose result can differ from CPU "
    "Spark in last-ulp due to parallel reduction ordering (reference "
    "docs/compatibility.md float semantics).")

HASH_SUBPARTITION_FALLBACK = conf(
    "spark.rapids.tpu.sql.join.subPartition.enabled", True,
    "Re-hash-partition oversized join build sides into sub-joins "
    "(reference GpuSubPartitionHashJoin).")

ADAPTIVE_ENABLED = conf(
    "spark.rapids.tpu.sql.adaptive.enabled", True,
    "Runtime-statistics re-planning (the AQE analogue, reference "
    "GpuOverrides.scala:496-564): joins measure both materialized inputs "
    "and build on the smaller side; shuffle reads coalesce partitions to "
    "the advisory size from real map-output stats.")

ADAPTIVE_ADVISORY_PARTITION_BYTES = conf(
    "spark.rapids.tpu.sql.adaptive.advisoryPartitionSizeInBytes",
    64 * 1024 * 1024,
    "Target bytes per coalesced shuffle-read group "
    "(spark.sql.adaptive.advisoryPartitionSizeInBytes role).",
    checker=_positive)

ADAPTIVE_SKEW_FACTOR = conf(
    "spark.rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor", 5.0,
    "A shuffle partition whose stored bytes exceed this factor times the "
    "median partition size (and the advisory size) splits into multiple "
    "independent sub-reads (spark.sql.adaptive.skewJoin."
    "skewedPartitionFactor / GpuCustomShuffleReaderExec skew-read role). "
    "Set <= 0 to disable splitting.")

RUNTIME_FILTER_ENABLED = conf(
    "spark.rapids.tpu.sql.join.runtimeFilter.enabled", True,
    "Bloom-filter the probe side of large adaptive joins with the "
    "materialized build side's keys before probing (the reference's "
    "BloomFilter JNI / bloom_filter_might_contain role).")

RUNTIME_FILTER_RATIO = conf(
    "spark.rapids.tpu.sql.join.runtimeFilter.sizeRatio", 4.0,
    "Apply the runtime filter only when probe bytes exceed build bytes "
    "by this factor (below it the filter pass costs more than it saves).",
    checker=_positive, internal=True)

AGG_FALLBACK_PARTITIONS = conf(
    "spark.rapids.tpu.sql.agg.fallbackPartitions", 8,
    "Bucket count for the high-cardinality aggregation fallback: when "
    "merged partial results exceed one target batch, partials are "
    "re-hash-partitioned into this many independently-merged buckets "
    "(reference GpuAggregateExec repartition-based fallback).",
    checker=_positive, internal=True)

CBO_ENABLED = conf(
    "spark.rapids.tpu.sql.optimizer.enabled", False,
    "Cost-based placement pass: un-tag isolated cheap device operators "
    "whose two host<->device transitions outweigh the device win "
    "(reference CostBasedOptimizer, also off by default).")

RETRY_ENABLED = conf(
    "spark.rapids.tpu.sql.retry.enabled", True,
    "Retry device work with halved batches on HBM RESOURCE_EXHAUSTED "
    "(reference RmmRapidsRetryIterator withSplitAndRetry analogue).")

RETRY_MAX_SPLITS = conf(
    "spark.rapids.tpu.sql.retry.maxSplits", 8,
    "Maximum times a batch may be halved before the OOM is rethrown.",
    checker=_positive)

RETRY_MAX_ATTEMPTS = conf(
    "spark.rapids.tpu.sql.retry.maxAttempts", 2,
    "Attempt-ladder depth of the OOM retry framework: how many times one "
    "unit of device work runs (spilling everything between attempts) "
    "before the ladder escalates — with_split_retry halves the batch, "
    "with_retry rethrows. The reference replays exactly once; raising "
    "this trades replay work for survival under sustained pressure.",
    checker=lambda v: None if v >= 1 else "must be >= 1")

RETRY_IO_ATTEMPTS = conf(
    "spark.rapids.tpu.retry.io.maxAttempts", 3,
    "Bounded retry for transient host-IO failures (spill block "
    "read/write, shuffle fetch, host<->device transfers): total attempts "
    "per IO unit before the OSError propagates (classified as class "
    "'io' by runtime.failure.classify). 1 disables retry.",
    checker=lambda v: None if v >= 1 else "must be >= 1")

RETRY_IO_BACKOFF_MS = conf(
    "spark.rapids.tpu.retry.io.backoffMs", 10,
    "Initial backoff before the first IO retry, in milliseconds; each "
    "further retry multiplies it by retry.io.backoffMultiplier.",
    checker=_non_negative)

RETRY_IO_BACKOFF_MULT = conf(
    "spark.rapids.tpu.retry.io.backoffMultiplier", 2.0,
    "Multiplier applied to the IO retry backoff after every attempt.",
    checker=_positive)

RETRY_IO_JITTER = conf(
    "spark.rapids.tpu.retry.io.jitterFraction", 0.25,
    "Deterministic seeded jitter applied to every IO-retry backoff "
    "sleep: each sleep is scaled by a factor in [1-f, 1+f] drawn from a "
    "splitmix64 stream seeded by (pid, site) — N worker processes "
    "replaying the same transient host-IO fault desynchronize instead "
    "of thundering-herding the spill disk, while any single process's "
    "backoff sequence stays exactly reproducible. 0 disables jitter.",
    checker=lambda v: None if 0.0 <= v <= 1.0 else "must be in [0, 1]")

TEST_INJECT_RETRY_OOM = conf(
    "spark.rapids.tpu.sql.test.injectRetryOOM", 0,
    "Test-only: force a synthetic device OOM on the Nth retryable block "
    "(reference spark.rapids.sql.test.injectRetryOOM).", internal=True)

TEST_FAULTS = conf(
    "spark.rapids.tpu.test.faults", "",
    "Site-addressable deterministic fault injection (chaos harness, "
    "runtime/faults.py): a ';'-separated list of `site:kind:trigger` "
    "rules, e.g. `spill_read:corrupt:nth=2`, `reserve:oom:every=3`, "
    "`shuffle_fetch:ioerror:p=0.1,seed=7`. Sites name the layer that "
    "fails (reserve, compile, execute, h2d, d2h, spill_write, "
    "spill_read, shuffle_write, shuffle_fetch, exchange); kinds pick "
    "the failure (oom, ioerror, corrupt, fatal, error); triggers are "
    "nth=N (once, on the Nth hit), every=N, p=F[,seed=N], or always. "
    "Every injection and recovery emits an obs instant. Empty disables "
    "injection (the default path is a no-op).",
    checker=lambda v: _check_fault_spec(v))


def _check_fault_spec(v):
    # deferred: the grammar lives with the injector (runtime/faults.py);
    # the checker only runs at conf.get() time, after imports settle
    from .runtime.faults import check_spec
    return check_spec(v)

SHUFFLE_MODE = conf(
    "spark.rapids.tpu.shuffle.mode", "MULTITHREADED",
    "MULTITHREADED: host-side threaded Arrow-IPC shuffle (reference mode 1). "
    "ICI: collective all-to-all exchange over the device mesh for co-located "
    "partitions (reference UCX-mode analogue). CACHE_ONLY: in-process, tests.",
    checker=_enum_checker("MULTITHREADED", "ICI", "CACHE_ONLY"))

SHUFFLE_WRITER_THREADS = conf(
    "spark.rapids.tpu.shuffle.multiThreaded.writer.threads", 8,
    "Thread pool size for the multithreaded shuffle writer.", checker=_positive)

SHUFFLE_READER_THREADS = conf(
    "spark.rapids.tpu.shuffle.multiThreaded.reader.threads", 8,
    "Thread pool size for the multithreaded shuffle reader.", checker=_positive)

SHUFFLE_COMPRESSION = conf(
    "spark.rapids.tpu.shuffle.compression.codec", "zstd",
    "Codec for shuffle Arrow IPC buffers: zstd, lz4, or none — applied "
    "inside the IPC layer (the nvcomp codec role, "
    "TableCompressionCodec.scala:42), so readers are codec-agnostic.",
    checker=_enum_checker("ZSTD", "LZ4", "NONE"))

EXCHANGE_COMPRESS = conf(
    "spark.rapids.tpu.exchange.compress.enabled", True,
    "Compress lanes on-device BEFORE the mesh all_to_all collective "
    "(the nvcomp-before-UCX analog): validity/flag lanes pack to 1 bit "
    "per row, integer lanes narrow to frame-of-reference uint8/16/32 "
    "words when their global live range allows (the range rides the "
    "exchange's own count fetch), and all narrow lanes fuse into one "
    "wide byte-word collective per round.  The "
    "tpu_exchange_wire_bytes_{pre,post}_compress metric families "
    "report the achieved ratio.", commonly_used=True)

EXCHANGE_QUOTA_AUTO = conf(
    "spark.rapids.tpu.exchange.quota.auto", True,
    "Derive each ragged-exchange round's slab quota from the exchanged "
    "per-destination count matrix (pow2-quantized): a uniform exchange "
    "finishes in one small round, and a hot destination widens the "
    "quota (bounded by the receive-buffer commitment) instead of "
    "forcing max_count/quota rounds on every chip.  false restores the "
    "fixed 2*cap/P fudge quota.")

EXCHANGE_QUOTA_ROWS = conf(
    "spark.rapids.tpu.exchange.quota.rows", 0,
    "Fixed per-round slab quota (rows per destination) for the ragged "
    "exchange; 0 sizes it from capacity (2*cap/P, pow2-rounded). "
    "Explicit values are pow2-rounded so compiled round variants stay "
    "bounded.", checker=_non_negative)

EXCHANGE_DONATE = conf(
    "spark.rapids.tpu.exchange.donate", "AUTO",
    "Donate the ragged exchange's receive buffers through each round "
    "program (double-buffering: rounds update the buffers in place "
    "instead of allocating and round-tripping fresh copies).  AUTO "
    "enables it on backends with buffer donation (TPU); ON/OFF force.",
    checker=_enum_checker("AUTO", "ON", "OFF"))

EXCHANGE_SPLIT_RETRY = conf(
    "spark.rapids.tpu.exchange.skew.splitRetry", True,
    "Skew mitigation for the distributed groupby: when the planned "
    "exchange would GROW a receive buffer (one hot hash partition), "
    "salt rows across destination pairs, merge, and re-exchange the "
    "(small) merged groups to their true owners — receive memory stays "
    "bounded by actual groups instead of the hot key's row count. "
    "Applies only when every merge kind is order-insensitive "
    "(sum/min/max/any/every; first/last keep the direct path).")

HOST_SPILL_LIMIT_BYTES = conf(
    "spark.rapids.tpu.memory.host.spillStorageSize", 8 << 30,
    "Host spill store byte limit before batches overflow to disk "
    "(reference RapidsHostMemoryStore limit).", checker=_positive)

HBM_BUDGET_BYTES = conf(
    "spark.rapids.tpu.memory.tpu.budgetBytes", 0,
    "Absolute HBM byte budget for operator-held batches; 0 derives it from "
    "allocFraction x discovered device memory (unlimited when memory stats "
    "are unavailable).  Exceeding the budget spills LRU batches to host.",
    checker=lambda v: None if v >= 0 else "must be >= 0")

HBM_BUDGET_FRACTION = conf(
    "spark.rapids.tpu.memory.tpu.allocFraction", 0.85,
    "Fraction of per-chip HBM the engine budgets for batches; exceeding the "
    "budget triggers spill-to-host before new device work is admitted.",
    checker=lambda v: None if 0 < v <= 1 else "must be in (0, 1]")

PARQUET_READER_TYPE = conf(
    "spark.rapids.tpu.sql.format.parquet.reader.type", "AUTO",
    "AUTO, PERFILE, COALESCING, or MULTITHREADED (reference 3 strategies).",
    checker=_enum_checker("AUTO", "PERFILE", "COALESCING", "MULTITHREADED"))

PARQUET_MT_THREADS = conf(
    "spark.rapids.tpu.sql.format.parquet.multiThreadedRead.numThreads", 8,
    "Thread pool for the multithreaded parquet reader.", checker=_positive)

ENABLED_FORMATS = {
    fmt: conf(
        f"spark.rapids.tpu.sql.format.{fmt}.enabled", True,
        f"Enable accelerated {fmt} scan.")
    for fmt in ("parquet", "csv", "json", "orc", "avro", "iceberg",
                "hivetext")
}

SPARK_VERSION = conf(
    "spark.rapids.tpu.spark.version", "3.5.0",
    "Spark line whose semantics the engine emulates; selects the shim "
    "(shims.py, the ShimLoader/SparkShimServiceProvider role).")

CPU_ORACLE_VALIDATE = conf(
    "spark.rapids.tpu.sql.test.validateWithCpu", False,
    "Test-only: run every device operator's CPU fallback too and compare.",
    internal=True)

METRICS_LEVEL = conf(
    "spark.rapids.tpu.sql.metrics.level", "MODERATE",
    "ESSENTIAL, MODERATE, or DEBUG metric collection per operator.",
    checker=_enum_checker("ESSENTIAL", "MODERATE", "DEBUG"))

PROFILE_PATH = conf(
    "spark.rapids.tpu.profile.path", "",
    "When set, wrap query execution in a jax-profiler trace written to "
    "this directory (the NVTX/CUPTI Profiler analogue; open in "
    "XProf/perfetto).")

PROFILE_SEGMENTS = conf(
    "spark.rapids.tpu.profile.segments", False,
    "Per-segment DEVICE-TIME attribution (exec/compiled.py): every "
    "compiled program execution blocks until its outputs are ready and "
    "records measured device wall, output rows and bytes against the "
    "segment's stable plan-node-id range — tracer `segment` spans, the "
    "tpu_segment_* registry families, and the explain_analyze() plan "
    "annotations all read it.  Whole-plan programs additionally "
    "RE-SPLIT at the seam boundaries the split compiler already knows "
    "(ignoring compile.seamSplitMinRows) so join subtrees and "
    "aggregates time separately.  Off by default: the disabled path is "
    "one conf check per program dispatch (no sync, no re-split).",
    commonly_used=True)

PROFILE_COST_ANALYSIS = conf(
    "spark.rapids.tpu.profile.costAnalysis", True,
    "Capture XLA cost_analysis()/memory_analysis() (FLOPs, bytes "
    "accessed, peak temp allocation) per compiled segment at COMPILE "
    "time and surface it next to measured device time "
    "(explain_analyze(), segment.* metrics) so predicted-vs-actual "
    "skew flags mis-fused segments.  Compile-time only: zero cost on "
    "the execute path.")

PROFILE_MEMORY = conf(
    "spark.rapids.tpu.profile.memory", True,
    "Device-MEMORY attribution (obs/memattr.py), active when "
    "profile.segments is on: every compiled segment dispatch is "
    "bracketed by a MemoryBudget census (resident, naked and "
    "spillable-resident bytes, peak delta across the window) and its "
    "XLA memory_analysis() bytes, building the per-query HBM timeline "
    "(reserve/release/spill/OOM watermarks with plan-node attribution) "
    "the EXPLAIN ANALYZE `hbm=` column, the segment.*.hbm_* metrics, "
    "tpu_segment_hbm_peak_bytes and the crash-dump forensics read "
    "from.  With profile.segments off this knob is never consulted — "
    "the execute path stays one conf check per dispatch.")

PROFILE_MEMORY_TIMELINE_EVENTS = conf(
    "spark.rapids.tpu.profile.memoryTimelineEvents", 512,
    "Bound on the per-query HBM-timeline event list (obs/memattr.py): "
    "past it further watermark samples are dropped and counted, so a "
    "reserve storm cannot grow query memory.", checker=_positive)

TRACE_ENABLED = conf(
    "spark.rapids.tpu.trace.enabled", False,
    "Collect query-lifecycle spans in memory (plan/compile/execute/"
    "transition/shuffle ranges, runtime incident events, data-movement "
    "counters) for TpuSession.last_query_profile() / DataFrame.metrics() "
    "without writing files. Off by default; the disabled path is a "
    "no-op tracer (obs/tracer.py).", commonly_used=True)

EVENT_LOG_DIR = conf(
    "spark.rapids.tpu.eventLog.dir", "",
    "When set, every query writes a structured JSONL event log "
    "(query_<id>.jsonl — the Spark history-server event-log analogue) "
    "and a Chrome trace-event JSON (query_<id>.trace.json, openable in "
    "perfetto — the NVTX/nsys analogue) into this directory. Implies "
    "span tracing for the query. Render reports with "
    "scripts/profile_report.py.", commonly_used=True)

METRICS_ENABLED = conf(
    "spark.rapids.tpu.metrics.enabled", True,
    "The always-on metrics plane (obs/registry.py + obs/recorder.py): "
    "process-wide counters/gauges/log2-histograms every runtime "
    "subsystem publishes into, plus the flight-recorder ring embedded "
    "in crash dumps. False turns every publish call into one attribute "
    "check (the A/B overhead knob bench.py reports against).",
    commonly_used=True)

METRICS_PORT = conf(
    "spark.rapids.tpu.metrics.port", -1,
    "TCP port for the on-demand Prometheus text-format endpoint "
    "(stdlib http.server thread, obs/export.py): GET /metrics for the "
    "exposition text, /metrics.json for the structured snapshot, "
    "/flight for the flight-recorder tail. 0 binds an EPHEMERAL port — "
    "N serving worker processes on one host cannot race a fixed port — "
    "and the bound port is reported by obs.export.bound_metrics_port(), "
    "ServingRuntime.stats() and every heartbeat line. -1 (default) "
    "disables the server.",
    checker=lambda v: None if v >= -1 else "must be >= -1")

METRICS_REPORT_INTERVAL_S = conf(
    "spark.rapids.tpu.metrics.reportIntervalS", 10.0,
    "Seconds between JSONL heartbeat snapshots of the metrics registry "
    "(obs/export.py Heartbeat) appended to metrics.heartbeatPath — the "
    "always-on metrics-sink cadence.", checker=_positive)

METRICS_HEARTBEAT_PATH = conf(
    "spark.rapids.tpu.metrics.heartbeatPath", "",
    "File the metrics heartbeat appends one JSON line to every "
    "reportIntervalS seconds ({ts, registry, flight_len}). Empty "
    "disables the heartbeat thread.")

METRICS_FLIGHT_EVENTS = conf(
    "spark.rapids.tpu.metrics.flightRecorderEvents", 1024,
    "Capacity of the always-on flight-recorder ring buffer (last N "
    "spans/instants across all queries, embedded in crash dumps).",
    checker=_positive)

RESULT_HEAD_ROWS = conf(
    "spark.rapids.tpu.sql.fetch.headRows", 4096,
    "Result-fetch head size: one speculative round trip ships the row "
    "count plus this many rows; only a larger result pays a second "
    "exactly-sized trip. Size for your link: ~RTT*bandwidth worth of "
    "rows.",
    checker=_positive)

RESULT_BOUND_FETCH_FACTOR = conf(
    "spark.rapids.tpu.sql.fetch.boundFactor", 4,
    "A static row bound up to boundFactor*headRows fetches exactly-sized "
    "in one trip; looser bounds fall back to the speculative head so a "
    "4M-row dense-domain bound cannot defeat the protocol.",
    checker=_positive)

SEAM_SPLIT_MIN_ROWS = conf(
    "spark.rapids.tpu.sql.compile.seamSplitMinRows", 2 << 20,
    "Minimum leaf-scan bucket size before a whole-plan program splits at "
    "row-collapse seams (join subtrees under aggregates). Each seam "
    "costs one host count sync (a full link RTT) plus an extra program "
    "dispatch; below this scale the trimmed padding is worth less than "
    "the round trips.", checker=_positive)

DENSE_AGG_DOMAIN_MAX = conf(
    "spark.rapids.tpu.sql.agg.denseDomainMax", 4096,
    "Largest combined key-domain product the no-sort dense group-by "
    "(direct bucket addressing over dictionary/boolean domains) will "
    "use; beyond it the sort-segment group-by runs instead.",
    checker=_positive)

AGG_INPUT_NARROWING = conf(
    "spark.rapids.tpu.sql.agg.inputNarrowing", True,
    "Gather int64 aggregate-input lanes as int32 when exact plan range "
    "statistics prove the values fit (row gathers are latency-bound per "
    "pass; half-width lanes halve the dominant group-by cost). Sums "
    "re-widen exactly.")

JOIN_LAZY_SELECTION = conf(
    "spark.rapids.tpu.sql.join.lazySelection", True,
    "Let a join whose parent consumes liveness as a mask (aggregation "
    "live lanes, a parent join's probe validity) emit a selection "
    "vector instead of compacting its output — skips a full "
    "argsort+gather pass per join output.")

APPROX_PERCENTILE_SKETCH_K = conf(
    "spark.rapids.tpu.sql.agg.approxPercentile.sketchSize", 129,
    "Order statistics kept per group by the mergeable approx_percentile "
    "summary (the t-digest delta analogue): rank error <= 1/(2(K-1)) "
    "per merge level.", checker=_positive)

REGEX_MAX_DFA_STATES = conf(
    "spark.rapids.tpu.sql.regexp.maxStates", 96,
    "DFA state budget for device regular expressions; patterns whose "
    "determinized automaton exceeds it fall back to CPU (the reference "
    "gates by RegexComplexityEstimator memory instead).",
    checker=_positive)

OOC_SORT_WINDOW_ROWS = conf(
    "spark.rapids.tpu.sql.sort.outOfCore.windowRows", 0,
    "Row budget per resident window of the out-of-core sorter; 0 sizes "
    "from the HBM budget (the GpuOutOfCoreSortIterator splitUntilSmaller "
    "role).", checker=_non_negative)

OOC_ENABLED = conf(
    "spark.rapids.tpu.sql.ooc.enabled", True,
    "Out-of-core execution tier: budget-driven graceful degradation for "
    "hash join and aggregation.  When an operator's measured working set "
    "exceeds the resident window (ooc.residentFraction x the HBM "
    "budget), both sides stream through budget-registered spillable "
    "partitions instead of materializing on device; the "
    "TpuSplitAndRetryOOM ladder also escalates into this tier before "
    "the query-level replay rung (docs/ROBUSTNESS.md).")

OOC_RESIDENT_FRACTION = conf(
    "spark.rapids.tpu.sql.ooc.residentFraction", 0.5,
    "Fraction of the HBM budget one out-of-core operator may hold "
    "resident at a time (the Theseus-style byte-budgeted window the "
    "spill-partition count is derived from).",
    checker=lambda v: None if 0.0 < v <= 1.0 else "must be in (0, 1]")

OOC_MAX_PARTITIONS = conf(
    "spark.rapids.tpu.sql.ooc.maxPartitions", 64,
    "Upper bound on spill partitions one out-of-core join/aggregation "
    "pass fans out to (partition count = measured bytes / resident "
    "window, pow2-rounded; skewed buckets re-partition recursively "
    "instead of widening past this).", checker=_positive)

OOC_MAX_DEPTH = conf(
    "spark.rapids.tpu.sql.ooc.maxDepth", 3,
    "Maximum recursive re-partition depth for an out-of-core bucket "
    "that still exceeds the resident window (re-salted hash per level "
    "so key skew cannot map a bucket onto itself); past it the "
    "split-retry ladder owns the remainder.", checker=_positive)

OOC_FORCE = conf(
    "spark.rapids.tpu.sql.ooc.force", False,
    "Force the out-of-core tier for every eligible hash join and "
    "aggregation regardless of measured bytes (test/ops knob; the "
    "bench --ooc leg and the chaos suite pin behavior with it).")

DELTA_OPTIMIZE_TARGET_ROWS = conf(
    "spark.rapids.tpu.delta.optimize.targetFileRows", 1 << 20,
    "Row target per output file for Delta OPTIMIZE / ZORDER compaction "
    "(the reference's optimize.maxFileSize analogue, rows not bytes "
    "because device buckets are row-shaped).", checker=_positive)

COLLECT_DEVICE_ENABLED = conf(
    "spark.rapids.tpu.sql.agg.collect.enabled", True,
    "Run collect_list/collect_set as the device sorted group-by "
    "emitting ragged columns; off forces the CPU aggregation path.")

RUNTIME_FILTER_FPP = conf(
    "spark.rapids.tpu.sql.runtimeFilter.fpp", 0.01,
    "Target false-positive probability sizing the join runtime bloom "
    "filter (the reference's BloomFilter JNI sizing role); lower = "
    "bigger filter, fewer wasted probe rows.", conf_type=float)

SEG_SCATTER_FREE = conf(
    "spark.rapids.tpu.sql.segments.scatterFree.enabled", True,
    "Run segmented reductions over sorted runs (group-by MIN/MAX, "
    "ignore-null FIRST/LAST, ANY/EVERY, f64 sums, count-distinct and "
    "percentile counts, window frames) as blocked segmented scans plus "
    "boundary gathers instead of jax.ops.segment_* scatters — scatters "
    "cost ~70ms per 1M rows on this platform and land in slow S(1) "
    "buffers (ops/segments.py). Off restores the scatter reductions "
    "for A/B comparison.")

MAX_SORT_OPERANDS = conf(
    "spark.rapids.tpu.sql.sort.maxSortOperands", 2,
    "Widest sort (key lanes + payload) any device kernel may emit; "
    "wider orderings chain stable sorts through a running permutation "
    "(ops/segments.py lexsort_capped). TPU sort COMPILE time scales "
    "brutally with operand count (2-op 31s, 3xi64 164s, 10-op ~10min "
    "at 1M), so 2 is the platform sweet spot; raise it only on "
    "backends whose sort compile is cheap.",
    checker=lambda v: None if v >= 2 else "must be >= 2")

JOIN_DENSE_BUILD_VIA_SORT = conf(
    "spark.rapids.tpu.sql.join.denseBuildViaSort", True,
    "Build dense join direct-address tables (per-key offsets, "
    "unique-key slots) from a sorted key lane + merge-rank instead of "
    "scatters: scatter-built tables land in S(1)-space buffers whose "
    "probe-side gathers run ~200MB/s, while sort outputs stay in fast "
    "memory. Off restores the scatter builders.")

JOIN_MATCHED_VIA_PRESENCE = conf(
    "spark.rapids.tpu.sql.join.matchedViaPresence", True,
    "Answer semi/anti-join matched flags over a dense key domain from a "
    "PRESENCE bitmap (one bool scatter over build rows + a 1-byte "
    "gather per probe row) instead of the sorted per-key offs table — "
    "the flag needs key existence only, so the build-sized sort + "
    "merge-rank behind the table never pays for itself (q21/q22-class "
    "anti joins against a 2M-row build drop ~10x on the cpu backend). "
    "Off restores the sorted offs path.")

JOIN_MATCHED_VIA_MERGE = conf(
    "spark.rapids.tpu.sql.join.matchedViaMerge", True,
    "Derive per-build/per-probe matched flags for outer and expanded "
    "joins from a sorted index lane + merge-rank difference instead of "
    "segment_max scatters (ops/segments.py matched_flags). Off "
    "restores the scatter reductions.")

COMPILE_CONST_LIFT = conf(
    "spark.rapids.tpu.sql.compile.constantLifting", True,
    "Lift plan literals (filter constants, projection scalars) out of "
    "traced device programs into runtime arguments, and key compiled "
    "programs on expression STRUCTURE instead of literal values — two "
    "queries differing only in literals (the dashboard / parameterized "
    "traffic shape) share one XLA executable instead of each paying a "
    "cold compile. Applies to both the per-operator jit cache and the "
    "whole-plan program cache (exec/compiled.py). Literals in positions "
    "whose kernels specialize on the host value (string patterns, IN "
    "lists, array lambdas) stay baked into the program and keyed by "
    "value.", commonly_used=True)

COMPILE_CACHE_DIR = conf(
    "spark.rapids.tpu.compile.cacheDir", "",
    "Directory for the PERSISTENT compile cache: XLA executables are "
    "AOT-serialized here (jax compilation cache) so a fresh process "
    "replays warmed queries with zero XLA compiles. Resolution order: "
    "the JAX_COMPILATION_CACHE_DIR environment variable when set (used "
    "exactly as given; this conf is then ignored), else this conf, else "
    "the fixed <checkout>/.jax_cache. jax's own cache key separates "
    "device topologies, so one directory serves them all.",
    commonly_used=True)

COMPILE_BG_ENABLED = conf(
    "spark.rapids.tpu.compile.background.enabled", True,
    "Compile downstream whole-plan SEGMENTS ahead of time on the "
    "background compile service (runtime/compile_service.py) while "
    "earlier segments execute: when a split plan compiles segment i, "
    "candidate programs for segment i+1 are speculatively AOT-compiled "
    "(lower().compile() over placeholder shapes) for the predicted "
    "seam output buckets, so the seam sync usually finds the next "
    "program ready. Only a seam the process has not seen speculates: "
    "a warm collect finds every next segment's program in the "
    "process-wide executable cache, submits nothing and waits for no "
    "thread. Mispredicted candidates are dropped; injected "
    "`compile` faults from background tasks surface on the consuming "
    "thread with the same recovery ladder as inline compiles.")

COMPILE_BG_THREADS = conf(
    "spark.rapids.tpu.compile.background.threads", 2,
    "Thread-pool size of the background compile service (XLA compiles "
    "release the GIL, so threads overlap real compile work — also the "
    "concurrency of bench.py --compile-only cache warmup).",
    checker=_positive)

COMPILE_BG_SPECULATE = conf(
    "spark.rapids.tpu.compile.background.speculateBuckets", 2,
    "Maximum candidate output buckets speculatively compiled per plan "
    "seam (the aggregate/join row-collapse points). Each candidate "
    "costs one background compile; a hit hides the next segment's "
    "compile behind the current segment's execution.",
    checker=_positive, internal=True)

PLAN_CACHE_ENTRIES = conf(
    "spark.rapids.tpu.compile.planCacheEntries", 256,
    "Bound on the process-wide whole-plan executable cache (canonical "
    "structure key -> compiled XLA program). LRU beyond it.",
    checker=_positive, internal=True)

SHAPE_BUCKETS = conf(
    "spark.rapids.tpu.sql.shape.buckets", "",
    "Explicit static-shape row-bucket set as ascending comma-separated "
    "capacities (e.g. `4096,65536,1048576,4194304`): device batches pad "
    "to the smallest listed bucket >= their row count (doubling past "
    "the largest), REPLACING the geometric minBucketRows/bucketGrowth "
    "ladder. A small coarse set quantizes many input sizes onto few "
    "compiled programs — the cross-scale-factor compile-cache hit — at "
    "the price of more padding. Empty keeps the geometric ladder.",
    checker=lambda v: _check_bucket_set(v))

SCAN_UPLOAD_CACHE_BYTES = conf(
    "spark.rapids.tpu.sql.scan.uploadCacheBytes", 4 << 30,
    "Byte cap on the shared scan-upload cache (one device copy per hot "
    "source table, exec/compiled.py): past it, least-recently-used "
    "table uploads evict (tpu_scan_upload_evictions_total counts them) "
    "so long multi-table sessions cannot grow device-pinned uploads "
    "without bound. 0 disables the cache entirely.",
    checker=_non_negative)


def _check_bucket_set(v):
    s = str(v).strip()
    if not s:
        return None
    try:
        caps = [int(x) for x in s.split(",")]
    except ValueError:
        return f"must be comma-separated integers, got {v!r}"
    if any(c <= 0 for c in caps):
        return "bucket capacities must be positive"
    if caps != sorted(caps) or len(set(caps)) != len(caps):
        return "bucket capacities must be strictly ascending"
    return None


def parse_bucket_set(raw: str):
    """Parsed ascending bucket list of a shape.buckets value ([] when
    unset) — shared by the conf checker and columnar.device."""
    s = str(raw or "").strip()
    return [int(x) for x in s.split(",")] if s else []


# --------------------------------------------------------------------------
# Concurrent serving plane (serving/runtime.py + serving/cache.py)
# --------------------------------------------------------------------------

SERVING_WORKERS = conf(
    "spark.rapids.tpu.serving.workers", 8,
    "Pipeline worker threads of the ServingRuntime: each admitted query "
    "runs its plan / result-cache probe / compile / device-execute "
    "phases on one worker, so up to this many queries are in SOME phase "
    "concurrently (XLA compiles release the GIL — one query's compile "
    "overlaps another's device execution).", checker=_positive)

SERVING_QUEUE_DEPTH = conf(
    "spark.rapids.tpu.serving.queueDepth", 64,
    "Bound on admitted-but-unfinished queries across all tenants. At "
    "the bound, submit() blocks (backpressure) up to "
    "serving.admitTimeoutMs and then raises AdmissionTimeout — load "
    "sheds at admission with a clean signal instead of a device OOM "
    "mid-query.", checker=_positive, commonly_used=True)

SERVING_ADMIT_TIMEOUT_MS = conf(
    "spark.rapids.tpu.serving.admitTimeoutMs", 10000,
    "Longest one submit() blocks for an admission slot when the queue "
    "is at queueDepth before AdmissionTimeout is raised (the "
    "backpressure signal; TenantSession.collect retries it once).",
    checker=_positive)

SERVING_DEVICE_SLOTS = conf(
    "spark.rapids.tpu.serving.deviceSlots", 0,
    "Concurrent device-execute grants the fair-share scheduler hands "
    "out. 0 (default) = auto: sql.concurrentTpuTasks (the GpuSemaphore "
    "sizing — one query's host tail overlaps another's device compute) "
    "on accelerator backends, but 1 on the CPU backend, where 'device "
    "compute' shares the host cores and concurrent XLA programs thrash "
    "each other's intra-op thread pools. Each grant still holds a "
    "semaphore permit inside the query, so the HBM story is unchanged.",
    checker=_non_negative)

SERVING_STARVATION_BOUND = conf(
    "spark.rapids.tpu.serving.starvationBound", 4,
    "Starvation bound of the weighted-deficit scheduler: a tenant with "
    "a runnable query is never passed over more than this many "
    "consecutive device grants — after that it is scheduled regardless "
    "of its deficit (the fairness invariant tests/test_serving.py's "
    "hammer asserts).", checker=_positive)

SERVING_RESULT_CACHE_BYTES = conf(
    "spark.rapids.tpu.serving.resultCache.bytes", 256 << 20,
    "Byte cap of the serving plan+result cache (LRU past it): repeated "
    "dashboard-style queries — same canonical plan STRUCTURE, same "
    "lifted literal values, same live source tables — return the cached "
    "result without touching the device. Entries are checksummed Arrow "
    "IPC payloads, invalidated the moment a source-table anchor is "
    "garbage collected. 0 disables the cache.",
    checker=_non_negative, commonly_used=True)

SERVING_DEADLINE_MS = conf(
    "spark.rapids.tpu.serving.deadlineMs", 0.0,
    "Per-query wall-clock deadline for serving queries, in milliseconds "
    "(0 disables). The clock starts when execution begins (queue wait "
    "is bounded separately by admitTimeoutMs); execution checks it "
    "at cooperative cancellation checkpoints — the compiled-plan seam "
    "brackets, the per-batch result stream, out-of-core partition/merge "
    "passes, exchange rounds and spill-all sweeps — and past the "
    "deadline raises QueryDeadlineExceeded, releasing the ticket's full "
    "device reservation (DeviceCensus shows zero residual). Per-submit "
    "override: TenantSession.submit(df, deadline_ms=...).",
    checker=_non_negative, commonly_used=True)

SERVING_POOL_PROCS = conf(
    "spark.rapids.tpu.serving.pool.processes", 0,
    "Fault-isolated multi-process serving (serving/workers.py): when "
    "> 0, the ServingRuntime supervises this many WORKER PROCESSES, "
    "each owning its own TpuSession / MemoryBudget / device slice, and "
    "dispatches admitted queries to them over an authenticated local "
    "socket. A fatal XLA error, SIGKILL or segfault in one worker loses "
    "only its in-flight queries — they redrive on a surviving worker "
    "(serving.redrive.maxAttempts) while other tenants' queries "
    "complete uninterrupted. Workers share the persistent compile "
    "cache and history store; their budgets reconcile through "
    "heartbeat-reported DeviceCensus totals so admission gates on the "
    "truthful cross-process HBM picture. 0 (default) keeps the "
    "single-process thread pipeline.",
    checker=_non_negative, commonly_used=True)

SERVING_REDRIVE_MAX = conf(
    "spark.rapids.tpu.serving.redrive.maxAttempts", 2,
    "How many times one serving query may REDRIVE onto a surviving "
    "worker after losing its worker process mid-flight (crash, "
    "SIGKILL, heartbeat-timeout hang, fatal device dump). Queries are "
    "read-only and deterministic, so a redriven result is bit-identical "
    "to an undisturbed run; past the bound the ticket fails with the "
    "worker-loss error (the Spark task-retry bound analogue).",
    checker=_non_negative)

SERVING_POOL_HEARTBEAT_MS = conf(
    "spark.rapids.tpu.serving.pool.heartbeatMs", 250,
    "Interval at which each serving worker process heartbeats the "
    "supervisor (pid, in-flight query, DeviceCensus live/peak bytes, "
    "bound metrics port).", checker=_positive)

SERVING_POOL_HEARTBEAT_MISSES = conf(
    "spark.rapids.tpu.serving.pool.heartbeatMisses", 12,
    "A worker whose last heartbeat is older than this many heartbeat "
    "intervals is declared HUNG: the supervisor SIGKILLs it, redrives "
    "its in-flight queries on surviving workers and (pool.restart) "
    "spawns a replacement.", checker=_positive)

SERVING_POOL_RESTART = conf(
    "spark.rapids.tpu.serving.pool.restart", True,
    "Supervised restart: replace a dead serving worker process (crash, "
    "kill, hang, fatal self-termination) with a fresh one so the pool "
    "holds its size. False leaves the pool smaller after each death "
    "(drain/teardown mode).")

SERVING_POOL_TELEMETRY_ENABLED = conf(
    "spark.rapids.tpu.serving.pool.telemetry.enabled", True,
    "Fleet observability federation: worker heartbeat frames piggyback "
    "a cumulative metrics-registry snapshot and a rolling flight-"
    "recorder tail, which the supervisor folds into the fleet-view "
    "registry (per-worker-labeled tpu_fleet_* families on the single "
    "Prometheus endpoint / stats()['fleet']) and into WorkerLost "
    "black-box forensics dumps. False keeps heartbeats bare "
    "(pid + census only, the PR 17 wire shape).")

SERVING_POOL_TELEMETRY_FLIGHT_EVENTS = conf(
    "spark.rapids.tpu.serving.pool.telemetry.flightEvents", 64,
    "How many of the newest in-worker flight-recorder events ride each "
    "heartbeat frame as the worker's black-box snapshot. The supervisor "
    "keeps only the latest snapshot per worker and embeds it into the "
    "WorkerLost dump when that worker dies by kill/hang — the cases "
    "where no in-worker dump is possible.", checker=_positive)

SERVING_POOL_TELEMETRY_MAX_FRAME_BYTES = conf(
    "spark.rapids.tpu.serving.pool.telemetry.maxFrameBytes", 262144,
    "Byte bound on one heartbeat frame's telemetry payload. Liveness "
    "beats observability: when a frame would exceed this, the flight "
    "snapshot is trimmed oldest-first, then dropped, then the registry "
    "snapshot is dropped — the bare heartbeat always goes out.",
    checker=_positive)

SERVING_ADMIT_WORKING_SET_FACTOR = conf(
    "spark.rapids.tpu.serving.admitWorkingSetFactor", 3.0,
    "HBM admission estimate: a query's device working set is assumed "
    "to be this factor times its source-table bytes, and the scheduler "
    "only overlaps device phases whose summed estimates fit the HBM "
    "budget (memory.tpu.budgetBytes / allocFraction) — queueing instead "
    "of betting on the OOM retry ladder. A query too big to ever fit "
    "still runs, alone.", checker=_positive, internal=True)


# --------------------------------------------------------------------------
# Compressed device-resident execution (ops/encodings.py): operators run
# directly on dictionary codes and FOR-narrowed integer lanes instead of
# decoding to full-width materialized columns first
# --------------------------------------------------------------------------

ENCODED_EXECUTION = conf(
    "spark.rapids.tpu.sql.encoded.execution.enabled", True,
    "Master switch for compressed device-resident execution "
    "(ops/encodings.py): equality/IN/range predicates on dictionary "
    "columns rewrite to CODE-SPACE predicates (the literal translates "
    "through the dictionary once at prepare time — no per-row remap "
    "gather), scan dictionaries upload ORDER-PRESERVING (sorted) so "
    "range predicates and ORDER BY compare codes directly, integer scan "
    "lanes FOR-narrow to the smallest value-preserving dtype (decode is "
    "a fused widen sunk to the consumer that truly needs width), and "
    "joins/group-bys keep hashing/accumulating codes. Off disables "
    "every encoded path — plans and results are bit-identical to the "
    "pre-encoding engine. Dispatch/fallback decisions are counted in "
    "tpu_encoded_dispatch_total / tpu_decode_bytes_total.",
    commonly_used=True)

ENCODED_DICT_PREDICATES = conf(
    "spark.rapids.tpu.sql.encoded.dict.predicates", "AUTO",
    "Code-space predicate rewrites on dictionary columns (needs "
    "encoded.execution.enabled): a literal comparison translates the "
    "literal through the column's dictionary at prepare time and "
    "compares codes (equality/IN: always; </<= ranges: against a rank "
    "bound when the dictionary is order-preserving, else through a "
    "per-dictionary rank table — the decode fallback, still on "
    "device). AUTO/ON behave the same today; OFF keeps the legacy "
    "unified-remap gathers.", checker=_enum_checker("AUTO", "ON", "OFF"))

ENCODED_DICT_SORT_SCAN = conf(
    "spark.rapids.tpu.sql.encoded.dict.sortOnScan", True,
    "Upload string dictionaries in SORTED (order-preserving) order at "
    "the host->device boundary (needs encoded.execution.enabled): codes "
    "then ARE ranks, so ORDER BY on dictionary columns skips its "
    "per-row rank-table gather and range predicates compare codes "
    "against one scalar bound. Pure representation change — decoded "
    "values are identical.")

ENCODED_NARROW_LANES = conf(
    "spark.rapids.tpu.sql.encoded.narrow.lanes", "AUTO",
    "FOR-narrow integer/date scan lanes to the smallest VALUE-PRESERVING "
    "signed dtype their live range fits (needs "
    "encoded.execution.enabled; the _negotiate_encoded legality pass "
    "approves columns per consumer chain): uploads ship fewer bytes, "
    "comparisons/arithmetic evaluate in the narrow dtype with "
    "overflow-checked promotion only when the exact result needs width, "
    "and sinks that need full width widen inside the fused program. "
    "AUTO/ON enable, OFF keeps full-width lanes.",
    checker=_enum_checker("AUTO", "ON", "OFF"))

ENCODED_IN_MAX_CODES = conf(
    "spark.rapids.tpu.sql.encoded.dict.inMaxCodes", 16,
    "Largest IN-list size rewritten to per-code equality comparisons "
    "(zero gathers); larger lists keep the per-dictionary membership "
    "mask gather.", checker=_positive)


# --------------------------------------------------------------------------
# Persistent performance-history plane (obs/history.py + obs/estimator.py)
# --------------------------------------------------------------------------

HISTORY_DIR = conf(
    "spark.rapids.tpu.history.dir", "",
    "Directory for the persistent performance-history store "
    "(obs/history.py): every completed query appends one JSONL record — "
    "measured device wall, per-segment device ms, compile ms, source "
    "bytes, peak HBM reservation — keyed by the canonical plan "
    "STRUCTURE (PR 7 constant-lifted structure key + resolved encoding "
    "policy + leaf shape bucket), so a fresh process serves calibrated "
    "cost estimates (obs/estimator.py, serving admission prediction) "
    "with zero re-measurement. Corrupt/truncated lines are tolerated "
    "on load; the file is byte/entry-capped with LRU compaction "
    "(history.maxBytes / history.maxEntries). Empty disables the plane "
    "(the disabled path is one cached conf check per query).",
    commonly_used=True)

HISTORY_MAX_BYTES = conf(
    "spark.rapids.tpu.history.maxBytes", 16 << 20,
    "Byte cap on the on-disk performance-history file: past it the "
    "store compacts — per-structure decay-weighted aggregates replace "
    "raw records and least-recently-updated structures drop first "
    "(the LRU half of the cap).", checker=_positive)

HISTORY_MAX_ENTRIES = conf(
    "spark.rapids.tpu.history.maxEntries", 4096,
    "Bound on distinct plan structures the history store tracks; "
    "beyond it, compaction drops least-recently-updated structures.",
    checker=_positive)

HISTORY_DECAY = conf(
    "spark.rapids.tpu.history.decay", 0.3,
    "Weight of the NEWEST observation in the store's exponentially "
    "decayed aggregates (device us, compile ms, working set): higher "
    "adapts faster to drift, lower smooths noise. In (0, 1].",
    checker=lambda v: None if 0 < v <= 1 else "must be in (0, 1]",
    internal=True)


JOIN_LATE_MATERIALIZATION = conf(
    "spark.rapids.tpu.sql.join.lateMaterialization.enabled", True,
    "Let equi-joins emit THIN batches: payload columns ride as per-side "
    "row-id selection lanes (the gather indices the join computed "
    "anyway) and materialize only at a pipeline sink (aggregate build, "
    "sort, exchange, collect) via one composed gather per source batch "
    "— row gathers are the dominant device cost on TPU, and a join "
    "chain otherwise re-gathers every payload column per join. Columns "
    "a mid-pipeline condition or projection needs are materialized "
    "early, and only those (plan/overrides.py legality pass).")


class TpuConf:
    """An immutable-ish view over a dict of raw settings with typed access.

    Like the reference, a fresh TpuConf is constructed from the session conf
    at plan time so per-query overrides take effect (GpuOverrides.scala:4571).
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._raw = dict(settings or {})
        self._cache: Dict[str, Any] = {}
        for k in self._raw:
            if (k.startswith("spark.rapids.tpu.") and k not in _REGISTRY
                    and not self._is_dynamic_key(k)):
                raise ValueError(f"unknown config key: {k}")

    _DYNAMIC_RE = re.compile(
        r"^spark\.rapids\.tpu\.sql\.(expression|exec|partitioning|command)\.\w+$")

    @classmethod
    def _is_dynamic_key(cls, key: str) -> bool:
        return cls._DYNAMIC_RE.match(key) is not None

    def get(self, entry: ConfEntry):
        if entry.key not in self._cache:
            raw = self._raw.get(entry.key, entry.default)
            self._cache[entry.key] = entry.convert(raw) if raw is not None else None
        return self._cache[entry.key]

    def get_raw(self, key: str, default=None):
        return self._raw.get(key, default)

    def is_op_enabled(self, kind: str, name: str) -> bool:
        """Per-operator auto-generated enable keys, default on."""
        raw = self._raw.get(f"spark.rapids.tpu.sql.{kind}.{name}")
        if raw is None:
            return True
        return _parse_bool(raw)

    def with_overrides(self, **kv) -> "TpuConf":
        merged = dict(self._raw)
        merged.update({k.replace("__", "."): v for k, v in kv.items()})
        return TpuConf(merged)

    # Convenience typed accessors used widely by the engine.
    @property
    def sql_enabled(self):
        return self.get(SQL_ENABLED)

    @property
    def explain(self):
        return str(self.get(EXPLAIN)).upper()

    @property
    def explain_only(self):
        return str(self.get(MODE)).upper() == "EXPLAINONLY"

    @property
    def batch_size_rows(self):
        return self.get(BATCH_SIZE_ROWS)

    @property
    def ansi(self):
        # explicit session setting wins; otherwise the pinned Spark
        # version's default (false through 3.x, true in 4.0 — shims.py)
        if ANSI_ENABLED.key in self._raw:
            return self.get(ANSI_ENABLED)
        return self.shims.ansi_default

    @property
    def shims(self):
        """Version shims for `spark.rapids.tpu.spark.version`
        (ShimLoader role, shims.py)."""
        from .shims import get_shims
        return get_shims(str(self.get(SPARK_VERSION)))

    @property
    def bucket_set(self):
        """Explicit shape.buckets capacities ([] = geometric ladder),
        parsed once per conf."""
        if "__bucket_set" not in self._cache:
            self._cache["__bucket_set"] = parse_bucket_set(
                self.get(SHAPE_BUCKETS))
        return self._cache["__bucket_set"]

    @property
    def bucket_min_rows(self):
        return self.get(BUCKET_MIN_ROWS)

    @property
    def bucket_growth(self):
        return self.get(BUCKET_GROWTH)


DEFAULT_CONF = TpuConf()


def generate_docs() -> str:
    """Markdown config reference (reference RapidsConf.help / docs/configs.md)."""
    lines = ["# spark-rapids-tpu configuration", "",
             "| key | default | meaning |", "|---|---|---|"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.internal:
            continue
        doc = e.doc.replace("|", "\\|").replace("\n", " ")
        lines.append(f"| `{e.key}` | `{e.default}` | {doc} |")
    lines += [
        "", "## Benchmark harness (bench.py)", "",
        "`python bench.py [scale] [--queries q1,q6,...] "
        "[--suite tpch|tpcds]`", "",
        "| flag / env | default | meaning |", "|---|---|---|",
        "| `--suite` | `tpch` | Workload: the 22-query TPC-H suite or "
        "the TPC-DS tranche (spark_rapids_tpu/tpcds.py). The tpcds "
        "report adds the operator-coverage matrix: per-query fallback "
        "reasons plus the sort_operand_max / scatter_op_count jaxpr "
        "lints, and a summary splitting queries into device-clean / "
        "with-fallbacks / not-whole-plan-traceable. |",
        "| `--queries` | all registered | Comma-separated subset of the "
        "suite's QUERIES registry. |",
        "| `--serving` | off | Concurrent serving sweep: closed-loop "
        "clients (one tenant each) over the query mix at concurrency "
        "1/2/4/8 through the ServingRuntime, vs the same multiset "
        "serially through the single-query path; reports p50/p99 "
        "latency, QPS, device utilization and result-cache outcomes "
        "(docs/SERVING.md; gated via check_regression sv: entries). "
        "Adds mp2/mp4 multi-process pool levels "
        "(serving.pool.processes) plus an mp2_kill chaos leg that "
        "SIGKILLs one worker mid-query and must stay oracle-matching "
        "via redrive (docs/ROBUSTNESS.md). |",
        "| `scale` | `1.0` | Linear datagen scale factor (SF1-ish row "
        "counts at 1.0; fixed-size dimensions never scale). |",
        "| `BENCH_BUDGET_S` | `1800` | Total wall budget; queries that "
        "do not fit are listed in `skipped`, and the last stdout line "
        "is always a complete parseable JSON result. |",
        "",
    ]
    return "\n".join(lines)


def all_entries() -> List[ConfEntry]:
    return list(_REGISTRY.values())


if __name__ == "__main__":
    import pathlib
    # regenerate through the CANONICAL module: running `-m ...config`
    # executes this file as __main__ with its own empty _REGISTRY, while
    # imported modules (runtime/failure.py) register their entries into
    # the sys.modules copy — generating from __main__'s registry would
    # silently drop them (scripts/check_docs.py guards this)
    from spark_rapids_tpu import config as _cfg
    from spark_rapids_tpu.runtime import failure as _failure  # noqa: F401
    out = pathlib.Path(__file__).resolve().parent.parent / "docs"
    out.mkdir(exist_ok=True)
    (out / "configs.md").write_text(_cfg.generate_docs())
    print(f"wrote {out / 'configs.md'}")
