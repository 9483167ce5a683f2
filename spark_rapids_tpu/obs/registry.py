"""Process-wide always-on metrics registry — the Spark metrics-sink role.

Reference (SURVEY §5): the plugin surfaces per-operator GPU metrics
through Spark's *always-on* metric sinks and the history server, not
just opt-in traces.  The query tracer (obs/tracer.py, OFF by default)
covers the per-query deep dive; this registry is the complement: one
process-wide `MetricsRegistry` that every runtime subsystem publishes
into unconditionally — visible between queries, across queries and at
crash time (runtime/failure.py embeds a snapshot in crash dumps).

Three metric kinds, Prometheus-shaped:

  * Counter   — monotonically increasing totals (`.inc`);
  * Gauge     — point-in-time levels (`.set`) and high-waters (`.max`);
  * Histogram — bounded log2-bucket distributions (`.observe`): bucket
    `i` counts values in (2^(i-1), 2^i], so a byte-skew or wait-time
    distribution costs at most `_MAX_BUCKET`+1 integers per series,
    never a per-observation list.

Series carry labels (query id, device index, operator class, ...).
Label cardinality is BOUNDED: past `max_series` distinct label sets per
metric, further sets collapse into one `~overflow` series, so a label
mistake (or a million query ids) cannot grow memory — the registry is
fixed-cost by construction, which is what lets it stay always-on.

Export lives in obs/export.py (JSONL heartbeat + Prometheus text
endpoint); `spark.rapids.tpu.metrics.enabled=false` turns every publish
call into one attribute check for A/B overhead runs.

Every family registered here must be documented in docs/METRICS.md —
scripts/check_docs.py lints `REGISTRY.family_names()` against it.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

#: log2 buckets 0..50: bucket 0 is (-inf, 1], bucket i is (2^(i-1), 2^i];
#: 2^50 covers a petabyte of bytes or ~35 years of milliseconds
_MAX_BUCKET = 50

#: label-set value a metric's series collapse into past max_series
OVERFLOW = "~overflow"


def bucket_index(v: float) -> int:
    """Log2 bucket of one observation (shared with tests: the
    independently-computed distributions use this same mapping)."""
    if v <= 1:
        return 0
    n = int(v) if float(v).is_integer() else int(v) + 1
    return min((n - 1).bit_length(), _MAX_BUCKET)


def bucket_le(i: int) -> int:
    """Inclusive upper bound of bucket `i` (the Prometheus `le`)."""
    return 1 << i if i else 1


class _HistogramState:
    __slots__ = ("count", "sum", "buckets")

    def __init__(self):
        self.count = 0
        self.sum = 0.0
        self.buckets: Dict[int, int] = {}


class Metric:
    """One metric family: a name + kind + label names, holding every
    labeled series.  Publish methods are self-locking and no-op when
    the owning registry is disabled."""

    def __init__(self, registry: "MetricsRegistry", name: str, kind: str,
                 help_: str, labelnames: Tuple[str, ...]):
        self._reg = registry
        self.name = name
        self.kind = kind
        self.help = help_
        self.labelnames = tuple(labelnames)
        self._series: Dict[tuple, Any] = {}
        self._lock = threading.Lock()

    def _key(self, labels: Dict[str, Any]) -> tuple:
        key = tuple(str(labels.get(n, "")) for n in self.labelnames)
        if key not in self._series and \
                len(self._series) >= self._reg.max_series:
            # bounded cardinality: late label sets share one series
            return tuple(OVERFLOW for _ in self.labelnames)
        return key

    # -- publish (each checks the registry's enabled flag first) ----------
    def inc(self, v: float = 1, **labels) -> None:
        if not self._reg.enabled:
            return
        with self._lock:
            k = self._key(labels)
            self._series[k] = self._series.get(k, 0) + v

    def set(self, v: float, **labels) -> None:
        if not self._reg.enabled:
            return
        with self._lock:
            self._series[self._key(labels)] = v

    def max(self, v: float, **labels) -> None:
        """High-water update: keep the larger of current and `v`."""
        if not self._reg.enabled:
            return
        with self._lock:
            k = self._key(labels)
            if v > self._series.get(k, float("-inf")):
                self._series[k] = v

    def add(self, v: float, **labels) -> None:
        """Gauge delta (active-count style: add(+1)/add(-1))."""
        if not self._reg.enabled:
            return
        with self._lock:
            k = self._key(labels)
            self._series[k] = self._series.get(k, 0) + v

    def observe(self, v: float, **labels) -> None:
        if not self._reg.enabled:
            return
        with self._lock:
            k = self._key(labels)
            st = self._series.get(k)
            if st is None:
                st = self._series[k] = _HistogramState()
            st.count += 1
            st.sum += float(v)
            i = bucket_index(v)
            st.buckets[i] = st.buckets.get(i, 0) + 1

    def set_histogram(self, count: int, sum_: float, buckets,
                      **labels) -> None:
        """Cumulative SET of one histogram series from a snapshot's
        `[[le, count], ...]` bucket list — the federation fold: a worker
        ships its full histogram state each heartbeat and set semantics
        make a dropped frame self-heal on the next one."""
        if not self._reg.enabled:
            return
        st = _HistogramState()
        st.count = int(count)
        st.sum = float(sum_)
        for le, c in buckets or ():
            i = int(le).bit_length() - 1 if int(le) > 1 else 0
            st.buckets[i] = int(c)
        with self._lock:
            self._series[self._key(labels)] = st

    # -- read -------------------------------------------------------------
    def value(self, **labels):
        """Current value of one series (0 / None when never published)."""
        key = tuple(str(labels.get(n, "")) for n in self.labelnames)
        with self._lock:
            v = self._series.get(key)
        if isinstance(v, _HistogramState):
            return {"count": v.count, "sum": v.sum,
                    "buckets": dict(v.buckets)}
        return 0 if v is None and self.kind == "counter" else v

    def series(self) -> List[dict]:
        with self._lock:
            items = list(self._series.items())
        out = []
        for key, v in items:
            labels = dict(zip(self.labelnames, key))
            if isinstance(v, _HistogramState):
                out.append({"labels": labels, "count": v.count,
                            "sum": v.sum,
                            "buckets": [[bucket_le(i), c] for i, c in
                                        sorted(v.buckets.items())]})
            else:
                out.append({"labels": labels, "value": v})
        return out


class MetricsRegistry:
    """The process-wide family registry (one global `REGISTRY` below;
    independent instances exist only for tests)."""

    def __init__(self, max_series: int = 64):
        self.enabled = True
        self.max_series = max_series
        self._families: Dict[str, Metric] = {}
        self._lock = threading.Lock()

    def _register(self, name: str, kind: str, help_: str,
                  labelnames: Tuple[str, ...]) -> Metric:
        with self._lock:
            m = self._families.get(name)
            if m is not None:
                if m.kind != kind or m.labelnames != tuple(labelnames):
                    raise ValueError(
                        f"metric {name!r} re-registered with a different "
                        f"shape ({m.kind}{m.labelnames} vs "
                        f"{kind}{tuple(labelnames)})")
                return m
            m = Metric(self, name, kind, help_, tuple(labelnames))
            self._families[name] = m
            return m

    def counter(self, name: str, help_: str, labelnames=()) -> Metric:
        return self._register(name, "counter", help_, tuple(labelnames))

    def gauge(self, name: str, help_: str, labelnames=()) -> Metric:
        return self._register(name, "gauge", help_, tuple(labelnames))

    def histogram(self, name: str, help_: str, labelnames=()) -> Metric:
        return self._register(name, "histogram", help_, tuple(labelnames))

    def family_names(self) -> List[str]:
        with self._lock:
            return sorted(self._families)

    def get(self, name: str) -> Optional[Metric]:
        with self._lock:
            return self._families.get(name)

    def reset(self) -> None:
        """Drop every series (families stay registered) — test isolation
        for exact-distribution assertions."""
        with self._lock:
            fams = list(self._families.values())
        for m in fams:
            with m._lock:
                m._series.clear()

    # -- export -----------------------------------------------------------
    def snapshot(self) -> dict:
        """Structured snapshot: every family with its labeled series."""
        with self._lock:
            fams = list(self._families.values())
        return {"ts": time.time(),
                "enabled": self.enabled,
                "families": [{"name": m.name, "kind": m.kind,
                              "help": m.help,
                              "labels": list(m.labelnames),
                              "series": m.series()}
                             for m in fams if m.series()]}

    def flat(self) -> Dict[str, Any]:
        """Compact `name{a=b}` -> value view (heartbeat lines, bench
        embedding, event-log query_end records).  Histograms flatten to
        `.count` / `.sum` entries."""
        out: Dict[str, Any] = {}
        for fam in self.snapshot()["families"]:
            for s in fam["series"]:
                lbl = ",".join(f"{k}={v}" for k, v in s["labels"].items()
                               if v != "")
                key = f"{fam['name']}{{{lbl}}}" if lbl else fam["name"]
                if "value" in s:
                    out[key] = s["value"]
                else:
                    out[key + ".count"] = s["count"]
                    out[key + ".sum"] = round(s["sum"], 3)
        return out

    def prometheus_text(self) -> str:
        """Prometheus exposition text format (served by the stdlib HTTP
        endpoint, obs/export.py)."""
        lines: List[str] = []
        for fam in self.snapshot()["families"]:
            name = fam["name"]
            lines.append(f"# HELP {name} {fam['help']}")
            lines.append(f"# TYPE {name} {fam['kind']}")
            for s in fam["series"]:
                lbl = ",".join(f'{k}="{v}"'
                               for k, v in s["labels"].items())
                if "value" in s:
                    lines.append(f"{name}{{{lbl}}} {s['value']}"
                                 if lbl else f"{name} {s['value']}")
                    continue
                cum = 0
                for le, c in s["buckets"]:
                    cum += c
                    ls = (lbl + "," if lbl else "") + f'le="{le}"'
                    lines.append(f"{name}_bucket{{{ls}}} {cum}")
                ls = (lbl + "," if lbl else "") + 'le="+Inf"'
                lines.append(f"{name}_bucket{{{ls}}} {s['count']}")
                lines.append(f"{name}_sum{{{lbl}}} {s['sum']}"
                             if lbl else f"{name}_sum {s['sum']}")
                lines.append(f"{name}_count{{{lbl}}} {s['count']}"
                             if lbl else f"{name}_count {s['count']}")
        return "\n".join(lines) + "\n"


#: THE process-wide registry every subsystem publishes into
REGISTRY = MetricsRegistry()


# ---------------------------------------------------------------------------
# Metric catalog: central declarations so the full family set exists at
# import time (scripts/check_docs.py lints these names against
# docs/METRICS.md) and call sites share one handle per family.
# ---------------------------------------------------------------------------

QUERIES_TOTAL = REGISTRY.counter(
    "tpu_queries_total",
    "Completed query collects by terminal status and root plan kind.",
    ("status", "kind"))

ACTIVE_QUERIES = REGISTRY.gauge(
    "tpu_active_queries",
    "Queries currently inside their instrumented execution scope.")

QUERY_WALL_MS = REGISTRY.histogram(
    "tpu_query_wall_ms",
    "End-to-end wall milliseconds per query collect (log2 buckets).")

DATA_BYTES = REGISTRY.counter(
    "tpu_data_movement_bytes_total",
    "Bytes moved per channel (h2d, d2h, shuffle_write, shuffle_read, "
    "ici_exchange) — fed by every tracer byte-counter call site, "
    "tracing on or off.",
    ("channel",))

RUNTIME_EVENTS = REGISTRY.counter(
    "tpu_runtime_events_total",
    "Runtime incident instants (oom_retry, spill, batch_split, io_retry, "
    "semaphore_wait, fault_injected, ...) by event name and category.",
    ("event", "cat"))

HBM_LIVE_BYTES = REGISTRY.gauge(
    "tpu_hbm_live_bytes",
    "Device bytes currently admitted by the HBM budget, per device.",
    ("device",))

HBM_PEAK_BYTES = REGISTRY.gauge(
    "tpu_hbm_peak_bytes",
    "Process-lifetime high-water of budget-admitted device bytes, per "
    "device.",
    ("device",))

HOST_SPILL_LIVE_BYTES = REGISTRY.gauge(
    "tpu_host_spill_live_bytes",
    "Bytes currently resident in the host spill tier.")

SPILL_BATCHES = REGISTRY.counter(
    "tpu_spill_batches_total",
    "Batches demoted per tier (host = device->host, disk = host->disk).",
    ("tier",))

SPILL_BYTES = REGISTRY.counter(
    "tpu_spill_bytes_total",
    "Bytes demoted per tier (host = device->host, disk = host->disk).",
    ("tier",))

SPILL_MS = REGISTRY.histogram(
    "tpu_spill_ms",
    "Milliseconds spent moving one spillable between tiers (op = spill "
    "| to_disk | read), log2 buckets — the spill wait-time histogram.",
    ("op",))

OOM_RETRIES = REGISTRY.counter(
    "tpu_oom_retries_total",
    "OOM-retry ladder replays (spill-everything-and-replay rungs).")

BATCH_SPLITS = REGISTRY.counter(
    "tpu_batch_splits_total",
    "Batches halved by the split-and-retry rung.")

IO_RETRIES = REGISTRY.counter(
    "tpu_io_retries_total",
    "Transient host-IO retries by injection/retry site.",
    ("site",))

RELEASE_UNDERFLOWS = REGISTRY.counter(
    "tpu_release_underflows_total",
    "Budget double-releases clamped to zero (should stay 0).")

SEMAPHORE_WAIT_MS = REGISTRY.histogram(
    "tpu_semaphore_wait_ms",
    "Milliseconds blocked acquiring a concurrentTpuTasks device permit, "
    "log2 buckets, one observation per acquisition.")

SHUFFLE_BYTES = REGISTRY.counter(
    "tpu_shuffle_bytes_total",
    "Serialized shuffle bytes by direction (written / read).",
    ("direction",))

SHUFFLE_PARTITION_BYTES = REGISTRY.histogram(
    "tpu_shuffle_partition_bytes",
    "Serialized bytes of each shuffle partition slice written by one "
    "map-task call, log2 buckets — the per-partition byte-skew "
    "distribution.")

ICI_EXCHANGE_BYTES = REGISTRY.counter(
    "tpu_ici_exchange_bytes_total",
    "Total post-compression wire bytes the mesh ships through ragged "
    "all_to_all exchange rounds and one-time dictionary gathers, summed "
    "across devices (masked slots transit too) — emitted once per "
    "exchange, off the per-device hot path.")

EXCHANGE_WIRE_PRE = REGISTRY.counter(
    "tpu_exchange_wire_bytes_pre_compress_total",
    "Wire bytes the planned exchange rounds WOULD have shipped at the "
    "logical lane widths (flags as int8, full-width integers), summed "
    "across devices — the numerator baseline of the on-wire "
    "compression ratio (spark.rapids.tpu.exchange.compress.enabled).")

EXCHANGE_WIRE_POST = REGISTRY.counter(
    "tpu_exchange_wire_bytes_post_compress_total",
    "Wire bytes actually shipped after bit-packing flag lanes and "
    "frame-of-reference narrowing integer lanes, summed across devices "
    "— post/pre is the achieved on-wire compression ratio.")

EXCHANGE_ROUNDS = REGISTRY.histogram(
    "tpu_exchange_rounds",
    "all_to_all rounds per ragged exchange call (log2 buckets): the "
    "skew-aware quota scheduler's output — uniform exchanges land in "
    "bucket 1, a hot destination no longer inflates everyone's round "
    "count.")

OPERATOR_ROWS = REGISTRY.counter(
    "tpu_operator_output_rows_total",
    "Output rows per operator class (published at query end, after "
    "lazy device counts coerce).",
    ("op",))

OPERATOR_BATCHES = REGISTRY.counter(
    "tpu_operator_output_batches_total",
    "Output batches per operator class.",
    ("op",))

OPERATOR_TIME_MS = REGISTRY.counter(
    "tpu_operator_time_ms_total",
    "Operator wall milliseconds per operator class.",
    ("op",))

COMPILES_TOTAL = REGISTRY.counter(
    "tpu_compiles_total",
    "Whole-plan XLA compile-cache outcomes (hit / miss).",
    ("outcome",))

ENCODED_DISPATCH = REGISTRY.counter(
    "tpu_encoded_dispatch_total",
    "Operator dispatches that stayed in the compressed domain "
    "(ops/encodings.py), by site (predicate_code, predicate_range, "
    "in_codes, predicate_narrow, arith_narrow, sort_codes, "
    "groupby_codes, narrow_upload, dict_sort_upload) and outcome "
    "(encoded = computed on codes/narrow lanes; decode = fell back to "
    "a rank-table/remap gather or full-width widen; oom_shed = a "
    "kernel-site chaos OOM shed the dispatch onto the decoded tier).",
    ("site", "outcome"))

DECODE_BYTES = REGISTRY.counter(
    "tpu_decode_bytes_total",
    "Bytes materialized by DECODING encoded columns (per-row rank/remap "
    "table gathers, full-width widens of FOR-narrowed lanes), by site — "
    "the volume the encoded-execution layer exists to shrink; counted "
    "at capacity scale when the decode is emitted into a program.",
    ("site",))

PLAN_CACHE = REGISTRY.counter(
    "tpu_plan_cache_total",
    "Process-wide whole-plan executable cache outcomes (canonical "
    "constant-lifted structure key, exec/compiled.py): hit = a query "
    "adopted another query's compiled program (literal-only variants, "
    "re-planned repeats); miss = a cacheable plan paid a fresh compile.",
    ("outcome",))

COMPILE_PERSISTENT_HITS = REGISTRY.counter(
    "tpu_compile_cache_persistent_hits_total",
    "XLA compiles served from the on-disk persistent compile cache "
    "(jax compilation cache; JAX_COMPILATION_CACHE_DIR, else "
    "spark.rapids.tpu.compile.cacheDir, else <checkout>/.jax_cache).")

COMPILE_PERSISTENT_MISSES = REGISTRY.gauge(
    "tpu_compile_cache_persistent_misses",
    "XLA compiles that consulted the persistent cache and missed "
    "(requests minus hits — maintained as a gauge: +1 per cache-using "
    "compile request, -1 when the request resolves to a hit).  0 on a "
    "fully warmed process: the zero-XLA-compiles replay proof.")

COMPILE_BG_MS = REGISTRY.histogram(
    "tpu_compile_background_ms",
    "Wall milliseconds of each background compile-service task "
    "(speculative split-plan segment compiles, --compile-only warmup), "
    "log2 buckets (runtime/compile_service.py).")

SCAN_UPLOAD_EVICTIONS = REGISTRY.counter(
    "tpu_scan_upload_evictions_total",
    "Hot-table device uploads evicted from the byte-capped shared "
    "scan-upload cache (spark.rapids.tpu.sql.scan.uploadCacheBytes).")

FAULTS_INJECTED = REGISTRY.counter(
    "tpu_faults_injected_total",
    "Chaos-harness faults fired, by injection site and kind.",
    ("site", "kind"))

CRASH_DUMPS = REGISTRY.counter(
    "tpu_crash_dumps_total",
    "Fatal-device crash dumps written by runtime/failure.py.")

GATHER_ROWS = REGISTRY.counter(
    "tpu_gather_rows_total",
    "Row gathers performed per site (rows x columns, capacity-based): "
    "probe/build = join-side payload gathers, late = deferred columns "
    "resolved at a pipeline sink through composed row-id lanes "
    "(columnar/lanes.py).",
    ("site",))

GATHER_BYTES = REGISTRY.counter(
    "tpu_gather_bytes_total",
    "Bytes moved by row gathers per site (data + validity + hi lanes at "
    "batch capacity) — the dominant device cost of join pipelines.",
    ("site",))

DEFERRED_GATHERS = REGISTRY.counter(
    "tpu_deferred_gathers_total",
    "Payload-column gathers a join SKIPPED by emitting a thin batch "
    "(late materialization): the column rides as a row-id lane and "
    "materializes at the pipeline sink — or never, if nothing "
    "references it.")

SEGMENT_DEVICE_MS = REGISTRY.histogram(
    "tpu_segment_device_ms",
    "Measured device wall milliseconds per compiled plan segment "
    "(dispatch + block_until_ready), log2 buckets, labeled by the "
    "segment's root operator class — populated only when "
    "spark.rapids.tpu.profile.segments is on (the attribution plane, "
    "exec/compiled.py).",
    ("segment",))

SEGMENT_ROWS = REGISTRY.counter(
    "tpu_segment_out_rows_total",
    "Output rows per compiled plan segment (root operator class), "
    "counted at the segment boundary when "
    "spark.rapids.tpu.profile.segments is on.",
    ("segment",))

SEGMENT_HBM_PEAK = REGISTRY.histogram(
    "tpu_segment_hbm_peak_bytes",
    "Measured HBM working set per compiled plan segment: the larger "
    "of the program's XLA memory_analysis() bytes (arguments + output "
    "+ temp + generated code) and the budget peak delta observed "
    "across its dispatch window, log2 buckets, labeled by the "
    "segment's root operator class — populated only when "
    "spark.rapids.tpu.profile.segments is on (the memory-attribution "
    "plane, obs/memattr.py).",
    ("segment",))

OVERHEAD_MS = REGISTRY.histogram(
    "tpu_overhead_ms",
    "Per-query wall milliseconds attributed to a fixed-overhead "
    "category by the wall-decomposition plane (exec/compiled.py, "
    "obs/profile.py wall_breakdown): `dispatch` = measured per-backend "
    "dispatch floor x program launches, `seam` = host sync + re-bucket "
    "at every SplitCompiledPlan boundary, `pad_waste` = the "
    "bucket-quantization tax (padded-minus-live rows priced at the "
    "segment's per-row device cost).  One observation per finished "
    "query per nonzero category, log2 buckets.",
    ("category",))

PAD_ROWS = REGISTRY.counter(
    "tpu_pad_rows_total",
    "Padded-minus-live rows per site: `upload` counts padding added "
    "when host batches are bucketed onto the device "
    "(columnar/device.py to_device, always-on), `segment` counts the "
    "padded input rows each profiled compiled-segment dispatch "
    "computed over (exec/compiled.py).",
    ("site",))

PAD_WASTE_MS = REGISTRY.histogram(
    "tpu_pad_waste_ms",
    "Estimated device milliseconds a profiled compiled segment spent "
    "computing over padding (device wall x padded input fraction), "
    "log2 buckets, labeled by the segment's root operator class — "
    "populated only when spark.rapids.tpu.profile.segments is on.",
    ("segment",))

HBM_RESIDUAL = REGISTRY.counter(
    "tpu_hbm_residual_bytes",
    "Naked (directly reserved, non-Spillable) budget bytes still live "
    "at query end — the leak check (obs/memattr.py): every completed "
    "query whose direct reserve/release pairs did not balance adds "
    "its residual here and flags memory.residual_naked_bytes in the "
    "profile.  Should stay 0.")

HBM_PREDICTION_ERROR = REGISTRY.histogram(
    "tpu_hbm_prediction_error_ratio",
    "Working-set-prediction calibration of the admission oracle: one "
    "observation per executed query that carried an admission-time "
    "working_set_bytes prediction, of max(predicted, measured) / "
    "min(predicted, measured) HBM bytes (>= 1; 1 = perfect), log2 "
    "buckets, labeled by estimate basis — the reservation-vs-actual "
    "curve scripts/history_report.py renders offline.",
    ("basis",))

SERVING_QUEUE_DEPTH = REGISTRY.gauge(
    "tpu_serving_queue_depth",
    "Admitted-but-unfinished queries in the ServingRuntime (the bounded "
    "admission queue's current depth, serving/runtime.py).")

SERVING_ADMIT_WAIT_MS = REGISTRY.histogram(
    "tpu_serving_admission_wait_ms",
    "Milliseconds one submit() blocked for an admission slot, log2 "
    "buckets, one observation per successful admission — queue "
    "backpressure shows up in the tail.")

SERVING_TENANT_DEVICE_US = REGISTRY.counter(
    "tpu_serving_tenant_device_us_total",
    "Measured device-execute MICROseconds per serving tenant (integer, "
    "so concurrent publication order cannot perturb the total — the "
    "fair-share hammer asserts exact equality against per-ticket sums).",
    ("tenant",))

SERVING_QUERIES = REGISTRY.counter(
    "tpu_serving_queries_total",
    "Serving-plane queries by tenant and terminal status (ok | error | "
    "admission_timeout | cache_hit).",
    ("tenant", "status"))

SERVING_RESULT_CACHE = REGISTRY.counter(
    "tpu_serving_result_cache_total",
    "Plan+result cache outcomes (serving/cache.py): hit, miss, store, "
    "evict (byte-cap LRU), invalidate (source-table anchor died), "
    "corrupt (checksum verification rejected a damaged payload — "
    "treated as a miss and recomputed).",
    ("outcome",))

SERVING_DEVICE_BUSY_US = REGISTRY.counter(
    "tpu_serving_device_busy_us_total",
    "Microseconds a serving device-execute grant was active (summed "
    "across slots) — device utilization is this over wall time, the "
    "overlap-is-real number bench.py --serving reports.")

HISTORY_RECORDS = REGISTRY.counter(
    "tpu_history_records_total",
    "Performance-history store outcomes per completed query "
    "(obs/history.py): ok = one JSONL record appended and folded into "
    "the structure's decay aggregate; io_error = the write failed (or "
    "a `history` chaos ioerror fired) and the entry was SKIPPED with "
    "the query unaffected; unkeyed = the plan produced no structure "
    "key (nothing recorded).",
    ("outcome",))

HISTORY_ESTIMATES = REGISTRY.counter(
    "tpu_history_estimates_total",
    "Cost-oracle estimate calls by basis (obs/estimator.py): "
    "exact_history = the structure key hit the persistent history and "
    "the decay-weighted measurement answered; static_cost = never-seen "
    "structure, answered from the static source-byte cost scaled by "
    "the continuously-fitted us-per-byte coefficient — the per-basis "
    "hit/miss/fallback counters of the admission oracle.",
    ("basis",))

HISTORY_PREDICTION_ERROR = REGISTRY.histogram(
    "tpu_history_prediction_error_ratio",
    "Prediction-vs-actual calibration of the cost oracle: one "
    "observation per executed query that carried an admission-time "
    "prediction, of max(predicted, measured) / min(predicted, "
    "measured) device-us (>= 1; 1 = perfect), log2 buckets, labeled "
    "by estimate basis — the how-wrong-is-the-oracle histogram "
    "stats(), the heartbeat and the Prometheus endpoint expose.",
    ("basis",))

SERVING_TENANT_PREDICTED_US = REGISTRY.counter(
    "tpu_serving_tenant_predicted_device_us_total",
    "Admission-time PREDICTED device microseconds per serving tenant "
    "(integer, summed over admitted queries) — read next to "
    "tpu_serving_tenant_device_us_total, the measured counter, for the "
    "per-tenant predicted-vs-measured calibration view.",
    ("tenant",))

SERVING_WORKERS_LIVE = REGISTRY.gauge(
    "tpu_serving_workers_live",
    "Live worker processes in the supervised serving pool "
    "(serving/workers.py): heartbeating and accepting dispatches. "
    "Dips below serving.pool.processes only for the crash-to-restart "
    "window.")

SERVING_WORKER_RESTARTS = REGISTRY.counter(
    "tpu_serving_worker_restarts_total",
    "Worker-process deaths handled by the supervisor, by reason: "
    "crash = the process died or its connection dropped (SIGKILL, "
    "segfault, injected worker:kill), hang = the heartbeat-miss window "
    "elapsed and the supervisor killed it, fatal = the worker "
    "self-terminated after a classified FATAL_DEVICE crash dump. Each "
    "death redrives the worker's in-flight queries; with pool.restart "
    "a replacement is spawned.",
    ("reason",))

SERVING_REDRIVES = REGISTRY.counter(
    "tpu_serving_redrives_total",
    "Queries re-dispatched onto a surviving worker after losing their "
    "worker process mid-flight (serving.redrive.maxAttempts bounds "
    "attempts per query; results stay bit-identical — queries are "
    "read-only and deterministic).",
    ("reason",))

SERVING_DEADLINE_CANCELS = REGISTRY.counter(
    "tpu_serving_deadline_cancellations_total",
    "Serving queries cancelled at a cooperative cancellation "
    "checkpoint: deadline = serving.deadlineMs (or the per-submit "
    "override) elapsed, injected = the deadline:timeout chaos site "
    "fired, drain = cancelled by an explicit cancel event. The "
    "cancelled ticket's full device reservation is released "
    "(DeviceCensus shows zero residual).",
    ("reason",))

SERVING_WORKER_HEARTBEATS = REGISTRY.counter(
    "tpu_serving_worker_heartbeats_total",
    "Worker-pool heartbeat frames the supervisor consumed (each "
    "carries the worker's pid, in-flight query and DeviceCensus "
    "live/peak bytes — the cross-process HBM picture admission "
    "reconciles against).")

DICT_REMAPS = REGISTRY.counter(
    "tpu_join_dict_remaps_total",
    "Host dictionary remap/unification computations (index_in + "
    "uniqueness unify). Cached per dictionary identity pair, so this "
    "counts cache MISSES — per-probe-batch recomputation regressions "
    "show up here.")

OOC_ELECTIONS = REGISTRY.counter(
    "tpu_ooc_elections_total",
    "Out-of-core tier elections by operator (join | agg | sort | "
    "query) and mode: bytes = the measured working set exceeded the "
    "resident window at execution time, rows = the legacy row-count "
    "gate tripped, forced = sql.ooc.force / an escalated context, "
    "proactive = the cost oracle's measured-basis working set elected "
    "OOC at plan time, admission = serving admitted an oversized query "
    "in OOC mode instead of running it solo, reactive = the "
    "TpuSplitAndRetryOOM ladder escalated into the OOC rung.",
    ("op", "mode"))

OOC_PARTITIONS = REGISTRY.counter(
    "tpu_ooc_partitions_total",
    "Spill partitions created by out-of-core join/aggregation passes "
    "(one increment per bucket per pass, recursive re-partitions "
    "included), by operator.",
    ("op",))

OOC_BYTES = REGISTRY.counter(
    "tpu_ooc_bytes_total",
    "Bytes routed through budget-registered spillable partitions by "
    "the out-of-core tier (both join sides, scattered aggregation "
    "partials), by operator — the degraded-but-running volume.",
    ("op",))

OOC_RECURSIONS = REGISTRY.counter(
    "tpu_ooc_recursions_total",
    "Out-of-core buckets that still exceeded the resident window and "
    "re-partitioned recursively with a re-salted hash (key skew), by "
    "operator.  Depth is bounded by sql.ooc.maxDepth; past it the "
    "split-retry ladder owns the remainder.",
    ("op",))


FLEET_FRAMES = REGISTRY.counter(
    "tpu_fleet_frames_total",
    "Heartbeat telemetry frames the supervisor processed into the "
    "fleet-view registry, by outcome: folded = the worker's registry "
    "snapshot merged into the per-worker tpu_fleet_* series, dropped = "
    "the frame was discarded whole (fleet chaos site: ioerror loses "
    "one frame, fatal additionally writes a classified dump; cumulative "
    "set semantics converge on the next beat either way), error = the "
    "snapshot failed to fold (malformed frame) and was skipped.",
    ("outcome",))


# ---------------------------------------------------------------------------
# Fleet-view registry (metrics federation, serving/workers.py).
#
# Worker heartbeat frames carry the worker's full cumulative
# REGISTRY.snapshot(); the supervisor folds each family into this
# SEPARATE registry under the name `tpu_fleet_` + <name minus tpu_> with
# a leading `worker` label.  Separate because (a) the per-worker shape
# (extra label) would collide with the supervisor's own identically-
# named families in one registry, and (b) these families are DYNAMIC —
# whatever the workers publish — so they stay out of the
# REGISTRY.family_names() docs lint.  Cumulative-SET folding makes the
# federation idempotent and self-healing: a dropped frame (fleet chaos
# site) just means the next beat lands the same-or-later totals, and
# per-worker counter series sum EXACTLY to the workers' own registries.
# ---------------------------------------------------------------------------

FLEET = MetricsRegistry(max_series=256)


def fleet_family_name(name: str) -> str:
    """`tpu_serving_x_total` -> `tpu_fleet_serving_x_total`."""
    return "tpu_fleet_" + (name[4:] if name.startswith("tpu_") else name)


def fold_fleet_snapshot(worker: str, snapshot: dict) -> None:
    """Fold one worker's cumulative registry snapshot into FLEET.
    Counters and gauges SET per-worker series; histograms set their
    full bucket state.  A family whose shape conflicts with an earlier
    fold is skipped — federation never raises into the reader loop."""
    for fam in (snapshot or {}).get("families") or ():
        try:
            name = fleet_family_name(fam["name"])
            kind = fam.get("kind") or "gauge"
            labelnames = ("worker",) + tuple(fam.get("labels") or ())
            reg = {"counter": FLEET.counter, "gauge": FLEET.gauge,
                   "histogram": FLEET.histogram}[kind]
            m = reg(name, fam.get("help", ""), labelnames)
        except (ValueError, KeyError, TypeError, AttributeError):
            continue
        for s in fam.get("series") or ():
            labels = dict(s.get("labels") or {})
            labels["worker"] = str(worker)
            try:
                if "value" in s:
                    m.set(s["value"], **labels)
                else:
                    m.set_histogram(s.get("count", 0), s.get("sum", 0.0),
                                    s.get("buckets"), **labels)
            except (TypeError, ValueError):
                continue


def drop_fleet_worker(worker: str) -> None:
    """A worker died: its GAUGE series (point-in-time state — HBM live,
    in-flight) died with the process, so drop them.  Counter and
    histogram series are CUMULATIVE WORK the fleet already did — they
    stay, and a restarted replacement publishes under a fresh worker
    id."""
    w = str(worker)
    for name in FLEET.family_names():
        m = FLEET.get(name)
        if m is None or m.kind != "gauge" or "worker" not in m.labelnames:
            continue
        widx = m.labelnames.index("worker")
        with m._lock:
            for key in [k for k in m._series if k[widx] == w]:
                del m._series[key]


_QUERY_SEQ_LOCK = threading.Lock()
_QUERY_SEQ = 0


def next_query_seq() -> int:
    """Process-monotonic query sequence number — the always-on query id
    the flight recorder tags lifecycle events with (the tracer's own
    query ids only exist when tracing is enabled)."""
    global _QUERY_SEQ
    with _QUERY_SEQ_LOCK:
        _QUERY_SEQ += 1
        return _QUERY_SEQ
