"""Hand-written Pallas kernel tier — the libcudf-equivalent layer.

The reference engine leans on libcudf device kernels (hash joins, hash
group-by, stream compaction); this engine's portable tier re-expresses
those as sorts + segmented scans because XLA's TPU lowering favors
them.  PR 9's attribution plane showed where that trade loses: the
join/shuffle-heavy tail (q3/q9/q15-class) spends its device time in the
sort-based probe (`ops/join._merge_rank` — two 2-operand sorts of
build+probe rows per probe op) and in keep-mask argsorts.  This package
is the hand-written kernel tier for exactly those segments
(`spark.rapids.tpu.sql.kernels.pallas.enabled` + per-kernel modes):

  * `hashjoin`  — murmur3 open-addressing hash table (hash-ordered
    layout, duplicate keys consecutive) + probe kernels gridded over
    probe blocks; emits the same gather-map/match-flag contract as the
    sorted probe, so late materialization, semi/anti/outer variants and
    dictionary-code keys ride through unchanged.
  * `segagg`    — bounded-domain segmented aggregation: block-local
    accumulate (one-hot MXU matmuls for sums/counts, masked VPU
    reductions for MIN/MAX/FIRST/LAST/ANY/EVERY) + one combine, no sort
    and no scatter, operating directly on dictionary codes and
    FOR-narrowed integer lanes.
  * `compact`   — selection compaction: blocked prefix sum + per-slot
    rank search replaces the stable keep-mask argsort.

Dispatch philosophy (fallback-safe): the sort-based tier stays intact
and every dispatch point NEGOTIATES — single exact key lane, domain and
build-size bounds, backend support, float-exactness — then counts the
decision in `tpu_kernel_dispatch_total` / `tpu_kernel_fallback_total`.
On backends without native Pallas lowering the kernels run under
`interpret=True`: the kernel bodies execute as discharged XLA ops
inside the same traced program, so tier-1 and the CPU container
exercise the REAL probe/accumulate/compact logic.  The `kernel` chaos
site fires at each election; an injected OOM there sheds the operator
onto the sort tier bit-identically (the fallback rung), a fatal
surfaces as a classified dump whose injected-fault record names the
kernel.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from ...config import (PALLAS_COMPACT, PALLAS_ENABLED, PALLAS_INTERPRET,
                       PALLAS_JOIN, PALLAS_JOIN_DENSE_REPLACE,
                       PALLAS_JOIN_MAX_BUILD, PALLAS_SEGAGG,
                       PALLAS_SEGAGG_MAX_DOMAIN, TpuConf)


@dataclasses.dataclass(frozen=True)
class KernelTier:
    """Resolved per-conf kernel-tier decisions (static for a query)."""
    join: bool
    segagg: bool
    compact: bool
    interpret: bool
    segagg_max_domain: int
    join_max_build: int
    join_dense_replace: str = "AUTO"   # AUTO | ON | OFF

    @property
    def mode(self) -> str:
        return "interpret" if self.interpret else "compiled"

    @property
    def any_enabled(self) -> bool:
        return self.join or self.segagg or self.compact


NO_TIER = KernelTier(False, False, False, False, 0, 0, "OFF")


def _native_backend() -> bool:
    """Whether pl.pallas_call lowers natively here (Mosaic: TPU only)."""
    return jax.default_backend() == "tpu"


def kernel_tier(conf: TpuConf) -> KernelTier:
    """The resolved tier for this conf, cached on the conf instance (one
    resolution per query plan; the disabled path is one dict hit)."""
    tier = conf._cache.get("__pallas_tier")
    if tier is not None:
        return tier
    tier = _resolve_tier(conf)
    conf._cache["__pallas_tier"] = tier
    return tier


def _resolve_tier(conf: TpuConf) -> KernelTier:
    if not conf.get(PALLAS_ENABLED):
        return NO_TIER
    native = _native_backend()
    imode = str(conf.get(PALLAS_INTERPRET)).upper()
    interpret = (not native) if imode == "AUTO" else imode == "ON"
    if not native and not interpret:
        # no native lowering and interpretation forbidden: the tier
        # cannot run anywhere on this backend
        from ...obs.registry import KERNEL_FALLBACK
        KERNEL_FALLBACK.inc(kernel="tier", reason="backend")
        return NO_TIER

    def mode(entry, auto: bool) -> bool:
        v = str(conf.get(entry)).upper()
        return auto if v == "AUTO" else v == "ON"

    # join/compact win on every backend (the interpreted kernels beat
    # the sort path on XLA-CPU too — measured in bench.py --kernels);
    # segagg's block accumulators only pay off where Pallas compiles
    # natively (XLA-CPU scatters are fast)
    return KernelTier(
        join=mode(PALLAS_JOIN, True),
        segagg=mode(PALLAS_SEGAGG, native and not interpret),
        compact=mode(PALLAS_COMPACT, True),
        interpret=interpret,
        segagg_max_domain=int(conf.get(PALLAS_SEGAGG_MAX_DOMAIN)),
        join_max_build=int(conf.get(PALLAS_JOIN_MAX_BUILD)),
        join_dense_replace=str(conf.get(PALLAS_JOIN_DENSE_REPLACE))
        .upper())


def tier_discriminant(conf: TpuConf) -> Optional[tuple]:
    """Kernel-tier discriminant for compiled-program cache keys
    (exec/compiled.py plan_structure_key): two confs whose RESOLVED
    tiers differ must never share an executable — in particular a
    persistent-cache entry compiled with kernels on must not cross-load
    into a kernels-off session or vice versa.  None when the tier is
    fully off (the key stays byte-identical to pre-tier builds)."""
    t = kernel_tier(conf)
    if not t.any_enabled:
        return None
    return ("pallas", t.join, t.segagg, t.compact, t.interpret,
            t.segagg_max_domain, t.join_max_build, t.join_dense_replace)


# ---------------------------------------------------------------------------
# Dispatch bookkeeping: metrics + the `kernel` chaos site
# ---------------------------------------------------------------------------

def _count_dispatch(kernel: str, tier: KernelTier) -> None:
    from ...obs.registry import KERNEL_DISPATCH
    KERNEL_DISPATCH.inc(kernel=kernel, mode=tier.mode)


def count_fallback(kernel: str, reason: str) -> None:
    from ...obs.registry import KERNEL_FALLBACK
    KERNEL_FALLBACK.inc(kernel=kernel, reason=reason)


def elect(conf: TpuConf, tier: KernelTier, kernel: str) -> bool:
    """Final election step for one operator dispatch onto `kernel`:
    fires the `kernel` chaos site (the injected-fault record names the
    kernel) and counts the dispatch.  An injected OOM at the site is
    the shed signal: the operator falls back to the sort-based tier
    bit-identically — returns False, counted as reason='oom' — instead
    of failing the query (the fallback rung the chaos suite asserts).
    Fatal/error/ioerror kinds propagate to their usual recovery
    ladders (a fatal becomes a classified dump naming the kernel)."""
    from ...runtime.faults import get_active_injector, get_injector
    from ...runtime.memory import TpuRetryOOM
    inj = get_injector(conf)
    if not inj.enabled:
        inj = get_active_injector()
    try:
        inj.fire("kernel", kernel=kernel, mode=tier.mode)
    except TpuRetryOOM:
        count_fallback(kernel, "oom")
        from ...obs.tracer import get_active
        get_active().instant("kernel_fallback", "runtime", kernel=kernel,
                             reason="oom")
        return False
    _count_dispatch(kernel, tier)
    return True


# ---------------------------------------------------------------------------
# Per-family election gates (the legality negotiations)
# ---------------------------------------------------------------------------

def elect_join(conf: TpuConf, build_capacity: int,
               dense_span: Optional[int] = None) -> Optional[KernelTier]:
    """The hash-probe join election visible at exec level: tier on,
    join family on, build side small enough to table, and — when the
    join ALSO qualifies for a dense direct-address table over
    `dense_span` keys — the denseReplace policy: AUTO replaces the
    dense table only when span > 4x build capacity (where the dense
    build's span-sized sorts dominate; below it the dense one-gather
    probes win).  Lane-count legality finishes inside
    ops.join.BuildTable (the canonical lane set is only known there)."""
    tier = kernel_tier(conf)
    if not tier.join:
        return None
    if build_capacity > tier.join_max_build:
        count_fallback("hash_probe_join", "build_too_large")
        return None
    if dense_span is not None:
        mode = tier.join_dense_replace
        replace = (mode == "ON") or (
            mode == "AUTO" and dense_span > 4 * build_capacity)
        if not replace:
            count_fallback("hash_probe_join", "dense_domain")
            return None
    if not elect(conf, tier, "hash_probe_join"):
        return None
    return tier


def elect_segagg(conf: TpuConf, total_domain: int,
                 has_float_sum: bool) -> Optional[KernelTier]:
    """Segmented-aggregation election: tier on, segagg family on, the
    packed key domain fits the block accumulator, and float sums are
    allowed to re-associate (variableFloatAgg — block-parallel partial
    sums combine in a different order than the sorted-run scan)."""
    tier = kernel_tier(conf)
    if not tier.segagg:
        return None
    if total_domain > tier.segagg_max_domain:
        count_fallback("segagg", "domain_too_large")
        return None
    if has_float_sum:
        from ...config import IMPROVED_FLOAT_OPS
        if not conf.get(IMPROVED_FLOAT_OPS):
            count_fallback("segagg", "float_exact")
            return None
    if not elect(conf, tier, "segagg"):
        return None
    return tier


def elect_compact(conf: TpuConf, capacity: int) -> Optional[KernelTier]:
    """Compaction election: tier on, compact family on, capacity large
    enough that the rank-search beats the argsort's fixed cost."""
    tier = kernel_tier(conf)
    if not tier.compact or capacity < 1024:
        return None
    if not elect(conf, tier, "compact"):
        return None
    return tier
