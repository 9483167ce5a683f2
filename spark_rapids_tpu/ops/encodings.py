"""Compressed device-resident execution: the encoding-aware layer.

"GPU Acceleration of SQL Analytics on Compressed Data" (PAPERS.md) shows
operators evaluated IN the encoded domain beat decode-then-compute by
large factors; Flare motivates keeping the whole encoded pipeline inside
one fused program.  This engine already stores strings as dictionary
codes and (since PR 6) keeps encodings alive across joins — but filters,
comparisons and ORDER BY still paid a per-row DECODE gather (a
dictionary-sized remap/rank table read at row capacity), and integer
lanes always rode at full logical width.  This module is the
encoded-execution layer behind ``spark.rapids.tpu.sql.encoded.*``:

  * **Code-space dictionary predicates.**  A literal predicate over a
    dictionary column translates the LITERAL through the dictionary once
    at prepare time (host, dictionary-sized, cached per dictionary
    identity) instead of remapping every row: equality/IN become
    ``code == c`` comparisons, ``<``/``<=`` ranges become one scalar
    rank-bound comparison when the dictionary is ORDER-PRESERVING and
    fall back to a per-dictionary rank-table gather (the decode rung,
    still on device) when it is not.

  * **Order-preserving scan dictionaries.**  The host->device boundary
    sorts each dictionary (columnar/device.py) so codes ARE ranks:
    ORDER BY on dictionary columns skips its rank-table gather
    (ops/sort.py) and range predicates take the scalar-bound path.
    A pure representation change — decoded values are identical.

  * **FOR-narrowed integer lanes.**  Integer/date scan columns whose
    live range fits a smaller signed dtype upload VALUE-PRESERVING
    narrow lanes (no bias: every consumer that widens via a plain
    dtype promotion still computes exact values, so decode is a fused
    ``convert`` sunk to the first consumer that truly needs width).
    Comparisons evaluate in the narrow dtype with runtime range guards
    (plan/expressions.py), and two-narrow-lane arithmetic promotes only
    to the exact width the result needs.

  * **RLE run-domain predicates.**  A run-length-encoded lane evaluates
    a predicate per RUN (run count, not row count) and expands the
    verdict mask by rank search — the bench.py --encodings A/B
    quantifies it against decode-first.

Fallback-safety: every encoded dispatch NEGOTIATES, fires the `kernel`
chaos site (runtime/faults.py), and an
injected OOM sheds the dispatch onto the decoded path bit-identically
(`tpu_encoded_dispatch_total{outcome=oom_shed}`).  With
``encoded.execution.enabled=false`` no encoded path is consulted at all
and plans/results are bit-identical to the pre-encoding engine.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from ..config import (ENCODED_DICT_PREDICATES, ENCODED_DICT_SORT_SCAN,
                      ENCODED_EXECUTION, ENCODED_IN_MAX_CODES,
                      ENCODED_NARROW_LANES, TpuConf)


@dataclasses.dataclass(frozen=True)
class EncodingPolicy:
    """Resolved per-conf encoded-execution decisions (static per query)."""
    enabled: bool
    dict_predicates: bool
    dict_sort_scan: bool
    narrow_lanes: bool
    in_max_codes: int

    @property
    def any_enabled(self) -> bool:
        return self.enabled and (self.dict_predicates or
                                 self.dict_sort_scan or self.narrow_lanes)


NO_ENCODING = EncodingPolicy(False, False, False, False, 0)


def encoding_policy(conf: TpuConf) -> EncodingPolicy:
    """The resolved policy for this conf, cached on the conf instance
    (the disabled path is one dict hit)."""
    pol = conf._cache.get("__encoding_policy")
    if pol is not None:
        return pol
    if not conf.get(ENCODED_EXECUTION):
        pol = NO_ENCODING
    else:
        def mode(entry, auto: bool) -> bool:
            v = str(conf.get(entry)).upper()
            return auto if v == "AUTO" else v == "ON"
        pol = EncodingPolicy(
            enabled=True,
            dict_predicates=mode(ENCODED_DICT_PREDICATES, True),
            dict_sort_scan=bool(conf.get(ENCODED_DICT_SORT_SCAN)),
            narrow_lanes=mode(ENCODED_NARROW_LANES, True),
            in_max_codes=int(conf.get(ENCODED_IN_MAX_CODES)))
    conf._cache["__encoding_policy"] = pol
    return pol


def encoding_discriminant(conf: TpuConf) -> Optional[tuple]:
    """Encoded-execution discriminant for compiled-program / upload cache
    keys: two confs whose RESOLVED policies differ must never share an
    executable or a device upload (the encoded representation changes
    lane dtypes and dictionary order).  None when fully off — the key
    stays byte-identical to pre-encoding builds."""
    p = encoding_policy(conf)
    if not p.any_enabled:
        return None
    return ("enc", p.dict_predicates, p.dict_sort_scan, p.narrow_lanes,
            p.in_max_codes)


# ---------------------------------------------------------------------------
# Dispatch bookkeeping: metrics + the `kernel` chaos site (fallback rung)
# ---------------------------------------------------------------------------

def count_dispatch(site: str, outcome: str = "encoded") -> None:
    from ..obs.registry import ENCODED_DISPATCH
    ENCODED_DISPATCH.inc(site=site, outcome=outcome)


def count_decode(site: str, nbytes: int) -> None:
    """One emitted decode pass (rank/remap gather, full-width widen)."""
    from ..obs.registry import DECODE_BYTES, ENCODED_DISPATCH
    DECODE_BYTES.inc(int(nbytes), site=site)
    ENCODED_DISPATCH.inc(site=site, outcome="decode")


def elect_encoded(conf: TpuConf, site: str) -> bool:
    """Final election for one encoded dispatch: fires the existing
    `kernel` chaos site (kernel=<site> names the encoded dispatch in the
    injected-fault record).  An injected OOM there is the shed signal —
    the dispatch falls back to the DECODED tier bit-identically
    (outcome=oom_shed) instead of failing the query; fatal/error kinds
    propagate to their usual recovery ladders."""
    from ..runtime.faults import get_active_injector, get_injector
    inj = get_injector(conf)
    if not inj.enabled:
        inj = get_active_injector()
    if inj.enabled:
        from ..runtime.memory import TpuRetryOOM
        try:
            inj.fire("kernel", kernel=site, mode="encoded")
        except TpuRetryOOM:
            count_dispatch(site, "oom_shed")
            from ..obs.tracer import get_active
            get_active().instant("kernel_fallback", "runtime", kernel=site,
                                 reason="oom")
            return False
    count_dispatch(site)
    return True


# ---------------------------------------------------------------------------
# Dictionary utilities (host side, cached per dictionary identity)
# ---------------------------------------------------------------------------
# The SAME pa.Array dictionary object flows through every batch of a
# scan, so O(dictionary) host work — orderedness checks, literal code
# lookups, rank tables — is computed once per dictionary.  Entries pin
# the dictionary so id() reuse cannot alias a stale hit.  All caches
# share one lock: the serving plane prepares plans concurrently, and a
# half-built entry must never be observable (the remap_codes_into race).

_DICT_META_LOCK = threading.RLock()
_ORDERED_CACHE: dict = {}
_UNIQUE_CACHE: dict = {}
_LITERAL_CODE_CACHE: dict = {}
_RANK_BOUND_CACHE: dict = {}
_RANK_TABLE_CACHE: dict = {}


def clear_dict_caches() -> None:
    with _DICT_META_LOCK:
        for c in (_ORDERED_CACHE, _UNIQUE_CACHE, _LITERAL_CODE_CACHE,
                  _RANK_BOUND_CACHE, _RANK_TABLE_CACHE):
            c.clear()


def _cache_get(cache: dict, key, pin):
    hit = cache.get(key)
    if hit is not None and hit[0] is pin:
        return hit
    return None


def _cache_put(cache: dict, key, pin, value) -> None:
    if len(cache) > 4096:
        cache.clear()
    cache[key] = (pin, value)


def is_ordered_dict(d: Optional[pa.Array]) -> bool:
    """True when the dictionary is STRICTLY increasing in Spark string
    order (unicode code points == UTF-8 byte order): codes are then
    rank-equivalent, so code comparisons ARE value comparisons."""
    if d is None:
        return False
    if len(d) <= 1:
        return True
    with _DICT_META_LOCK:
        hit = _cache_get(_ORDERED_CACHE, id(d), d)
        if hit is not None:
            return hit[1]
        s = d.cast(pa.string())
        ordered = bool(pc.all(
            pc.less(s.slice(0, len(s) - 1), s.slice(1))).as_py())
        _cache_put(_ORDERED_CACHE, id(d), d, ordered)
        return ordered


def is_unique_dict(d: Optional[pa.Array]) -> bool:
    """Duplicate-free dictionary: value equality == code equality, the
    legality gate for code-space equality/IN (a COMPUTED dictionary —
    e.g. a substring projection's — may repeat values, and a single
    translated code would miss the duplicates' rows)."""
    if d is None:
        return False
    if len(d) <= 1:
        return True
    with _DICT_META_LOCK:
        hit = _cache_get(_UNIQUE_CACHE, id(d), d)
        if hit is not None:
            return hit[1]
        u = len(pc.unique(d.cast(pa.string()))) == len(d)
        _cache_put(_UNIQUE_CACHE, id(d), d, u)
        return u


#: literal-absent sentinel: never equals a valid code (>= 0) and never
#: equals the -1 "string absent from target dictionary" remap marker
ABSENT_CODE = -2


def literal_code(d: Optional[pa.Array], value: str) -> int:
    """Code of `value` in the dictionary, or ABSENT_CODE.  One host
    lookup per (dictionary identity, value) — the prepare-time literal
    translation code-space equality predicates ride on."""
    if d is None or len(d) == 0:
        return ABSENT_CODE
    key = (id(d), value)
    with _DICT_META_LOCK:
        hit = _cache_get(_LITERAL_CODE_CACHE, key, d)
        if hit is not None:
            return hit[1]
        idx = pc.index(d.cast(pa.string()), pa.scalar(value)).as_py()
        code = ABSENT_CODE if idx is None or idx < 0 else int(idx)
        _cache_put(_LITERAL_CODE_CACHE, key, d, code)
        return code


def rank_bounds(d: Optional[pa.Array], value: str):
    """(count_less, count_less_eq) of `value` against the dictionary's
    entries in Spark string order — the scalar bounds range predicates
    compare ranks (or, for an ordered dictionary, codes) against:
        col <  value  <=>  rank(col) <  count_less
        col <= value  <=>  rank(col) <  count_less_eq
    """
    if d is None or len(d) == 0:
        return 0, 0
    key = (id(d), value)
    with _DICT_META_LOCK:
        hit = _cache_get(_RANK_BOUND_CACHE, key, d)
        if hit is not None:
            return hit[1]
        s = d.cast(pa.string())
        less = int(pc.sum(pc.less(s, pa.scalar(value)),
                          min_count=0).as_py() or 0)
        leq = int(pc.sum(pc.less_equal(s, pa.scalar(value)),
                         min_count=0).as_py() or 0)
        _cache_put(_RANK_BOUND_CACHE, key, d, (less, leq))
        return less, leq


def rank_table(d: Optional[pa.Array]) -> np.ndarray:
    """ranks[code] -> rank of the code's string in the sorted dictionary
    (ops/sort.dictionary_ranks), cached per identity — the decode rung
    for range predicates over UNORDERED dictionaries."""
    if d is None or len(d) == 0:
        return np.zeros(1, np.int32)
    with _DICT_META_LOCK:
        hit = _cache_get(_RANK_TABLE_CACHE, id(d), d)
        if hit is not None:
            return hit[1]
        from .sort import dictionary_ranks
        ranks = dictionary_ranks(d)
        _cache_put(_RANK_TABLE_CACHE, id(d), d, ranks)
        return ranks


def sort_dictionary_encode(arr: pa.Array):
    """Dictionary-encode an arrow string array with an ORDER-PRESERVING
    (sorted, duplicate-free) dictionary: -> (codes int32 np array with
    nulls as 0, dictionary pa.StringArray, null mask np bool).  The
    host->device boundary's encoded upload (columnar/device.py)."""
    if not pa.types.is_dictionary(arr.type):
        arr = pc.dictionary_encode(arr)
    d = arr.dictionary.cast(pa.string())
    codes_arr = arr.indices.fill_null(0) if arr.null_count else arr.indices
    codes = codes_arr.to_numpy(zero_copy_only=False).astype(np.int32)
    if len(d) == 0:
        return codes, d, None
    order = pc.sort_indices(d).to_numpy(zero_copy_only=False)
    sorted_d = d.take(pa.array(order, pa.int64()))
    # arrow dictionary_encode already dedupes, so sorted == strictly
    # increasing; remap codes through the inverse permutation
    remap = np.empty(len(d), np.int32)
    remap[order] = np.arange(len(d), dtype=np.int32)
    return remap[codes], sorted_d, None


# ---------------------------------------------------------------------------
# FOR-narrowed integer lanes (value-preserving dtype demotion)
# ---------------------------------------------------------------------------
# No bias: the narrow lane holds the exact values, so ANY consumer that
# widens via a plain dtype promotion (expression casts, concat dtype
# promotion, astype in hashing/sort/host-fetch) computes exact results —
# correctness never depends on the encoding metadata, which is why the
# legality pass can stay an optimization, not a safety requirement.

_NARROW_STEPS = {8: (np.int8, np.int16, np.int32),
                 4: (np.int8, np.int16),
                 2: (np.int8,)}


def narrow_np_dtype(lo: int, hi: int, base: np.dtype):
    """Smallest signed dtype (< base width) exactly holding [lo, hi],
    or None when no narrowing applies."""
    base = np.dtype(base)
    if base.kind != "i" or base.itemsize not in _NARROW_STEPS:
        return None
    for cand in _NARROW_STEPS[base.itemsize]:
        info = np.iinfo(cand)
        if info.min <= lo and hi <= info.max:
            return np.dtype(cand)
    return None


def narrow_widths(itemsize_a: int, itemsize_b: int, op: str) -> int:
    """Itemsize (bytes) that EXACTLY represents op over two signed
    integer lanes: add/sub need one extra bit (double the wider side),
    mul needs the sum of the widths.  The overflow-checked promotion
    rule narrow arithmetic uses — dtype-only, so compiled programs keyed
    on lane dtypes stay value-agnostic."""
    if op == "mul":
        need = itemsize_a + itemsize_b
    else:
        need = 2 * max(itemsize_a, itemsize_b)
    w = 1
    while w < need:
        w *= 2
    return w


_SIGNED_BY_SIZE = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32, 8: jnp.int64}


def exact_arith_dtype(a_dtype, b_dtype, op: str, logical_dtype):
    """jnp dtype for exact narrow arithmetic, or None when the exact
    width is not narrower than the logical compute dtype (promote as
    usual — the 'only when the live range requires it' rule)."""
    a, b = np.dtype(a_dtype), np.dtype(b_dtype)
    if a.kind != "i" or b.kind != "i":
        return None
    logical = np.dtype(logical_dtype)
    if logical.kind != "i":
        return None
    w = narrow_widths(a.itemsize, b.itemsize, op)
    if w >= logical.itemsize or w > 8:
        return None
    return _SIGNED_BY_SIZE[w]


# ---------------------------------------------------------------------------
# RLE run-domain predicates (the bench --encodings A/B primitive)
# ---------------------------------------------------------------------------

def rle_predicate_mask(values: jnp.ndarray, lengths: jnp.ndarray,
                       n: int, pred) -> jnp.ndarray:
    """Row mask of `pred` over an RLE lane WITHOUT decoding: the
    predicate evaluates per RUN (run count, not row count) and the
    verdict expands to rows by rank search against the run ends —
    gathers a bool per row from a runs-sized table instead of
    materializing the decoded value lane first."""
    verdict = pred(values)
    ends = jnp.cumsum(lengths.astype(jnp.int32))
    rows = jnp.arange(n, dtype=jnp.int32)
    run_of_row = jnp.searchsorted(ends, rows, side="right")
    run_of_row = jnp.clip(run_of_row, 0, values.shape[0] - 1)
    in_range = rows < ends[-1]
    return jnp.take(verdict, run_of_row) & in_range


# ---------------------------------------------------------------------------
# Narrow-domain comparison (runtime range guards)
# ---------------------------------------------------------------------------

def narrow_compare(symbol: str, narrow_lane: jnp.ndarray,
                   wide_other: jnp.ndarray) -> jnp.ndarray:
    """Compare a FOR-narrowed lane against a full-width lane WITHOUT
    widening the rows: the wide side (a literal broadcast — possibly a
    lifted runtime scalar, so the guards must be data, not trace-time
    branches) casts DOWN into the narrow dtype, with range guards
    supplying the answer wherever the cast would wrap.  Exact for every
    int64 value of the wide side."""
    info = np.iinfo(np.dtype(narrow_lane.dtype))
    lo = jnp.asarray(info.min, wide_other.dtype)
    hi = jnp.asarray(info.max, wide_other.dtype)
    below = wide_other < lo          # other smaller than every lane value
    above = wide_other > hi          # other larger than every lane value
    dn = jnp.clip(wide_other, lo, hi).astype(narrow_lane.dtype)
    if symbol == "=":
        core, if_below, if_above = narrow_lane == dn, False, False
    elif symbol == "!=":
        core, if_below, if_above = narrow_lane != dn, True, True
    elif symbol == "<":
        core, if_below, if_above = narrow_lane < dn, False, True
    elif symbol == "<=":
        core, if_below, if_above = narrow_lane <= dn, False, True
    elif symbol == ">":
        core, if_below, if_above = narrow_lane > dn, True, False
    elif symbol == ">=":
        core, if_below, if_above = narrow_lane >= dn, True, False
    else:
        raise ValueError(f"narrow_compare: unknown symbol {symbol!r}")
    out = jnp.where(below, jnp.asarray(if_below, bool),
                    jnp.where(above, jnp.asarray(if_above, bool), core))
    return out


def common_narrow_dtype(a_dtype, b_dtype):
    """Widest of two signed narrow dtypes (value-preserving common
    compare dtype), or None when either side is not a narrow int."""
    a, b = np.dtype(a_dtype), np.dtype(b_dtype)
    if a.kind != "i" or b.kind != "i":
        return None
    return _SIGNED_BY_SIZE[max(a.itemsize, b.itemsize)]
