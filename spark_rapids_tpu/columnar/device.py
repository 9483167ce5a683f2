"""Device-side columnar batches for TPU execution.

Role of GpuColumnVector/ColumnarBatch in the reference (GpuColumnVector.java),
re-designed for XLA's compilation model instead of translated:

  * **Static-shape row bucketing.** XLA compiles one program per shape, so a
    per-batch dynamic row count would blow up the jit cache (SURVEY §7 hard
    part (f)).  Every device column is padded to a *capacity* drawn from a
    small geometric set of buckets; the logical `num_rows` travels alongside
    as data (a scalar passed into kernels), never as a shape.  Kernels mask
    rows `>= num_rows` out of every reduction/aggregation.

  * **Validity as a bool lane.** Spark's three-valued null semantics are
    carried as a dense bool array per column (True = valid).  Padding rows are
    invalid.  This fuses freely with elementwise compute on the VPU.

  * **Strings as dictionary codes.** TPUs have no ragged tensors; string
    columns are dictionary-encoded at the host boundary (int32 codes on
    device + a host-side pyarrow dictionary).  Equality/ordering/hash/groupby
    run on codes (order via a host-computed rank permutation of the sorted
    dictionary); byte-level kernels get (offsets, bytes) tensors on demand
    (ops/strings.py).

  * **Decimal(≤18,s) as int64 unscaled lanes**; wide decimal (>18) is a
    (hi, lo) int64 pair (TPU has no int128) — see ops/decimal.py.

  * **DOUBLE stored as int64 bit patterns.** TPUs emulate f64 as a
    float32-pair (double-double, ~48-bit mantissa, f32 exponent range), so
    device transfers of raw f64 are lossy (measured: 1e300 -> inf).  Columns
    that merely pass through the device must survive bit-exactly, so DOUBLE's
    physical lane is the int64 bitcast; kernels bitcast to f64 only when
    actually computing (ops/kernels.py compute_view).  Compute results carry
    the emulation's reduced precision — a documented deviation, same spirit
    as the reference's float notes in docs/compatibility.md.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as t
from ..config import TpuConf, DEFAULT_CONF
from .host import HostBatch, dtype_to_arrow


def merge_origin(origins) -> str:
    """Provenance of data merged from several batches/files: the single
    shared file, or "" for mixed/unknown (input_file_name contract)."""
    s = {o or "" for o in origins}
    return s.pop() if len(s) == 1 else ""


def bucket_capacity(n: int, conf: TpuConf = DEFAULT_CONF) -> int:
    """Smallest static-shape bucket >= n.

    An explicit `spark.rapids.tpu.sql.shape.buckets` set wins when
    configured: capacities quantize onto exactly that list (doubling
    past its largest entry), so one compiled program serves every input
    size inside a bucket and cross-scale-factor runs land on the same
    shapes — the compile-cache hit the persistent cache needs.

    Otherwise buckets grow geometrically (x growth) up to batchSizeRows,
    then x2 above it to halve worst-case padding waste: batches above
    the target size are expected to be split upstream (coalesce/retry
    machinery), so the >target regime only exists transiently.
    """
    explicit = conf.bucket_set
    if explicit:
        for cap in explicit:
            if cap >= n:
                return cap
        cap = explicit[-1]
        while cap < n:
            cap *= 2
        return cap
    cap = conf.bucket_min_rows
    growth = conf.bucket_growth
    target = conf.batch_size_rows
    while cap < n:
        cap *= growth if cap < target else 2
    return cap


@dataclasses.dataclass
class DeviceColumn:
    """One column on device: padded data lane + validity lane.

    data      : jnp array, shape (capacity,) in the physical dtype
                (types.physical_np_dtype); strings are int32 dictionary codes.
    validity  : jnp bool array, shape (capacity,); padding rows are False.
    dtype     : logical Spark type.
    dictionary: host pyarrow array of unique values for STRING columns
                (codes index into it); None otherwise.
    data_hi   : high int64 lane for wide decimals; None otherwise.

    RAGGED (ARRAY<primitive>) columns — the SURVEY §7c values+offsets
    dual-tensor design (reference nested cuDF LIST columns,
    GpuColumnVector.java type mapping):
    offsets   : int32, shape (row_capacity + 1,); row i's elements are
                data[offsets[i]:offsets[i+1]].  Null/padding rows carry
                empty spans.  When set, `data` is the flat VALUES lane
                (its own value-capacity bucket) and `validity` stays the
                per-ROW null mask with shape (row_capacity,).
    elem_valid: bool per VALUE (null elements); same shape as data.
    """
    data: jax.Array
    validity: jax.Array
    dtype: t.DataType
    dictionary: Optional[pa.Array] = None
    data_hi: Optional[jax.Array] = None
    offsets: Optional[jax.Array] = None
    elem_valid: Optional[jax.Array] = None
    # ENCODED-lane metadata (ops/encodings.py, informational only —
    # correctness NEVER depends on it): ("for", lo, hi) marks a
    # VALUE-PRESERVING narrowed integer lane (data dtype smaller than
    # the logical physical dtype, values exact, live range [lo, hi]);
    # ("dict_sorted",) marks an order-preserving dictionary upload.
    # Paths that rebuild columns may drop it freely: every consumer
    # either understands narrow lanes or widens via plain dtype
    # promotion, which is exact.
    enc: Optional[tuple] = None

    @property
    def capacity(self) -> int:
        if self.offsets is not None:
            return self.offsets.shape[0] - 1
        return self.data.shape[0]

    @property
    def value_capacity(self) -> int:
        """Flat values-lane capacity of a ragged column."""
        return self.data.shape[0]

    def nbytes(self) -> int:
        n = self.data.size * self.data.dtype.itemsize + self.validity.size
        if self.data_hi is not None:
            n += self.data_hi.size * 8
        if self.offsets is not None:
            n += self.offsets.size * 4
        if self.elem_valid is not None:
            n += self.elem_valid.size
        return n

    def with_dtype(self, dtype: t.DataType) -> "DeviceColumn":
        return dataclasses.replace(self, dtype=dtype)


@dataclasses.dataclass
class DeviceBatch:
    """A batch of device columns sharing one capacity and logical row count.

    `num_rows` is either a host int or a 0-d jax int scalar: operators whose
    output count is data-dependent (filter, join) leave it on device so
    chained device work never stalls on a D2H sync; host-side consumers
    coerce with `int(db.num_rows)` (one sync) when they truly need the value
    (coalesce sizing, limits, final collect)."""
    columns: List[DeviceColumn]
    num_rows: object   # int | jax.Array 0-d
    names: List[str]
    # scan provenance for input_file_name (GpuInputFileBlock role):
    # "" = unknown / non-file source / mixed files
    origin_file: str = ""
    # LAZY SELECTION VECTOR (the cuDF gather-map-deferred idea,
    # JoinGatherer.scala role): when set, live rows are `sel`-True rows,
    # NOT a front prefix, and num_rows is their (device) count.  Row
    # gathers are the dominant device cost on TPU (~20ms per pass at
    # 1M), so a join feeding a mask-aware consumer (aggregation live
    # mask, another join's probe liveness) skips its output compaction
    # entirely.  Prefix-assuming operators (fetch, concat, slicing)
    # compact on entry via ops.batch_ops.ensure_prefix.
    sel: object = None   # Optional[jax.Array]
    # LATE-MATERIALIZATION state (columnar/lanes.py ThinState): when
    # set, columns listed in thin.pending are ZERO-capacity placeholders
    # backed by (source batch, row-id lane) pairs; sinks resolve them
    # with one composed gather per source via lanes.materialize_batch.
    thin: object = None  # Optional[lanes.ThinState]

    @property
    def capacity(self) -> int:
        if self.thin is not None:
            return self.thin.capacity
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def schema(self) -> t.StructType:
        return t.StructType([t.StructField(n, c.dtype)
                             for n, c in zip(self.names, self.columns)])

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]

    def column_by_name(self, name: str) -> DeviceColumn:
        return self.columns[self.names.index(name)]

    def select(self, indices: Sequence[int]) -> "DeviceBatch":
        return DeviceBatch([self.columns[i] for i in indices], self.num_rows,
                           [self.names[i] for i in indices],
                           self.origin_file, sel=self.sel,
                           thin=None if self.thin is None
                           else self.thin.select(indices))

    def nbytes(self) -> int:
        n = sum(c.nbytes() for c in self.columns)
        if self.thin is not None:
            n += self.thin.nbytes()
        return n

    def row_mask(self) -> jax.Array:
        """Bool mask of logically-live rows: the selection vector when
        present, else True for row < num_rows (prefix liveness)."""
        if self.sel is not None:
            return self.sel
        return jnp.arange(self.capacity, dtype=jnp.int32) < jnp.int32(self.num_rows)

    def __repr__(self):
        return (f"DeviceBatch(rows={self.num_rows}/cap={self.capacity}, "
                f"{self.schema.simple_string})")


# ---------------------------------------------------------------------------
# Decimal128 buffer plumbing (narrow decimals ride as int64 unscaled values)
# ---------------------------------------------------------------------------

def _decimal128_lanes(arr: pa.Array) -> np.ndarray:
    """(n, 2) uint64 [lo, hi] little-endian lanes of a decimal128 array."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    buf = arr.buffers()[1]
    words = np.frombuffer(buf, dtype=np.uint64)
    words = words[arr.offset * 2: (arr.offset + len(arr)) * 2]
    return words.reshape(-1, 2)


def _decimal128_from_unscaled(unscaled: np.ndarray, validity: np.ndarray,
                              dt: t.DecimalType) -> pa.Array:
    lo = unscaled.astype(np.int64).view(np.uint64)
    hi = np.where(unscaled < 0, np.uint64(0xFFFFFFFFFFFFFFFF), np.uint64(0))
    lanes = np.empty((len(unscaled), 2), dtype=np.uint64)
    lanes[:, 0] = lo
    lanes[:, 1] = hi
    validity_buf = pa.py_buffer(np.packbits(validity, bitorder="little").tobytes())
    data_buf = pa.py_buffer(lanes.tobytes())
    return pa.Array.from_buffers(pa.decimal128(dt.precision, dt.scale),
                                 len(unscaled), [validity_buf, data_buf])


# ---------------------------------------------------------------------------
# Host -> device (the RowToColumnar / HostColumnarToGpu analogue)
# ---------------------------------------------------------------------------

def _pad(np_arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    if len(np_arr) == capacity:
        return np_arr
    out = np.full(capacity, fill, dtype=np_arr.dtype)
    out[: len(np_arr)] = np_arr
    return out


def _arrow_column_to_device(arr: pa.Array, dt: t.DataType, capacity: int,
                            device=None, policy=None,
                            narrow_ok: bool = False) -> DeviceColumn:
    """`policy` (ops/encodings.EncodingPolicy) turns on the ENCODED
    upload forms: order-preserving (sorted) dictionaries for strings and
    — when `narrow_ok` (negotiated per scan column by
    plan/overrides._negotiate_encoded) — value-preserving FOR-narrowed
    integer lanes.  None keeps the pre-encoding representation
    bit-identically."""
    import pyarrow.compute as pc
    n = len(arr)
    validity_np = np.zeros(capacity, dtype=bool)
    if n:
        validity_np[:n] = pc.is_valid(arr).to_numpy(zero_copy_only=False)

    if isinstance(dt, t.ArrayType):
        return _arrow_list_to_device(arr, dt, capacity, validity_np, device,
                                     policy)

    dictionary = None
    hi_np = None
    enc = None
    if isinstance(dt, t.StringType):
        if policy is not None and policy.dict_sort_scan:
            from ..ops.encodings import (count_dispatch, is_ordered_dict,
                                         sort_dictionary_encode)
            codes_np, dictionary, _m = sort_dictionary_encode(arr)
            data_np = _pad(codes_np, capacity)
            if len(dictionary):
                # publish orderedness under the identity pin so later
                # prepare-time checks are one dict hit
                is_ordered_dict(dictionary)
            enc = ("dict_sorted",)
            count_dispatch("dict_sort_upload")
        else:
            if not pa.types.is_dictionary(arr.type):
                arr = pc.dictionary_encode(arr)
            codes_arr = arr.indices.fill_null(0) if arr.null_count \
                else arr.indices
            data_np = _pad(
                codes_arr.to_numpy(zero_copy_only=False).astype(np.int32),
                capacity)
            dictionary = arr.dictionary.cast(pa.string())
    elif isinstance(dt, t.DecimalType):
        if dt.is_wide:
            lanes = _decimal128_lanes(arr)
            data_np = _pad(lanes[:, 0].view(np.int64), capacity)
            # hi lane needs sign-correct padding of 0 which is fine (value 0)
            hi_np = _pad(lanes[:, 1].view(np.int64), capacity)
        else:
            lanes = _decimal128_lanes(arr)
            data_np = _pad(lanes[:, 0].view(np.int64), capacity)
    elif isinstance(dt, t.TimestampType):
        a = arr.cast(pa.timestamp("us", tz="UTC")).cast(pa.int64())
        a = a.fill_null(0) if a.null_count else a
        data_np = _pad(a.to_numpy(zero_copy_only=False), capacity)
    elif isinstance(dt, t.DateType):
        a = arr.cast(pa.int32())
        a = a.fill_null(0) if a.null_count else a
        data_np = _pad(a.to_numpy(zero_copy_only=False), capacity)
    elif isinstance(dt, t.NullType):
        data_np = np.zeros(capacity, dtype=np.int32)
    elif isinstance(dt, t.DoubleType):
        a = arr.fill_null(0) if arr.null_count else arr
        f64 = a.to_numpy(zero_copy_only=False).astype(np.float64, copy=False)
        data_np = _pad(f64.view(np.int64), capacity)
    else:
        np_dt = t.physical_np_dtype(dt)
        a = arr.fill_null(False if np_dt == np.bool_ else 0) if arr.null_count else arr
        data_np = _pad(a.to_numpy(zero_copy_only=False).astype(np_dt, copy=False),
                       capacity)

    # FOR-narrowing (value-preserving): integer-family lanes whose live
    # range fits a smaller signed dtype upload narrow — fewer H2D bytes,
    # narrow-domain predicates/arithmetic — and widen exactly via plain
    # dtype promotion wherever full width is needed.  DOUBLE's int64
    # lane is a BITCAST (never narrowed); string codes stay int32.
    if (policy is not None and policy.narrow_lanes and narrow_ok and
            enc is None and hi_np is None and n and
            data_np.dtype.kind == "i" and
            not isinstance(dt, (t.DoubleType, t.StringType, t.NullType))):
        live = data_np[:n][validity_np[:n]]
        if live.size:
            from ..ops.encodings import count_dispatch, narrow_np_dtype
            lo_v, hi_v = int(live.min()), int(live.max())
            ndt = narrow_np_dtype(min(lo_v, 0), max(hi_v, 0),
                                  data_np.dtype)
            if ndt is not None:
                data_np = data_np.astype(ndt)
                enc = ("for", lo_v, hi_v)
                count_dispatch("narrow_upload")

    put = (lambda x: jax.device_put(x, device)) if device is not None else jnp.asarray
    return DeviceColumn(put(data_np), put(validity_np), dt, dictionary,
                        None if hi_np is None else put(hi_np), enc=enc)


def _unsplit(device):
    """Where a ragged column's lanes go when flat lanes go to `device`:
    offsets (rows + 1) and value lanes (a bucket of their own) do not fit
    a split of the rows, so under a NamedSharding they live whole on every
    device of its mesh (GSPMD still partitions the flat columns around
    them); anything else is taken as it is."""
    if isinstance(device, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(device.mesh,
                                          jax.sharding.PartitionSpec())
    return device


def _arrow_list_to_device(arr: pa.Array, dt: t.ArrayType, capacity: int,
                          validity_np: np.ndarray, device=None,
                          policy=None) -> DeviceColumn:
    """ListArray -> ragged device column: int32 offsets (row capacity+1)
    + flat values lane in its own bucket.  Null rows get empty spans so
    kernels never need the row validity to bound a segment."""
    device = _unsplit(device)
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    n = len(arr)
    if n:
        arr = arr.cast(pa.list_(arr.type.value_type))
        raw_off = np.asarray(arr.offsets.to_numpy(zero_copy_only=False),
                             np.int64)
        values = arr.values[raw_off[0]:raw_off[-1]]
        raw_off = raw_off - raw_off[0]
        # null rows -> empty spans (rebuild offsets monotonically)
        lens = np.diff(raw_off)
        lens[~validity_np[:n]] = 0
        # rebuild a compacted values array when null rows carried values
        if lens.sum() != len(values):
            keep = np.zeros(len(values), bool)
            for i in range(n):
                if validity_np[i]:
                    keep[raw_off[i]:raw_off[i + 1]] = True
            values = values.filter(pa.array(keep))
        off = np.zeros(capacity + 1, np.int32)
        off[1:n + 1] = np.cumsum(lens).astype(np.int32)
        off[n + 1:] = off[n]
    else:
        values = pa.array([], dtype_to_arrow(dt.element_type))
        off = np.zeros(capacity + 1, np.int32)

    vcap = bucket_capacity(max(len(values), 1))
    # ragged value lanes keep sorted-dict encoding but never narrow
    # (offset/value-lane plumbing assumes physical dtypes)
    vcol = _arrow_column_to_device(values, dt.element_type, vcap, device,
                                   policy=policy, narrow_ok=False)
    put = (lambda x: jax.device_put(x, device)) if device is not None \
        else jnp.asarray
    return DeviceColumn(vcol.data, put(validity_np), dt,
                        vcol.dictionary, vcol.data_hi,
                        offsets=put(off), elem_valid=vcol.validity)


def to_device(hb: HostBatch, conf: TpuConf = DEFAULT_CONF,
              capacity: Optional[int] = None, device=None,
              encoded_cols=None) -> DeviceBatch:
    """`encoded_cols`: column names approved for FOR-narrowed lanes by
    the _negotiate_encoded legality pass (plan/overrides.py); None means
    no narrowing (un-negotiated uploads stay full width).  Sorted-
    dictionary encoding applies to every upload when the policy is on —
    a pure representation change, safe for any consumer."""
    cap = capacity or bucket_capacity(max(hb.num_rows, 1), conf)
    if cap > hb.num_rows:
        # always-on pad accounting at bucket time: the rows the capacity
        # bucket adds over the live count (the overhead plane's upload
        # site; profiled segment dispatches price this padding in ms)
        from ..obs.registry import PAD_ROWS
        PAD_ROWS.inc(cap - hb.num_rows, site="upload")
    from ..ops.encodings import encoding_policy
    pol = encoding_policy(conf)
    if not pol.any_enabled:
        pol = None
    cols = []
    for i, f in enumerate(hb.schema.fields):
        cols.append(_arrow_column_to_device(
            hb.rb.column(i), f.data_type, cap, device, policy=pol,
            narrow_ok=encoded_cols is not None and f.name in encoded_cols))
    return DeviceBatch(cols, hb.num_rows, list(hb.schema.names))


# ---------------------------------------------------------------------------
# Device -> host (the ColumnarToRow / BringBackToHost analogue)
# ---------------------------------------------------------------------------

def _device_column_to_arrow(col: DeviceColumn, num_rows: int,
                            fetched=None) -> pa.Array:
    if fetched is not None:
        data_np, valid_np, hi_np, off_np, ev_np = fetched
    else:
        data_np, valid_np, hi_np, off_np, ev_np = jax.device_get(
            (col.data, col.validity, col.data_hi, col.offsets,
             col.elem_valid))
    dt = col.dtype
    if isinstance(dt, t.ArrayType):
        off = np.asarray(off_np)[:num_rows + 1].astype(np.int32)
        nvals = int(off[-1]) if len(off) else 0
        vcol = DeviceColumn(col.data, col.elem_valid, dt.element_type,
                            col.dictionary)
        values = _device_column_to_arrow(
            vcol, nvals, (data_np, ev_np, None, None, None))
        valid = np.asarray(valid_np)[:num_rows].astype(bool)
        return pa.ListArray.from_arrays(
            pa.array(off, pa.int32()), values,
            mask=pa.array(~valid) if not valid.all() else None)
    data = np.asarray(data_np)[:num_rows]
    valid = np.asarray(valid_np)[:num_rows].astype(bool)
    if isinstance(dt, t.StringType):
        codes = np.where(valid, data, -1).astype(np.int32)
        dict_arr = col.dictionary if col.dictionary is not None else pa.array([], pa.string())
        indices = pa.array(codes, pa.int32(), mask=~valid)
        return pa.DictionaryArray.from_arrays(indices, dict_arr).cast(pa.string())
    if isinstance(dt, t.DecimalType):
        if dt.is_wide:
            lo = data.astype(np.int64).view(np.uint64)
            if hi_np is None:
                # device-computed wide result: single int64 lane, sign-extend
                hi_np = np.where(data.astype(np.int64) < 0,
                                 np.int64(-1), np.int64(0))
                hi_lane = hi_np.view(np.uint64)
            else:
                hi_lane = np.asarray(hi_np)[:num_rows].view(np.uint64)
            lanes = np.empty((num_rows, 2), dtype=np.uint64)
            lanes[:, 0] = lo
            lanes[:, 1] = hi_lane
            validity_buf = pa.py_buffer(np.packbits(valid, bitorder="little").tobytes())
            return pa.Array.from_buffers(pa.decimal128(dt.precision, dt.scale),
                                         num_rows,
                                         [validity_buf, pa.py_buffer(lanes.tobytes())])
        return _decimal128_from_unscaled(data, valid, dt)
    if isinstance(dt, t.NullType):
        return pa.nulls(num_rows)
    if isinstance(dt, t.DoubleType):
        # Two storage lanes exist: int64 f64-bit-patterns (host pass-through)
        # and native f64 (computed on device) — see ops/kernels.py views.
        if data.dtype == np.float64:
            return pa.array(data, pa.float64(), mask=~valid)
        f64 = data.astype(np.int64).view(np.float64)
        return pa.array(f64, pa.float64(), mask=~valid)
    arrow_type = dtype_to_arrow(dt)
    if isinstance(dt, t.TimestampType):
        return pa.array(data.astype(np.int64), pa.int64(), mask=~valid).cast(arrow_type)
    if isinstance(dt, t.DateType):
        return pa.array(data.astype(np.int32), pa.int32(), mask=~valid).cast(arrow_type)
    return pa.array(data, arrow_type, mask=~valid)


def to_host(db: DeviceBatch, fetch_rows: Optional[int] = None) -> HostBatch:
    """Bring a batch to host.

    ONE D2H round trip for the row count and every lane of every column
    (a separate int(num_rows) fetch would be a second host sync).

    fetch_rows: upper bound on live rows KNOWN BY THE CALLER (a static
    limit, an already-synced count).  Lanes are device-sliced to it before
    the transfer: ship live rows, not the padded bucket (a 1M-row bucket
    carrying 1,760 live rows is 25 MB padded and 42 KB live).  Ragged
    value lanes are sliced via the (host-known) offsets
    bound only when the whole column is fetched, because the value count
    of a row prefix is itself device data."""
    n, fetched = _fetch_lanes(db, fetch_rows)
    if fetch_rows is not None:
        n = min(n, fetch_rows)
    return _build_host_batch(db, n, fetched)


def _fetch_lanes(db: DeviceBatch, fetch_rows: Optional[int]):
    """device_get count + lanes in one round trip; lanes prefix-sliced to
    fetch_rows when given.  Returns (clamped live count, fetched lists)."""
    if db.sel is not None or db.thin is not None:
        from ..ops.batch_ops import ensure_prefix
        db = ensure_prefix(db)
    cols = db.columns
    if fetch_rows is not None and fetch_rows < db.capacity:
        h = fetch_rows
        sl = []
        for c in cols:
            if c.offsets is not None:
                # offsets prefix is enough for rebuild; values lanes keep
                # full length (their live length is offsets[h], on device)
                sl.append(dataclasses.replace(
                    c, offsets=c.offsets[:h + 1]))
            else:
                sl.append(dataclasses.replace(
                    c, data=c.data[:h], validity=c.validity[:h],
                    data_hi=None if c.data_hi is None else c.data_hi[:h]))
        cols = sl
    n_f, fetched = jax.device_get(
        (db.num_rows, [(c.data, c.validity, c.data_hi, c.offsets,
                        c.elem_valid) for c in cols]))
    return int(n_f), fetched        # TRUE count (may exceed fetch_rows)


def _build_host_batch(db: DeviceBatch, n: int, fetched) -> HostBatch:
    arrays = [_device_column_to_arrow(c, n, f)
              for c, f in zip(db.columns, fetched)]
    schema = pa.schema([pa.field(n, a.type) for n, a in zip(db.names, arrays)])
    if not arrays:
        return HostBatch(pa.RecordBatch.from_pydict({}))
    return HostBatch(pa.RecordBatch.from_arrays(arrays, schema=schema))


# Result-fetch head default: one speculative round trip ships the count
# plus this many rows (~40 KB/column at 4096, covering every TPC-H final
# result).  The SOURCE OF TRUTH is the config entry; conf=None callers
# read it from DEFAULT_CONF so tuning the default cannot fork the two.


def fetch_result_batch(db: DeviceBatch, bound: Optional[int] = None,
                       conf: Optional[TpuConf] = None,
                       metrics: Optional[dict] = None) -> HostBatch:
    """Bring a RESULT batch to host in as few syncs and bytes as it needs.

    The live rows of every operator output are a front prefix of the
    padded bucket (filters compact, aggregates emit groups first, sorts
    order dead rows last), so the fetch never needs the padding:

      * static row count           -> one trip, exactly n rows
      * static bound (limit/top-N) -> one trip, bound rows
      * unknown count              -> ONE speculative trip fetching the
        count + a RESULT_HEAD_ROWS prefix together; a second trip only
        when the result is genuinely bigger than the head.

    Each trip is a blocking device-to-host read: `metrics["host_syncs"]`
    counts them."""
    from ..config import (DEFAULT_CONF, RESULT_BOUND_FETCH_FACTOR,
                          RESULT_HEAD_ROWS)
    conf = conf or DEFAULT_CONF
    head_rows = conf.get(RESULT_HEAD_ROWS)
    bound_factor = conf.get(RESULT_BOUND_FETCH_FACTOR)
    cap = db.capacity

    def trip():
        if metrics is not None:
            metrics["host_syncs"] = metrics.get("host_syncs", 0) + 1

    trip()
    if isinstance(db.num_rows, int):
        return to_host(db, fetch_rows=min(db.num_rows, cap))
    if any(c.offsets is not None for c in db.columns):
        # ragged value lanes aren't prefix-sliceable by a row bound (the
        # value count of a prefix is device data).  A small static bound
        # fetches exactly-sized in one trip; otherwise the cheap scalar
        # count goes first so an all-padding bucket never ships lanes
        if bound is not None and bound < cap:
            return to_host(db, fetch_rows=bound)
        n = int(jax.device_get(db.num_rows))
        trip()
        return to_host(db, fetch_rows=max(n, 0) if n < cap else None)
    # a small static bound buys an exact one-trip fetch; a loose bound
    # (dense-domain group counts can reach 4M) must not defeat the head
    # protocol, so past boundFactor x the head size we speculate instead
    if bound is not None and bound <= bound_factor * head_rows:
        head = min(cap, bound)
    else:
        head = min(cap, head_rows)
    if head >= cap:
        return to_host(db)
    n, fetched = _fetch_lanes(db, head)
    if n <= head:
        return _build_host_batch(db, n, fetched)
    # result larger than the head: pay the second, exactly-sized trip
    trip()
    return to_host(db, fetch_rows=n)


def empty_device_batch(schema: t.StructType, conf: TpuConf = DEFAULT_CONF) -> DeviceBatch:
    hb = HostBatch(pa.RecordBatch.from_pydict(
        {f.name: pa.array([], dtype_to_arrow(f.data_type)) for f in schema.fields}))
    return to_device(hb, conf)
