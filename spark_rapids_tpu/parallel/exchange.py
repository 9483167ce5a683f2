"""Distributed hash exchange + aggregation over a device mesh.

This is the ICI-native counterpart of the reference's shuffle exchange +
final aggregation (GpuShuffleExchangeExecBase.scala:167 followed by
GpuHashAggregateExec): every chip hash-partitions its row shard on
device and `lax.all_to_all` moves each hash range to its owner chip
over ICI; the owner then runs the same sort-segment groupby kernel
locally.

The exchange plane is **data-movement-optimal** (Theseus, PAPERS.md —
distributed query engines win or lose on data movement):

  * NO sort at all in the prepare step — per-destination row ranks
    (P cumsums, ~50x cheaper than a sort at 1M rows) address each
    round's O(P x quota) slab directly; the old fused path's (P, C)
    bucket stack (P full stable argsorts, P×C staging per lane) is
    retired;
  * lanes are **compressed before the collective** (ops/bitpack.py):
    validity/flag lanes ride 1 bit per row, integer lanes narrow to
    frame-of-reference uint8/16/32 words when their global live range
    (exchanged with the count matrix — no extra sync) allows, and every
    narrow lane fuses into ONE wide byte-word collective per round
    instead of one dispatch per lane (the nvcomp-before-UCX analog of
    the reference's shuffle, SURVEY §shuffle);
  * round quotas are **skew-aware**: the host plans per-round quotas
    from the exchanged count matrix (pow2-quantized so compiled round
    variants stay bounded), so a uniform exchange finishes in one small
    round and a hot destination no longer forces `max_cnt / quota`
    rounds on everyone;
  * rounds are **double-buffered**: slab staging for round r+1 is its
    own dispatch overlapping round r's collective (async dispatch), and
    receive buffers are donated (`donate_argnums`) through the round
    program instead of round-tripping fresh allocations.

`RaggedExchange` is the windowed bounce-buffer role of the reference's
UCX transport (BufferSendState / WindowedBlockIterator): bounded
in-flight buffers regardless of total shuffle size.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import types as t
from ..config import (EXCHANGE_COMPRESS, EXCHANGE_DONATE,
                      EXCHANGE_QUOTA_AUTO, EXCHANGE_QUOTA_ROWS,
                      EXCHANGE_SPLIT_RETRY)
from ..obs.registry import (EXCHANGE_ROUNDS, EXCHANGE_WIRE_POST,
                            EXCHANGE_WIRE_PRE, ICI_EXCHANGE_BYTES)
from ..obs.tracer import get_active
from ..ops import groupby as G
from ..ops.bitpack import (bytes_to_words, for_decode, for_encode,
                           pack_bits, unpack_bits, wire_dtype_for,
                           words_to_lane)
from ..ops.hashing import hash_int64
from ..runtime.faults import fire_active
from .mesh import SHARD_AXIS

#: lane wire treatments a caller can declare per lane
RAW = "raw"      # integer/float payload; FOR-narrowed when range allows
FLAG = "flag"    # bool lane; rides the packed bit plane (1 bit/row)


def _knob(conf, entry):
    """Conf value, or the entry default for conf-less mesh primitives."""
    return conf.get(entry) if conf is not None else entry.default


def _pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def partition_ids(keys: jax.Array, valid: jax.Array, num_parts: int,
                  seed: int = 42) -> jax.Array:
    """Murmur3-based destination per row (GpuHashPartitioningBase role).
    Null keys hash to the seed, matching Spark's null-handling."""
    h = hash_int64(keys.astype(jnp.int64), jnp.uint32(seed))
    h = jnp.where(valid, h, jnp.uint32(seed))
    return (h % jnp.uint32(num_parts)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Ragged exchange: rank-addressed slabs, compressed quota-scheduled rounds
# ---------------------------------------------------------------------------

def _exclusive_cumsum(x):
    return jnp.concatenate([jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1]])


def _int_sentinels(dtype):
    info = jnp.iinfo(dtype)
    return info.max, info.min


def ragged_prepare(nparts: int, kinds: Sequence[str]):
    """Trace fn: rank every live row within its destination segment
    and exchange per-dest counts.  Staging after this point is one
    (P, quota) slab per round — O(C) — instead of the retired (P, C)
    bucket stack (P stable argsorts and worst-case-skew padding per
    lane).

    Also computes each integer lane's local live [min, max] so the host
    can plan frame-of-reference wire widths from the SAME fetch that
    returns the count matrix — compression planning costs no extra sync.

    Returns (rank (C,), counts (P,), in_counts (P,), lane_stats
    (nlanes, 2)): in_counts[s] = rows source chip s sends me.
    """
    def prep(lanes, live, dest, axis=SHARD_AXIS):
        # Per-destination RANKS instead of a materialized dest sort: a
        # sort of C rows costs ~50x a cumsum on both TPU and CPU, and
        # the slab layout only needs each live row's position within
        # its destination segment — P cumsums deliver that, lanes are
        # never gathered into dest order (each round's staging scatters
        # row ids straight into the O(P x quota) slab it ships; row
        # order within a destination is unspecified, the exchange
        # contract).  For very wide meshes one argsort + an inverse
        # permutation would win again (nparts log C vs nparts x C).
        cap = live.shape[0]
        rank = jnp.zeros((cap,), jnp.int32)
        counts_l = []
        for p in range(nparts):
            mask = live & (dest == p)
            c = jnp.cumsum(mask.astype(jnp.int32))
            rank = jnp.where(mask, c - 1, rank)
            counts_l.append(c[-1])
        counts = jnp.stack(counts_l)
        in_counts = jax.lax.all_to_all(counts, axis, split_axis=0,
                                       concat_axis=0, tiled=True)
        stats = []
        for lane, kind in zip(lanes, kinds):
            if kind == RAW and jnp.issubdtype(lane.dtype, jnp.integer) \
                    and lane.dtype.itemsize > 1:
                hi_s, lo_s = _int_sentinels(lane.dtype)
                lo = jnp.min(jnp.where(live, lane, hi_s)).astype(jnp.int64)
                hi = jnp.max(jnp.where(live, lane, lo_s)).astype(jnp.int64)
            else:                  # flags / floats / int8: never narrowed
                lo, hi = jnp.int64(0), jnp.int64(-1)
            stats.append(jnp.stack([lo, hi]))
        return rank, counts, in_counts, jnp.stack(stats)
    return prep


def _stage_round(nparts: int, cap: int, quota: int, plan: tuple):
    """Trace fn: gather + encode ONE round's send slab.  Separate from
    the collective so the host can dispatch round r+1's staging while
    round r's all_to_all is still in flight (the overlap half of the
    double buffer)."""
    def stage(lanes, rank, dest, live, counts, biases, r):
        q_iota = jnp.arange(quota, dtype=jnp.int32)
        m = (r * quota + q_iota)[None, :] < counts[:, None]     # (P, Q)
        # rows whose in-dest rank falls in this round's window scatter
        # their OWN index into the slab slot (dest, rank - r*quota) —
        # the dest-ordered slab without ever sorting the lanes
        rel = rank - r * quota
        sel = live & (rel >= 0) & (rel < quota)
        pos = jnp.where(sel, dest * quota + rel, nparts * quota)
        src = jnp.zeros((nparts * quota,), jnp.int32).at[pos].set(
            jnp.arange(cap, dtype=jnp.int32), mode="drop") \
            .reshape(nparts, quota)
        words, flags = [], [m]                   # gather of O(P x Q)
        for i, (lane, spec) in enumerate(zip(lanes, plan)):
            slab = lane[src]
            if spec[0] == FLAG:
                flags.append(slab)
                continue
            _, logical, wire = spec
            if slab.dtype == jnp.bool_:        # compress off: byte flags
                slab = slab.astype(jnp.uint8)
            elif str(wire) != str(logical):
                slab = for_encode(slab, biases[i], np.dtype(wire))
            words.append(bytes_to_words(slab))
        wire_slab = jnp.concatenate(words, axis=-1) if words else \
            jnp.zeros((nparts, quota, 0), jnp.uint8)
        flag_slab = pack_bits(
            jnp.stack(flags, axis=1).reshape(nparts, len(flags) * quota))
        return wire_slab, flag_slab
    return stage


def _collective_round(nparts: int, quota: int, recv_cap: int, plan: tuple):
    """Trace fn: ONE fused byte-word all_to_all + ONE packed-flag
    all_to_all per round (was one collective per lane), then a compact
    scatter into the donated receive buffers at the deterministic
    arrival layout [R_s + r*quota, ...)."""
    nflags = 1 + sum(1 for s in plan if s[0] == FLAG)
    wire_width = sum(1 if s[1] == "bool" else np.dtype(s[2]).itemsize
                     for s in plan if s[0] == RAW)

    def rnd(wire_slab, flag_slab, in_counts, biases, recv_lanes,
            recv_live, r, axis=SHARD_AXIS):
        q_iota = jnp.arange(quota, dtype=jnp.int32)
        ex_w = jax.lax.all_to_all(wire_slab, axis, split_axis=0,
                                  concat_axis=0, tiled=True) \
            if wire_width else wire_slab
        ex_f = jax.lax.all_to_all(flag_slab, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        flags = unpack_bits(ex_f).reshape(nparts, nflags, quota)
        m_ex = flags[:, 0, :]
        base = _exclusive_cumsum(in_counts.astype(jnp.int32))
        pos = base[:, None] + r * quota + q_iota[None, :]
        pos = jnp.where(m_ex, pos, recv_cap)       # masked -> dropped
        pos_f = pos.reshape(-1)
        out_lanes = []
        boff, fi = 0, 1
        for i, spec in enumerate(plan):
            if spec[0] == FLAG:
                e = flags[:, fi, :]
                fi += 1
            else:
                _, logical, wire = spec
                is_bool = logical == "bool"
                w = 1 if is_bool else np.dtype(wire).itemsize
                chunk = ex_w[..., boff:boff + w]
                boff += w
                if is_bool:
                    e = chunk[..., 0].astype(jnp.bool_)
                else:
                    lane = words_to_lane(chunk, np.dtype(wire))
                    e = for_decode(lane, biases[i], np.dtype(logical)) \
                        if wire != logical else lane
            out_lanes.append(recv_lanes[i].at[pos_f].set(
                e.reshape(-1), mode="drop"))
        out_live = recv_live.at[pos_f].set(m_ex.reshape(-1), mode="drop")
        return out_lanes, out_live
    return rnd


class _PlanState:
    """Host-side state of one planned exchange call: the dest-sorted
    lanes, the fetched count matrix, the wire/compression plan and the
    round schedule — exposed so skew-aware consumers (split-retry) can
    inspect counts BEFORE committing to the rounds."""
    __slots__ = ("lanes", "rank", "dest", "live", "counts_dev",
                 "in_counts", "biases", "plan", "schedule", "recv_cap",
                 "max_cnt", "per_shard_in", "would_grow", "stats",
                 "arrivals")

    def __init__(self):
        self.would_grow = False


class RaggedExchange:
    """Host-driven ragged all-to-all over a mesh axis.

    One prepare dispatch (per-dest ranks + count/range exchange), then a
    quota-scheduled sequence of compressed round dispatches, each
    staging O(P x quota) = O(C).  `plan_call` + `run_rounds` split the
    count-plan from the data movement so consumers can act on skew
    (distributed_groupby_ragged's split-retry) before any row moves.

    `kinds` declares per-lane wire treatment (RAW / FLAG); `conf` (a
    TpuConf, optional) reads the `spark.rapids.tpu.exchange.*` knobs —
    conf-less callers get the documented defaults."""

    def __init__(self, mesh: Mesh, nlanes: int, cap: int,
                 quota: int = 0, recv_cap: int = 0,
                 kinds: Optional[Sequence[str]] = None, conf=None,
                 donate: Optional[bool] = None):
        self.mesh = mesh
        self.nparts = mesh.devices.size
        self.cap = cap
        self.kinds = tuple(kinds) if kinds is not None \
            else (RAW,) * nlanes
        assert len(self.kinds) == nlanes
        conf_quota = int(_knob(conf, EXCHANGE_QUOTA_ROWS))
        self.quota = _pow2ceil(quota or conf_quota or
                               max(8, (2 * cap) // self.nparts))
        self.quota = max(self.quota, 8)    # bit-packing granularity
        self.recv_cap = recv_cap or 2 * cap
        self.compress = bool(_knob(conf, EXCHANGE_COMPRESS))
        self.quota_auto = bool(_knob(conf, EXCHANGE_QUOTA_AUTO))
        dmode = str(_knob(conf, EXCHANGE_DONATE)).upper()
        if donate is None:
            donate = dmode == "ON" or (
                dmode == "AUTO" and jax.default_backend() != "cpu")
        self.donate = bool(donate)
        self.last_stats: Dict[str, int] = {}

        axis = mesh.axis_names[0]
        spec = P(axis)
        self._axis = axis
        self._spec = spec
        self._lane_specs = [spec] * nlanes
        prep = ragged_prepare(self.nparts, self.kinds)
        self._prep = jax.jit(shard_map(
            lambda lanes, live, dest: prep(lanes, live, dest, axis),
            mesh=mesh, in_specs=(self._lane_specs, spec, spec),
            out_specs=(spec, spec, spec, spec),
            check_vma=False))
        self._stages: Dict[tuple, object] = {}
        self._rounds: Dict[tuple, object] = {}
        self._zeros: Dict[tuple, object] = {}

    # -- compiled-program caches (pow2 quotas bound the variant count) ----
    def _stage_fn(self, quota: int, plan: tuple):
        key = (quota, plan)
        fn = self._stages.get(key)
        if fn is None:
            stage = _stage_round(self.nparts, self.cap, quota, plan)
            fn = jax.jit(shard_map(
                stage, mesh=self.mesh,
                in_specs=(self._lane_specs, self._spec, self._spec,
                          self._spec, self._spec, P(), None),
                out_specs=(self._spec, self._spec), check_vma=False))
            self._stages[key] = fn
        return fn

    def _round_fn(self, quota: int, recv_cap: int, plan: tuple):
        key = (quota, recv_cap, plan)
        fn = self._rounds.get(key)
        if fn is None:
            rnd = _collective_round(self.nparts, quota, recv_cap, plan)
            mapped = shard_map(
                lambda w, f, ic, b, recv, rlive, r:
                rnd(w, f, ic, b, recv, rlive, r, self._axis),
                mesh=self.mesh,
                in_specs=(self._spec, self._spec, self._spec, P(),
                          self._lane_specs, self._spec, None),
                out_specs=(self._lane_specs, self._spec),
                check_vma=False)
            # the double-buffer half: receive buffers are DONATED so
            # every round updates them in place instead of allocating +
            # round-tripping a fresh copy (no-op on backends without
            # donation, where XLA copies as before)
            fn = jax.jit(mapped, donate_argnums=(4, 5)) if self.donate \
                else jax.jit(mapped)
            self._rounds[key] = fn
        return fn

    def _zeros_fn(self, n: int, dtypes: tuple):
        key = (n, dtypes)
        fn = self._zeros.get(key)
        if fn is None:
            shard = NamedSharding(self.mesh, self._spec)
            fn = jax.jit(
                lambda: tuple(jnp.zeros((n,), jnp.dtype(d))
                              for d in dtypes) + (jnp.zeros((n,), bool),),
                out_shardings=tuple([shard] * (len(dtypes) + 1)))
            self._zeros[key] = fn
        return fn

    # -- planning ---------------------------------------------------------
    def _wire_plan(self, lane_dtypes, stats: np.ndarray
                   ) -> Tuple[tuple, np.ndarray]:
        """Per-lane wire treatment + FOR biases from the exchanged lane
        ranges.  Returns (hashable plan, biases (nlanes,) int64)."""
        lo = stats[:, :, 0].min(axis=0)
        hi = stats[:, :, 1].max(axis=0)
        plan, biases = [], np.zeros(len(lane_dtypes), np.int64)
        for i, (dt, kind) in enumerate(zip(lane_dtypes, self.kinds)):
            dt = np.dtype(dt)
            if kind == FLAG and self.compress:
                plan.append((FLAG,))
                continue
            if kind == FLAG or dt == np.dtype(bool):   # bool: byte wire
                plan.append((RAW, "bool", "bool"))
                continue
            wire = dt
            if self.compress and np.issubdtype(dt, np.integer) \
                    and dt.itemsize > 1:
                wire = wire_dtype_for(int(lo[i]), int(hi[i]), dt)
                if wire != dt:
                    biases[i] = int(lo[i]) if lo[i] <= hi[i] else 0
            plan.append((RAW, dt.str, np.dtype(wire).str))
        return tuple(plan), biases

    def _plan_quotas(self, max_cnt: int, recv_cap: int) -> List[int]:
        """Skew-aware round schedule: pow2 quota sized from the ACTUAL
        count matrix, capped by the per-dest share of the receive
        commitment — a uniform exchange finishes in one small round, a
        hot destination widens the quota (staging never exceeds what the
        receive buffers already allocate) instead of forcing
        max_cnt/quota rounds on everyone."""
        if not max_cnt:
            return []
        if not self.quota_auto:
            q = self.quota
        else:
            cap_q = max(self.quota, _pow2ceil(recv_cap // self.nparts))
            q = max(8, min(_pow2ceil(max_cnt), cap_q))
        return [q] * (-(-max_cnt // q))

    def plan_call(self, lanes, live, dest) -> _PlanState:
        """Run the prepare dispatch and the ONE host sync this exchange
        needs: counts, in_counts and lane ranges arrive in a single
        fetch; the wire plan and round schedule are derived from them."""
        fire_active("exchange")     # chaos site: the collective fabric
        st = _PlanState()
        rank, counts, in_counts, stats = \
            self._prep(list(lanes), live, dest)
        counts_h, in_h, stats_h = jax.device_get(
            (counts, in_counts, stats))
        nl = len(self.kinds)
        st.lanes, st.rank = list(lanes), rank
        st.dest, st.live = dest, live
        st.counts_dev = counts
        st.in_counts = in_counts
        st.stats = np.asarray(stats_h).reshape(self.nparts, nl, 2)
        st.max_cnt = int(np.asarray(counts_h).max())
        per_shard = np.asarray(in_h).reshape(self.nparts,
                                             self.nparts).sum(1)
        # per-device arrival counts ride into the mesh timeline: the
        # skew picture an operator needs to read a slow exchange
        st.arrivals = [int(x) for x in per_shard]
        st.per_shard_in = int(per_shard.max())
        # receive buffers size to the ACTUAL arrival volume (pow2-
        # quantized so downstream capacity-keyed traces stay bounded):
        # a partial-aggregated exchange at 1M rows/device receives ~5k
        # group rows, not 2M — memory AND the consumer's merge capacity
        # scale with real skew/compaction, never worst case
        recv_cap = min(self.recv_cap,
                       max(64, _pow2ceil(st.per_shard_in)))
        while st.per_shard_in > recv_cap:
            recv_cap *= 2
        st.would_grow = recv_cap > self.recv_cap
        st.recv_cap = recv_cap
        st.plan, st.biases = self._wire_plan(
            [l.dtype for l in st.lanes], st.stats)
        st.schedule = self._plan_quotas(st.max_cnt, recv_cap)
        return st

    def _account(self, st: _PlanState) -> None:
        """Wire accounting, ONCE per exchange (not per device): the
        pre/post-compress ratio plus the legacy total ICI counter."""
        rounds = len(st.schedule)
        if not rounds:
            self.last_stats = {"rounds": 0, "quota": 0, "wire_pre": 0,
                               "wire_post": 0, "recv_cap": st.recv_cap}
            return
        q = st.schedule[0]
        logical_row = sum(
            1 if s[0] == FLAG or s[1] == "bool" else
            np.dtype(s[1]).itemsize for s in st.plan) + 1   # + slot mask
        nflags = 1 + sum(1 for s in st.plan if s[0] == FLAG)
        wire_row = sum(np.dtype(s[2]).itemsize for s in st.plan
                       if s[0] == RAW and s[1] != "bool")
        wire_row += sum(1 for s in st.plan
                        if s[0] == RAW and s[1] == "bool")
        wire_row += nflags / 8.0
        slots = rounds * self.nparts * q
        pre = int(slots * logical_row) * self.nparts
        post = int(slots * wire_row) * self.nparts
        # per-device HBM footprints of the exchange machinery itself —
        # the mesh half of the memory-attribution timeline: the staged
        # send slab one round holds (wire widths, double-buffered so up
        # to 2x live) and the receive buffers that persist across every
        # round (decoded lane widths at recv_cap)
        slab_bytes = int(self.nparts * q * wire_row)
        decoded_row = sum(np.dtype(s[1]).itemsize
                          if s[0] == RAW and s[1] != "bool" else 1
                          for s in st.plan) + 1       # + live bool
        recv_buffer_bytes = int(self.nparts * st.recv_cap * decoded_row)
        self.last_stats = {"rounds": rounds, "quota": q,
                           "wire_pre": pre, "wire_post": post,
                           "recv_cap": st.recv_cap,
                           "slab_bytes": slab_bytes,
                           "recv_buffer_bytes": recv_buffer_bytes}
        EXCHANGE_WIRE_PRE.inc(pre)
        EXCHANGE_WIRE_POST.inc(post)
        EXCHANGE_ROUNDS.observe(rounds)
        ICI_EXCHANGE_BYTES.inc(post)
        tr = get_active()
        tr.add_bytes("ici_exchange_bytes", post)
        tr.instant("ici_exchange", "shuffle", rounds=rounds, quota=q,
                   bytes=post, bytes_pre_compress=pre,
                   recv_cap=st.recv_cap,
                   slab_bytes=slab_bytes,
                   recv_buffer_bytes=recv_buffer_bytes,
                   arrivals=getattr(st, "arrivals", None))
        from ..obs.memattr import get_active_recorder
        rec = get_active_recorder()
        if rec is not None:
            rec.on_external("exchange", bytes=recv_buffer_bytes,
                            slab_bytes=slab_bytes, rounds=rounds)

    def run_rounds(self, st: _PlanState):
        """Execute the planned rounds: staging for round r+1 overlaps
        round r's collective (two async dispatches per round), receive
        buffers donate through every round.

        Per-round host dispatch wall (staging vs collective) is
        recorded into one `exchange_timing` instant after the loop —
        the per-round half of the query mesh timeline
        (QueryProfile.mesh_timeline).  The pre-round `exchange_round`
        state instants stay FIRST so a fatal mid-round still dumps its
        round state (test_chaos)."""
        import time as _time
        self._account(st)
        recv_cap = st.recv_cap
        n = self.nparts * recv_cap
        dtypes = tuple(np.dtype(s[1]).str if s[0] == RAW and
                       s[1] != "bool" else "bool" for s in st.plan)
        bufs = self._zeros_fn(n, dtypes)()
        recv, rlive = list(bufs[:-1]), bufs[-1]
        biases = jnp.asarray(st.biases)
        tr = get_active()
        rounds = len(st.schedule)
        if rounds:
            q = st.schedule[0]
            stage = self._stage_fn(q, st.plan)
            rnd = self._round_fn(q, recv_cap, st.plan)
            stage_ms: List[float] = []
            coll_ms: List[float] = []
            t0 = _time.perf_counter()
            slab = stage(st.lanes, st.rank, st.dest, st.live,
                         st.counts_dev, biases, jnp.int32(0))
            pending_stage = _time.perf_counter() - t0
            for r in range(rounds):
                # round state into the flight recorder: a fatal mid-
                # exchange dumps exactly which round died (test_chaos)
                tr.instant("exchange_round", "shuffle", r=r,
                           rounds=rounds, quota=q, recv_cap=recv_cap)
                fire_active("exchange", round=r)
                # exchange-round cancellation checkpoint: a deadline-
                # armed query cancels between collective rounds
                from ..exec.plan import checkpoint_active
                checkpoint_active("exchange_round")
                t0 = _time.perf_counter()
                nxt = stage(st.lanes, st.rank, st.dest, st.live,
                            st.counts_dev, biases, jnp.int32(r + 1)) \
                    if r + 1 < rounds else None
                t1 = _time.perf_counter()
                recv, rlive = rnd(slab[0], slab[1], st.in_counts,
                                  biases, recv, rlive, jnp.int32(r))
                t2 = _time.perf_counter()
                # round r's staging was dispatched the PREVIOUS
                # iteration (the double buffer) — attribute it to r,
                # and hold this iteration's dispatch for round r+1
                stage_ms.append(round(pending_stage * 1e3, 3))
                pending_stage = t1 - t0
                coll_ms.append(round((t2 - t1) * 1e3, 3))
                slab = nxt
            tr.instant("exchange_timing", "shuffle", rounds=rounds,
                       quota=q, recv_cap=recv_cap, stage_ms=stage_ms,
                       collective_ms=coll_ms)
        return recv, rlive, st.in_counts

    def __call__(self, lanes, live, dest):
        """lanes: list of (n_devices*cap,) sharded arrays; live/dest same
        shape.  Returns (recv lanes [(n_devices*recv_cap,)], recv live,
        in_counts (n_devices*P,))."""
        return self.run_rounds(self.plan_call(lanes, live, dest))


# ---------------------------------------------------------------------------
# Dictionary lanes: the dictionary crosses the wire ONCE, codes per round
# ---------------------------------------------------------------------------

def exchange_dictionary(mesh: Mesh, dict_lane, dict_cap: int,
                        axis: str = SHARD_AXIS):
    """All-gather every shard's local dictionary ONCE so encoded lanes
    can ride the round loop as narrow int32 codes (further FOR-narrowed
    when the code range allows) instead of decoded wide values — the
    "exchange the dictionary once, not per round" half of executing on
    compressed data (PAPERS.md, GPU SQL on compressed data).

    `dict_lane` is sharded (n_devices * dict_cap,): shard s's slice is
    its local dictionary (padded arbitrarily past its live size).
    Returns the replicated global dictionary (n_devices * dict_cap,);
    shard s's codes address it at `code + s * dict_cap` (see
    `globalize_codes`)."""
    spec = P(axis)
    fn = jax.jit(shard_map(
        lambda d: jax.lax.all_gather(d, axis, tiled=True),
        mesh=mesh, in_specs=spec, out_specs=P(), check_vma=False))
    out = fn(dict_lane)
    nbytes = out.size * out.dtype.itemsize * mesh.devices.size
    ICI_EXCHANGE_BYTES.inc(nbytes)
    EXCHANGE_WIRE_PRE.inc(nbytes)
    EXCHANGE_WIRE_POST.inc(nbytes)
    # through the ACTIVE tracer, not the bare registry channel: the
    # wire bytes attribute to the owning query's counters (and the
    # tracer publishes the same registry channel underneath), and the
    # gather lands on the query's mesh timeline
    tr = get_active()
    tr.add_bytes("ici_exchange_bytes", nbytes)
    tr.instant("ici_dict_gather", "shuffle", bytes=nbytes,
               dict_cap=dict_cap)
    return out


def globalize_codes(mesh: Mesh, codes, dict_cap: int,
                    axis: str = SHARD_AXIS):
    """Rebase each shard's local dictionary codes into the all-gathered
    global dictionary's index space (`code + shard * dict_cap`)."""
    spec = P(axis)
    fn = jax.jit(shard_map(
        lambda c: c + jax.lax.axis_index(axis).astype(c.dtype) * dict_cap,
        mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
    return fn(codes)


# ---------------------------------------------------------------------------
# Distributed groupby over the exchange (partial -> exchange -> merge)
# ---------------------------------------------------------------------------

def _merge_kind(kind: str) -> str:
    if kind in (G.COUNT, G.COUNT_ALL, G.SUM):
        return G.SUM
    if kind in (G.MIN, G.MAX, G.ANY, G.EVERY):
        return kind
    if kind in (G.FIRST, G.FIRST_NN):
        return G.FIRST_NN
    if kind in (G.LAST, G.LAST_NN):
        return G.LAST_NN
    raise ValueError(kind)


#: merge kinds safe to split-retry (a second associative merge pass
#: cannot change the result; FIRST/LAST depend on arrival order)
_ORDER_FREE = (G.SUM, G.MIN, G.MAX, G.ANY, G.EVERY)


def distributed_groupby_ragged(mesh: Mesh, key_dtype: t.DataType,
                               agg_specs: List[G.AggSpec], local_cap: int,
                               conf=None):
    """Distributed groupby: partial sort-segment groupby per shard ->
    compressed ragged exchange of the partials (one row per (shard,
    group)) -> merge groupby on the owning chip.  Pre-aggregating before
    the exchange is the classic partial/final split (reference
    partial-mode GpuHashAggregateExec before the shuffle).

    Skew split-retry: when the planned exchange would GROW a receive
    buffer (one hot hash partition), and every merge kind is
    order-insensitive, rows are salted across destination pairs, merged,
    and a second (tiny) exchange+merge over the merged groups restores
    single-owner partitions — receive memory stays bounded by actual
    groups, not by the hot key's row count.

    Returns run(keys, key_valid, vals, val_valids) -> ((kd, kv), outs,
    ngroups) with merge outputs sharded per the exchange layout."""
    nparts = mesh.devices.size
    axis = mesh.axis_names[0]
    spec = P(axis)
    key_info = [(key_dtype, True,
                 str(np.dtype(t.physical_np_dtype(key_dtype))))]
    partial = G.groupby_trace(key_info, agg_specs, local_cap, local_cap)
    merge_specs = [G.AggSpec(_merge_kind(s.kind), i, s.dtype)
                   for i, s in enumerate(agg_specs)]
    recv_cap = 2 * local_cap
    nspecs = len(agg_specs)
    split_ok = bool(_knob(conf, EXCHANGE_SPLIT_RETRY)) and \
        all(m.kind in _ORDER_FREE for m in merge_specs)

    def partial_step(keys, key_valid, vals, val_valids):
        out_keys, outs, ngroups = partial(
            (keys,), (key_valid,), tuple(vals), tuple(val_valids),
            jnp.ones((local_cap,), bool))
        (kd, kv) = out_keys[0]
        g_live = jnp.arange(local_cap, dtype=jnp.int32) < ngroups
        dest = partition_ids(kd, kv & g_live, nparts)
        lanes = [kd, kv] + [x for d, v in outs for x in (d, v)]
        return lanes, g_live, dest

    n_lanes = 2 + 2 * nspecs
    kinds = [RAW, FLAG] + [RAW, FLAG] * nspecs
    partial_fn = jax.jit(shard_map(
        partial_step, mesh=mesh,
        in_specs=(spec, spec, spec, spec),
        out_specs=(spec, spec, spec), check_vma=False))

    # salt: alternate rows of a hot partition across a destination pair
    # (d, d + P/2) — the split half of split-retry
    stride = max(nparts // 2, 1)

    def salt_step(dest, g_live):
        iota = jnp.arange(dest.shape[0], dtype=jnp.int32)
        salted = (dest + (iota % 2) * stride) % nparts
        return jnp.where(g_live, salted, dest)

    salt_fn = jax.jit(shard_map(salt_step, mesh=mesh,
                                in_specs=(spec, spec), out_specs=spec,
                                check_vma=False))

    merge_fns = {}

    def merge_fn_for(rc: int):
        # the exchange grows its receive buffer under skew; the merge
        # trace is capacity-static, so build one per observed size
        fn = merge_fns.get(rc)
        if fn is None:
            merge = G.groupby_trace(key_info, merge_specs, rc, rc)

            def merge_step(lanes, rlive):
                kd = lanes[0]
                kv = lanes[1] & rlive
                r_vals = tuple(lanes[2 + 2 * i] for i in range(nspecs))
                r_vv = tuple(lanes[3 + 2 * i] & rlive
                             for i in range(nspecs))
                m_keys, m_outs, m_groups = merge((kd,), (kv,), r_vals,
                                                 r_vv, rlive)
                return m_keys[0], m_outs, m_groups[None]

            fn = jax.jit(shard_map(
                merge_step, mesh=mesh, in_specs=(spec, spec),
                out_specs=(spec, spec, spec), check_vma=False))
            merge_fns[rc] = fn
        return fn

    relabel_fns = {}

    def relabel_fn_for(rc: int):
        # pass-2 routing: liveness + TRUE hash destination of the
        # pass-1 merged groups
        fn = relabel_fns.get(rc)
        if fn is None:
            def relabel(kd, kv, ng):
                live = jnp.arange(rc, dtype=jnp.int32) < ng[0]
                return live, partition_ids(kd, kv & live, nparts)
            fn = jax.jit(shard_map(
                relabel, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=(spec, spec), check_vma=False))
            relabel_fns[rc] = fn
        return fn

    ex = RaggedExchange(mesh, nlanes=n_lanes, cap=local_cap,
                        recv_cap=recv_cap, kinds=kinds, conf=conf)
    ex2_cache = {}

    def merge_once(exchange, st):
        recv, rlive, _ = exchange.run_rounds(st)
        rc = rlive.shape[0] // nparts
        kd, outs, ng = merge_fn_for(rc)(recv, rlive)
        return kd, outs, ng, rc

    def run(keys, key_valid, vals, val_valids):
        lanes, g_live, dest = partial_fn(keys, key_valid, tuple(vals),
                                         tuple(val_valids))
        st = ex.plan_call(lanes, g_live, dest)
        if not (split_ok and st.would_grow):
            kd, outs, ng, _ = merge_once(ex, st)
            return kd, outs, ng
        # split-retry: salt destinations, merge, then re-exchange the
        # (small) merged groups to their true owners
        get_active().instant("exchange_skew_split", "shuffle",
                             per_shard_in=st.per_shard_in,
                             recv_cap=ex.recv_cap)
        dest2 = salt_fn(dest, g_live)
        st2 = ex.plan_call(lanes, g_live, dest2)
        kd1, outs1, ng1, rc1 = merge_once(ex, st2)
        (k1, kv1) = kd1
        live2, true_dest = relabel_fn_for(rc1)(k1, kv1, ng1)
        lanes2 = [k1, kv1] + [x for d, v in outs1 for x in (d, v)]
        ex2 = ex2_cache.get(rc1)
        if ex2 is None:
            ex2 = RaggedExchange(mesh, nlanes=n_lanes, cap=rc1,
                                 recv_cap=2 * rc1, kinds=kinds,
                                 conf=conf)
            ex2_cache[rc1] = ex2
        st3 = ex2.plan_call(lanes2, live2, true_dest)
        kd2, outs2, ng2, _ = merge_once(ex2, st3)
        return kd2, outs2, ng2

    shard = NamedSharding(mesh, spec)
    return run, shard


def distributed_groupby_step(mesh: Mesh, key_dtype: t.DataType,
                             agg_specs: List[G.AggSpec], local_cap: int,
                             conf=None):
    """The fused distributed groupby entry point, retired ONTO the
    ragged pipeline: the old single-program (P, C) bucket stack (P full
    stable argsorts + P x C staging per lane, worst-case-skew padded)
    is gone — this is now an alias of `distributed_groupby_ragged`,
    whose staging is one dest-lexsort + O(C) quota slabs and whose wire
    format is compressed (25x less per-row work at 1M rows/device).

    Kept as a separate name so callers expressing "the fused step"
    keep working; same signature, same result layout contract (merge
    outputs sharded over the mesh, per-shard group counts)."""
    return distributed_groupby_ragged(mesh, key_dtype, agg_specs,
                                      local_cap, conf=conf)


# ---------------------------------------------------------------------------
# Distributed sort + co-partitioned join over the ragged exchange
# ---------------------------------------------------------------------------

def distributed_sort(mesh: Mesh, keys, vals, live, boundaries):
    """Global sort across the mesh: range-partition rows by the boundary
    table (the GpuRangePartitioner role), ragged-exchange each range to
    its owner chip, then one local lexsort per shard.  Shard s ends up
    holding the s-th global value range in sorted order.

    keys/vals/live: (n_devices*cap,) sharded int64/int64/bool.
    boundaries: host np array of P-1 ascending split points.
    Returns (sorted keys, sorted vals, live) per the exchange layout."""
    nparts = mesh.devices.size
    axis = mesh.axis_names[0]
    cap = keys.shape[0] // nparts
    b = jnp.asarray(np.asarray(boundaries, np.int64))

    def dest_fn(k, lv):
        from ..ops.search import searchsorted
        d = searchsorted(b, k, side="right").astype(jnp.int32)
        return jnp.where(lv, d, 0)
    dest = jax.jit(dest_fn)(keys, live)

    ex = RaggedExchange(mesh, nlanes=2, cap=cap)
    (rk, rv), rlive, _ = ex([keys, vals], live, dest)

    spec = P(axis)

    def local_sort(k, v, lv):
        order = jnp.lexsort((k, (~lv).astype(jnp.int8)))
        return k[order], v[order], lv[order]

    fn = jax.jit(shard_map(local_sort, mesh=mesh,
                               in_specs=(spec, spec, spec),
                               out_specs=(spec, spec, spec),
                               check_vma=False))
    return fn(rk, rv, rlive)


def co_partitioned_join_count(mesh: Mesh, lk, llive, rk, rlive):
    """Distributed equi-join skeleton: hash-exchange BOTH sides with the
    same partitioner (each key owned by exactly one chip), then a local
    sorted-probe count per shard.  Returns the per-shard pair counts —
    their sum is the global inner-join cardinality, which validates the
    co-partitioning layout the full join exec runs on."""
    nparts = mesh.devices.size
    axis = mesh.axis_names[0]
    lcap = lk.shape[0] // nparts
    rcap = rk.shape[0] // nparts

    dest_l = jax.jit(lambda k, lv: partition_ids(k, lv, nparts))(lk, llive)
    dest_r = jax.jit(lambda k, lv: partition_ids(k, lv, nparts))(rk, rlive)

    exl = RaggedExchange(mesh, nlanes=1, cap=lcap)
    (elk,), ellive, _ = exl([lk], llive, dest_l)
    exr = RaggedExchange(mesh, nlanes=1, cap=rcap)
    (erk,), errive, _ = exr([rk], rlive, dest_r)

    spec = P(axis)
    big = jnp.int64(2 ** 63 - 1)   # dead-row fill, clamped out below

    def local_count(lks, llv, rks, rlv):
        # dead rows sort to the int64-max tail; clamping both search
        # bounds to the live-prefix length keeps the count exact even for
        # genuine int64-max keys (everything below nlive with that value
        # is live by construction)
        rs = jnp.sort(jnp.where(rlv, rks, big))
        nlive = jnp.sum(rlv, dtype=jnp.int64)
        from ..ops.search import searchsorted
        lo = jnp.minimum(searchsorted(rs, lks, side="left"), nlive)
        hi = jnp.minimum(searchsorted(rs, lks, side="right"), nlive)
        return jnp.sum(jnp.where(llv, hi - lo, 0),
                       dtype=jnp.int64)[None]

    fn = jax.jit(shard_map(local_count, mesh=mesh,
                               in_specs=(spec, spec, spec, spec),
                               out_specs=spec, check_vma=False))
    return fn(elk, ellive, erk, errive)


def distributed_window_rank(mesh: Mesh, part_keys, order_keys, live):
    """Window rank() over the mesh: hash-exchange rows so every window
    PARTITION lands wholly on one chip (the reference's pre-window
    hash exchange), then one local sort + segment rank per shard —
    the mesh-path analogue of exec/window.py's partition machinery.

    part_keys/order_keys/live: (n_devices*cap,) sharded int64/int64/bool.
    Returns (part_keys, order_keys, rank, live) in the exchange layout:
    rank is Spark rank() (ties share, gaps after)."""
    nparts = mesh.devices.size
    axis = mesh.axis_names[0]
    cap = part_keys.shape[0] // nparts

    def dest_fn(k, lv):
        h = hash_int64(k.astype(jnp.int64), jnp.uint32(42))
        return jnp.where(lv, (h % jnp.uint32(nparts)).astype(jnp.int32),
                         0)
    dest = jax.jit(dest_fn)(part_keys, live)

    ex = RaggedExchange(mesh, nlanes=2, cap=cap)
    (pk, ok), rlive, _ = ex([part_keys, order_keys], live, dest)

    spec = P(axis)

    def local_rank(pk, ok, lv):
        n = pk.shape[0]
        order = jnp.lexsort((ok, pk, (~lv).astype(jnp.int8)))
        s_pk, s_ok, s_lv = pk[order], ok[order], lv[order]
        first = jnp.concatenate([jnp.ones((1,), bool),
                                 s_pk[1:] != s_pk[:-1]])
        peer = first | jnp.concatenate([jnp.ones((1,), bool),
                                        s_ok[1:] != s_ok[:-1]])
        idx = jnp.arange(n, dtype=jnp.int64)
        from ..ops.kernels import blocked_cummax
        part_start = blocked_cummax(
            jnp.where(first, idx, jnp.int64(-1)).astype(jnp.int64))
        peer_start = blocked_cummax(
            jnp.where(peer, idx, jnp.int64(-1)).astype(jnp.int64))
        s_rank = peer_start - part_start + 1
        # invert the sort: rank back in exchange-layout row order
        inv = jnp.argsort(order)
        return pk, ok, s_rank[inv], lv

    fn = jax.jit(shard_map(local_rank, mesh=mesh,
                               in_specs=(spec, spec, spec),
                               out_specs=(spec, spec, spec, spec),
                               check_vma=False))
    return fn(pk, ok, rlive)
