#!/usr/bin/env python3
"""The probe of a small sorted build side, alone on the chip.

    python3 scripts/probe_microbench.py [--rows N] [--reps R]   (on a TPU)

Probes N (default 4,194,304) int64 keys against a sorted single-lane build
(ops/join.py BuildTable without a domain) of L live keys, L = 64, 650,
4,096, 8,192 (and 16,384 to see past SMALL_BUILD_CAP), and times, on the
host clock around `block_until_ready`, the median of R runs of:

  * `aligned merge`: `_aligned_merge`, the merge-rank search (two sorts of
    N + capacity rows and a cumsum) and the candidate gather, as a unique
    build over SMALL_BUILD_CAP rows is probed;
  * `semi merge`: `_matched_merge`, two merge-rank searches (four sorts),
    as a semi or anti join probes such a build;
  * `compare`: `_small_build_lookup`, every probe key compared with the
    live keys, ops/join.py `_CMP_BLOCK` of them a step;
  * `aligned compare` / `semi compare`: `_aligned_compare` and
    `_matched_compare`, the comparison as `probe_aligned` and
    `probe_matched_lazy` run it at a build of at most SMALL_BUILD_CAP rows.

The build's capacity is 16,384 rows whatever L (the merge path's sorts
run over N + capacity rows either way, and the comparison walks the live
keys alone; one capacity compiles each program once); a quarter of the
probe keys are live build keys, -1 and keys above 2**32 among them.  Every case is checked against the merge path on the
device, bit for bit.  `compare_ms.<b>` times the compare at a block of b
keys (`--blocks`) besides ops/join.py's own.  Lines go to stdout and
chiprun_out/probe_microbench.json; `ps_per_pair` is the compare's time
over N x L.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.getcwd())

import jax                                                   # noqa: E402
import jax.numpy as jnp                                      # noqa: E402
import numpy as np                                           # noqa: E402

from spark_rapids_tpu.ops import join as J                   # noqa: E402

LIVE = (64, 650, 4_096, 8_192, 16_384)
CAPACITY = 16_384


def timed(fn, args, reps):
    jax.block_until_ready(fn(*args))            # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1000.0)
    return statistics.median(ts)


def build_side(live, rng):
    """(perm, sorted_hash, valid_count) of a unique build of `live` keys
    at CAPACITY rows, laid out as BuildTable._sort leaves it: live keys
    ascending, then the sentinel."""
    cap = CAPACITY
    keys = np.unique(rng.integers(-2**40, 2**40, live + 64))[:live]
    keys[:2] = [-1, 2**33 + 7]
    keys = np.sort(keys).astype(np.int64)
    sorted_hash = np.full(cap, np.uint64(2**64 - 1), np.uint64)
    sorted_hash[:live] = keys.view(np.uint64)
    order = np.argsort(sorted_hash, kind="stable")
    sorted_hash = sorted_hash[order]
    perm = rng.permutation(cap).astype(np.int32)
    return keys, cap, (jnp.asarray(perm), jnp.asarray(sorted_hash),
                       jnp.int32(live))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4_194_304)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--blocks", default="128,512")
    args = ap.parse_args()
    n, reps = args.rows, args.reps
    dev = jax.devices()[0]
    rng = np.random.default_rng(38)
    out = {"device": dev.device_kind, "platform": dev.platform, "rows": n,
           "reps": reps, "small_build_cap": J.SMALL_BUILD_CAP,
           "block": J._CMP_BLOCK, "cases": []}
    aligned_merge = jax.jit(J._aligned_merge)
    semi_merge = jax.jit(J._matched_merge)
    compare = jax.jit(J._small_build_lookup)
    aligned_compare = jax.jit(J._aligned_compare)
    semi_compare = jax.jit(J._matched_compare)
    bad = []
    for live in LIVE:
        keys, cap, (perm, sorted_hash, vc) = build_side(live, rng)
        probe = rng.integers(-2**40, 2**40, n)
        hit = rng.random(n) < 0.25
        probe[hit] = keys[rng.integers(0, live, int(hit.sum()))]
        probe[:4] = [-1, 2**33 + 7, -2, 2**40]
        h = jnp.asarray(probe.astype(np.int64)).astype(jnp.uint64)
        pv = jnp.asarray(rng.random(n) < 0.97)

        want_row, want_ok = aligned_merge(perm, sorted_hash, vc, h, pv)
        got_row, got_ok = aligned_compare(perm, sorted_hash, vc, h, pv)
        ok_m, ok_c = np.asarray(want_ok), np.asarray(got_ok)
        if not (np.array_equal(ok_m, ok_c) and np.array_equal(
                np.asarray(want_row)[ok_m], np.asarray(got_row)[ok_c])):
            bad.append(f"aligned {live}")
        if not np.array_equal(np.asarray(semi_merge(perm, sorted_hash, vc,
                                                    h, pv)),
                              np.asarray(semi_compare(perm, sorted_hash, vc,
                                                      h, pv))):
            bad.append(f"semi {live}")
        a = (perm, sorted_hash, vc, h, pv)
        rec = {"live": live, "capacity": cap,
               "matches": int(ok_m.sum()),
               "aligned_merge_ms": timed(aligned_merge, a, reps),
               "semi_merge_ms": timed(semi_merge, a, reps),
               "compare_ms": timed(compare, (sorted_hash, perm, vc, h),
                                   reps),
               "aligned_compare_ms": timed(aligned_compare, a, reps),
               "semi_compare_ms": timed(semi_compare, a, reps)}
        rec["ps_per_pair"] = rec["compare_ms"] * 1e9 / (n * live)
        own = J._CMP_BLOCK
        for b in (int(x) for x in args.blocks.split(",") if x):
            J._CMP_BLOCK = b            # read while the lookup is traced
            rec[f"compare_ms.{b}"] = timed(
                jax.jit(J._small_build_lookup), (sorted_hash, perm, vc, h),
                reps)
        J._CMP_BLOCK = own
        out["cases"].append(rec)
        print(" ".join(f"{k}={v:.4f}" if isinstance(v, float) else
                       f"{k}={v}" for k, v in rec.items()), flush=True)
    out["bit_exact"] = not bad
    out["mismatches"] = bad
    print("BIT_EXACT", not bad, bad, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probe_microbench.json", "w") as f:
        json.dump(out, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
