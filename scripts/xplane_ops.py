#!/usr/bin/env python
"""Summarize a jax.profiler trace: per-op device time from the xplane.

Usage: python scripts/xplane_ops.py /tmp/jaxprof [topN]
Aggregates XLA op events on the device plane by op category (the HLO
fingerprint up to the numeric suffix) and prints total us + count,
descending: the measured per-op breakdown of one trace.
"""
import collections
import glob
import sys

from tensorflow.tsl.profiler.protobuf import xplane_pb2

path = sys.argv[1] if len(sys.argv) > 1 else "/tmp/jaxprof"
topn = int(sys.argv[2]) if len(sys.argv) > 2 else 40

files = glob.glob(path + "/plugins/profile/*/*.xplane.pb")
assert files, f"no xplane under {path}"
sp = xplane_pb2.XSpace()
with open(files[-1], "rb") as f:
    sp.ParseFromString(f.read())

for plane in sp.planes:
    is_dev = ("TPU" in plane.name or "/device" in plane.name.lower()
              or "Accelerator" in plane.name)
    if not is_dev:
        continue
    evmeta = {m.id: m.name for m in plane.event_metadata.values()}
    agg = collections.Counter()
    cnt = collections.Counter()
    total = 0
    for line in plane.lines:
        for ev in line.events:
            name = evmeta.get(ev.metadata_id, "?")
            dur = ev.duration_ps / 1e6  # -> us
            key = name.split(".")[0].rstrip("0123456789_")
            agg[key] += dur
            cnt[key] += 1
            total += dur
    print(f"== plane: {plane.name}  lines={len(plane.lines)} "
          f"total={total/1e3:.1f}ms")
    for k, us in agg.most_common(topn):
        print(f"  {us/1e3:9.2f}ms  n={cnt[k]:5d}  {k}")
