#!/usr/bin/env python
"""Profile one TPC-H query's warm whole-plan run on the real chip.

Usage: python scripts/profile_q3.py [query] [scale]
Writes a profiler trace to /tmp/jaxprof (open the xplane.pb with
tensorboard_plugin_profile, or parse it directly — see git history for
a snippet) and prints cold/warm timings.  The compile cache is placed by
TpuSession() (exec/compiled.configure_persistent_cache).
"""
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

qname = sys.argv[1] if len(sys.argv) > 1 else "q3"
scale = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0

from spark_rapids_tpu import tpch
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.session import TpuSession

t0 = time.perf_counter()
tables = tpch.gen_tables(scale=scale)
print(f"datagen {time.perf_counter()-t0:.1f}s", file=sys.stderr)

dev = TpuSession()
dfq = tpch.QUERIES[qname](dev, tables)
q = dfq.physical()

t0 = time.perf_counter()
out = q.collect(ExecContext(dev.conf))
print(f"cold: {time.perf_counter()-t0:.1f}s", file=sys.stderr)

for i in range(2):
    t0 = time.perf_counter()
    out = q.collect(ExecContext(dev.conf))
    print(f"warm{i}: {time.perf_counter()-t0:.2f}s", file=sys.stderr)

import shutil
shutil.rmtree("/tmp/jaxprof", ignore_errors=True)
with jax.profiler.trace("/tmp/jaxprof"):
    t0 = time.perf_counter()
    out = q.collect(ExecContext(dev.conf))
    wall = time.perf_counter() - t0
print(f"profiled warm: {wall:.2f}s", file=sys.stderr)
print(out.to_pydict() if out.num_rows < 5 else f"{out.num_rows} rows",
      file=sys.stderr)
