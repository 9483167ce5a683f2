#!/usr/bin/env python
"""Decompose a query's warm wall time: device execute vs host fetch.

Usage: python scripts/decompose.py q16 [scale]
Prints: warm wall, execute-only (dispatch+device, synced via scalar),
fetch-only, output capacities/rows/bytes — what attributes a query's
wall to host syncs and transfers vs device work.  The compile cache is
placed by TpuSession() (exec/compiled.configure_persistent_cache).
"""
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

qname = sys.argv[1] if len(sys.argv) > 1 else "q16"
scale = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0

from spark_rapids_tpu import tpch
from spark_rapids_tpu.exec.compiled import CompiledPlan, _find_split_seams, SplitCompiledPlan
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.session import TpuSession

tables = tpch.gen_tables(scale=scale)
dev = TpuSession()
q = tpch.QUERIES[qname](dev, tables).physical()
ctx = ExecContext(dev.conf)

t0 = time.perf_counter()
out = q.collect(ctx)
print(f"cold+upload: {time.perf_counter()-t0:.1f}s", flush=True)
for i in range(2):
    t0 = time.perf_counter()
    out = q.collect(ctx)
    print(f"warm wall{i}: {time.perf_counter()-t0:.2f}s ({out.num_rows} rows)",
          flush=True)

plan = getattr(q, "_compiled_plan", None)
print(f"plan type: {type(plan).__name__}")
if isinstance(plan, CompiledPlan):
    t0 = time.perf_counter()
    outs = plan.execute(ctx)
    # force device completion with ONE tiny fetch
    first = outs[0]
    _ = jax.device_get(first.columns[0].data.ravel()[0])
    t_exec = time.perf_counter() - t0
    tot = 0
    for db in outs:
        cap = db.capacity
        nb = db.nbytes() if hasattr(db, "nbytes") else -1
        n = db.num_rows if isinstance(db.num_rows, int) else "dev"
        print(f"  out batch: cap={cap} rows={n} bytes={nb}")
        tot += nb
    t0 = time.perf_counter()
    from spark_rapids_tpu.columnar.device import to_host
    hbs = [to_host(db) for db in outs]
    t_fetch = time.perf_counter() - t0
    print(f"execute+sync: {t_exec:.2f}s  fetch: {t_fetch:.2f}s  "
          f"out_bytes={tot/1e6:.1f}MB", flush=True)
elif isinstance(plan, SplitCompiledPlan):
    # time each segment
    import spark_rapids_tpu.exec.compiled as C
    t0 = time.perf_counter()
    out = plan.collect(ctx)
    print(f"split collect: {time.perf_counter()-t0:.2f}s; "
          f"segments={len(plan.seams)+1}")
