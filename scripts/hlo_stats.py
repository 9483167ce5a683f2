#!/usr/bin/env python
"""Trace a TPC-H query's whole-plan program and report HLO size stats
WITHOUT the device: runs on the CPU backend, so trace time and program
shape are visible locally (compile on the chip scales with the same
program).

Usage: python scripts/hlo_stats.py q16 [scale]
Prints: trace seconds, jaxpr eqn count, stablehlo op histogram (top 20),
sort op count/operand widths, total lowered text size.
"""
import collections
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax

qname = sys.argv[1] if len(sys.argv) > 1 else "q16"
scale = float(sys.argv[2]) if len(sys.argv) > 2 else 1.0

from spark_rapids_tpu import tpch
from spark_rapids_tpu.exec.compiled import (CompiledPlan, _find_split_seams,
                                            SplitCompiledPlan, _flatten_batch,
                                            _trace_context)
from spark_rapids_tpu.exec.plan import ExecContext
from spark_rapids_tpu.session import TpuSession

t0 = time.perf_counter()
tables = tpch.gen_tables(scale=scale)
print(f"datagen {time.perf_counter()-t0:.1f}s", file=sys.stderr)

dev = TpuSession()
dfq = tpch.QUERIES[qname](dev, tables)
q = dfq.physical()
root = q.root
ctx = ExecContext(dev.conf)

seams = _find_split_seams(root)
print(f"split seams: {[type(s).__name__ for s in seams]}")

plan = CompiledPlan(root, ctx.conf)
pairs = plan._leaf_batches(ctx)
flat_in = []
in_specs = []
for node, dbs in pairs:
    node_specs = []
    for db in dbs:
        arrays, spec = _flatten_batch(db)
        flat_in.extend(arrays)
        node_specs.append(spec)
    in_specs.append((node, node_specs))
print(f"leaf arrays: {len(flat_in)}; "
      f"total in bytes: {sum(a.nbytes for a in flat_in)/1e6:.1f}MB")

from spark_rapids_tpu.exec.compiled import _rebuild_batch

def run(flat):
    i = 0
    for node, node_specs in in_specs:
        batches = []
        for spec in node_specs:
            db, i = _rebuild_batch(flat, spec, i)
            batches.append(db)
        node._trace_batches = batches
    try:
        trace_ctx = _trace_context(ctx)
        outs = list(root.execute(trace_ctx))
    finally:
        for node, _ in in_specs:
            node._trace_batches = None
    flat_out = []
    for db in outs:
        arrays, _ = _flatten_batch(db)
        flat_out.extend(arrays)
    return flat_out

t0 = time.perf_counter()
traced = jax.make_jaxpr(run)(flat_in)
trace_s = time.perf_counter() - t0
n_eqns = len(traced.eqns)

def count_all(jaxpr, ctr):
    for e in jaxpr.eqns:
        ctr[e.primitive.name] += 1
        for sub in e.params.values():
            if hasattr(sub, "jaxpr"):
                count_all(sub.jaxpr, ctr)
ctr = collections.Counter()
count_all(traced.jaxpr, ctr)
print(f"trace: {trace_s:.1f}s, top-level eqns: {n_eqns}, "
      f"total (nested): {sum(ctr.values())}")
print("top prims:", ctr.most_common(25))

t0 = time.perf_counter()
lowered = jax.jit(run).lower(flat_in)
low_s = time.perf_counter() - t0
txt = lowered.as_text()
print(f"lower: {low_s:.1f}s, stablehlo text: {len(txt)/1e6:.1f}MB")
ops = collections.Counter(re.findall(r"stablehlo\.(\w+)", txt))
print("top stablehlo:", ops.most_common(25))
sorts = re.findall(r'"stablehlo.sort"\(([^)]*)\)', txt)
widths = [s.count("%") for s in sorts]
print(f"sort ops: {len(sorts)}, operand widths: "
      f"{collections.Counter(widths).most_common()}")

t0 = time.perf_counter()
comp = lowered.compile()
print(f"CPU compile: {time.perf_counter()-t0:.1f}s", file=sys.stderr)
