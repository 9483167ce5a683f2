#!/usr/bin/env python3
"""Which whole-plan programs does a benchmark cell compile, under which keys?

    cd <tree> && python3 <this file> --workload <cell> --seed 7 --seconds 1 \
        --trace 0 --rehearse-cpu --scale 0.002

Runs the cell's CPU rehearsal IN THE TREE AT THE WORKING DIRECTORY (its
`benchmarks/run.py`, its `spark_rapids_tpu`) and prints one `PROGRAM` line
for every program the run compiles: the root node, the number of inputs,
a hash of the program's process-wide cache key, a hash and the length of
its StableHLO lowered for platform `tpu` (jax's Python lowering: no chip
needed).  Run it in two trees (this one and `git archive <parent>` unpacked
beside it, the cell's benchmark files laid over) and `diff` the PROGRAM
lines: equal lines mean the change hands XLA the parent's programs letter
for letter under the parent's keys, which is how PRs 29 and 32 showed that
the one-chip cells could not move.  The lines hold no time and no device
number.
"""
import hashlib
import os
import sys

ROOT = os.getcwd()
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]
sys.argv[0] = os.path.join(ROOT, "benchmarks", "run.py")

import jax                                                   # noqa: E402
from spark_rapids_tpu.exec import compiled as C              # noqa: E402

_aot_compile = C.CompiledPlan.aot_compile
_seen = set()


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _canon(key) -> str:
    """`repr(key)` with every set in sorted order: a set of strings prints
    in the order of the process's hash seed."""
    if isinstance(key, (tuple, list)):
        return "(" + ", ".join(_canon(k) for k in key) + ")"
    if isinstance(key, (set, frozenset)):
        return "{" + ", ".join(sorted(_canon(k) for k in key)) + "}"
    if isinstance(key, dict):
        return _canon(sorted((_canon(k), _canon(v)) for k, v in key.items()))
    return repr(key)


def aot_compile(self, ctx, flat_in=None, in_specs=None, pairs=None):
    if flat_in is None:
        pairs = self._leaf_batches(ctx)
        flat_in, in_specs = self._flatten_inputs(pairs)
    key = self._build_cache_key(flat_in, in_specs)
    shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in flat_in]
    with C._TRACE_LOCK:
        text = jax.jit(self._make_runner(in_specs, ctx, {})).trace(
            shapes).lower(lowering_platforms=("tpu",)).as_text()
    _seen.add((type(self.root).__name__, len(flat_in), _sha(_canon(key)),
               _sha(text), len(text)))
    return _aot_compile(self, ctx, flat_in, in_specs, pairs)


def main() -> int:
    C.CompiledPlan.aot_compile = aot_compile
    import run as bench_run
    rc = bench_run.main()
    for row in sorted(_seen):
        print("PROGRAM", *row)
    return rc


if __name__ == "__main__":
    sys.exit(main())
