#!/usr/bin/env bash
# How PR 24's chip runs were grouped: one phase per chip-tool call, so that
# every run of a cell shares one compile cache (<checkout>/.jax_cache lives
# only as long as the call's machine).  From the repo root:
#
#   chiprun --timeout 3000 -- bash benchmarks/chip_runs.sh <phase> <cell> [seconds] [cold]
#
# `cold` points JAX_COMPILATION_CACHE_DIR at an empty directory of the
# checkout, so that the phase's first run compiles as the driver's will.
#
# OUT=<dir> writes there and not under chiprun_out/ (a run from an unpacked
# `git archive` tree writes back to the repo's chiprun_out/ so).
# Phases (each writes chiprun_out/<cell>/<phase>/*.out|err and a summary):
#   look     one traced run with a 12-query slice kept as .xplane.pb, dumped
#            by trace_reduce.py for the by-hand look; then two plain runs
#   sets     two sets of 6 plain runs, the same 6 seeds in both, then 3
#            traced runs of 10 s on fresh seeds
#   seeds    6 short plain runs on fresh seeds
#   controls the float32 control on 3 seeds, at the cell's own size
#   length   3 plain runs of <seconds> on fresh seeds: a window length tried
set -u
phase=$1; cell=$2; seconds=${3:-30}
out=${OUT:-chiprun_out}/$cell/$phase; mkdir -p "$out"
if [ "${4:-}" = cold ]; then export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache_cold; fi
run() {  # run <tag> <args...>: one process, result line kept in summary.txt
  local tag=$1; shift
  python3 benchmarks/run.py --workload "$cell" "$@" >"$out/$tag.out" 2>"$out/$tag.err"
  echo "rc=$? $tag $*" >>"$out/summary.txt"
  tail -n 1 "$out/$tag.out" >>"$out/summary.txt"
  grep -E "data SF|warm-up round|first collect|window |collects, wall|FAILED" "$out/$tag.out" >>"$out/setup.txt"
}
case $phase in
  look)
    run t201 --seed 201 --seconds 10 --trace 1 --trace-queries 12 --keep-trace "$out/slice.xplane.pb"
    python3 benchmarks/harness/trace_reduce.py "$out/slice.xplane.pb" >"$out/dump.txt" 2>&1
    run p202 --seed 202 --seconds "$seconds" --trace 0
    run p203 --seed 203 --seconds "$seconds" --trace 0 ;;
  sets)
    for set in A B; do for seed in 2147484659 2147484693 2147484713 3000001019 3000001037 4294968311; do
      run "$set$seed" --seed $seed --seconds "$seconds" --trace 0; done; done
    for seed in 2147484743 3000001077 4294968357; do
      run "T$seed" --seed $seed --seconds 10 --trace 1; done ;;
  seeds)
    for seed in 11 2147483777 2147483783 3000000079 3000000103 4294967377; do
      run "S$seed" --seed $seed --seconds 10 --trace 0; done ;;
  controls)
    for seed in 2147484659 3000001019 4294968311; do
      run "C$seed" --seed $seed --seconds 5 --trace 0 --control float32_money; done ;;
  length)
    for seed in 2147483869 3000000121 4294967497; do
      run "L$seed" --seed $seed --seconds "$seconds" --trace 0; done ;;
  *) echo "unknown phase $phase" >&2; exit 2 ;;
esac
cat "$out/summary.txt"
