#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, before making any data, when jax finds no TPU or fewer chips
than the cell asks for.  `--rehearse-cpu [--scale 0.002]` is the sandbox dry
run: every line it prints says so, the result line included, and it is never
a fallback.  The last stdout line of a chip run is one JSON object
(`correct`, `attempted`, `failed`, `metrics`, `device`, traced: `breakdown`,
last: `checks`).  Everything else is in benchmarks/harness/.
"""
import time
T0 = time.perf_counter()                # set-up is counted from here

import argparse                          # noqa: E402
import os                                # noqa: E402
import sys                               # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="sandbox dry run on the CPU backend")
    ap.add_argument("--scale", type=float, default=None,
                    help="scale factor of the dry run (--rehearse-cpu only)")
    ap.add_argument("--trace-queries", type=int, default=None,
                    help="length of the traced slice (default: the "
                         "workload file's)")
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="copy the traced slice's .xplane.pb here")
    ap.add_argument("--control", default=None, metavar="NAME",
                    help="put a stand-in from benchmarks/harness/controls.py "
                         "under the run (the lower-precision control and "
                         "the planted faults; never a benchmark run)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import cell
    tamper = None
    if args.control:
        from harness import controls
        tamper = controls.BY_NAME[args.control]()
    line = cell.run_cell(args, T0, tamper)
    prefix = "REHEARSAL platform=cpu " if args.rehearse_cpu else ""
    if args.control:
        prefix += f"CONTROL {args.control} "
    cell.print_result(line, prefix)
    return 0


if __name__ == "__main__":
    sys.exit(main())
