"""A field of the profiler trace's reduction (harness/trace_reduce.py, plus
the roofline share worked out in harness/cell.py).  None where the run took
no trace or the trace holds no device plane."""


def read(spec, run):
    return (run.get("trace") or {}).get(spec["key"])
