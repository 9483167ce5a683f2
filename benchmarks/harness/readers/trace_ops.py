"""Device time of the ops named in `spec["names"]`, as a share (%) of the
traced slice's device busy time: the sum over the reduction's `device_ops`
(harness/trace_reduce.py keeps the ten longest, mean of the chips) of those
whose folded name is one of the names or starts with one (`all-reduce`,
`all-reduce-start`, `all-reduce.kLoop`).  An op below the ten longest is not
in the reduction and counts 0.  None where the run took no trace or the
trace holds no device plane."""


def read(spec, run):
    trace = run.get("trace") or {}
    ops, busy_s = trace.get("device_ops"), trace.get("busy_s")
    if ops is None or not busy_s:
        return None
    names = tuple(spec["names"])
    return 100.0 * sum(s for name, s in ops if name.startswith(names)) / busy_s
