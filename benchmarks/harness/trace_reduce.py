"""From a profiler trace (`.xplane.pb`) to device busy / idle time, per-op
device time and labelled idle gaps.  Read with nothing but jax
(`jax.profiler.ProfileData`); the arithmetic works on plain tuples so that
it can be checked on synthetic intervals (benchmarks/tests).

Busy is the UNION of the device-op intervals inside the traced slice, never
their sum: ops nest (a `while` holds its body's ops) and DMA runs beside
them.  The
slice runs from the start of the first `collect:<query>` annotation to the
end of the last, so profiler start-up and shutdown are outside it.

    python benchmarks/harness/trace_reduce.py <file.xplane.pb>   # look by hand
"""
from __future__ import annotations

import bisect
import gzip
import collections
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]                 # start, end (ns)
Event = Tuple[str, float, float]               # name, start, end (ns)

ANNOTATION = "collect:"
BETWEEN = "between-collects"
# Looked at by hand on the first chip trace (PR 24, PERF.md section 5): every
# chip is a plane "/device:TPU:<n>" with the lines "XLA Modules" (one event
# per program run), "XLA Ops" (the TensorCore's HLO ops, nested under `while`
# and the like), "Async XLA Ops" (DMA: slice-start..done, copy-start..done,
# running beside them) and "TC Overlay".  Busy is the union of the two op
# lines; per-op time is read off "XLA Ops" alone.  The `collect:<query>`
# annotations are on plane "/host:CPU", line "python3", on the same clock.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping intervals."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What [lo, hi] holds besides the (merged) busy intervals."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, float]:
    """Per-event-name time with nested children taken out, so that the
    names add up to the busy time of a properly nested line."""
    total: Dict[str, float] = collections.defaultdict(float)
    stack: List[List] = []                     # [name, end, covered-from]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, end, since = stack.pop()
            total[name] += max(0.0, end - since)
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        while stack and e > stack[-1][1]:      # overlaps, not nested: the
            stack[-1][1] = s                   # earlier one ends here
            close(s)
        if stack:                              # parent ran alone up to here
            total[stack[-1][0]] += max(0.0, s - stack[-1][2])
            stack[-1][2] = max(stack[-1][2], s)
        stack.append([name, e, s])
    close(float("inf"))
    return dict(total)


def fold(op_name: str) -> str:
    """XLA's own op name folded by category.  On the TPU an event is named
    by its whole HLO line (`%fusion.26 = u32[8388608]{...} fusion(...),
    kind=kCustom, calls=...`): keep the instruction's name without `%` and
    numeric suffix (the folding of scripts/xplane_ops.py), and for a fusion
    its kind, which tells a gather/scatter (`kCustom`) from an elementwise
    pass (`kLoop`) -> `fusion.kCustom`, `slice-start`, `sort`."""
    head, _, rest = op_name.partition(" = ")
    head = head.lstrip("%")
    name = head.split(".")[0].rstrip("0123456789_") or head
    kind = re.search(r"\bkind=(k\w+)", rest)
    return f"{name}.{kind.group(1)}" if kind else name


def label_gaps(idle: Sequence[Interval], annotations: Sequence[Event],
               busy: Sequence[Interval]) -> Dict[str, float]:
    """Idle time by what the host was inside: each gap is cut where a
    `collect:<query>` annotation starts or ends; a piece inside a collect
    lies before that collect's first device op (`#lead`: plan, dispatch),
    after its last (`#tail`: sync, fetch, bookkeeping) or between two
    (`#mid`: a seam); a piece outside every collect is `between-collects`.
    Annotations come from one client, so they do not overlap."""
    out: Dict[str, float] = collections.defaultdict(float)
    starts = [s for s, _e in busy]
    spans = []                       # (start, end, name, first op, last op)
    for name, s, e in annotations:
        i, j = bisect.bisect_left(starts, s), bisect.bisect_left(starts, e)
        first = busy[i][0] if i < j else e
        last = busy[j - 1][1] if i < j else s
        spans.append((s, e, name, first, last))
    cuts = sorted({t for s, e, *_ in spans for t in (s, e)})
    span_starts = [sp[0] for sp in spans]
    for gs, ge in idle:
        lo_i, hi_i = bisect.bisect_right(cuts, gs), bisect.bisect_left(cuts, ge)
        edges = [gs] + cuts[lo_i:hi_i] + [ge]
        for ps, pe in zip(edges, edges[1:]):
            k = bisect.bisect_right(span_starts, ps) - 1
            label = BETWEEN
            if k >= 0 and ps < spans[k][1]:
                _s, _e, name, first, last = spans[k]
                label = name + ("#lead" if ps < first else
                                "#tail" if ps >= last else "#mid")
            out[label] += pe - ps
    return out


def reduce(device_lines: Sequence[dict], annotations: Sequence[Event],
           top: int = 10) -> Optional[dict]:
    """device_lines: per chip {"ops": TensorCore op events, "async": DMA
    events that run beside them}; annotations: the host's `collect:<query>`
    spans.  None where there is nothing to read (no device plane, or no
    annotation): a CPU rehearsal's trace."""
    annotations = sorted((a for a in annotations
                          if a[0].startswith(ANNOTATION)),
                         key=lambda a: a[1])
    if not annotations or not any(c["ops"] for c in device_lines):
        return None
    lo, hi = annotations[0][1], max(a[2] for a in annotations)
    busy_ns = []
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for chip in device_lines:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in chip["ops"]
                  if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for _n, s, e in inside]
                     + clip([(s, e) for _n, s, e in chip.get("async", [])],
                            lo, hi))
        busy_ns.append(length(busy))
        for name, ns in self_times(inside).items():
            ops[fold(name)] += ns
        for label, ns in label_gaps(gaps(busy, lo, hi), annotations,
                                    busy).items():
            idle[label] += ns
    chips = len(busy_ns)
    window_s = (hi - lo) / 1e9
    busy_s = sum(busy_ns) / chips / 1e9
    if busy_s <= 0:
        return None

    def ranked(d):
        return [[k, v / chips / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"window_s": window_s, "busy_s": busy_s, "chips": chips,
            "device_idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "queries": dict(collections.Counter(
                a[0][len(ANNOTATION):] for a in annotations)),
            "device_ops": ranked(ops), "idle_gaps": ranked(idle)}


# -- reading the file -------------------------------------------------------

def load(path: str):
    """-> (per-chip device lines, host annotations, {plane: {line: events}})."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):                     # the tests' kept sample
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device_lines, annotations, seen = [], [], {}
    for plane in data.planes:
        lines = seen.setdefault(plane.name, {})
        for line in plane.lines:
            # several threads can share a line name ("python3"): keep all
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for ev in line.events)
        if DEVICE_PLANE.match(plane.name):
            device_lines.append({"ops": lines.get(OPS_LINE, []),
                                 "async": lines.get(ASYNC_LINE, [])})
        elif plane.name.startswith("/host:"):
            annotations.extend(e for events in lines.values() for e in events
                               if e[0].startswith(ANNOTATION))
    return device_lines, annotations, seen


def reduce_file(path: str) -> Optional[dict]:
    device_lines, annotations, _seen = load(path)
    return reduce(device_lines, annotations)


def main(argv) -> int:
    device_lines, annotations, seen = load(argv[1])
    for plane, lines in seen.items():
        print(f"PLANE {plane}")
        for line, events in lines.items():
            names = collections.Counter(fold(n) for n, _s, _e in events)
            span = (f"{min(e[1] for e in events):.0f}.."
                    f"{max(e[2] for e in events):.0f} ns") if events else "-"
            print(f"  LINE {line!r}: {len(events)} events, {span}; "
                  f"{names.most_common(8)}")
    print(reduce(device_lines, annotations))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
