#!/usr/bin/env python
"""A profiler trace (`.xplane.pb`) by plan operator and by layer boundary.

    python scripts/trace_by_operator.py <file.xplane.pb> [top]

Two tables, both on the profiler's one clock:

  * device self time by plan node and op kind.  A whole-plan program is
    traced with every operator inside `jax.named_scope(<node id>)`
    (exec/compiled.py), so an op's `op_name` reads
    `jit(run)/SortExec#0/HashAggregateExec#1/HashJoinExec#4/...`; the op
    belongs to the innermost node.  The ids are EXPLAIN's.  Ops traced at
    a program boundary stand in a row of their own: `<node>/sink` (a
    program's end: deferred lanes resolved for the fetch) and
    `<node>/seam` (the program that resolves a split plan's seam batch
    after its row-count sync).
  * host time by `tpu.*` span (obs/tracer.CollectSpan) inside each
    `collect:<query>` annotation, each span's own time (nested spans taken
    out), and how much of it the device sat idle.

CAVEAT: jax's compile-cache key leaves debug info out, so a program
loaded from a cache entry that an older build wrote carries no node
names.  Take the trace with an empty cache (`JAX_COMPILATION_CACHE_DIR`
at an empty directory; docs/PROFILING.md section 9).

The arithmetic (self times, interval unions, gaps) is the benchmark's own,
benchmarks/harness/trace_reduce.py.  What `jax.profiler.ProfileData` does
not hand out, the `op_name` that the profiler keeps per op in the
plane's event metadata (stat `tf_op`), is read off the file's protobuf
wire format here.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import os
import re
import sys
from typing import Dict, Iterator, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(ROOT, "spark_rapids_tpu")
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
from harness.trace_reduce import (ANNOTATION, DEVICE_PLANE, clip,  # noqa: E402
                                  fold, gaps, length, load, self_times,
                                  union)

# a node id, and the program boundary an op was traced at, if any: the
# sink of a whole-plan program, or a split plan's seam (its own program)
NODE = re.compile(r"([A-Za-z_]\w*#\d+(?:/(?:sink|seam)(?=/))?)")
SPAN = "tpu."
NO_NODE = "(no plan node)"
EAGER = "(eager) "               # + the op's source file
OP_NAME_STAT = "tf_op"          # the profiler's name for HLO `op_name`
PROGRAM_STAT = "program_id"
SOURCE_STAT = "source"          # file:line of the op's innermost frame


# -- the op names, from the protobuf wire format ----------------------------
# XSpace{planes=1} XPlane{name=2, event_metadata=4 (map: value=2),
# stat_metadata=5 (map: value=2)} XEventMetadata{name=2, stats=5}
# XStatMetadata{id=1, name=2} XStat{metadata_id=1, str_value=5, ref_value=7}

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, varint value or bytes) of one message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield field, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def op_names(path: str) -> Tuple[Dict[str, set], Dict[str, set],
                                 Dict[str, str]]:
    """({device event name (the HLO line): the `op_name`s its metadata
    holds}, {event name: the ids of the programs that hold it}, {event
    name: the source file of the op}) over the device planes; more than one
    name or program where two programs hold the same line."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = f.read()
    ops: Dict[str, set] = collections.defaultdict(set)
    programs: Dict[str, set] = collections.defaultdict(set)
    sources: Dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = collections.defaultdict(list)
        for f_, v in _fields(plane):
            parts[f_].append(v)
        name = parts[2][0].decode() if parts[2] else ""
        if not DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for entry in parts[5]:
            meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
            stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
        for entry in parts[4]:
            event_name = ""
            for f_, v in _fields(dict(_fields(entry)).get(2, b"")):
                if f_ == 2:
                    event_name = v.decode()
                elif f_ == 5:
                    stat = dict(_fields(v))
                    kind = stat_names.get(stat.get(1))
                    if kind == OP_NAME_STAT:
                        ops[event_name].add(
                            stat[5].decode(errors="replace") if 5 in stat
                            else stat_names.get(stat.get(7), ""))
                    elif kind == PROGRAM_STAT:
                        programs[event_name].add(stat.get(3, stat.get(4)))
                    elif kind == SOURCE_STAT and 5 in stat:
                        sources[event_name] = stat[5].decode(
                            errors="replace").rsplit(":", 1)[0]
    return ops, programs, sources


def node_of(texts: set) -> str:
    """The innermost plan node of an op's `op_name`(s), with `/sink` or
    `/seam` where the op was traced at that boundary of the node."""
    nodes = {(NODE.findall(t) or [NO_NODE])[-1] for t in texts} or {NO_NODE}
    return nodes.pop() if len(nodes) == 1 else "(several nodes)"


# -- the two tables ---------------------------------------------------------

def device_by_node(device_lines, annotations, names, programs,
                   sources) -> dict:
    """Self time of the TensorCore's ops inside each `collect:<query>`
    annotation (node ids are a plan's own: `HashJoinExec#4` of q3 is not
    q5's), by (query, plan node) and by (query, plan node, op kind),
    seconds per chip; `annotations` sorted by start.  A program none of
    whose ops names a node is not a whole-plan program: the eager programs
    between segments (a seam's compaction and slices) go under EAGER and
    the file that traced the op.  So does every op of a trace in which no
    program names a node (see CAVEAT): it is then read by source file."""
    # (a line as plain as `iota()` can stand in two programs: only a line
    # that one program alone holds says what that program is)
    planned = {pid for name, texts in names.items()
               if node_of(texts) != NO_NODE
               and len(programs.get(name, ())) == 1
               for pid in programs[name]}
    starts = [a[1] for a in annotations]
    by_node: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    by_kind: Dict[Tuple[str, str, str], float] = \
        collections.defaultdict(float)
    for chip in device_lines:
        inside = []
        for name, s, e in chip["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < annotations[i][2]:
                inside.append(((name, annotations[i][0]), s,
                               min(e, annotations[i][2])))
        for (name, query), ns in self_times(inside).items():
            node = node_of(names.get(name, ()))
            if node == NO_NODE and not planned & programs.get(name, set()):
                node = EAGER + (os.path.relpath(sources[name], PACKAGE)
                                if name in sources else "?")
            by_node[query, node] += ns
            by_kind[query, node, fold(name)] += ns
    chips = max(len(device_lines), 1)
    return {"by_node": {k: v / chips / 1e9 for k, v in by_node.items()},
            "by_kind": {k: v / chips / 1e9 for k, v in by_kind.items()}}


def host_by_span(host_events, annotations, busy) -> dict:
    """Per query name: collects, and per `tpu.*` span its own time and the
    part of it in which the device was idle, ms a collect."""
    spans = sorted((e for e in host_events if e[0].startswith(SPAN)),
                   key=lambda e: (e[1], -e[2]))
    out = {}
    for query in sorted({a[0] for a in annotations}):
        mine = [a for a in annotations if a[0] == query]
        own: Dict[str, float] = collections.defaultdict(float)
        idle: Dict[str, float] = collections.defaultdict(float)
        wall = covered = whole = 0.0
        for _n, lo, hi in mine:
            inside = [e for e in spans if lo <= e[1] and e[2] <= hi]
            wall += hi - lo
            covered += length(union((s, e) for _n, s, e in inside))
            for i, (name, s, e) in enumerate(inside):
                nested = [(s2, e2) for _n2, s2, e2 in inside[i + 1:]
                          if s2 < e and s <= s2 and e2 <= e]
                alone = gaps(union(nested), s, e)
                own[name] += length(alone)
                if name == SPAN + "collect":
                    whole += e - s
                idle[name] += sum(length(gaps(clip(busy, a, b), a, b))
                                  for a, b in alone)
        n = len(mine)
        out[query] = {
            "collects": n, "wall_ms": wall / n / 1e6,
            "in_spans_pct": 100.0 * covered / wall if wall else 0.0,
            "children_pct": 100.0 * (1.0 - own[SPAN + "collect"] / whole)
            if whole else 0.0,
            "spans": {k: (own[k] / n / 1e6, idle[k] / n / 1e6)
                      for k in own}}
    return out


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__)
        return 2
    path, top = argv[1], int(argv[2]) if len(argv) > 2 else 10
    device_lines, annotations, seen = load(path)
    annotations = sorted(annotations, key=lambda a: a[1])
    if not annotations:
        print(f"no `{ANNOTATION}<query>` annotation in {path}: nothing to "
              "cut the trace by")
        return 1
    names, programs, sources = op_names(path)
    dev = device_by_node(device_lines, annotations, names, programs, sources)
    total = sum(dev["by_node"].values())
    print(f"device self time {total:.4f} s in {len(annotations)} collects "
          f"({len(names)} named ops)")

    def named_share(of: Dict[str, float]) -> str:
        all_ = sum(of.values())
        planned = sum(v for k, v in of.items() if not k.startswith(EAGER))
        one = planned - of.get(NO_NODE, 0.0) - of.get("(several nodes)", 0.0)
        return (f"{all_:.4f} s, {100.0 * one / all_:.1f}% names one plan "
                f"node ({100.0 * one / planned if planned else 0.0:.1f}% of "
                f"the {planned:.4f} s inside whole-plan programs)")

    def summed(rows, at: int) -> Dict[str, float]:
        out: Dict[str, float] = collections.defaultdict(float)
        for key, v in rows:
            out[key[at]] += v
        return out

    if total:
        print("  all ops: " + named_share(summed(dev["by_node"].items(), 1)))
        if all(k[1].startswith(EAGER) for k in dev["by_node"]):
            print("  no program names a node: programs loaded from an older "
                  "cache entry carry no names (see CAVEAT)")
    for kind in ("fusion.kCustom", "sort"):
        of_kind = summed(((k, v) for k, v in dev["by_kind"].items()
                          if k[2] == kind), 1)
        if of_kind:
            print(f"  {kind}: " + named_share(of_kind))
    for query, in_query in sorted(summed(dev["by_node"].items(), 0).items()):
        print(f"{query}: device self time {in_query:.4f} s")
        nodes = sorted(((k[1], v) for k, v in dev["by_node"].items()
                        if k[0] == query), key=lambda kv: -kv[1])[:top]
        for node, s in nodes:
            kinds = sorted(((k[2], v) for k, v in dev["by_kind"].items()
                            if k[:2] == (query, node)),
                           key=lambda kv: -kv[1])[:3]
            print(f"  {node:<34} {s:9.4f} s {100.0 * s / in_query:5.1f}%   "
                  + ", ".join(f"{k} {v:.4f}" for k, v in kinds))

    busy = union((s, e) for chip in device_lines
                 for line in (chip["ops"], chip.get("async", []))
                 for _n, s, e in line)
    host = [e for plane, lines in seen.items() if plane.startswith("/host:")
            for events in lines.values() for e in events]
    for query, row in host_by_span(host, annotations, busy).items():
        print(f"{query}: {row['collects']} collects, {row['wall_ms']:.3f} ms "
              f"each, {row['in_spans_pct']:.1f}% inside tpu.collect, "
              f"{row['children_pct']:.1f}% of which its child spans cover")
        print(f"  {'span':<18} {'own ms':>10} {'device idle ms':>15}")
        for name, (own, idle) in sorted(row["spans"].items(),
                                        key=lambda kv: -kv[1][0]):
            print(f"  {name:<18} {own:>10.3f} {idle:>15.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
