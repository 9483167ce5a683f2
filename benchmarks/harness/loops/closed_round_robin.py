"""One client, closed loop: the next query is sent when the last one's Arrow
table is in hand, round-robin over the cell's query list.  Each call is timed
on the host clock from call to table.  The query in flight when the time is
up completes and counts."""
from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple


@dataclass
class Record:
    query: str
    wall_s: float
    answer: object = None          # pa.Table, None where the call raised
    metrics: Optional[dict] = field(default_factory=dict)  # None: stand-in
    error: Optional[str] = None


@dataclass
class Window:
    start: float
    end: float
    records: List[Record]

    @property
    def seconds(self) -> float:
        return self.end - self.start


def run(queries: Sequence[Tuple[str, Callable]], seconds: float = None,
        count: int = None, inspect: Callable = None,
        annotate: Callable = None,
        clock: Callable[[], float] = time.perf_counter) -> Window:
    """`queries`: (name, call) with call() -> answer.  Stops when `seconds`
    have passed since the start or `count` calls were made.  `inspect(name)`
    -> the program's counters of the call just made, read off the clock;
    `annotate(name)` -> a context manager around the call (the traced
    slice's profiler annotation)."""
    if (seconds is None) == (count is None):
        raise ValueError("give seconds or count")
    records: List[Record] = []
    start = now = clock()
    i = 0
    while (now - start < seconds) if count is None else (i < count):
        name, call = queries[i % len(queries)]
        span = annotate(name) if annotate else contextlib.nullcontext()
        t0 = clock()
        try:
            with span:
                answer = call()
            now = clock()
            records.append(Record(name, now - t0, answer,
                                  inspect(name) if inspect else {}))
        except Exception as exc:          # a failed operation, counted
            now = clock()
            records.append(Record(name, now - t0,
                                  error=f"{type(exc).__name__}: {exc}"))
        i += 1
    return Window(start, now, records)


def percentile(values: Sequence[float], share: float) -> float:
    """Nearest rank: the smallest value with at least `share` of all
    values at or below it."""
    ranked = sorted(values)
    return ranked[max(0, math.ceil(share * len(ranked)) - 1)]


def end_to_end(window: Window) -> dict:
    """Taken over all the work and all the time of the window; the tail is
    the nearest-rank 95th percentile of every call's wall, a call that
    raised included.  A cell reports those that BENCHMARK.json lists for
    it."""
    walls = [r.wall_s for r in window.records]
    return {"query_ms": 1e3 * window.seconds / len(walls),
            "query_p95_ms": 1e3 * percentile(walls, 0.95)}
