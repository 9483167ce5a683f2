"""Median host-clock wall of one query's collects in the window: a statistic
of pieces, so per-layer, beside `query_ms`."""
import statistics


def read(spec, run):
    walls = [r.wall_s for r in run["window"].records
             if r.query == spec["key"] and r.error is None]
    return 1e3 * statistics.median(walls) if walls else None
