"""Persistent compile cache hits or misses across the window
(`persistent_cache_stats()` after minus before): XLA compiles that happened
inside the timed region.  Expected 0 misses."""


def read(spec, run):
    return run["pcache_after"][spec["key"]] - run["pcache_before"][spec["key"]]
