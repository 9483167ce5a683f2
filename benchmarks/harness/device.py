"""First contact with jax: which device this run is on, the table of peaks,
the sync round trip.  A run that finds no TPU (or fewer chips than the cell
asks for) ends here, before any data is made: there is no CPU fallback.
`--rehearse-cpu` is the sandbox dry run and says so on every line."""
from __future__ import annotations

import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def find(chips: int, rehearse_cpu: bool, say) -> dict:
    import jax
    if rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    import jaxlib
    say(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"devices={dev['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} python={sys.version.split()[0]}")
    if rehearse_cpu:
        if dev["platform"] != "cpu":
            raise SystemExit("--rehearse-cpu did not land on the CPU backend")
        return dev
    if dev["platform"] != "tpu":
        raise SystemExit(
            f"benchmark FAILED: no TPU was found; jax reports platform="
            f"{dev['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}). There is no CPU "
            f"fallback; --rehearse-cpu is the sandbox dry run.")
    if dev["count"] < chips:
        raise SystemExit(f"benchmark FAILED: the cell asks for {chips} chip(s), "
                     f"jax found {dev['count']}")
    return dev


def peaks(device_kind: str) -> dict:
    """Peaks of this device kind.  An unknown kind is an error, never a
    default: a roofline share against the wrong peak is a wrong number."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"benchmarks/harness/peaks.json ({sorted(table)}); "
                       f"add its published peaks with their source")
    return table[device_kind]


def measure_rtt() -> float:
    """Median host<->device sync round trip in seconds: the fetch of a fresh
    4-byte device-computed value (copy of `bench.measure_rtt`)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((1,), jnp.int32)
    jax.device_get(f(x))
    times = []
    for _ in range(11):
        t0 = time.perf_counter()
        jax.device_get(f(x))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)
