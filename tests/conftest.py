"""Test bootstrap: force an 8-device virtual CPU mesh.

Tests must run without the real chip and with 8 virtual devices so
multi-chip shardings are exercised (the driver separately dry-runs
__graft_entry__.dryrun_multichip the same way).  jax.config is used rather
than environment variables so the bootstrap holds however pytest was
started; it applies because no backend is initialized yet.
"""
import os

os.environ.setdefault("JAX_ENABLE_X64", "1")
# Persistent-cache AOT loads warn about XLA pseudo machine features
# (+prefer-no-gather etc.) that host detection never reports; the spam
# drowns test output. ERROR-level C++ logs are noise here — real failures
# surface as Python exceptions.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache: jit compiles dominate suite wall time; with a
# warm cache the full suite finishes headless well under the 10-minute budget.
# The engine's one resolver places it (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache) — kernel tests that never build a session share it.
from spark_rapids_tpu.config import DEFAULT_CONF  # noqa: E402
from spark_rapids_tpu.exec.compiled import \
    configure_persistent_cache  # noqa: E402

configure_persistent_cache(DEFAULT_CONF)

import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; full-tranche bench paths opt out here
    config.addinivalue_line(
        "markers", "slow: full-scale suite/bench paths excluded from "
                   "tier-1 (run explicitly or via bench.py)")


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_code_memory():
    """Bound per-process compiled-executable accumulation.

    The engine memoizes every jitted kernel for the process lifetime;
    the full suite now compiles enough distinct programs (TPC-H +
    TPC-DS + kernels) to exhaust the JIT's executable code space and
    segfault inside XLA near the end of a single-process run.  Dropping
    the caches between modules once accumulation passes a threshold
    keeps the process far from the cliff; shared kernels re-jit (or
    reload from the persistent cache) in a few seconds per clear.
    """
    yield
    from spark_rapids_tpu.testing import (clear_compiled_caches,
                                          compiled_cache_entries)
    if compiled_cache_entries() > 1200:
        clear_compiled_caches()


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
