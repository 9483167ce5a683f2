"""spark-rapids-tpu: a TPU-native columnar SQL execution engine.

A from-scratch framework with the capabilities of NVIDIA's RAPIDS Accelerator
for Apache Spark (reference: /root/reference, v24.06.0-SNAPSHOT), re-designed
for TPU hardware: JAX/XLA for the compute path (jit-traced expression trees,
static-shape bucketed columnar batches, sort/segment-based
aggregation), `jax.sharding.Mesh` + shard_map collectives for
distributed exchange, Arrow as the host/wire columnar format.

Layer map (mirrors SURVEY.md §1):
  runtime/   - device manager, semaphore, retry/spill (ref L1)
  columnar/  - host (Arrow) + device (bucketed jnp) batches (ref L2)
  plan/      - expressions, logical plan, overrides/tagging engine (ref L3)
  exec/      - physical operators (ref L4)
  io/        - parquet/csv/json scans + writers (ref L5)
  shuffle/   - partitioners + multithreaded host shuffle + ICI exchange (ref L6)
  parallel/  - mesh management, distributed query steps (ref §2.10)
  ops/       - the kernel library: the cuDF/JNI role, played by jnp (ref L0)
"""

__version__ = "0.1.0"

# Spark semantics require 64-bit ints (LongType) and doubles (DoubleType).
# TPUs emulate s64/f64 (two-lane), which XLA handles; correctness first, with
# optional f32 compute modes where compatibility.md-style deviations are OK.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

from . import types
from .config import TpuConf, DEFAULT_CONF
from .session import DataFrame, TpuSession, col, lit
