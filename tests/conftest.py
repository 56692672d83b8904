"""Test fixtures: hermetic multi-device JAX on CPU.

SURVEY.md §4 carry-over: the reference tests multi-node for real on one
machine (Spark ``local-cluster[N,...]``); our analog is JAX on a virtual
8-device CPU platform (``--xla_force_host_platform_device_count``), set
BEFORE any jax import anywhere in the test process. The environment is
what pins the executor/trainer processes the tests spawn to the same
platform.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
# Single-host harness: each trainer process owns a private virtual CPU
# device set, so the multi-node jax.distributed bootstrap (default ON for
# real clusters) must be disabled.
os.environ["TFOS_TPU_DISTRIBUTED"] = "0"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tensorflowonspark_tpu import util  # noqa: E402

# The suite is COMPILE-dominated — hundreds of jitted programs, most
# identical run to run — so the session shares the persistent compile
# cache every other compiling process of this repo uses (.jax_cache at
# the checkout root unless JAX_COMPILATION_CACHE_DIR places it).
util.enable_compile_cache()
