import os

# Tests run on the CPU, on a virtual 8-device mesh so multi-chip sharding
# paths are exercised without TPU hardware; Pallas kernels run in interpret
# mode.  What only the chip can show is chip_smoke.py's job (README "Run").
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite is compile-dominated (dozens of
# jitted tree-build programs), and the cache makes re-runs take minutes
# instead of tens of minutes.  Same placement rule as every entry point.
from lightgbm_tpu.utils.compile_cache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()
