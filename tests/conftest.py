import os
import sys

# 8-way host-device simulation so the sharded-solver parity tests
# (tests/test_solver_shard.py) exercise real multi-device shard_map in
# tier-1.  Must land before the first jax import initialises the
# backend; append so an operator-supplied XLA_FLAGS still wins.  (The
# 512-device forcing remains the dry-run's job only — launch/dryrun.py.)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Persistent jit cache: tier-1 is compile-bound (a flat tail of ~200
# small jit compiles), so cache compiled executables across pytest runs
# where JAX_COMPILATION_CACHE_DIR says, else in-repo (.jax_cache/,
# gitignored).  The min-compile-time floor is dropped to 0 because the
# tail is exactly the sub-second compiles the default threshold (1s)
# would refuse to cache.
import jax  # noqa: E402  (env above must precede backend init)

from repro.cachedirs import setup_compile_cache  # noqa: E402

setup_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
