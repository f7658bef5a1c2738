"""Where this checkout keeps its on-disk caches (both gitignored).

* JAX's persistent compilation cache: the directory that
  ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the
  variable itself), else ``<checkout>/.jax_cache/``.  Entry points
  (``chip_smoke.py``, ``benchmarks/run.py``, ``repro.launch.*``) and
  ``tests/conftest.py`` call :func:`setup_compile_cache` before their
  first compile; nothing else sets a cache directory.
* The MDM plan cache (:class:`repro.deploy.PlanCache`):
  ``<checkout>/.mdm_plan_cache/``, independent of the compile cache.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at this checkout's
    directory unless ``JAX_COMPILATION_CACHE_DIR`` is set; returns the
    directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def plan_cache_dir() -> str:
    """Root of the persistent MDM plan cache."""
    return os.path.join(CHECKOUT, ".mdm_plan_cache")
