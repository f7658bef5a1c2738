"""Single entry points for the version-sensitive JAX APIs this repo uses.

Each shim has one body, written for the installed JAX (0.9.0).  Call
sites go through these names (reprolint RPL001 enforces it), so the next
JAX move is absorbed here and nowhere else:

* :func:`enable_x64` — the config-scoped float64 context manager
  (``jax.enable_x64``).  It affects *tracing*, so wrap the jit'd call
  site, not the kernel body.
* :func:`make_abstract_mesh` — ``jax.sharding.AbstractMesh`` from
  ``(axis_sizes, axis_names)``.
* :func:`shard_map` — ``jax.shard_map`` with its ``check_vma`` and
  ``axis_names`` keywords.

* :func:`uncached_compiles` — compile without writing JAX's
  persistent compilation cache, for programs whose outputs must keep a
  non-default layout.

One backend capability probe also lives here:

* :func:`has_batched_tridiagonal_solve` — whether
  ``jax.lax.linalg.tridiagonal_solve`` lowers (and executes) with
  leading batch dimensions on the active backend.  The batched crossbar
  engine's line preconditioner depends on it; backends without the
  batched lowering fall back to the Jacobi diagonal, and the engine
  reports which one it picked (``repro.crossbar.batched``).
"""
from __future__ import annotations

import contextlib
from functools import lru_cache
from typing import Any, Callable, ContextManager, Sequence

import jax


def enable_x64(enabled: bool = True) -> ContextManager:
    """Config-scoped float64 enablement.

    Prefer wrapping the *outermost* (trace-time) call: inside an already
    traced jit the dtypes are frozen and the flag has no effect.
    """
    return jax.enable_x64(enabled)


def make_abstract_mesh(axis_sizes: Sequence[int],
                       axis_names: Sequence[str]):
    """Build an ``AbstractMesh`` from ``(axis_sizes, axis_names)``."""
    from jax.sharding import AbstractMesh

    if len(axis_sizes) != len(axis_names):
        raise ValueError(
            f"axis_sizes {tuple(axis_sizes)} and axis_names "
            f"{tuple(axis_names)} must have equal length")
    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def shard_map(f: Callable, mesh: Any, in_specs: Any, out_specs: Any,
              check_vma: bool = True,
              axis_names: frozenset | None = None) -> Callable:
    """``jax.shard_map``; ``axis_names`` is the set of *manual* mesh axes
    (None: all of them)."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)


@contextlib.contextmanager
def uncached_compiles():
    """Programs compiled inside are not written to the persistent cache.

    With JAX 0.9.0 on a TPU v5e, an executable read back from the
    persistent compilation cache returns its outputs in the default
    layout, whatever format it was compiled to give them, even outputs
    that alias a donated input.  A program whose outputs must keep a
    non-default format compiles in here, so that no later process reads
    it back.  The override is thread-local.
    """
    from jax._src import config

    with config.persistent_cache_min_compile_time_secs(float("inf")):
        yield


@lru_cache(maxsize=None)
def has_batched_tridiagonal_solve(platform: str | None = None) -> bool:
    """Probe: does ``tridiagonal_solve`` have a batched lowering here?

    The line preconditioner in :mod:`repro.crossbar.batched` solves
    ``(T, J)``-batched tridiagonal chains in one call; some backends
    (historically GPU's cusparse ``gtsv2`` path) reject leading batch
    dims or specific dtypes at lowering time.  This executes a tiny
    2-dim-batched solve on ``platform`` (default: the active backend)
    and reports whether it compiles *and* returns finite values, so the
    engine can decide between the line and Jacobi preconditioners
    without call-site version/backend guards.  Cached per platform —
    the probe runs at most once per process.
    """
    import threading

    # The probe is typically triggered at *trace time* (inside the
    # engine's jit).  JAX's ambient trace state is thread-local, so a
    # fresh worker thread is the one reliable way to run an independent
    # eager execution from inside a trace: jnp constants would become
    # tracers in the caller's trace, and ensure_compile_time_eval leaks
    # the eval trace into tridiagonal_solve's scan-based CPU lowering
    # (NotImplementedError: evaluation rule for 'empty').
    out: list[bool] = []
    t = threading.Thread(target=lambda: out.append(_probe_tridiagonal(
        platform)), daemon=True)
    t.start()
    t.join()
    return bool(out and out[0])


def _probe_tridiagonal(platform: str | None) -> bool:
    try:
        import numpy as np

        m = 4
        dl = np.zeros((2, 3, m), np.float32)
        d = np.full((2, 3, m), 2.0, np.float32)
        du = np.zeros((2, 3, m), np.float32)
        b = np.ones((2, 3, m, 1), np.float32)
        args = (dl, d, du, b)
        if platform:  # jit follows input placement
            args = jax.device_put(args, jax.devices(platform)[0])
        out = np.asarray(
            jax.jit(jax.lax.linalg.tridiagonal_solve)(*args))
        return bool(np.all(np.isfinite(out)) and np.allclose(out, 0.5))
    except Exception:  # lowering/runtime rejection -> Jacobi fallback
        return False
