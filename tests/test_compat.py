"""JAX version-compat shims (one body each, for the installed JAX):
x64 scoping, AbstractMesh construction, shard_map, and the batched
tridiagonal capability probe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import (
    enable_x64,
    has_batched_tridiagonal_solve,
    make_abstract_mesh,
    shard_map,
    uncached_compiles,
)


def test_enable_x64_scopes_dtype():
    assert jnp.zeros((1,), jnp.float64).dtype == jnp.float32  # off outside
    with enable_x64():
        assert jnp.zeros((1,), jnp.float64).dtype == jnp.float64
    assert jnp.zeros((1,), jnp.float64).dtype == jnp.float32  # restored


def test_make_abstract_mesh_old_style_args():
    """(axis_sizes, axis_names) is the signature of the installed JAX."""
    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    assert dict(mesh.shape) == {"data": 16, "model": 16}
    pod = make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    assert tuple(pod.axis_names) == ("pod", "data", "model")


def test_make_abstract_mesh_rejects_mismatched_args():
    with pytest.raises(ValueError):
        make_abstract_mesh((16, 16), ("data",))


@pytest.mark.parametrize("manual", [None, frozenset({"x"})])
def test_shard_map_shim(manual):
    """The shim forwards check_vma and the manual-axes set to
    jax.shard_map: a per-shard sum plus one psum equals the total."""
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("x",))
    f = shard_map(lambda a: jax.lax.psum(a.sum(keepdims=True), "x"),
                  mesh, in_specs=P("x"), out_specs=P(),
                  check_vma=False, axis_names=manual)
    out = jax.jit(f)(jnp.arange(8.0))
    np.testing.assert_allclose(np.asarray(out), [28.0])


def test_tridiagonal_probe_on_active_backend():
    """CPU (and every backend this repo currently runs on) supports the
    batched tridiagonal_solve lowering the line preconditioner needs."""
    assert has_batched_tridiagonal_solve() is True
    # Cached: the second call must not re-execute the probe.
    assert has_batched_tridiagonal_solve() is True


def test_tridiagonal_probe_inside_trace():
    """The probe is consulted at trace time inside the engine's jit; it
    must return a concrete Python bool there, not a tracer (it escapes
    the ambient trace on a worker thread)."""
    has_batched_tridiagonal_solve.cache_clear()  # force a real probe
    seen = []

    @jax.jit
    def f(x):
        seen.append(has_batched_tridiagonal_solve())
        return x

    f(jnp.ones(2))
    assert seen == [True]


def test_tridiagonal_probe_unknown_platform_is_false():
    assert has_batched_tridiagonal_solve("no_such_backend") is False


def test_uncached_compiles_writes_no_cache_entry(monkeypatch):
    """Inside the context a fresh compile is not written to the
    persistent cache; after it, compiles are written again."""
    from jax._src import compilation_cache, compiler

    written = []
    monkeypatch.setattr(compiler, "_cache_read",
                        lambda *a, **k: (None, None))   # always compile
    monkeypatch.setattr(compilation_cache, "put_executable_and_time",
                        lambda key, name, *a, **k: written.append(name))
    x = np.arange(5.0, dtype=np.float32)
    with uncached_compiles():
        jax.jit(lambda v: v * 3.0 - 11.0)(x)
    inside = list(written)
    jax.jit(lambda v: v * 5.0 - 13.0)(x)
    assert inside == []
    assert written, "the compile after the context was not written"
