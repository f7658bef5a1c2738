"""cim_mvm backend dispatch: three-way parity and the
never-interpret-on-a-hot-path guarantee.

Parity triangle per (mode, shape): the Pallas kernel in interpret mode
(bit-faithful block execution), the fused XLA fallback (the production
non-TPU path), and the materialised ``noisy_magnitude`` paper path
(``cim_mvm_ref``) must all agree to <= 1e-5 relative.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mdm import MODES
from repro.core.tiling import CrossbarSpec
from repro.kernels.cim_mvm.ops import (
    IMPLS,
    cim_mvm,
    deploy,
    resolve_impl,
    served_impl,
)
from repro.kernels.cim_mvm.ref import cim_mvm_ref

SPEC = CrossbarSpec(rows=16, cols=16, n_bits=8)


def _three_way(mode, shape, spec, eta=2e-3, n_bits=None):
    I, N, M = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(I * N + M))
    w = jax.random.normal(k1, (I, N)) * 0.2
    x = jax.random.normal(k2, (M, I))
    dep, plan = deploy(w, spec, mode, eta=eta)
    y_xla = np.asarray(cim_mvm(x, dep, impl="xla"))
    y_int = np.asarray(cim_mvm(x, dep, impl="interpret"))
    x_pad = jnp.pad(x, ((0, 0), (0, dep.codes.shape[0] - I)))
    y_ref = np.asarray(cim_mvm_ref(x_pad, dep.codes.astype(jnp.int32),
                                   plan, spec, eta)[:, :N])
    np.testing.assert_allclose(y_xla, y_int, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y_xla, y_ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y_int, y_ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("shape", [
    (48, 6, 4),             # non-divisible rows/cols
    (70, 13, 5),            # multi-tile, nothing divides
    pytest.param((130, 21, 9), marks=pytest.mark.slow),
])
def test_three_way_parity(mode, shape):
    _three_way(mode, shape, SPEC)


@pytest.mark.parametrize("mode", ["baseline", "mdm"])
def test_three_way_parity_odd_bits(mode):
    _three_way(mode, (33, 7, 3), CrossbarSpec(rows=32, cols=32, n_bits=4),
               eta=1e-3)


def test_resolve_impl_never_interprets():
    """"auto" resolves to a production path on every backend, with no
    capability probe: Pallas on TPU, XLA elsewhere.  Interpret must be
    an explicit opt-in (tests only)."""
    assert resolve_impl("auto") in ("pallas", "xla")
    assert resolve_impl("auto") != "interpret"
    expect = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert resolve_impl("auto") == expect
    for impl in IMPLS:
        if impl != "auto":
            assert resolve_impl(impl) == impl
    with pytest.raises(ValueError):
        resolve_impl("mosaic")


@pytest.mark.parametrize("field", ["gain", "col_pos", "noise"])
def test_served_impl_routes_nonideal_to_xla(field, monkeypatch):
    """Steered onto the TPU dispatch, an ideal deployment takes the
    kernel; gain, column permutations and per-read noise take XLA under
    "auto" and raise under an explicit kernel request."""
    import repro.kernels.cim_mvm.ops as ops

    real = ops.resolve_impl
    monkeypatch.setattr(ops, "resolve_impl", lambda impl="auto": (
        "pallas" if impl == "auto" else real(impl)))
    w = jax.random.normal(jax.random.PRNGKey(5), (32, 8)) * 0.1
    dep, _ = deploy(w, SPEC, "mdm")
    assert served_impl(dep) == "pallas"
    noisy = field == "noise"
    if field == "gain":
        dep = dataclasses.replace(dep, gain=jnp.ones(dep.codes.shape))
    elif field == "col_pos":
        dep, _ = deploy(w, SPEC, "xchangr")
        assert dep.col_pos is not None
    assert served_impl(dep, noisy=noisy) == "xla"
    for impl in ("pallas", "interpret"):
        with pytest.raises(ValueError):
            served_impl(dep, impl, noisy=noisy)
    assert served_impl(dep, "xla", noisy=noisy) == "xla"


def test_xla_impl_batched_input():
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 16)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 64))
    dep, _ = deploy(w, CrossbarSpec(rows=64, cols=64, n_bits=8), "mdm")
    y = cim_mvm(x, dep, impl="xla")
    assert y.shape == (2, 3, 16)
    y_flat = cim_mvm(x.reshape(6, 64), dep, impl="xla")
    np.testing.assert_allclose(np.asarray(y).reshape(6, 16),
                               np.asarray(y_flat), rtol=1e-6)


def test_xla_matches_interpret_at_serving_scale():
    """Spot-check the default dispatch at a layer-like shape (the 2048^2
    10x-speed criterion is recorded by benchmarks/deploy_throughput; a
    tier-1 test just pins numerical agreement at a non-toy size)."""
    spec = CrossbarSpec(rows=64, cols=64, n_bits=8)
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 192)) * 0.05
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 256))
    dep, _ = deploy(w, spec, "mdm")
    y_auto = np.asarray(cim_mvm(x, dep))          # auto -> xla on CPU
    y_int = np.asarray(cim_mvm(x, dep, impl="interpret"))
    # Norm-scaled: both paths sum the same f32 products in different
    # orders, so they differ by f32 rounding, about sqrt(256) * 2^-24
    # ~ 1e-6 of the output norm.  A per-element relative error is
    # ill-posed here: outputs that cancel to near zero inflate it
    # while their absolute error stays ~1e-7.
    err = np.linalg.norm(y_auto - y_int) / np.linalg.norm(y_int)
    assert err <= 1e-5
