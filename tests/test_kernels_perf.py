"""Beyond-paper §Perf kernels: flash attention + sLSTM scan, interpret-
mode allclose sweeps vs pure-jnp oracles.

Sweeps are deterministic seeded parametrize grids (the ``hypothesis``
package is not installable in the offline CI image).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_tpu
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.slstm_scan import slstm_scan
from repro.kernels.slstm_scan.ref import slstm_scan_ref
from repro.models.attention import flash_attention


@pytest.mark.parametrize("case", [
    # B, Sq, Skv, H, Hkv, Dh, window
    (2, 64, 64, 4, 2, 32, 0),
    (1, 40, 72, 6, 3, 16, 24),
    (2, 1, 96, 4, 4, 32, 0),        # decode shape
    (1, 33, 33, 8, 1, 16, 0),       # MQA
])
def test_flash_kernel_vs_exact(case):
    B, Sq, Skv, H, Hkv, Dh, win = case
    ks = jax.random.split(jax.random.PRNGKey(sum(case)), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, Dh), jnp.float32)
    qpos = jnp.arange(Sq) + max(0, Skv - Sq)
    kpos = jnp.arange(Skv)
    out = flash_attention_tpu(q, k, v, q_positions=qpos, k_positions=kpos,
                              window=win, block_q=32, block_k=32)
    ref = attention_ref(q, k, v, qpos, kpos, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_matches_pure_jax_path():
    """The TPU kernel and the model zoo's chunked-scan implementation are
    the same function."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 48, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 48, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 48, 2, 32), jnp.float32)
    pos = jnp.arange(48)
    a = flash_attention_tpu(q, k, v, q_positions=pos, k_positions=pos,
                            block_q=16, block_k=16)
    b = flash_attention(q, k, v, q_positions=pos, k_positions=pos, chunk=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-5, atol=2e-5)


def test_serve_engine_pallas_attention_matches_jax():
    """attn_impl="pallas" on ServeEngine (1-D positions, ring cache)
    serves the same greedy tokens and logits as the chunked-scan path;
    off TPU the kernel runs in interpret mode."""
    from repro.configs.phi3_mini_38b import SMOKE
    from repro.distributed.sharding import ShardingCtx
    from repro.models.model import apply_model, init_params
    from repro.serve.engine import ServeEngine

    cfg = SMOKE.replace(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                 cfg.vocab_size)
    outs, logits = [], []
    for impl in ("jax", "pallas"):
        c = cfg.replace(attn_impl=impl)
        outs.append(np.asarray(
            ServeEngine(c, params, max_seq=32).generate(prompts, 6)))
        logits.append(np.asarray(apply_model(params, c, ShardingCtx(),
                                             tokens=prompts)[0]))
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_allclose(logits[1], logits[0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,t,h,dh,seed", [
    (1, 3, 1, 4, 0),        # minimal dims, t < chunk
    (5, 70, 4, 16, 1),      # strategy maxima, t spans many chunks
    (2, 16, 2, 8, 2),       # t == chunk exactly
    (3, 17, 1, 16, 3),      # one past a chunk boundary
    (1, 33, 4, 4, 42),
    (4, 15, 2, 8, 99),      # one short of a chunk boundary
])
def test_slstm_kernel_sweep(b, t, h, dh, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    gx = jax.random.normal(ks[0], (b, t, h, 4 * dh)) * 0.5
    r = jax.random.normal(ks[1], (h, dh, 4 * dh)) * 0.1
    h0 = jax.random.normal(ks[2], (b, h, dh)) * 0.1
    c0 = jax.random.normal(ks[3], (b, h, dh)) * 0.1
    hs, hT, cT = slstm_scan(gx, r, h0, c0, block_b=2, chunk=16)
    hs2, hT2, cT2 = slstm_scan_ref(gx, r, h0, c0)
    np.testing.assert_allclose(np.asarray(hs), np.asarray(hs2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hT2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cT), np.asarray(cT2),
                               rtol=1e-5, atol=1e-6)
