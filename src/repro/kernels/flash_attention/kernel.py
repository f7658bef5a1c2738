"""Pallas TPU flash attention (beyond-paper §Perf kernel).

The pure-JAX chunked flash attention keeps O(seq) memory but still
round-trips its (BQ, BK) score tiles and online-softmax carries through
HBM on every chunk — the dominant memory-term contributor of the dense
train cells.  This kernel keeps everything tile-resident in VMEM:

  * grid (B*H, Sq/BQ, Skv/BK); the KV axis is the fastest-varying grid
    dim, so the (m, l, acc) scratch accumulators persist in VMEM across
    a full KV sweep — HBM sees only q/k/v reads and one output write.
  * GQA without materialisation: the k/v BlockSpec index maps divide the
    head index by the group size, so each KV head's tile is fetched for
    its G query heads directly from the (B*Hkv, S, Dh) layout.
  * positions-based masking (causal + sliding window + ring-buffer
    validity) identical to the pure-JAX path.  Query positions arrive
    as a (Sq, 1) column and key positions as a (1, Skv) row, so each
    position block is 2-D and the mask is a plain broadcast compare; the
    softmax carries are (BQ, 1) columns.

MXU alignment: BQ/BK multiples of 128 (or the whole padded length), Dh
is the lane dim.  The KV axis is "arbitrary" and the others
"parallel".  Validated in interpret mode against the pure-jnp oracle
(tests/test_kernels_perf.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_s, l_s, acc_s, *, scale: float, window: int,
                  n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                     # (BQ, Dh)
    k = k_ref[0].astype(jnp.float32)                     # (BK, Dh)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    qp = qpos_ref[...]                                   # (BQ, 1)
    kp = kpos_ref[...]                                   # (1, BK)
    valid = kp <= qp
    if window:
        valid &= (qp - kp) < window
    s = jnp.where(valid, s, NEG_INF)

    m_prev, l_prev = m_s[...], l_s[...]                  # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    m_safe = jnp.maximum(m_new, NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    corr = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
    m_s[...] = m_new
    l_s[...] = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finish():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_positions: jax.Array, k_positions: jax.Array,
                           *, scale: float, window: int, group: int,
                           block_q: int, block_k: int,
                           interpret: bool) -> jax.Array:
    """q: (BH, Sq, Dh); k, v: (BHkv, Skv, Dh); q_positions (Sq, 1) and
    k_positions (1, Skv) int32.  Pre-padded to blocks."""
    BH, Sq, Dh = q.shape
    Skv = k.shape[1]
    n_q, n_k = Sq // block_q, Skv // block_k
    grid = (BH, n_q, n_k)

    kernel = functools.partial(_flash_kernel, scale=scale, window=window,
                               n_k=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda bh, qi, ki: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda bh, qi, ki: (0, ki)),
            pl.BlockSpec((1, block_q, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, block_k, Dh),
                         lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dh),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(q_positions, k_positions, q, k, v)
