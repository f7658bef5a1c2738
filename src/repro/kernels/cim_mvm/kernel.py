"""Fused bit-sliced CIM matmul with parasitic-resistance distortion.

Computes  y = x @ W'  where W' is the PR-distorted effective weight of a
bit-sliced crossbar deployment (paper Eq 17):

    W'[i,n] = sign * scale * [ (1 + eta * p[i,n]) * M0 + eta * M1 ]
    M0      = code[i,n] * 2^-K                  (clean magnitude)
    M1      = sum_k bit_k(code) * 2^-(k+1) * col(n, k)

``p`` is the physical row position after the MDM plan, ``col(n,k)`` the
physical column of bit plane k (mirrored when the dataflow is reversed).

TPU adaptation (vs. the paper's PyTorch flow, which materialises K bit
planes in DRAM): the bit extraction, distortion and matmul are fused in
VMEM — weights travel HBM->VMEM once as int16 codes (2 bytes instead of
K bytes of bit planes + 4 bytes of float weights), the K-step bit loop is
fully unrolled over registers in int32 arithmetic, and the final
contraction feeds the MXU at f32 precision with f32 accumulation.

The row-position table is stored per column tile, ``pos[i, n // wpt]``,
so its block is ``wpt`` times narrower than the codes block.  Mosaic
cannot repeat elements along the lane axis, so the kernel expands it
with a one-hot matmul on the MXU, ``pos @ E`` with ``E[t, n] = (n // wpt
== t)``.  Positions are split into 8-bit halves so that both bf16
operands are exact integers: the expansion is exact for any position
below 2^16.

Grid: (M/BM, N/BN, I/BI), accumulation over the last (fastest-varying,
"arbitrary") axis so each output block stays resident in VMEM; the M
and N axes are "parallel".  ``BN // wpt`` must be a multiple of 128 or
the whole tile count (picked by ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _expand_positions(pos: jax.Array, wpt: int) -> jax.Array:
    """(BI, BT) int32 per-tile positions -> (BI, BT * wpt) f32, exactly."""
    bt = pos.shape[1]
    bn = bt * wpt
    t = jax.lax.broadcasted_iota(jnp.int32, (bt, bn), 0)
    n = jax.lax.broadcasted_iota(jnp.int32, (bt, bn), 1)
    onehot = (n // wpt == t).astype(jnp.bfloat16)
    lo = (pos & 0xFF).astype(jnp.bfloat16)
    hi = (pos >> 8).astype(jnp.bfloat16)
    # One bf16 pass is exact here; an explicit precision keeps an
    # ambient default_matmul_precision("highest") from asking Mosaic
    # for an f32 contraction of bf16 operands, which it refuses.
    expand = lambda a: jax.lax.dot_general(
        a, onehot, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)
    return expand(lo) + 256.0 * expand(hi)


def _cim_mvm_kernel(x_ref, codes_ref, pos_ref, scale_ref, out_ref, *,
                    n_bits: int, wpt: int, cols: int, eta: float,
                    reversed_df: bool):
    """One (BM, BN) output block, accumulating one BI slab of the inner dim.

    x_ref:     (BM, BI)  f32 activations
    codes_ref: (BI, BN)  int16 signed quantisation codes (sign * magnitude)
    pos_ref:   (BI, BN // wpt) int32 physical row positions per column-tile
    scale_ref: (1, 1)    f32 quantisation scale (SMEM)
    out_ref:   (BM, BN)  f32 accumulator
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    c = codes_ref[...].astype(jnp.int32)
    mag = jnp.abs(c)
    sign = jnp.where(c < 0, -1.0, 1.0)

    # Clean magnitude: sum_k b_k 2^-(k+1) == code * 2^-K, exactly.
    m0 = mag.astype(jnp.float32) * (2.0 ** -n_bits)

    # Column-distance moment: unrolled over the K bit planes (registers
    # only — no bit-plane tensor ever exists in memory).  The block
    # width is a multiple of wpt, so the slot is block-relative.
    bn = mag.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1) % wpt
    m1 = jnp.zeros_like(m0)
    for k in range(n_bits):
        bit = ((mag >> (n_bits - 1 - k)) & 1).astype(jnp.float32)
        col = slot * n_bits + k
        if reversed_df:
            col = (cols - 1) - col
        m1 = m1 + bit * ((2.0 ** -(k + 1)) * col.astype(jnp.float32))

    # Physical row position p[i, n] = pos[i, n // wpt].
    p = _expand_positions(pos_ref[...], wpt)

    scale = scale_ref[0, 0]
    w_eff = sign * scale * ((1.0 + eta * p) * m0 + eta * m1)

    out_ref[...] += jax.lax.dot_general(
        x_ref[...], w_eff, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def cim_mvm_pallas(x: jax.Array, codes: jax.Array, pos: jax.Array,
                   scale: jax.Array, *, n_bits: int, wpt: int, cols: int,
                   eta: float, reversed_df: bool,
                   block_m: int, block_n: int, block_i: int,
                   interpret: bool) -> jax.Array:
    """Raw pallas_call; expects pre-padded block-aligned operands."""
    M, I = x.shape
    _, N = codes.shape
    grid = (M // block_m, N // block_n, I // block_i)

    kernel = functools.partial(
        _cim_mvm_kernel, n_bits=n_bits, wpt=wpt, cols=cols, eta=eta,
        reversed_df=reversed_df)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_i), lambda m, n, k: (m, k)),
            pl.BlockSpec((block_i, block_n), lambda m, n, k: (k, n)),
            pl.BlockSpec((block_i, block_n // wpt), lambda m, n, k: (k, n)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="cim_mvm",
    )(x, codes, pos, scale)
