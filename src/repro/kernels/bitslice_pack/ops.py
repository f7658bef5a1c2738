"""Jit'd wrapper for the bit-plane expansion kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.bitslice_pack.kernel import bitslice_pack_pallas
from repro.kernels.runtime import default_interpret, round_up


@partial(jax.jit, static_argnames=("n_bits", "reversed_df", "interpret"))
def bitslice_pack(codes: jax.Array, n_bits: int, reversed_df: bool = False,
                  interpret: bool | None = None) -> jax.Array:  # reprolint: disable=RPL004 -- validation wrapper: None resolves to default_interpret() at trace time, False on TPU; hot path uses the fused XLA bit-slice
    """Expand (I, N) integer codes into (I, N, n_bits) uint8 bit planes."""
    if interpret is None:
        interpret = default_interpret()
    I, N = codes.shape
    bi = min(256, round_up(I, 8))
    bn = min(128, round_up(N, 8))
    ip, np_ = round_up(I, bi), round_up(N, bn)
    padded = jnp.pad(codes, ((0, ip - I), (0, np_ - N)))
    out = bitslice_pack_pallas(padded, n_bits=n_bits, reversed_df=reversed_df,
                               block_i=bi, block_n=bn, interpret=interpret)
    return out[:I, :N]
