"""Jit'd wrapper for the Pallas sLSTM scan kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.runtime import default_interpret, round_up
from repro.kernels.slstm_scan.kernel import slstm_scan_pallas


@partial(jax.jit, static_argnames=("block_b", "chunk", "interpret"))
def slstm_scan(gx: jax.Array, r_gates: jax.Array, h0: jax.Array,
               c0: jax.Array, block_b: int = 8, chunk: int = 128,
               interpret: bool | None = None):  # reprolint: disable=RPL004 -- validation wrapper: None resolves to default_interpret() at trace time, False on TPU; recurrent serving stays on the XLA scan
    """gx: (B, T, H, 4Dh); returns (hs (B,T,H,Dh) f32, hT, cT)."""
    if interpret is None:
        interpret = default_interpret()
    B, T, H, Dh4 = gx.shape
    bb = min(block_b, B)
    ch = min(chunk, T)
    bp, tp = round_up(B, bb), round_up(T, ch)
    gx_p = jnp.pad(gx, ((0, bp - B), (0, tp - T), (0, 0), (0, 0)))
    pad_b = ((0, bp - B), (0, 0), (0, 0))
    h0_p, c0_p = jnp.pad(h0, pad_b), jnp.pad(c0, pad_b)
    hs, hT, cT = slstm_scan_pallas(gx_p, r_gates, h0_p, c0_p,
                                   block_b=bb, chunk=ch, t_valid=T,
                                   interpret=interpret)
    return hs[:B, :T], hT[:B], cT[:B]
