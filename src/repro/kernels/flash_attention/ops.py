"""Jit'd wrapper for the Pallas flash-attention kernel.

Drop-in for ``repro.models.attention.flash_attention`` on TPU: same
(B, S, H, Dh) interfaces and position-based masking.  The wrapper
flattens (B, H) onto the grid axis, pads sequence dims to block
multiples (padded keys get EMPTY_POS and self-mask), and restores
layout.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.runtime import default_interpret, round_up

# np, not jnp: module-level jnp would compute at import time (RPL005).
EMPTY_POS = np.int32(2 ** 30)


@partial(jax.jit, static_argnames=("window", "block_q", "block_k",
                                   "interpret"))
def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        q_positions: jax.Array, k_positions: jax.Array,
                        window: int = 0, block_q: int = 128,
                        block_k: int = 128,
                        interpret: bool | None = None) -> jax.Array:  # reprolint: disable=RPL004 -- None resolves to default_interpret() at trace time: the compiled kernel on TPU, interpret mode (validation) elsewhere
    """q: (B, Sq, H, Dh); k, v: (B, Skv, Hkv, Dh) -> (B, Sq, H, Dh).

    Positions are 1-D: ``q_positions`` (Sq,), ``k_positions`` (Skv,).
    """
    if interpret is None:
        interpret = default_interpret()
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv

    bq = min(block_q, round_up(Sq, 8))
    bk = min(block_k, round_up(Skv, 8))
    sq_p, sk_p = round_up(Sq, bq), round_up(Skv, bk)

    # Positions as a (Sq, 1) column and a (1, Skv) row: 2-D blocks that
    # Mosaic can tile.
    qp = jnp.pad(q_positions.astype(jnp.int32), (0, sq_p - Sq))[:, None]
    kp = jnp.pad(k_positions.astype(jnp.int32), (0, sk_p - Skv),
                 constant_values=EMPTY_POS)[None, :]
    qt = jnp.pad(q, ((0, 0), (0, sq_p - Sq), (0, 0), (0, 0)))
    kt = jnp.pad(k, ((0, 0), (0, sk_p - Skv), (0, 0), (0, 0)))
    vt = jnp.pad(v, ((0, 0), (0, sk_p - Skv), (0, 0), (0, 0)))

    # (B, S, H, Dh) -> (B*H, S, Dh); kv -> (B*Hkv, S, Dh)
    qt = qt.transpose(0, 2, 1, 3).reshape(B * H, sq_p, Dh)
    kt = kt.transpose(0, 2, 1, 3).reshape(B * Hkv, sk_p, Dh)
    vt = vt.transpose(0, 2, 1, 3).reshape(B * Hkv, sk_p, Dh)

    out = flash_attention_pallas(
        qt, kt, vt, qp, kp, scale=Dh ** -0.5, window=window, group=G,
        block_q=bq, block_k=bk, interpret=interpret)
    out = out.reshape(B, H, sq_p, Dh).transpose(0, 2, 1, 3)
    return out[:, :Sq]
