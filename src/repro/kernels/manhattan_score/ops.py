"""Jit'd wrapper for the Manhattan score/NF reduction kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.manhattan_score.kernel import manhattan_score_pallas
from repro.kernels.runtime import default_interpret, round_up


@partial(jax.jit, static_argnames=("nf_unit", "block_t", "interpret"))
def manhattan_score(masks: jax.Array, nf_unit: float = 1.0,
                    block_t: int = 8, interpret: bool | None = None):  # reprolint: disable=RPL004 -- validation wrapper: None resolves to default_interpret() at trace time, False on TPU; planning uses the fused XLA scorer
    """Row scores, row counts and per-tile NF for tile masks.

    masks: (..., R, C) activity masks (any integer/float 0-1 dtype).
    Returns (scores (..., R), counts (..., R), nf (...)).
    """
    if interpret is None:
        interpret = default_interpret()
    batch = masks.shape[:-2]
    R, C = masks.shape[-2:]
    flat = masks.reshape(-1, R, C)
    T = flat.shape[0]
    bt = min(block_t, T) if T else 1
    tp = round_up(max(T, 1), bt)
    flat = jnp.pad(flat, ((0, tp - T), (0, 0), (0, 0)))
    scores, counts, nf = manhattan_score_pallas(
        flat, nf_unit=nf_unit, block_t=bt, interpret=interpret)
    return (scores[:T].reshape(*batch, R), counts[:T].reshape(*batch, R),
            nf[:T, 0].reshape(batch))
