"""Fused XLA path for the CIM matmul (production path off TPU).

Computes the same PR-distorted matmul as the Pallas kernel
(:mod:`repro.kernels.cim_mvm.kernel`) as a single fusible XLA graph:
the int16 signed codes are expanded to the effective weight matrix on
the fly — the K-step bit loop runs unrolled over (I, N) planes, so no
(I, N, K) bit tensor is ever materialised, mirroring the register-level
unroll of the kernel.  XLA fuses the expansion into one elementwise
pipeline feeding the matmul, keeping weight traffic at 2 B/weight
(measured against the paper's materialised-bit-plane flow in
``benchmarks/cim_traffic.py``).

This is the hot path on every backend but TPU, and on TPU for
deployments with a per-weight gain, a column permutation or per-read
noise (``repro.kernels.cim_mvm.ops.served_impl``); interpret mode is
strictly a test/validation vehicle and is never dispatched from serving
code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cim_effective_weights(codes: jax.Array, pos: jax.Array,
                          scale: jax.Array, *, n_bits: int, wpt: int,
                          cols: int, eta: float, reversed_df: bool,
                          col_pos: jax.Array | None = None) -> jax.Array:
    """Effective PR-distorted weight matrix from signed codes.

    codes: (I, N) int16 signed quantisation codes (sign * magnitude).
    pos:   (I, N // wpt) int32 physical row positions per column-tile.
    scale: () f32 quantisation scale.
    col_pos: optional (Ti, Tn, cols) int32 per-tile physical bitline of
    each dataflow-layout column (column-permuting mapping pipelines);
    None keeps the fixed layout.
    Returns (I, N) f32 — Eq 17's W' with the same row/column split as
    the Pallas kernel:  W' = sign * scale * [(1 + eta*p) * M0 + eta*M1].
    """
    c = codes.astype(jnp.int32)
    mag = jnp.abs(c).astype(jnp.uint32)
    sign = jnp.where(c < 0, -1.0, 1.0)

    # Clean magnitude: code * 2^-K == sum_k b_k 2^-(k+1), exactly.
    m0 = mag.astype(jnp.float32) * (2.0 ** -n_bits)

    # Column-distance moment, unrolled over the K bit planes.
    N = codes.shape[1]
    slot = jnp.arange(N, dtype=jnp.int32) % wpt
    if col_pos is not None:
        # Tile coordinates of every (input row, output column) pair:
        # the bitline of bit k then resolves per tile through col_pos.
        rows = codes.shape[0] // col_pos.shape[0]
        tii = jnp.arange(codes.shape[0], dtype=jnp.int32) // rows
        tnn = jnp.arange(N, dtype=jnp.int32) // wpt
    m1 = jnp.zeros_like(m0)
    for k in range(n_bits):
        bit = ((mag >> (n_bits - 1 - k)) & 1).astype(jnp.float32)
        col = slot * n_bits + k
        if reversed_df:
            col = (cols - 1) - col
        if col_pos is None:
            colf = col.astype(jnp.float32)                     # (N,)
        else:
            colf = col_pos[tii[:, None], tnn[None, :],
                           col[None, :]].astype(jnp.float32)   # (I, N)
        m1 = m1 + bit * (2.0 ** -(k + 1)) * colf

    # Physical row position p[i, n] = pos[i, n // wpt].
    p = jnp.repeat(pos.astype(jnp.float32), wpt, axis=1)
    return sign * scale * ((1.0 + eta * p) * m0 + eta * m1)


def cim_mvm_xla(x: jax.Array, codes: jax.Array, pos: jax.Array,
                scale: jax.Array, *, n_bits: int, wpt: int, cols: int,
                eta: float, reversed_df: bool,
                gain: jax.Array | None = None,
                col_pos: jax.Array | None = None,
                read_key: jax.Array | None = None,
                sigma_read: float = 0.0) -> jax.Array:
    """y = x @ W' with on-the-fly code expansion; x: (M, I) f32.

    ``gain`` (optional, (I, N) f32 from ``repro.nonideal.inject``)
    multiplies the effective weights cell-wise — programming variation /
    drift folded per weight; it fuses into the same elementwise pipeline
    feeding the matmul, so the weight-traffic story is unchanged.
    ``col_pos`` (optional, (Ti, Tn, cols) int32) applies a per-tile
    bitline permutation to the column-distance moment (X-CHANGR-style
    mapping pipelines).
    ``read_key`` + ``sigma_read`` add fresh per-read weight noise: iid
    per-cell conductance noise of relative std ``sigma_read`` carries a
    per-bit value std of ``sigma_read * 2^-(k+1)``, which sums over the
    K independent bit planes to a per-weight std of
    ``scale * sigma_read * sqrt((1 - 4^-K) / 3)`` — the first-order
    weight-level aggregate (the per-cell reference is the sampled
    ``read`` field of :class:`repro.nonideal.models.CellSample`).  The
    noise term fuses into the same elementwise pipeline as the gain.
    """
    w_eff = cim_effective_weights(codes, pos, scale, n_bits=n_bits,
                                  wpt=wpt, cols=cols, eta=eta,
                                  reversed_df=reversed_df,
                                  col_pos=col_pos)
    if gain is not None:
        w_eff = w_eff * gain
    if read_key is not None and sigma_read > 0.0:
        agg = float(((1.0 - 4.0 ** -n_bits) / 3.0) ** 0.5)
        eps = jax.random.normal(read_key, w_eff.shape, jnp.float32)
        w_eff = w_eff + (sigma_read * agg) * scale * eps
    return jax.lax.dot_general(
        x.astype(jnp.float32), w_eff, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
