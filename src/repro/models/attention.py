"""Attention (pure JAX) with GQA, RoPE, sliding windows and ring-buffer
KV caches.

:func:`flash_attention` is the prefill and training path: the KV
sequence is scanned in fixed chunks with an online-softmax accumulator
(running max / denominator / weighted sum), so no (Sq, Skv) score matrix
is ever materialised — prefill at 32k and the 80-layer dry-runs stay
linear in sequence length.  :func:`decode_attention` is the slot-pool
decode path: one query token per lane attends to the lane's whole ring
in one pass, reading the cache as it is stored (no chunk reshape, no
pad), since its (B, H, C) scores are small.  Numerics: f32 softmax and
accumulation on both paths.

Masking is position-based: both query and key carry *absolute* token
positions, so the same code path serves training (k_positions = arange),
full-cache decode, and sliding-window ring buffers (k_positions follows
the ring; empty slots hold EMPTY_POS and mask themselves out).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
# np, not jnp: a module-level jnp constant would initialise the backend
# and transfer at import time (reprolint RPL005); jnp ops accept the
# numpy scalar and it stays int32 under weak typing.
EMPTY_POS = np.int32(2 ** 30)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (B, S, H, Dh); positions: (S,) shared across
    the batch, or (B, S) per-sequence (slot-pool decode, where every
    lane sits at its own absolute position)."""
    Dh = x.shape[-1]
    half = Dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[None, :, None, None].astype(jnp.float32) * freqs
    else:
        ang = positions[:, :, None, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_positions: jax.Array, k_positions: jax.Array,
                    window: int = 0, chunk: int = 512,
                    gqa_broadcast: str = "repeat",
                    remat_chunk: bool = False) -> jax.Array:
    """q: (B, Sq, H, Dh); k, v: (B, Skv, Hkv, Dh) -> (B, Sq, H, Dh).

    The prefill and training path (and ``ServeEngine``'s decode); the
    slot-pool decode attends through :func:`decode_attention`.

    Causal: key position must be <= query position (absolute positions);
    with ``window`` > 0 additionally q_pos - k_pos < window.
    ``q_positions`` / ``k_positions`` are (Sq,) / (Skv,) shared across
    the batch, or (B, Sq) / (B, Skv) per-sequence — the slot-pool decode
    path, where each batch lane carries its own position clock and ring
    occupancy.  The 1-D form normalises to a broadcast batch dim of 1,
    so the shared-positions path computes bit-identically to before.

    GQA is handled by broadcasting KV heads to the full H inside each
    chunk (transient, chunk-sized) rather than reshaping H -> (Hkv, G):
    splitting the head dim would leave no dimension divisible by the TP
    mesh axis and forces the SPMD partitioner into full replication of
    every attention intermediate (observed as "involuntary full
    rematerialization" warnings and ~100x inflated HBM traffic).
    Keeping H intact keeps every (B, *, H, *) tensor TP-sharded.
    """
    B, Sq, H, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = Dh ** -0.5
    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    # Normalise positions to (b, S) with b in {1, B}; the b=1 path is
    # the historical shared-positions computation, unchanged.
    qp = q_positions if q_positions.ndim == 2 else q_positions[None]
    kp = k_positions if k_positions.ndim == 2 else k_positions[None]
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(kp, ((0, 0), (0, pad)), constant_values=EMPTY_POS)

    qf = q.astype(jnp.float32)
    k_chunks = k.reshape(B, n_chunks, chunk, Hkv, Dh).swapaxes(0, 1)
    v_chunks = v.reshape(B, n_chunks, chunk, Hkv, Dh).swapaxes(0, 1)
    p_chunks = kp.reshape(kp.shape[0], n_chunks, chunk).swapaxes(0, 1)

    init = (jnp.full((B, Sq, H), NEG_INF, jnp.float32),
            jnp.zeros((B, Sq, H), jnp.float32),
            jnp.zeros((B, Sq, H, Dh), jnp.float32))

    # "take": a static gather along the head axis produces the (B,c,H,Dh)
    # tensor directly — the H-dim output shards on the TP axis, whereas
    # "repeat"'s broadcast+reshape goes through a (B,c,Hkv,G,Dh)
    # intermediate with no TP-divisible dim, forcing SPMD replication of
    # every attention chunk tensor (§Perf iteration 1).
    head_map = jnp.arange(H, dtype=jnp.int32) // G if G > 1 else None

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        k_c, v_c, k_pos = xs
        if G > 1:  # broadcast KV heads to H (chunk-transient)
            if gqa_broadcast == "take":
                k_c = jnp.take(k_c, head_map, axis=2)
                v_c = jnp.take(v_c, head_map, axis=2)
            else:
                k_c = jnp.repeat(k_c, G, axis=2)
                v_c = jnp.repeat(v_c, G, axis=2)
        s = jnp.einsum("bqhd,bchd->bqhc", qf,
                       k_c.astype(jnp.float32)) * scale
        valid = k_pos[:, None, :] <= qp[:, :, None]       # (b, Sq, C)
        if window:
            valid &= (qp[:, :, None] - k_pos[:, None, :]) < window
        s = jnp.where(valid[:, :, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.maximum(m_new, NEG_INF / 2)          # fully-masked guard
        p = jnp.exp(s - m_safe[..., None])
        corr = jnp.exp(jnp.minimum(m_prev - m_safe, 0.0))
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqhc,bchd->bqhd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc), None

    if remat_chunk:
        # Backward recomputes each chunk's score/softmax tensors from
        # (q, k_c, v_c) instead of saving them stacked over chunks.
        body = jax.checkpoint(body)
    (m, l, acc), _ = jax.lax.scan(body, init, (k_chunks, v_chunks, p_chunks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     q_positions: jax.Array, k_positions: jax.Array,
                     window: int = 0) -> jax.Array:
    """One pass over each lane's ring: the slot-pool decode's attention.

    q: (B, Sq, H, Dh) with Sq = 1 in decode; k, v: (B, C, Hkv, Dh), the
    ring as stored; q_positions (B, Sq), k_positions (B, C) -> (B, Sq, H,
    Dh).  The same masking as :func:`flash_attention` (``k_pos <=
    q_pos``, the window, EMPTY_POS lanes masked out, a fully masked lane
    reads 0) and the same f32 softmax and accumulation, but the scores
    over the whole ring are formed at once — (B, Sq, H, C) floats, small
    at Sq = 1 — so the cache is neither reshaped into chunks nor padded.
    GQA groups the query heads by KV head, so the cache is never
    broadcast to H.
    """
    B, Sq, H, Dh = q.shape
    Hkv = k.shape[2]
    G = H // Hkv
    qf = q.astype(jnp.float32).reshape(B, Sq, Hkv, G, Dh)
    s = jnp.einsum("bqkgd,bckd->bqkgc", qf,
                   k.astype(jnp.float32)) * Dh ** -0.5
    qp, kp = q_positions[:, :, None], k_positions[:, None, :]
    valid = kp <= qp                                      # (B, Sq, C)
    if window:
        valid &= (qp - kp) < window
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    m = jnp.maximum(jnp.max(s, axis=-1), NEG_INF / 2)     # fully-masked guard
    p = jnp.exp(s - m[..., None])
    acc = jnp.einsum("bqkgc,bckd->bqkgd", p, v.astype(jnp.float32))
    out = acc / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[..., None]
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)
