"""Slot-pool ("paged") KV cache for continuous batching.

A :class:`SlotPool` owns one fixed-capacity per-slot decode state
(``init_decode_state(per_slot=True)``): the batch axis is a pool of
``capacity`` slots, each holding one sequence's ring-buffer KV cache,
recurrent state and position clock.  Batch composition changes by
**index update only** — a sequence joins by scattering its prefilled
B=1 state into its slot, and evicts by resetting that slot's ``kpos``
lanes to ``EMPTY_POS`` (self-masking: a dead slot attends to nothing
and nothing attends to it).  Shapes never change, so the decode
lowerable downstream compiles once per ``(capacity, max_seq)`` and is
reused for every composition — the recompilation guarantee the
serving-tier gates pin (``traces`` counts actual retraces).

Join masks the tail of the padded prompt out of the cache: prefill runs
at a fixed ``max_prompt`` length (one trace for all prompts), so cache
entries at positions >= the true prompt length are garbage — their
``kpos`` is rewritten to ``EMPTY_POS``.  Unlike ring-buffer garbage
*behind* the clock, padding garbage sits at positions future queries
would attend to, so it must be masked explicitly.

All three state transforms (join / evict / fork-merge for multi-bank
decode) are jits over the pool state (join/evict donate it); slot
index and length are traced scalars, so serving any slot reuses one
trace.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.attention import EMPTY_POS
from repro.models.model import init_decode_state


def _map_slot_state(pool: dict, src: dict | None, leaf_fn, pos_fn,
                    kpos_fn):
    """Rebuild a per-slot state dict, dispatching on the special keys.

    ``pos`` (B,) and ``kpos`` (R, B, C) carry per-slot occupancy and
    need their own updates; every other leaf is (R, B, ...) and gets
    the generic ``leaf_fn``.
    """
    out = {}
    for slot_name, sub in pool.items():
        if slot_name == "pos":
            out[slot_name] = pos_fn(sub, None if src is None
                                    else src[slot_name])
            continue
        out[slot_name] = {}
        for k, leaf in sub.items():
            s = None if src is None else src[slot_name][k]
            fn = kpos_fn if k == "kpos" else leaf_fn
            out[slot_name][k] = fn(leaf, s)
    return out


class SlotPool:
    """Fixed-capacity slot pool over the per-slot decode state."""

    def __init__(self, cfg: ModelConfig, capacity: int, max_seq: int):
        self.cfg = cfg
        self.capacity = capacity
        self.max_seq = max_seq
        self.state = init_decode_state(cfg, capacity, max_seq,
                                       per_slot=True)
        self._free = sorted(range(capacity))
        # Host-side retrace counters: the bodies below run only when
        # jax traces them, so these count compilations, not calls —
        # the receipt behind the "compiles once per (capacity,
        # max_seq)" guarantee.
        self.traces = {"join": 0, "evict": 0, "merge": 0}

        # The jitted functions' names are the device programs' names in
        # a profiler trace (``jit_serve_join`` and so on).
        def serve_join(pool, src, slot, length):
            self.traces["join"] += 1
            return _map_slot_state(
                pool, src,
                leaf_fn=lambda p, s: p.at[:, slot].set(s[:, 0]),
                pos_fn=lambda p, s: p.at[slot].set(length),
                kpos_fn=lambda p, s: p.at[:, slot].set(
                    jnp.where(s[:, 0] >= length, EMPTY_POS, s[:, 0])))

        def serve_evict(pool, slot):
            self.traces["evict"] += 1
            return _map_slot_state(
                pool, None,
                leaf_fn=lambda p, s: p,
                pos_fn=lambda p, s: p.at[slot].set(0),
                kpos_fn=lambda p, s: p.at[:, slot].set(EMPTY_POS))

        def serve_merge(a, b, take_b):
            self.traces["merge"] += 1

            def pick(x, y):
                m = take_b.reshape((1, -1) + (1,) * (x.ndim - 2)) \
                    if x.ndim >= 2 else take_b
                return jnp.where(m, y, x)

            return _map_slot_state(
                a, b, leaf_fn=pick, pos_fn=pick, kpos_fn=pick)

        # Only the pool state donates: the B=1 source is *read* (sliced
        # into the scatter), so its buffers can't alias the output.
        self._join = jax.jit(serve_join, donate_argnums=(0,))
        self._evict = jax.jit(serve_evict, donate_argnums=(0,))
        # merge: jnp.where can't alias every operand pair, so donation
        # would only warn; the copy is transient (multi-epoch swaps).
        self._merge = jax.jit(serve_merge)

    # -- slot bookkeeping ----------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return self.capacity - len(self._free)

    def acquire(self) -> int | None:
        """Lowest free slot, or None when the pool is full."""
        return self._free.pop(0) if self._free else None

    # -- state transforms ----------------------------------------------

    def fresh_seq_state(self):
        """A B=1 per-slot state for one prefill (same cache depth)."""
        return init_decode_state(self.cfg, 1, self.max_seq,
                                 per_slot=True)

    def join(self, slot: int, seq_state, length) -> None:
        """Scatter a prefilled B=1 state into ``slot``.

        ``length`` is the true (unpadded) prompt length: the slot's
        clock is set to it and every cache entry the padded prefill
        wrote at positions >= length is masked to EMPTY_POS.
        """
        self.state = self._join(self.state, seq_state,
                                jnp.int32(slot), jnp.int32(length))

    def evict(self, slot: int) -> None:
        """Mask ``slot`` dead (kpos -> EMPTY_POS, clock -> 0), free it."""
        self.state = self._evict(self.state, jnp.int32(slot))
        self._free.append(slot)
        self._free.sort()

    def merge(self, state_a, state_b, take_b):
        """Per-slot merge of two post-decode states (pure; returns it).

        ``take_b`` is a (capacity,) bool mask: slots where it is True
        take ``state_b``'s lanes, the rest take ``state_a``'s — the
        join step of multi-bank decode (in-flight sequences pinned to
        different checkpoint epochs decode separately, then merge).
        Pure (no donation — ``where`` can't alias both operands); the
        caller installs the result.
        """
        return self._merge(state_a, state_b, jnp.asarray(take_b, bool))

    def fork(self):
        """A device copy of the pool state (fodder for a donating jit)."""
        return jax.tree_util.tree_map(
            lambda x: jnp.array(x, copy=True), self.state)
