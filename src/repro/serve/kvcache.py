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

All the state transforms (join / evict / fork-merge for multi-bank
decode) are programs over the pool state (join/evict donate it), each
compiled once on first use; slot index and length are traced scalars,
so serving any slot reuses one program.

**Layout.**  The pool also runs the decode step (:meth:`SlotPool.decode`),
and its device layout is that step's choice: the first bank's decode is
compiled with the state's layout left to the compiler
(:func:`compile_decode`), the pool is placed in the format it chose
(``state_format``), and join, evict, fork, merge and the decode of any
later bank take and return that format, so neither a step, a
composition change nor a hot swap re-lays the whole cache.  Every
program here is compiled outside the persistent compilation cache
(:func:`repro.compat.uncached_compiles`), which would hand such a
program back with its outputs in the default layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout

from repro.compat import uncached_compiles
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


# The state transforms.  Their names are the device programs' names in a
# profiler trace (``jit_serve_join`` and so on).

def serve_join(pool, src, slot, length):
    return _map_slot_state(
        pool, src,
        leaf_fn=lambda p, s: p.at[:, slot].set(s[:, 0]),
        pos_fn=lambda p, s: p.at[slot].set(length),
        kpos_fn=lambda p, s: p.at[:, slot].set(
            jnp.where(s[:, 0] >= length, EMPTY_POS, s[:, 0])))


def serve_evict(pool, slot):
    return _map_slot_state(
        pool, None,
        leaf_fn=lambda p, s: p,
        pos_fn=lambda p, s: p.at[slot].set(0),
        kpos_fn=lambda p, s: p.at[:, slot].set(EMPTY_POS))


def serve_merge(a, b, take_b):
    def pick(x, y):
        m = take_b.reshape((1, -1) + (1,) * (x.ndim - 2)) \
            if x.ndim >= 2 else take_b
        return jnp.where(m, y, x)

    return _map_slot_state(a, b, leaf_fn=pick, pos_fn=pick, kpos_fn=pick)


def serve_fork(pool):
    return jax.tree.map(jnp.copy, pool)


def compile_decode(decode, args, like=None, sharding=None):
    """Compile the slot-pool decode step for ``args``.

    ``decode`` is (params, state, ...) -> (tokens, state); ``args`` are
    its arguments, concrete or abstract.  The state (argument 1) is
    donated.  With ``like`` None its input and output formats are
    ``Layout.AUTO``: the compiler lays the pool out the way the step
    reads and writes it and aliases the donated buffers, so a step
    copies nothing of cache size (``sharding`` places the state; None:
    the default device).  Otherwise the formats are those of ``like``,
    an earlier compile of the step for another bank, so that banks of
    other trees share the pool as it lies.
    """
    if like is None:
        fmt = Format(Layout.AUTO, sharding)
        state = jax.tree.map(lambda _: fmt, args[1])
        # The (B,) tokens take AUTO too: a donated (B,) int32 ``pos``
        # may alias them, and a donated input's output must be AUTO.
        out = (fmt, state)
    else:
        state, out = like.input_formats[0][1], like.output_formats
    with uncached_compiles():
        return jax.jit(
            decode, donate_argnums=(1,),
            in_shardings=(None, state) + (None,) * (len(args) - 2),
            out_shardings=out).lower(*args).compile()


def compile_transform(fn, args, state_format, n_pools: int = 1,
                      donate=()):
    """Compile ``fn``, whose first ``n_pools`` arguments and whose output
    are pool states, with those held in ``state_format`` (None: the
    default layout)."""
    with uncached_compiles():
        return jax.jit(
            fn, donate_argnums=donate, out_shardings=state_format,
            in_shardings=(state_format,) * n_pools
            + (None,) * (len(args) - n_pools)).lower(*args).compile()


def _signature(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple((x.shape, x.dtype) for x in leaves)


class SlotPool:
    """Fixed-capacity slot pool over the per-slot decode state."""

    def __init__(self, cfg: ModelConfig, capacity: int, max_seq: int,
                 decode=None, decode_args: tuple = ()):
        """``decode``: the slot-pool decode step, (params, state, *rest)
        -> (tokens, state), run through :meth:`decode`;
        ``decode_args``: its (params, *rest) for the first bank, concrete
        or abstract.  The pool is laid out the way that step's compile
        chose (``state_format``); without ``decode`` it keeps the
        default layout."""
        self.cfg = cfg
        self.capacity = capacity
        self.max_seq = max_seq
        # Host-side retrace counters: the wrappers below run only when
        # jax traces them, so these count compilations, not calls —
        # the receipt behind the "compiles once per (capacity,
        # max_seq)" guarantee.
        self.traces = {"join": 0, "evict": 0, "merge": 0}
        self._programs: dict = {}     # pool transforms, by name
        self._decodes: dict = {}      # decode steps, by bank signature
        self._decode_fn = decode
        self._first_decode = None
        self.state_format = None
        self.decode_temp_bytes = None
        if decode is not None:
            params, *rest = decode_args
            self._first_decode = self._decode_program(params, rest)
            self._decodes[_signature((params, tuple(rest)))] = \
                self._first_decode
            self.state_format = self._first_decode.input_formats[0][1]
            # The receipt that no step copies the cache: the program's
            # scratch, next to a state of ``capacity * max_seq`` entries.
            self.decode_temp_bytes = int(
                self._first_decode.memory_analysis().temp_size_in_bytes)
        # Made in its format, not re-laid after: no second copy.
        self.state = compile_transform(
            lambda: init_decode_state(cfg, capacity, max_seq,
                                      per_slot=True),
            (), self.state_format, n_pools=0)()
        self._free = sorted(range(capacity))

    def _decode_program(self, params, rest):
        state = init_decode_state(self.cfg, self.capacity, self.max_seq,
                                  abstract=True, per_slot=True)
        return compile_decode(self._decode_fn, (params, state, *rest),
                              like=self._first_decode)

    def _run(self, fn, args, n_pools: int = 1, donate=()):
        """Run the pool transform ``fn``, compiled once on first use."""
        name = fn.__name__.removeprefix("serve_")
        prog = self._programs.get(name)
        if prog is None:
            @functools.wraps(fn)
            def traced(*a):
                if name in self.traces:
                    self.traces[name] += 1
                return fn(*a)

            prog = self._programs[name] = compile_transform(
                traced, args, self.state_format, n_pools, donate)
        return prog(*args)

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
        # Only the pool state donates: the B=1 source is *read* (sliced
        # into the scatter), so its buffers can't alias the output.
        self.state = self._run(serve_join, (self.state, seq_state,
                                            jnp.int32(slot),
                                            jnp.int32(length)),
                               donate=(0,))

    def evict(self, slot: int) -> None:
        """Mask ``slot`` dead (kpos -> EMPTY_POS, clock -> 0), free it."""
        self.state = self._run(serve_evict, (self.state, jnp.int32(slot)),
                               donate=(0,))
        self._free.append(slot)
        self._free.sort()

    def merge(self, state_a, state_b, take_b):
        """Per-slot merge of two post-decode states (pure; returns it).

        ``take_b`` is a (capacity,) bool mask: slots where it is True
        take ``state_b``'s lanes, the rest take ``state_a``'s — the
        join step of multi-bank decode (in-flight sequences pinned to
        different checkpoint epochs decode separately, then merge).
        Pure (no donation — ``where`` can't alias both operands, so
        donation would only warn; the copy is transient); the caller
        installs the result.
        """
        return self._run(serve_merge,
                         (state_a, state_b, jnp.asarray(take_b, bool)),
                         n_pools=2)

    def fork(self):
        """A device copy of the pool state, in its format (fodder for a
        donating jit)."""
        return self._run(serve_fork, (self.state,))

    def decode(self, params, state, *rest):
        """One decode step on ``state`` (donated) -> (tokens, state).

        A bank whose ``(params, *rest)`` tree or dtypes differ from every
        bank before it (a redeploy with other nonideal, health or
        mapping settings, a checkpoint in another dtype) gets a program
        of its own, compiled on its first step and held to the pool's
        format, so a hot swap never re-lays the pool.
        """
        key = _signature((params, rest))
        prog = self._decodes.get(key)
        if prog is None:
            prog = self._decodes[key] = self._decode_program(params, rest)
        return prog(params, state, *rest)
