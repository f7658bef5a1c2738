"""Continuous-batching serving engine over the CIM path.

:class:`ContinuousEngine` is the multi-tenant tier on top of the
single-batch :class:`repro.serve.engine.ServeEngine` machinery: an
Orca-style scheduler iteration loop (``step``) that admits queued
prompts mid-flight into a fixed-capacity :class:`repro.serve.kvcache
.SlotPool`, runs one batched decode for every live slot, streams the
sampled tokens, and evicts finished sequences — no request ever waits
for a batch, only for a slot.

**Recompilation guarantee.**  Prefill runs at a fixed ``(1,
max_prompt)`` shape and joins into the pool by index update; decode
runs at a fixed ``(capacity,)`` shape with dead slots self-masked
(``kpos == EMPTY_POS``, temperature 0).  Batch composition never
changes a shape, so each lowerable compiles exactly once per
``(capacity, max_seq, max_prompt)`` (``self.traces`` is the receipt).
Per-sequence ``temperature`` and sampling keys are runtime operands —
mixed-temperature tenants share the one trace.

**Bank epochs and hot swaps.**  Every sequence is pinned at admission
to the ``(params, cim)`` *bank* then serving.  Any hot swap — a health
heal/advance restack or an async redeploy — installs a *new* bank
epoch between decode iterations (fresh tree objects, never mutation):
in-flight sequences keep decoding against their admission bank
bit-deterministically, new admissions see the new bank, and a bank is
garbage-collected once nothing references it.  When live sequences
span several epochs, each epoch decodes the full slot batch against
its own bank and the per-slot states merge by mask — still one decode
trace while banks share their tree; a bank of another tree (other
nonideal, health or mapping settings) compiles a decode of its own,
held to the pool's layout (:meth:`repro.serve.kvcache.SlotPool.decode`).

**Async redeploy.**  ``begin_redeploy(new_params)`` deploys the new
checkpoint's tiles through the shared :class:`repro.deploy.PlanCache`
manifest in a background thread while the old bank keeps serving; the
finished bank is installed at the next iteration boundary via the same
fresh-tree atomicity contract.  Zero downtime, zero failed requests.

Per-sequence sampling is bit-deterministic per request ``seed``: token
``n`` draws from ``fold_in(PRNGKey(seed), n)`` through
:func:`repro.serve.engine.sample_tokens_batch`, whose row independence
(plus the per-lane attention masking and row-wise matmuls) makes a
sequence's output independent of its slot and batchmates.  (Per-read
conductance noise ``sigma_read > 0`` draws one key per *iteration*, so
only the noiseless path is composition-independent.)
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tm
from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingCtx
from repro.models.model import apply_model
from repro.serve.engine import (
    _C_SWAPS,
    _H_DECODE,
    deploy_serving_bank,
    sample_tokens_batch,
)
from repro.serve.kvcache import SlotPool
from repro.serve.scheduler import Request, RequestScheduler

_H_OCCUPANCY = tm.histogram(
    "repro_serve_batch_occupancy",
    "Live slots / capacity per scheduler iteration.",
    buckets=(0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
_C_REDEPLOYS = tm.counter(
    "repro_serve_redeploys_total",
    "Async checkpoint redeploys installed into the serving loop.")
_C_PREFILL_TOKENS = tm.counter(
    "repro_serve_prefill_tokens_total",
    "Tokens computed by admission prefills: the prompt's own, or "
    "padding up to max_prompt.", labels=("kind",))
_G_DECODE_TEMP = tm.gauge(
    "repro_serve_decode_temp_bytes",
    "Scratch bytes of the compiled slot-pool decode program.")

_UNSET = object()


@dataclasses.dataclass(frozen=True)
class Bank:
    """One immutable serving bank: a checkpoint's params + cim tree."""

    epoch: int
    params: Any
    cim: Any


def make_slot_prefill(cfg: ModelConfig, ctx: ShardingCtx):
    """(params, state(B=1), tokens (1, P), length, key, temp[, cim,
    read_key]) -> (first_token (1,), state).

    ``tokens`` is the prompt padded to the fixed ``max_prompt`` P (one
    trace for all prompt lengths); the first token samples from the
    logits row at the true ``length - 1``.  The join step downstream
    masks the padding tail out of the cache.
    """

    def prefill(params, state, tokens, length, key, temp,
                cim=None, read_key=None):
        logits, state, _ = apply_model(params, cfg, ctx, tokens=tokens,
                                       state=state, decode=False,
                                       cim=cim, read_key=read_key)
        lg = jax.lax.dynamic_index_in_dim(logits, length - 1, axis=1,
                                          keepdims=False)
        k0 = jax.random.fold_in(key, 0)
        tok = sample_tokens_batch(lg, k0[None], temp[None])
        return tok, state

    return prefill


def make_slot_decode(cfg: ModelConfig, ctx: ShardingCtx):
    """(params, state, tokens (B,), keys (B, 2), counts (B,), temps
    (B,)[, cim, read_key]) -> (next_tokens (B,), state).

    ``keys`` are the per-sequence base keys, ``counts`` the tokens each
    sequence has emitted so far: token n draws from ``fold_in(base,
    n)``, independent of slot index and batch composition.  Dead slots
    carry temperature 0 (greedy over garbage logits, discarded) and
    EMPTY_POS cache lanes, so they cost nothing semantically.
    """

    def decode(params, state, tokens, keys, counts, temps,
               cim=None, read_key=None):
        step_keys = jax.vmap(jax.random.fold_in)(keys, counts)
        logits, state, _ = apply_model(params, cfg, ctx,
                                       tokens=tokens[:, None],
                                       state=state, decode=True,
                                       cim=cim, read_key=read_key)
        tok = sample_tokens_batch(logits[:, 0], step_keys, temps)
        return tok, state

    return decode


class ContinuousEngine:
    """Multi-tenant continuous-batching engine (see module docstring)."""

    def __init__(self, cfg: ModelConfig, params,
                 ctx: ShardingCtx | None = None, capacity: int = 4,
                 max_seq: int = 256, max_prompt: int = 32,
                 plan_cache=None, nonideal=None, nonideal_seed: int = 0,
                 fault_aware: bool = True, pipeline=None, health=None):
        if cfg.frontend:
            raise ValueError("ContinuousEngine serves token frontends "
                             "only (embedding prompts are not paged)")
        if cfg.attn_impl == "pallas":
            raise NotImplementedError(
                "the slot-pool decode path carries per-lane (B, S) "
                "positions, which the TPU flash kernel does not take; "
                "use attn_impl='jax'")
        if max_prompt > max_seq:
            raise ValueError("max_prompt must be <= max_seq")
        self.cfg = cfg
        self.ctx = ctx or ShardingCtx()
        self.capacity = capacity
        self.max_seq = max_seq
        self.max_prompt = max_prompt
        self.plan_cache = None
        if cfg.cim.enabled:
            from repro.deploy import PlanCache
            self.plan_cache = (plan_cache if plan_cache is not None
                               else PlanCache())
        self._nonideal = nonideal
        self._nonideal_seed = nonideal_seed
        self._fault_aware = fault_aware
        self._pipeline = pipeline
        self._health_cfg = health
        cim, self.deploy_report, self.lifetime, self.health = \
            deploy_serving_bank(
                cfg, params, self.ctx, plan_cache=self.plan_cache,
                nonideal=nonideal, nonideal_seed=nonideal_seed,
                fault_aware=fault_aware, pipeline=pipeline,
                health=health)
        self.banks: dict[int, Bank] = {0: Bank(0, params, cim)}
        self.serving_epoch = 0
        self._next_epoch = 1

        self.scheduler = RequestScheduler()
        # Per-slot host mirrors of the decode operands (index-updated
        # on join/evict, like the device state).
        self._tok = np.zeros(capacity, np.int32)
        self._keys = np.zeros((capacity, 2), np.uint32)
        self._nem = np.zeros(capacity, np.int32)
        self._temp = np.zeros(capacity, np.float32)

        self._read_noise = bool(cfg.cim.enabled and nonideal is not None
                                and nonideal.sigma_read > 0.0)
        self._read_base = jax.random.fold_in(
            jax.random.PRNGKey(nonideal_seed), 11)
        self._read_round = 0
        self._probe_base = jax.random.PRNGKey(nonideal_seed)

        # The jitted functions' names are the device programs' names in
        # a profiler trace (``jit_serve_prefill``, ``jit_serve_decode``).
        self.traces = {"prefill": 0, "decode": 0}
        prefill = make_slot_prefill(cfg, self.ctx)
        decode = make_slot_decode(cfg, self.ctx)

        def serve_prefill(*a, **kw):
            self.traces["prefill"] += 1
            return prefill(*a, **kw)

        def serve_decode(*a, **kw):
            self.traces["decode"] += 1
            return decode(*a, **kw)

        self._prefill = jax.jit(serve_prefill, donate_argnums=(1,))
        # The pool compiles the decode for bank 0 here, takes the layout
        # that compile chose for the state, and runs every decode step
        # (see ``SlotPool.decode``).
        i32 = jax.ShapeDtypeStruct((capacity,), jnp.int32)
        first = (params, i32,
                 jax.ShapeDtypeStruct((capacity, 2), jnp.uint32), i32,
                 jax.ShapeDtypeStruct((capacity,), jnp.float32), cim,
                 self._read_base if self._read_noise else None)
        self.pool = SlotPool(cfg, capacity, max_seq, decode=serve_decode,
                             decode_args=first)
        self._decode = self.pool.decode
        self.decode_temp_bytes = self.pool.decode_temp_bytes
        _G_DECODE_TEMP.set(self.decode_temp_bytes)

        self._lock = threading.Lock()
        self._pending = None
        self._redeploy_thread: threading.Thread | None = None
        self.iterations = 0

    # -- public API ----------------------------------------------------

    def submit(self, prompt, max_tokens: int, temperature: float = 0.0,
               seed: int = 0, on_token=None) -> int:
        """Enqueue one request; returns its rid (tokens land in
        ``results[rid]`` once finished, streamed via ``on_token``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size > self.max_prompt:
            raise ValueError(f"prompt length {prompt.size} > "
                             f"max_prompt {self.max_prompt}")
        return self.scheduler.submit(prompt, max_tokens, temperature,
                                     seed, on_token)

    @property
    def results(self) -> dict[int, list[int]]:
        return self.scheduler.results

    def run(self, max_iters: int | None = None) -> dict[int, list[int]]:
        """Step until every submitted request has finished."""
        it = 0
        while self.scheduler.pending:
            self.step()
            it += 1
            if max_iters is not None and it >= max_iters:
                break
        return dict(self.scheduler.results)

    def step(self) -> None:
        """One scheduler iteration: install pending bank -> admit ->
        batched decode -> stream -> evict."""
        with tm.span("serve/iteration", it=self.iterations,
                     live=self.pool.n_live,
                     queued=self.scheduler.queue_depth):
            self._install_pending()
            while self.scheduler.queue and self.pool.n_free:
                req = self.scheduler.pop_admission()
                with tm.span("serve/admit", rid=req.rid,
                             prompt_tokens=int(req.prompt.size),
                             padded_to=self.max_prompt):
                    self._admit(req)
            _H_OCCUPANCY.observe(self.pool.n_live / self.capacity)
            if self.scheduler.live:
                self._decode_iteration()
        self.iterations += 1

    # -- admission -----------------------------------------------------

    def _admit(self, req: Request) -> None:
        bank = self.banks[self.serving_epoch]
        slot = self.pool.acquire()
        L = int(req.prompt.size)
        _C_PREFILL_TOKENS.labels(kind="prompt").inc(L)
        _C_PREFILL_TOKENS.labels(kind="padding").inc(self.max_prompt - L)
        with tm.span("serve/admit_inputs"):
            prompt = np.zeros((1, self.max_prompt), np.int32)
            prompt[0, :L] = req.prompt
            base = np.asarray(jax.random.PRNGKey(req.seed), np.uint32)
            st = self.pool.fresh_seq_state()
            args = (jnp.asarray(prompt), jnp.int32(L), jnp.asarray(base),
                    jnp.float32(req.temperature), bank.cim,
                    self._next_read_key())
        with tm.span("serve/prefill_call"):
            tok, st = self._prefill(bank.params, st, *args)
        with tm.span("serve/join_call"):
            self.pool.join(slot, st, L)
        self.scheduler.start(req, slot, self.serving_epoch)
        with tm.span("serve/first_token_wait"):
            tok0 = int(np.asarray(tok)[0])
        self._tok[slot] = tok0
        self._keys[slot] = base
        self._nem[slot] = 1
        self._temp[slot] = req.temperature
        if self.scheduler.record_token(slot, tok0):
            self._evict(slot)

    def _evict(self, slot: int) -> None:
        self.scheduler.finish(slot)
        with tm.span("serve/evict_call"):
            self.pool.evict(slot)
        self._tok[slot] = 0
        self._keys[slot] = 0
        self._nem[slot] = 0
        self._temp[slot] = 0.0

    # -- decode --------------------------------------------------------

    def _decode_iteration(self) -> None:
        live = self.scheduler.live
        t_on = tm.enabled()
        t0 = tm.monotonic() if t_on else 0.0
        with tm.span("serve/decode_batch", live=len(live),
                     epochs=len(self.scheduler.epochs_live())):
            tok_host = self._decode_all_banks()
        if t_on:
            _H_DECODE.observe(tm.monotonic() - t0)
        with tm.span("serve/stream"):
            finished = []
            for slot in sorted(live):
                t = int(tok_host[slot])
                self._tok[slot] = t
                self._nem[slot] += 1
                if self.scheduler.record_token(slot, t):
                    finished.append(slot)
            for slot in finished:
                self._evict(slot)

    def _decode_all_banks(self) -> np.ndarray:
        """One decode step for all live slots, grouped by bank epoch.

        The common case is one epoch: a single donating decode on the
        pool state.  Across a hot swap, each live epoch decodes the
        full batch against its own bank (one trace serves every bank
        of one tree) and the per-slot states merge by epoch
        mask; tokens are taken per-slot from the owning epoch's call.
        """
        epochs = self.scheduler.epochs_live()
        with tm.span("serve/decode_inputs"):
            tok = jnp.asarray(self._tok)
            keys = jnp.asarray(self._keys)
            nem = jnp.asarray(self._nem)
            temps = jnp.asarray(self._temp)
            rk = self._next_read_key()

        if len(epochs) == 1:
            bank = self.banks[epochs[0]]
            with tm.span("serve/decode_call"):
                tok_out, state = self._decode(bank.params,
                                              self.pool.state, tok, keys,
                                              nem, temps, bank.cim, rk)
            self.pool.state = state
            with tm.span("serve/decode_wait"):
                return np.asarray(tok_out)

        per_epoch_tok: dict[int, np.ndarray] = {}
        merged = None
        for i, e in enumerate(epochs):
            bank = self.banks[e]
            st_in = (self.pool.fork() if i < len(epochs) - 1
                     else self.pool.state)
            with tm.span("serve/decode_call"):
                tok_out, st_out = self._decode(bank.params, st_in, tok,
                                               keys, nem, temps,
                                               bank.cim, rk)
            with tm.span("serve/decode_wait"):
                per_epoch_tok[e] = np.asarray(tok_out)
            if merged is None:
                merged = st_out
            else:
                take_b = np.zeros(self.capacity, bool)
                for slot, seq in self.scheduler.live.items():
                    take_b[slot] = seq.epoch == e
                merged = self.pool.merge(merged, st_out, take_b)
        self.pool.state = merged
        tok_host = per_epoch_tok[epochs[0]].copy()
        for slot, seq in self.scheduler.live.items():
            tok_host[slot] = per_epoch_tok[seq.epoch][slot]
        return tok_host

    def _next_read_key(self):
        if not self._read_noise:
            return None
        self._read_round += 1
        return jax.random.fold_in(self._read_base, self._read_round)

    # -- banks / hot swap ----------------------------------------------

    def _install_bank(self, params, cim) -> int:
        """Install a new serving bank epoch (fresh-tree atomicity)."""
        e = self._next_epoch
        self._next_epoch += 1
        self.banks[e] = Bank(e, params, cim)
        self.serving_epoch = e
        self._gc_banks()
        return e

    def _gc_banks(self) -> None:
        held = {seq.epoch for seq in self.scheduler.live.values()}
        held.add(self.serving_epoch)
        for e in [e for e in self.banks if e not in held]:
            del self.banks[e]

    def _swap(self, dirty: set) -> None:
        """Restack heal-refreshed groups into a *new* bank epoch.

        Unlike ``ServeEngine._swap`` (which replaces the whole serving
        tree under a snapshotting generate loop), the continuous tier
        models every swap as a bank epoch: in-flight sequences stay
        pinned to their admission epoch, only new admissions (and the
        next decode of sequences already on the serving epoch — which
        is the same set, since pinning is by epoch) see the heal.
        """
        if not dirty:
            return
        from repro.deploy import restack_group
        with tm.span("serve/swap", groups=len(dirty)):
            cur = self.banks[self.serving_epoch]
            cim = {slot: dict(sub) for slot, sub in cur.cim.items()}
            for slot, pname in dirty:
                cim[slot][pname] = restack_group(self.lifetime, slot,
                                                 pname)
            self._install_bank(cur.params, cim)
        _C_SWAPS.inc(len(dirty))

    def advance(self, dt: float) -> None:
        """Advance the drift clock; heal-swaps land as a new epoch."""
        if self.health is None:
            return
        self._swap(self.health.advance(dt))

    def check_health(self, read_key=None):
        """One probe round + remediation; swaps land as a new epoch."""
        if self.health is None:
            return None
        if read_key is None and self._read_noise:
            read_key = jax.random.fold_in(
                jax.random.fold_in(self._probe_base, 9),
                self.health.rounds)
        self._swap(self.health.probe(read_key))
        return self.health.report()

    # -- async redeploy ------------------------------------------------

    def begin_redeploy(self, params, *, nonideal=_UNSET,
                       nonideal_seed=_UNSET, fault_aware=_UNSET,
                       pipeline=_UNSET, health=_UNSET
                       ) -> threading.Thread:
        """Deploy a new checkpoint in the background; swap when ready.

        Tile planning/packaging runs in a worker thread through the
        shared plan-cache manifest while the current bank keeps
        serving; the finished bank (with fresh lifetime capture +
        health controller when armed) is installed at the next
        ``step()`` boundary.  Unspecified keyword arguments inherit the
        engine's init-time deployment settings.  Returns the thread
        (``join()`` it to rendezvous; serving never has to).
        """
        if (self._redeploy_thread is not None
                and self._redeploy_thread.is_alive()):
            raise RuntimeError("a redeploy is already in progress")
        nonideal = self._nonideal if nonideal is _UNSET else nonideal
        seed = (self._nonideal_seed if nonideal_seed is _UNSET
                else nonideal_seed)
        fault_aware = (self._fault_aware if fault_aware is _UNSET
                       else fault_aware)
        pipeline = self._pipeline if pipeline is _UNSET else pipeline
        health = self._health_cfg if health is _UNSET else health

        def work():
            with tm.span("serve/redeploy"):
                cim, report, lifetime, controller = deploy_serving_bank(
                    self.cfg, params, self.ctx,
                    plan_cache=self.plan_cache, nonideal=nonideal,
                    nonideal_seed=seed, fault_aware=fault_aware,
                    pipeline=pipeline, health=health)
            with self._lock:
                self._pending = (params, cim, report, lifetime,
                                 controller)

        t = threading.Thread(target=work, name="repro-serve-redeploy",
                             daemon=True)
        self._redeploy_thread = t
        t.start()
        return t

    def redeploy_ready(self) -> bool:
        with self._lock:
            return self._pending is not None

    def _install_pending(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, None
        if pending is None:
            return
        params, cim, report, lifetime, controller = pending
        self._install_bank(params, cim)
        self.deploy_report = report
        # The old lifetime/monitors describe the retired checkpoint;
        # the redeploy captured fresh ones (or none, when health is
        # unarmed).
        self.lifetime, self.health = lifetime, controller
        _C_REDEPLOYS.inc()
