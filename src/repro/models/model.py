"""Model assembly: pattern-scanned decoder stack covering the whole
assigned architecture pool (dense GQA / MoE / Mamba hybrid / xLSTM /
frontend-stub VLM & audio).

Layers are stacked per pattern slot and iterated with ``lax.scan`` so the
HLO stays O(1) in depth (essential for the 80-layer dry-runs).  The same
``apply_model`` serves training (no state), prefill (state threaded, all
positions) and decode (state threaded, one position): attention caches
are ring buffers keyed by absolute positions, recurrent blocks carry
O(1) states.

When a ``cim`` deployment tree is threaded in (``cfg.cim.enabled``
serving — built by ``repro.deploy.deploy_model_params`` at engine
init), the attention q/k/v/o and dense-MLP projection matmuls route
through the backend-dispatched ``cim_mvm`` op instead of plain
einsum/matmul, evaluating the model under the deployed crossbars'
parasitic-resistance distortion.  The deployments ride the layer scan
as stacked pytrees, exactly like the parameters they shadow.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingCtx, logical_spec, shard
from repro.models import schema as sch
from repro.models.attention import (
    EMPTY_POS,
    decode_attention,
    flash_attention,
    rope,
)
from repro.models.moe import moe_ffn
from repro.models.recurrent import (
    mamba_decode,
    mamba_mixer,
    mlstm_decode,
    mlstm_mixer,
    slstm_mixer,
)

ModelState = dict[str, Any]
# Attention-cache leaves of an ``attn``/``hybrid`` slot's decode state.
KV_KEYS = ("k", "v", "kpos")


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (n * w.astype(jnp.float32)).astype(x.dtype)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _cim_matmul(x: jax.Array, w: jax.Array, dep,
                read_key: jax.Array | None = None) -> jax.Array:
    """x @ w, through the deployed crossbars when a CimDeployment exists.

    A deployment carrying ``degraded != 0`` is demoted to the digital
    matmul on the full-precision weight: positive counts are programmed
    bits lost to line-open faults after the spare-line remap (spares
    exhausted — the deploy report lists every demotion with its
    reason); the negative sentinel is a *runtime* demotion by the
    health controller (:mod:`repro.health`) after the remediation
    ladder ran out of rungs.  Either way the crossbar output would be
    wrong, so the full-precision fallback serves.  ``read_key`` threads
    per-read conductance noise into ``cim_mvm`` (None = noiseless).
    """
    if dep is None:
        return x @ w
    from repro.kernels.cim_mvm.ops import cim_mvm
    if dep.degraded is None:
        return cim_mvm(x, dep, read_key=read_key).astype(x.dtype)
    w2 = w.reshape(dep.in_dim, dep.out_dim)
    return jax.lax.cond(
        dep.degraded != 0,
        lambda: (x @ w2).astype(x.dtype),
        lambda: cim_mvm(x, dep, read_key=read_key).astype(x.dtype))


def dense_mlp(p: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
              prefix: str = "ffn_", cim: dict | None = None,
              read_key: jax.Array | None = None) -> jax.Array:
    g = lambda n: p[prefix + n]
    c = lambda n: None if cim is None else cim.get(prefix + n)
    mm = lambda a, w, dep: _cim_matmul(a, w, dep, read_key=read_key)
    if cfg.mlp_type == "swiglu":
        h = (_silu(mm(x, g("w_gate"), c("w_gate")))
             * mm(x, g("w_up"), c("w_up")))
    else:
        h = jax.nn.gelu(mm(x, g("w_up"), c("w_up")))
    h = shard(h, ctx, "batch", "seq", "act_mlp")
    return mm(h, g("w_down"), c("w_down"))


# ----------------------------- attention ---------------------------------

def attn_apply(p: dict, x: jax.Array, cfg: ModelConfig, ctx: ShardingCtx,
               positions: jax.Array, cache: dict | None,
               prefix: str = "", cim: dict | None = None,
               read_key: jax.Array | None = None,
               layer: jax.Array | None = None):
    """Returns (y, new_cache).

    ``layer`` is given on the slot-pool decode path only (one token,
    per-lane positions; see :func:`apply_model`): ``cache`` then holds
    the whole (R, B, C, ...) stack, this layer's token is written into
    each lane's ring in place at ``[layer, lane, pos % C]``, and the
    layer's ring is read as stored by :func:`decode_attention`.
    """
    g = lambda n: p[prefix + n]
    c = lambda n: None if cim is None else cim.get(prefix + n)
    B, S, D = x.shape

    def qkv_proj(name):
        w, dep = g(name), c(name)
        if dep is None:
            return jnp.einsum("bsd,dhk->bshk", x, w)
        return _cim_matmul(x, w, dep,
                           read_key=read_key).reshape(B, S, *w.shape[-2:])

    q = qkv_proj("wq")
    k = qkv_proj("wk")
    v = qkv_proj("wv")
    if cfg.qkv_bias:
        q, k, v = q + g("bq"), k + g("bk"), v + g("bv")
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    H = q.shape[2]
    tp = dict(ctx.mesh.shape).get("model", 1) if ctx.mesh else 1
    if cfg.attn_fallback_shard == "query" and H % tp != 0:
        # Heads can't take the TP axis: shard queries instead of the QK
        # contraction dim (head_dim) — scores stay shard-local.
        q = shard(q, ctx, "batch", "act_seq_q", None, None)
        k = shard(k, ctx, "batch", None, None, None)
        v = shard(v, ctx, "batch", None, None, None)
    else:
        q = shard(q, ctx, "batch", "seq", "act_heads", "act_head_dim")
        k = shard(k, ctx, "batch", "seq", "act_kv", "act_head_dim")
        v = shard(v, ctx, "batch", "seq", "act_kv", "act_head_dim")

    new_cache = None
    if layer is not None:
        C = cache["k"].shape[2]
        idx = positions % C                            # (B, 1)
        b = jnp.arange(B)[:, None]
        ck = cache["k"].at[layer, b, idx].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[layer, b, idx].set(v.astype(cache["v"].dtype))
        cp = cache["kpos"].at[layer, b, idx].set(positions)
        new_cache = {"k": ck, "v": cv, "kpos": cp}
        k_all, v_all, k_pos = (
            jax.lax.dynamic_index_in_dim(a, layer, keepdims=False)
            for a in (ck, cv, cp))
    elif cache is None:
        k_all, v_all, k_pos = k, v, positions
    elif positions.ndim == 2:
        # Slot-pool prefill: every batch lane carries its own position
        # clock, so the cache write is a per-lane scatter and ``kpos``
        # is per-lane (B, C).  Evicted lanes hold EMPTY_POS everywhere
        # and mask themselves out of attention entirely.
        C = cache["k"].shape[1]
        Sw = min(S, C)
        kw, vw, pw = k[:, S - Sw:], v[:, S - Sw:], positions[:, S - Sw:]
        idx = pw % C                                   # (B, Sw)
        b = jnp.arange(idx.shape[0])[:, None]
        ck = cache["k"].at[b, idx].set(kw.astype(cache["k"].dtype))
        cv = cache["v"].at[b, idx].set(vw.astype(cache["v"].dtype))
        cp = cache["kpos"].at[b, idx].set(pw)
        new_cache = {"k": ck, "v": cv, "kpos": cp}
        k_all, v_all, k_pos = ck, cv, cp
    else:
        C = cache["k"].shape[1]
        Sw = min(S, C)
        kw, vw, pw = k[:, S - Sw:], v[:, S - Sw:], positions[S - Sw:]
        if cfg.cache_update == "dus" and not cfg.sliding_window:
            # No ring wraparound without a window (C >= max position):
            # one contiguous dynamic-update-slice keeps the cache write
            # shard-local (the index-array scatter below replicates the
            # cache under SPMD — the dominant prefill collective).
            start = pw[0]
            ck = jax.lax.dynamic_update_slice(
                cache["k"], kw.astype(cache["k"].dtype), (0, start, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], vw.astype(cache["v"].dtype), (0, start, 0, 0))
            cp = jax.lax.dynamic_update_slice(cache["kpos"], pw, (start,))
        else:
            idx = pw % C
            ck = cache["k"].at[:, idx].set(kw.astype(cache["k"].dtype))
            cv = cache["v"].at[:, idx].set(vw.astype(cache["v"].dtype))
            cp = cache["kpos"].at[idx].set(pw)
        new_cache = {"k": ck, "v": cv, "kpos": cp}
        k_all, v_all, k_pos = ck, cv, cp

    if layer is not None:
        out = decode_attention(q, k_all, v_all, q_positions=positions,
                               k_positions=k_pos, window=cfg.sliding_window)
    elif cfg.attn_impl == "pallas":
        from repro.kernels.flash_attention import flash_attention_tpu
        out = flash_attention_tpu(q, k_all, v_all, q_positions=positions,
                                  k_positions=k_pos,
                                  window=cfg.sliding_window)
    else:
        out = flash_attention(q, k_all, v_all, q_positions=positions,
                              k_positions=k_pos, window=cfg.sliding_window,
                              chunk=cfg.attn_chunk,
                              gqa_broadcast=cfg.gqa_broadcast,
                              remat_chunk=cfg.attn_remat_chunk)
    if c("wo") is None:
        y = jnp.einsum("bshk,hkd->bsd", out, g("wo"))
    else:
        y = _cim_matmul(out.reshape(B, S, -1), g("wo"), c("wo"),
                        read_key=read_key)
    return y, new_cache


# --------------------------- block dispatch ------------------------------

def block_apply(bt: str, p: dict, x: jax.Array, cfg: ModelConfig,
                ctx: ShardingCtx, positions: jax.Array,
                state: dict | None, decode: bool,
                cim: dict | None = None,
                read_key: jax.Array | None = None,
                layer: jax.Array | None = None):
    """Apply one block. Returns (x, new_state_slice, aux_loss).

    ``layer``: see :func:`attn_apply` (slot-pool decode only).
    """
    aux = jnp.zeros((), jnp.float32)
    new_state: dict = {}
    h = rmsnorm(x, p["norm"], cfg.norm_eps)

    if bt == "attn":
        y, cache = attn_apply(p, h, cfg, ctx, positions,
                              None if state is None else state, cim=cim,
                              read_key=read_key, layer=layer)
        if cache is not None:
            new_state = cache
    elif bt == "hybrid":
        cache_in = None if state is None else \
            {k: state[k] for k in KV_KEYS}
        y_attn, cache = attn_apply(p, h, cfg, ctx, positions, cache_in,
                                   prefix="attn_", cim=cim,
                                   read_key=read_key, layer=layer)
        ssm_in = None if state is None else (state["conv"], state["ssm"])
        if decode:
            y_ssm, (cs, hs) = mamba_decode(p, h, ssm_in, prefix="ssm_")
        else:
            y_ssm, (cs, hs) = mamba_mixer(p, h, ssm_in,
                                          chunk=cfg.ssm_chunk, prefix="ssm_")
        y = 0.5 * (y_attn + y_ssm)
        if state is not None:
            new_state = dict(cache, conv=cs, ssm=hs)
    elif bt == "mamba":
        ssm_in = None if state is None else (state["conv"], state["ssm"])
        if decode:
            y, (cs, hs) = mamba_decode(p, h, ssm_in)
        else:
            y, (cs, hs) = mamba_mixer(p, h, ssm_in, chunk=cfg.ssm_chunk)
        if state is not None:
            new_state = {"conv": cs, "ssm": hs}
    elif bt == "mlstm":
        st = None if state is None else (state["S"], state["n"])
        if decode:
            y, (Sm, nv) = mlstm_decode(p, h, st)
        else:
            y, (Sm, nv) = mlstm_mixer(p, h, st, chunk=cfg.mlstm_chunk)
        if state is not None:
            new_state = {"S": Sm, "n": nv}
    elif bt == "slstm":
        st = None if state is None else (state["h"], state["c"])
        y, (hh, cc) = slstm_mixer(p, h, st, ctx=ctx, tp=cfg.slstm_tp)
        if state is not None:
            new_state = {"h": hh, "c": cc}
    else:
        raise ValueError(f"unknown block type {bt}")

    x = x + y
    x = shard(x, ctx, "batch", "seq", "act_embed")

    if bt in ("attn", "hybrid") and cfg.mlp_type != "none":
        hf = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
        if cfg.n_experts:
            yf, aux = moe_ffn(p, hf, cfg, ctx, cim=cim, read_key=read_key)
        else:
            yf = dense_mlp(p, hf, cfg, ctx, cim=cim, read_key=read_key)
        x = x + yf
        x = shard(x, ctx, "batch", "seq", "act_embed")
    return x, new_state, aux


# ----------------------------- full model --------------------------------

def _remat_wrap(fn, cfg: ModelConfig, train: bool):
    if not train or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)  # "full": recompute everything


def apply_model(params: dict, cfg: ModelConfig, ctx: ShardingCtx, *,
                tokens: jax.Array | None = None,
                embeds: jax.Array | None = None,
                state: ModelState | None = None,
                decode: bool = False,
                return_hidden: bool = False,
                cim: dict | None = None,
                read_key: jax.Array | None = None):
    """Returns (logits_or_hidden, new_state, aux_loss).

    ``cim``: optional per-slot CimDeployment tree (stacked over pattern
    repeats) routing projection matmuls through the crossbar path.
    ``read_key``: optional PRNG key for per-read crossbar conductance
    noise (one key per forward pass; each deployment decorrelates via
    its stacked per-repeat ``noise_tag``, so the shared key is safe to
    closure-capture across the layer scan).  None = noiseless serving.
    """
    if embeds is None:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = embeds.astype(jnp.dtype(cfg.dtype))
    B, S = x.shape[0], x.shape[1]
    x = shard(x, ctx, "batch", "seq", "act_embed")

    pos0 = jnp.zeros((), jnp.int32) if state is None else state["pos"]
    if jnp.ndim(pos0) == 0:
        positions = pos0 + jnp.arange(S, dtype=jnp.int32)
    else:
        # Per-slot decode state (``init_decode_state(per_slot=True)``):
        # pos is (B,) and every lane gets its own absolute positions.
        positions = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]

    pattern = cfg.block_pattern
    slot_names = [f"slot{i}_{bt}" for i, bt in enumerate(pattern)]
    xs: dict = {"params": {n: params[n] for n in slot_names}}
    # Slot-pool decode (one token, per-lane positions): the attention
    # caches ride the scan's carry whole, so each layer writes its B
    # tokens in place and reads its ring once, where slicing them in
    # as ``xs`` and back out as ``ys`` would copy the whole cache.
    # Recurrent leaves are small and keep the xs/ys route.
    kv: dict = {}
    if state is not None:
        if positions.ndim == 2 and S == 1:
            kv = {n: {k: state[n][k] for k in KV_KEYS} for n in slot_names
                  if "k" in state[n]}
        if kv:
            xs["layer"] = jnp.arange(cfg.pattern_repeats)
        xs["state"] = {n: {k: leaf for k, leaf in state[n].items()
                           if n not in kv or k not in KV_KEYS}
                       for n in slot_names}
    if cim is not None:
        xs["cim"] = {n: cim.get(n, {}) for n in slot_names}

    train = state is None

    def repeat_body(carry, xs_t):
        x, aux, kv = carry
        kv = dict(kv)
        new_states = {}
        for i, bt in enumerate(pattern):
            n = slot_names[i]
            st = xs_t["state"][n] if state is not None else None
            if n in kv:
                st = dict(st, **kv[n])
            ci = xs_t["cim"][n] if cim is not None else None
            x, ns, a = block_apply(bt, xs_t["params"][n], x, cfg, ctx,
                                   positions, st, decode, cim=ci,
                                   read_key=read_key,
                                   layer=xs_t["layer"] if kv else None)
            if n in kv:
                kv[n] = {k: ns[k] for k in KV_KEYS}
                ns = {k: leaf for k, leaf in ns.items() if k not in KV_KEYS}
            new_states[n] = ns
            aux = aux + a
        return (x, aux, kv), new_states

    body = _remat_wrap(repeat_body, cfg, train)
    (x, aux, kv), ys = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), kv), xs)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    new_state = None
    if state is not None:
        new_state = {n: dict(ys[n], **kv.get(n, {})) for n in slot_names}
        new_state["pos"] = pos0 + S

    if return_hidden:
        return x, new_state, aux

    logits = lm_logits(params, cfg, ctx, x)
    return logits, new_state, aux


def lm_logits(params: dict, cfg: ModelConfig, ctx: ShardingCtx,
              hidden: jax.Array) -> jax.Array:
    logits = jnp.einsum("bsd,dv->bsv", hidden,
                        params["lm_head"]).astype(jnp.float32)
    logits = shard(logits, ctx, "batch", "seq", "act_vocab")
    if cfg.padded_vocab > cfg.vocab_size:
        pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
        logits = logits - 1e9 * pad_mask.astype(jnp.float32)
    return logits


# ------------------------------- state -----------------------------------

def _state_defs(cfg: ModelConfig, batch: int, cache_len: int,
                per_slot: bool = False):
    """shape/dtype/logical-dims/fill for every decode-state tensor."""
    R = cfg.pattern_repeats
    Hkv, Dh = cfg.n_kv_heads, cfg.resolved_head_dim
    H = cfg.n_heads
    Di = cfg.d_model * cfg.ssm_expand
    N, K = cfg.ssm_state, cfg.ssm_conv
    C = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    dt = jnp.dtype(cfg.dtype)

    def attn_defs():
        # ``per_slot``: every batch lane keeps its own ring occupancy
        # (slot-pool serving — lanes join/evict independently), so
        # ``kpos`` grows a batch axis.
        kpos = (((R, batch, C), jnp.int32,
                 ("layers", "cache_batch", None), int(EMPTY_POS))
                if per_slot else
                ((R, C), jnp.int32, ("layers", None), int(EMPTY_POS)))
        return {
            "k": ((R, batch, C, Hkv, Dh), dt,
                  ("layers", "cache_batch", "cache_seq", "cache_kv",
                   "cache_head_dim"), 0),
            "v": ((R, batch, C, Hkv, Dh), dt,
                  ("layers", "cache_batch", "cache_seq", "cache_kv",
                   "cache_head_dim"), 0),
            "kpos": kpos,
        }

    def mamba_defs():
        return {
            "conv": ((R, batch, K - 1, Di), dt,
                     ("layers", "cache_batch", None, "inner"), 0),
            "ssm": ((R, batch, Di, N), jnp.float32,
                    ("layers", "cache_batch", "inner", "state"), 0),
        }

    defs: dict = {}
    for i, bt in enumerate(cfg.block_pattern):
        n = f"slot{i}_{bt}"
        if bt == "attn":
            defs[n] = attn_defs()
        elif bt == "hybrid":
            defs[n] = dict(attn_defs(), **mamba_defs())
        elif bt == "mamba":
            defs[n] = mamba_defs()
        elif bt == "mlstm":
            Dhm = (cfg.d_model * cfg.ssm_expand) // H
            defs[n] = {
                "S": ((R, batch, H, Dhm, Dhm), jnp.float32,
                      ("layers", "cache_batch", "heads", "head_dim", None), 0),
                "n": ((R, batch, H, Dhm), jnp.float32,
                      ("layers", "cache_batch", "heads", "head_dim"), 0),
            }
        elif bt == "slstm":
            Dhs = cfg.d_model // H
            defs[n] = {
                "h": ((R, batch, H, Dhs), jnp.float32,
                      ("layers", "cache_batch", "heads", "head_dim"), 0),
                "c": ((R, batch, H, Dhs), jnp.float32,
                      ("layers", "cache_batch", "heads", "head_dim"), 0),
            }
    return defs


def _map_state(defs: dict, fn):
    out = {}
    for slot, d in defs.items():
        out[slot] = {k: fn(*v) for k, v in d.items()}
    return out


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int,
                      abstract: bool = False,
                      per_slot: bool = False) -> ModelState:
    """Fresh decode state. ``kpos`` slots start at EMPTY_POS (self-masking).

    Attention caches per slot are (R, B, C, Hkv, Dh) ring buffers with
    C = min(cache_len, sliding_window or cache_len).

    ``per_slot=True`` builds the slot-pool layout the continuous-batching
    tier serves from: ``pos`` is (B,) and ``kpos`` is (R, B, C), so every
    batch lane advances its own position clock and ring occupancy —
    lanes join/evict by index update, never by reshape.
    """
    defs = _state_defs(cfg, batch, cache_len, per_slot=per_slot)
    pos_shape = (batch,) if per_slot else ()
    if abstract:
        st = _map_state(defs, lambda sh, dt, dims, fill:
                        jax.ShapeDtypeStruct(sh, dt))
        st["pos"] = jax.ShapeDtypeStruct(pos_shape, jnp.int32)
        return st
    st = _map_state(defs, lambda sh, dt, dims, fill:
                    jnp.full(sh, fill, dt))
    st["pos"] = jnp.zeros(pos_shape, jnp.int32)
    return st


def state_partition_specs(cfg: ModelConfig, ctx: ShardingCtx, batch: int,
                          cache_len: int, per_slot: bool = False):
    defs = _state_defs(cfg, batch, cache_len, per_slot=per_slot)
    specs = _map_state(defs, lambda sh, dt, dims, fill:
                       logical_spec(sh, dims, ctx.mesh, ctx.rules))
    from jax.sharding import PartitionSpec as P
    specs["pos"] = P()
    return specs


# ------------------------------- params ----------------------------------

def init_params(cfg: ModelConfig, key: jax.Array, dtype=None):
    return sch.materialize(cfg, key, dtype)


def param_partition_specs(cfg: ModelConfig, ctx: ShardingCtx):
    return sch.partition_specs(cfg, ctx)


# -------------------------------- loss -----------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Token-mean CE; labels < 0 are masked."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    ce = (logz - gold) * valid
    return ce.sum() / jnp.maximum(valid.sum(), 1)


def train_loss(params: dict, cfg: ModelConfig, ctx: ShardingCtx,
               batch: dict) -> tuple[jax.Array, dict]:
    """batch: {"tokens": (B, S+1)} or {"embeds": (B,S,D), "labels": (B,S)}."""
    if "embeds" in batch:
        embeds, labels = batch["embeds"], batch["labels"]
        hidden, _, aux = apply_model(params, cfg, ctx, embeds=embeds,
                                     return_hidden=True)
    else:
        toks = batch["tokens"]
        hidden, _, aux = apply_model(params, cfg, ctx, tokens=toks[:, :-1],
                                     return_hidden=True)
        labels = toks[:, 1:]

    if cfg.loss_chunk and hidden.shape[1] % cfg.loss_chunk == 0:
        nc = hidden.shape[1] // cfg.loss_chunk
        B = hidden.shape[0]
        hs = hidden.reshape(B, nc, cfg.loss_chunk, -1).swapaxes(0, 1)
        ls = labels.reshape(B, nc, cfg.loss_chunk).swapaxes(0, 1)

        @jax.checkpoint
        def chunk_ce(h_c, l_c):
            lg = lm_logits(params, cfg, ctx, h_c)
            valid = l_c >= 0
            safe = jnp.maximum(l_c, 0)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, safe[..., None], axis=-1)[..., 0]
            return ((logz - gold) * valid).sum(), valid.sum()

        def body(carry, xs):
            tot, cnt = carry
            s, c = chunk_ce(*xs)
            return (tot + s, cnt + c), None

        (tot, cnt), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
            (hs, ls))
        ce = tot / jnp.maximum(cnt, 1)
    else:
        logits = lm_logits(params, cfg, ctx, hidden)
        ce = cross_entropy(logits, labels)

    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}
