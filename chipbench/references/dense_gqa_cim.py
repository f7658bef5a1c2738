"""Plain float32 reference of a dense GQA decoder on MDM-mapped crossbars.

Imports nothing of the program under test.  From a configuration file
and the weights the benchmark made, it

1. bit-slices each deployed matrix into n-bit magnitude codes and a
   digital sign (scale: the largest magnitude lands on the all-ones
   code, with 1e-6 of headroom);
2. plans each crossbar tile by MDM (paper steps 1 and 3): the dataflow
   is reversed, so bit plane k of weight slot s sits on bitline
   ``cols - 1 - (s * n_bits + k)``, and rows are sorted by their active
   cells, densest first, ties by the Manhattan score sum(1 + bitline),
   then by index;
3. materialises Eq 17 for every weight:
   ``w' = sign * scale * sum_k b_k 2^-(k+1) (1 + eta * (p + c_k))``
   with p the physical row and c_k the bitline of bit k;
4. runs the model in float32 at "highest" matmul precision: embedding,
   per layer RMSNorm, q/k/v, rotate-half RoPE, causal attention (with
   the sliding window, where one is stated), o, residual, RMSNorm,
   SwiGLU, residual; final RMSNorm and the digital LM head over the
   real vocabulary.

``lowp`` names a control, the reference computed below what the
configuration states:

* ``"fp8"``: every matmul operand and attention's q, k, v and
  probabilities are rounded through float8 e4m3 (per-tensor scale for
  weights, per-row for activations), the precision below the bfloat16
  that the configuration serves in;
* ``"bf16"``: only the deployed crossbar matmuls round both operands,
  W' and the activation, to bfloat16 (float32 accumulation), as a
  crossbar matmul contracted on bfloat16 operands would.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

DEPLOYED = ("wq", "wk", "wv", "wo", "ffn_w_gate", "ffn_w_up", "ffn_w_down")


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "hkv": cfg["num_key_value_heads"],
            "dh": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "vp": -(-cfg["vocab_size"] // 128) * 128,
            "layers": cfg["num_hidden_layers"],
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "window": int(cfg.get("sliding_window") or 0)}


# ------------------------------------------------------------- weights


def _layout(s: dict) -> dict:
    """Parameter tree as the served model takes it: name -> (shape,
    init stddev or "ones"); per-layer tensors stacked on axis 0."""
    d, h, hkv, dh, f, vp, r = (s["d"], s["h"], s["hkv"], s["dh"], s["f"],
                               s["vp"], s["layers"])
    layer = {
        "norm": ((r, d), "ones"),
        "wq": ((r, d, h, dh), d ** -0.5),
        "wk": ((r, d, hkv, dh), d ** -0.5),
        "wv": ((r, d, hkv, dh), d ** -0.5),
        "wo": ((r, h, dh, d), (h * dh) ** -0.5),
        "ffn_norm": ((r, d), "ones"),
        "ffn_w_gate": ((r, d, f), d ** -0.5),
        "ffn_w_up": ((r, d, f), d ** -0.5),
        "ffn_w_down": ((r, f, d), f ** -0.5),
    }
    return {"embed": ((vp, d), 0.02), "final_norm": ((d,), "ones"),
            "lm_head": ((d, vp), d ** -0.5), "slot0_attn": layer}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, beyond 32 bits too."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_weights(cfg: dict, seed: int):
    """All weights on the device, in bfloat16, from one jitted call."""
    lay = _layout(sizes(cfg))
    leaves, tree = jax.tree_util.tree_flatten(
        lay, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))

    def build(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, init) in zip(keys, leaves):
            if init == "ones":
                out.append(jnp.ones(shape, jnp.bfloat16))
            else:
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * init).astype(jnp.bfloat16))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(build)(seed_key(seed))


# ------------------------------------------------------------- Eq 17


def as_matrix(name: str, w: jax.Array) -> jax.Array:
    """One layer's tensor as its (in, out) matmul weight."""
    if name in ("wq", "wk", "wv"):
        return w.reshape(w.shape[0], -1)
    if name == "wo":
        return w.reshape(-1, w.shape[-1])
    return w


@partial(jax.jit, static_argnames=("rows", "cols", "n_bits", "eta"))
def eq17(w: jax.Array, *, rows: int, cols: int, n_bits: int,
         eta: float) -> jax.Array:
    """The PR-distorted effective matrix W' (I, N) of one deployed W."""
    w = w.astype(jnp.float32)
    K = n_bits
    wpt = cols // K
    levels = (1 << K) - 1
    scale = (jnp.max(jnp.abs(w)) * np.float32((1 << K) / levels)
             * np.float32(1.0 + 1e-6) + np.float32(1e-30))
    code = jnp.clip(jnp.round(jnp.abs(w) / scale * np.float32(1 << K)),
                    0, levels).astype(jnp.int32)
    sign = jnp.where(w < 0, -1.0, 1.0)
    I, N = w.shape
    ti, tn = -(-I // rows), -(-N // wpt)
    code_p = jnp.pad(code, ((0, ti * rows - I), (0, tn * wpt - N)))
    shifts = jnp.arange(K - 1, -1, -1, dtype=jnp.int32)
    bits = ((code_p[..., None] >> shifts) & 1).astype(jnp.int32)
    # Bitline of bit k of weight slot s under the reversed dataflow.
    slot = jnp.arange(tn * wpt) % wpt
    bitline = (cols - 1) - (slot[:, None] * K + jnp.arange(K)[None, :])
    # Per tile row: active cells and Manhattan score, (I_pad, Tn).
    b4 = bits.reshape(ti * rows, tn, wpt, K)
    count = b4.sum(axis=(2, 3))
    score = (b4 * (1 + bitline).reshape(tn, wpt, K)[None]).sum(axis=(2, 3))
    s_max = cols * (cols + 1) // 2
    key = (count * (s_max + 1) + score).reshape(ti, rows, tn)
    perm = jnp.argsort(-key, axis=1, stable=True)      # physical -> row
    position = jnp.argsort(perm, axis=1)                # row -> physical
    p = position.reshape(ti * rows, tn).astype(jnp.float32)
    p = jnp.repeat(p, wpt, axis=1)                      # (I_pad, N_pad)
    bw = 2.0 ** -(1.0 + jnp.arange(K, dtype=jnp.float32))
    bitsf = bits.astype(jnp.float32)
    m0 = code_p.astype(jnp.float32) * np.float32(2.0 ** -K)
    m1 = jnp.einsum("ink,nk->in", bitsf, bw * bitline.astype(jnp.float32),
                    precision="highest")
    w_eff = sign * scale * ((1.0 + eta * p[:I, :N]) * m0[:I, :N]
                            + eta * m1[:I, :N])
    return w_eff


def build(params, cfg: dict) -> dict:
    """float32 reference weights: Eq-17 W' for every deployed matrix,
    digital embedding and head sliced to the real vocabulary."""
    s = sizes(cfg)
    cim = cfg["cim"]
    kw = dict(rows=cim["rows"], cols=cim["cols"], n_bits=cim["n_bits"],
              eta=float(cim["eta"]))
    layer = params["slot0_attn"]
    mats = {name: jnp.stack([eq17(as_matrix(name, layer[name][r]), **kw)
                             for r in range(s["layers"])])
            for name in DEPLOYED}
    f32 = lambda a: a.astype(jnp.float32)
    return {"embed": f32(params["embed"][:s["v"]]),
            "lm_head": f32(params["lm_head"][:, :s["v"]]),
            "final_norm": f32(params["final_norm"]),
            "norm": f32(layer["norm"]), "ffn_norm": f32(layer["ffn_norm"]),
            **mats}


# ------------------------------------------------------------- forward


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round through float8 e4m3 with an absmax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    sc = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x: (S, H, Dh), positions 0..S-1, rotate-half pairing."""
    S, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _bf16(x: jax.Array) -> jax.Array:
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def forward(ref: dict, tokens: jax.Array, cfg: dict,
            lowp: str | None = None) -> jax.Array:
    """tokens (S,) -> logits (S, vocab), float32."""
    if lowp not in (None, "fp8", "bf16"):
        raise ValueError(f"unknown control {lowp!r}")
    s = sizes(cfg)
    h, hkv, dh = s["h"], s["hkv"], s["dh"]
    same = lambda a: a  # noqa: E731
    act = (lambda a: _fp8(a, -1)) if lowp == "fp8" else same
    wq8 = (lambda w: _fp8(w, None)) if lowp == "fp8" else same
    xbar = _bf16 if lowp == "bf16" else same

    def mm(a, w):
        """A deployed (crossbar) matmul."""
        return xbar(act(a)) @ xbar(wq8(w))

    S = tokens.shape[0]
    x = ref["embed"][tokens]
    pos = jnp.arange(S)
    allowed = pos[None, :] <= pos[:, None]
    if s["window"]:
        allowed &= (pos[:, None] - pos[None, :]) < s["window"]
    for li in range(s["layers"]):
        hn = _rmsnorm(x, ref["norm"][li], s["eps"])
        q = _rope(mm(hn, ref["wq"][li]).reshape(S, h, dh), s["theta"])
        k = _rope(mm(hn, ref["wk"][li]).reshape(S, hkv, dh), s["theta"])
        v = mm(hn, ref["wv"][li]).reshape(S, hkv, dh)
        k = jnp.repeat(k, h // hkv, axis=1)
        v = jnp.repeat(v, h // hkv, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", act(q), act(k)) / math.sqrt(dh)
        sc = jnp.where(allowed[None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("hqk,khd->qhd", act(pr), act(v))
        x = x + mm(o.reshape(S, h * dh), ref["wo"][li])
        hn = _rmsnorm(x, ref["ffn_norm"][li], s["eps"])
        g = mm(hn, ref["ffn_w_gate"][li])
        u = mm(hn, ref["ffn_w_up"][li])
        x = x + mm(jax.nn.silu(g) * u, ref["ffn_w_down"][li])
    x = _rmsnorm(x, ref["final_norm"], s["eps"])
    return act(x) @ wq8(ref["lm_head"])


def make_forward(cfg: dict, lowp: str | None = None):
    """Jitted ``(ref, tokens) -> logits`` at "highest" precision."""
    def run(ref, tokens):
        with jax.default_matmul_precision("highest"):
            return forward(ref, tokens, cfg, lowp)
    return jax.jit(run)


def make_gaps(cfg: dict):
    """Jitted ``(ref, tokens, targets) -> gap`` per position: how far the
    reference logit of ``targets[i]`` lies below the reference's best
    at position i."""
    def run(ref, tokens, targets):
        with jax.default_matmul_precision("highest"):
            lg = forward(ref, tokens, cfg)
        got = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got
    return jax.jit(run)


def make_control_gaps(cfg: dict, kind: str = "fp8"):
    """Jitted ``(ref, tokens) -> gap`` per position of the token that the
    control ``kind`` (see ``forward``) puts first, against the float32
    reference."""
    def run(ref, tokens):
        with jax.default_matmul_precision("highest"):
            lg = forward(ref, tokens, cfg)
            low = forward(ref, tokens, cfg, lowp=kind)
        pick = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(lg, pick[:, None], axis=-1)[:, 0]
        return jnp.max(lg, axis=-1) - got
    return jax.jit(run)
