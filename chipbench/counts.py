"""Operations and bytes, counted from shapes alone.

Every count here is a function of a call's or a token's shapes and of
the configuration, never of the code that implements the call, so a
later change to the program cannot move the yardstick.
"""
from __future__ import annotations


def dense_shapes(cfg: dict) -> dict:
    """The sizes the counts need, from a configuration file's keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "hkv": cfg["num_key_value_heads"], "dh": dh,
            "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
            "layers": cfg["num_hidden_layers"],
            "window": cfg.get("sliding_window") or 0}


def matmul_weights_per_layer(s: dict) -> int:
    """Weights of q, k, v, o and the SwiGLU gate, up and down."""
    d, h, hkv, dh, f = s["d"], s["h"], s["hkv"], s["dh"], s["f"]
    return d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f


def token_flops(s: dict, context: int, sampled: bool) -> int:
    """Useful FLOPs of one token that attends to ``context`` positions.

    2 per matmul weight it passes through, QK and PV at the context
    actually attended (capped by a sliding window), and the head only
    where a token is sampled.  The Eq-17 expansion is not counted.
    """
    if s["window"]:
        context = min(context, s["window"])
    per_layer = 2 * matmul_weights_per_layer(s) + 4 * context * s["h"] * s["dh"]
    head = 2 * s["d"] * s["v"] if sampled else 0
    return s["layers"] * per_layer + head


def prefill_flops(s: dict, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens without its padding; the head
    runs once, at the last position, which samples the first token."""
    w = s["window"] or prompt_len
    if prompt_len <= w:
        contexts = prompt_len * (prompt_len + 1) // 2
    else:
        contexts = w * (w + 1) // 2 + (prompt_len - w) * w
    per_layer = (2 * matmul_weights_per_layer(s) * prompt_len
                 + 4 * contexts * s["h"] * s["dh"])
    return s["layers"] * per_layer + 2 * s["d"] * s["v"]


def cim_mvm_flops(m: int, i: int, n: int) -> int:
    """y (M, N) = x (M, I) @ W' (I, N)."""
    return 2 * m * i * n


def cim_mvm_bytes(m: int, i: int, n: int, *, n_bits: int, rows: int,
                  cols: int, gain: bool) -> float:
    """The least a crossbar matmul's deployment must carry.

    (n_bits + 1) / 8 bytes per signed code, one byte per tile row for
    its physical position, 4 bytes per weight of gain where the
    deployment has one, and bfloat16 activations in and out.
    """
    wpt = cols // n_bits
    col_tiles = -(-n // wpt)
    codes = i * n * (n_bits + 1) / 8
    positions = i * col_tiles
    gain_bytes = 4 * i * n if gain else 0
    acts = 2 * m * i + 2 * m * n
    return codes + positions + gain_bytes + acts


def least_seconds(flops: float, nbytes: float, peak: dict
                  ) -> tuple[float, str]:
    """The roofline's least time and the side that bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
