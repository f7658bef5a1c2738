"""Useful FLOPs of the window's tokens over (window x peak bf16 FLOP/s),
percent.  Prompt tokens without padding and generated tokens; 2 per
matmul weight, attention at the context attended, the head where a
token is sampled (``chipbench.counts``)."""


def read(run):
    peak = run.peak.get("bf16_flops_per_s")
    if not peak or run.flops <= 0:
        return None
    return 100.0 * run.flops / (run.window_s * peak)
