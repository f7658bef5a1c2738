"""Serving launcher (smoke-scale batched generation).

    PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b \
        --smoke --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import telemetry as tm
from repro.cachedirs import setup_compile_cache
from repro.configs import get_config
from repro.data import SyntheticTokenDataset
from repro.models.model import init_params
from repro.serve.engine import ServeEngine


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg, jax.random.PRNGKey(0))  # reprolint: disable=RPL003 -- serve smoke CLI: deterministic params make runs comparable
    eng = ServeEngine(cfg, params,
                      max_seq=args.prompt_len + args.gen + 1,
                      temperature=args.temperature)
    ds = SyntheticTokenDataset(cfg.vocab_size, args.prompt_len, args.batch)
    prompts = jax.numpy.asarray(ds.batch_at(0)[:, :args.prompt_len])
    if cfg.frontend:
        from repro.models.frontend import synthetic_embeddings
        prompts = synthetic_embeddings(cfg, args.batch, args.prompt_len,
                                       jax.random.PRNGKey(1))  # reprolint: disable=RPL003 -- serve smoke CLI: deterministic synthetic embeddings
    t0 = tm.monotonic()
    out = eng.generate(prompts, args.gen)
    dt = tm.monotonic() - t0
    toks = args.batch * args.gen
    print(f"generated {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. compile)")
    print(np.asarray(out)[:2])


if __name__ == "__main__":
    main()
