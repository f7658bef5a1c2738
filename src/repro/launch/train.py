"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch phi3-mini-3.8b \
        --smoke --steps 100 --batch 8 --seq 128

Full-size runs target the production mesh (requires real devices or the
dry-run's forced host device count); --smoke runs the reduced config on
whatever devices exist (the end-to-end example path).
"""
from __future__ import annotations

import argparse
import json

from repro.cachedirs import setup_compile_cache
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data import SyntheticTokenDataset
from repro.distributed.sharding import ShardingCtx
from repro.train.trainer import Trainer


def main():
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compression", default="",
                    choices=["", "int8_ef"])
    ap.add_argument("--metrics-out", default="")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    tcfg = TrainConfig(total_steps=args.steps, learning_rate=args.lr,
                       microbatches=args.microbatches,
                       checkpoint_dir=args.ckpt_dir,
                       checkpoint_every=args.ckpt_every,
                       grad_compression=args.grad_compression)
    ds = SyntheticTokenDataset(cfg.vocab_size, args.seq, args.batch,
                               seed=tcfg.seed)
    tr = Trainer(cfg, tcfg, ds, ctx=ShardingCtx())
    if args.resume:
        tr.resume_or_init()
    else:
        tr.init_state()
    log = tr.run(args.steps)
    for m in log[-5:]:
        print({k: round(v, 4) if isinstance(v, float) else v
               for k, v in m.items()})
    if tr.watchdog.stragglers:
        print(f"watchdog: {len(tr.watchdog.stragglers)} straggler steps")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(log, f)


if __name__ == "__main__":
    main()
