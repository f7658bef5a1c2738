"""Readings that set a cell's correctness limit (not a benchmark run).

    python3 chipbench/control.py --workload CELL --seconds S --seeds 1,2,3 [--control fp8,bf16]

For each seed, one run of the cell as the benchmark makes it (set-up,
window, sample of finished requests), in one process, printing one
JSON line: the widest logit gap of the program's served tokens against
the float32 reference, and with ``--control`` for each named control
of the reference (``fp8``: the whole model in float8; ``bf16``: the
crossbar matmuls on bfloat16 operands) the widest gap of the tokens
that it puts first on the same prompts and tokens, and whether the
control put in the program's place comes out correct.  The largest
program gap over a dozen seeds is the limit's lower reading; the
smallest gap of the control below the configuration's precision is
its upper one.

``--trace-out DIR`` traces each run and writes its device ops by name.

    python3 chipbench/control.py --workload CELL --seconds S --rates 2,4,8

instead sweeps an open-loop mix over fixed arrival rates on one
deployment and prints, per rate, the tails and the backlog at the
window's close: the knee is the highest rate whose backlog stays flat.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402


def op_table(tr) -> list:
    """Every device op of a trace by name: [name, calls, seconds]."""
    lo, hi = tr.window()
    tab: dict = {}
    for ops in tr.devices.values():
        for o in ops:
            if o.end > lo and o.start < hi:
                n, t = tab.get(o.name, (0, 0.0))
                tab[o.name] = (n + 1, t + (o.end - o.start) * 1e-9)
    return sorted(([k, n, t] for k, (n, t) in tab.items()),
                  key=lambda r: -r[2])


def readings(args, bench) -> None:
    for seed in args.seeds:
        t0 = time.perf_counter()
        keep: dict = {}
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               bool(args.trace_out), t0,
                               control=args.control, keep=keep)
        if args.trace_out and keep["run"].trace is not None:
            os.makedirs(args.trace_out, exist_ok=True)
            with open(os.path.join(args.trace_out,
                                   f"ops-{seed}.json"), "w") as f:
                json.dump({"result": res,
                           "ops": op_table(keep["run"].trace)}, f,
                          indent=1)
        c = res["checks"]
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "logit_gap": c["logit_gap"]["value"],
            "control_gap": c.get("controls"),
            "control_correct": harness.control_verdicts(c),
            "compared_tokens": c["compared_tokens"]["value"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "peak": res["device"]["memory_peak_bytes"]}), flush=True)
        gc.collect()


def sweep(args, bench) -> None:
    import jax

    from chipbench import program

    cell = harness.cell_of(bench, args.workload)
    cfg = harness.load_data("configs", cell["config"])
    mix = harness.load_data("traffic", cell["traffic"])
    ref_mod = harness.load_module("references", cfg["reference"])
    seed = args.seeds[0]
    params = ref_mod.make_weights(cfg, seed)
    with tempfile.TemporaryDirectory() as tmp:
        eng = program.make_engine(cfg, mix, params, tmp)
    warm = harness.traffic.rng_for(seed, 4).integers(
        0, cfg["vocab_size"], mix["max_prompt"], dtype="int32")
    eng.submit(warm, max_tokens=2)
    eng.run()
    ttft = harness.load_module("metrics", "ttft_p90_ms")
    itl = harness.load_module("metrics", "itl_p95_ms")
    for rate in args.rates:
        m = dict(mix, rate_per_s=rate)
        run = harness.Run(cell, cfg, m, {}, seed)
        harness._drive(run, eng, args.seconds, cfg["vocab_size"], False)
        open_at_close = sum(1 for rid in run.due
                            if len(run.recs[rid].tokens)
                            < run.recs[rid].max_tokens)
        unanswered = sum(1 for rid in run.due
                         if not run.recs[rid].tokens)
        print(json.dumps({
            "rate_per_s": rate, "due": len(run.due),
            "ttft_p90_ms": ttft.read(run), "itl_p95_ms": itl.read(run),
            "tokens_per_s": run.tokens_in_window() / run.window_s,
            "queued_at_close": eng.scheduler.queue_depth,
            "unfinished_at_close": open_at_close,
            "unanswered_at_close": unanswered}), flush=True)
        eng.run()          # drain before the next rate
    del eng
    jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=lambda s: [int(x) for x in s.split(",")],
                    required=True)
    ap.add_argument("--control", default=(),
                    type=lambda s: tuple(s.split(",")),
                    help="controls to read, e.g. fp8,bf16")
    ap.add_argument("--trace-out", help="trace the runs; write each "
                    "run's device ops by name to this directory")
    ap.add_argument("--rates", type=lambda s: [float(x)
                                               for x in s.split(",")])
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    try:
        harness.prepare(bench, args.workload)
    except harness.SetupError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    (sweep if args.rates else readings)(args, bench)
    return 0


if __name__ == "__main__":
    sys.exit(main())
