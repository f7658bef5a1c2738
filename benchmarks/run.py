"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME] [--list]

Prints a ``name,seconds,derived`` CSV line per benchmark plus each
module's detailed output, and dumps results/benchmarks.json.

The :data:`BENCHES` table is the **registry of record**: the semantic
auditor (``repro.analysis.audit``) cross-checks it against the module
files on disk and against the ``--only`` names ``scripts/test_nightly
.sh`` invokes, so a benchmark module that exists but is not registered
— or a nightly entry that silently matches nothing — fails CI.
``--only`` accepts either the registered benchmark name or the module
name (one module may back several benchmarks) and **errors** on an
unknown token instead of no-opping: a typo'd nightly line must fail
loudly, not skip the benchmark and exit 0.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from typing import Callable

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import telemetry as tm  # noqa: E402  (stdlib-only, jax-free)
from repro.cachedirs import setup_compile_cache  # noqa: E402  (jax-free at import)

# 8-way host-device simulation for the sharded-solver rows (must land
# before the first jax import initialises the backend); append so an
# operator-supplied XLA_FLAGS still wins.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

from benchmarks import (  # noqa: E402
    accuracy_noise,
    cim_traffic,
    deploy_throughput,
    fault_tolerance,
    hypothesis_fit,
    mapping_matrix,
    nf_reduction,
    planning_cost,
    roofline_table,
    serving_health,
    serving_load,
    solver_throughput,
    theorem1,
)


@dataclasses.dataclass(frozen=True)
class Bench:
    """One registered benchmark.

    ``name`` is the historical results/benchmarks.json key (stable —
    changing it orphans recorded history); ``module`` is the backing
    ``benchmarks/<module>.py`` file; ``run`` takes the ``--quick``
    flag.  ``seed`` is the registered workload seed for stochastic
    open-loop benchmarks (recorded into the results entry so a run is
    replayable from its record alone); None for deterministic ones.
    """

    name: str
    module: str
    run: Callable[[bool], dict]
    seed: int | None = None


BENCHES: tuple[Bench, ...] = (
    # paper §III-A (Theorem 1)
    Bench("theorem1_sparsity", "theorem1", lambda q: theorem1.run()),
    # paper Fig 4
    Bench("manhattan_hypothesis_fit", "hypothesis_fit",
          lambda q: hypothesis_fit.run(n_tiles=64 if q else 500)),
    # paper Fig 5
    Bench("nf_reduction", "nf_reduction", lambda q: nf_reduction.run()),
    # paper Fig 6
    Bench("accuracy_under_noise", "accuracy_noise",
          lambda q: accuracy_noise.run(train_steps=60 if q else 250)),
    # paper §IV "lightweight" claim
    Bench("mdm_planning_cost", "planning_cost",
          lambda q: planning_cost.run()),
    # §Perf: solver scale-out matrix (seed lax.map vs batched vs
    # sharded/mixed on the 8-way device simulation), both regimes:
    # 64x64 paper-scale tiles (work-bound on small hosts) and
    # 32x32 tiles (latency-bound; the sharded engine's >= 2x row).
    Bench("solver_throughput", "solver_throughput",
          lambda q: solver_throughput.run(
              n_tiles=128 if q else 512, rows=32 if q else 64,
              cols=32 if q else 64, seq_tiles=32 if q else 64)),
    Bench("solver_throughput_32x32", "solver_throughput",
          lambda q: solver_throughput.run(
              n_tiles=128 if q else 512, rows=32, cols=32,
              seq_tiles=32 if q else 64)),
    # §Perf: fused CIM path vs materialised bit-planes
    Bench("cim_traffic", "cim_traffic", lambda q: cim_traffic.run()),
    # §Perf: whole-model deployment engine — fused vs per-layer
    # planning, cache-hit redeploy, CIM serving tokens/s
    Bench("deploy_throughput", "deploy_throughput",
          lambda q: deploy_throughput.run(n_per_shape=1 if q else 3)),
    # §Nonideal: stuck-fault x variation Monte-Carlo distributions,
    # baseline vs MDM vs fault-aware vs significance-weighted MDM
    Bench("fault_tolerance", "fault_tolerance",
          lambda q: fault_tolerance.run(
              n_rows=128 if q else 256, n_samples=3 if q else 6,
              rates=(0.01, 0.05) if q else (0.002, 0.01, 0.05),
              sigmas=(0.0,) if q else (0.0, 0.1))),
    # §Nonideal: line-open (wordline + bitline) rate sweep — spare-line
    # row+column remapping vs the row-only sorts (structural faults)
    Bench("fault_line_open", "fault_tolerance",
          lambda q: fault_tolerance.run_line_open(
              n_rows=128 if q else 256, n_samples=2,
              rates=((0.05, 0.02),) if q
              else ((0.02, 0.01), (0.05, 0.02), (0.08, 0.05)))),
    # §Nonideal: lifetime resilience — monitored (probe + remediation
    # ladder) vs unmonitored twin engines through an aging sweep
    Bench("serving_health", "serving_health",
          lambda q: serving_health.run(
              ages=(3e2, 1e4) if q else (3e2, 1e4, 3e5))),
    # §Serving tier: continuous batching over the CIM path — saturating
    # capacity sweep, open-loop Poisson latency (the registered seed
    # drives the arrival process), mid-load async redeploy gates
    Bench("serving_load", "serving_load",
          lambda q: serving_load.run(
              capacities=(1, 2, 4) if q else (1, 2, 4, 8),
              n_requests=8 if q else 16, max_tokens=6 if q else 8,
              latency_n=12 if q else 24, arrival_seed=1234),
          seed=1234),
    # §Mapping API: registered row x column strategy matrix (Eq-16
    # NF on the standard 64x64 population)
    Bench("mapping_matrix", "mapping_matrix",
          lambda q: mapping_matrix.run(n_rows=128 if q else 512)),
    # §Dry-run / §Roofline summary
    Bench("roofline_table", "roofline_table",
          lambda q: roofline_table.run()),
)


def registered_modules() -> frozenset[str]:
    """Module names the registry covers (auditor entry point)."""
    return frozenset(b.module for b in BENCHES)


def resolve_only(token: str) -> list[Bench]:
    """Benches selected by one ``--only`` token (name or module).

    An exact registered-name match selects that one benchmark; only
    otherwise does the token select every benchmark its module backs —
    so a name that doubles as a module name (``fault_tolerance``) stays
    addressable on its own.  Raises ``KeyError`` on an unknown token —
    the silent-no-op behaviour this replaced let a typo'd nightly entry
    skip its benchmark while exiting 0.
    """
    hits = [b for b in BENCHES if token == b.name]
    if not hits:
        hits = [b for b in BENCHES if token == b.module]
    if not hits:
        raise KeyError(
            f"unknown benchmark {token!r}; known names: "
            f"{[b.name for b in BENCHES]} (module names also accepted)")
    return hits


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced tile counts / training steps")
    ap.add_argument("--only", default="",
                    help="run one benchmark (registered name or module "
                         "name); unknown names are an error")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    ap.add_argument("--trace", action="store_true",
                    help="record a results/trace/<name>.jsonl span "
                         "trace per benchmark (summarise with "
                         "scripts/trace_report.py)")
    args = ap.parse_args()

    if args.list:
        for b in BENCHES:
            tail = "" if b.seed is None else f" seed={b.seed}"
            print(f"{b.name} (benchmarks/{b.module}.py){tail}")
        return

    if args.only:
        try:
            selected = resolve_only(args.only)
        except KeyError as e:
            ap.error(str(e))
    else:
        selected = list(BENCHES)

    out = os.path.join(os.path.dirname(__file__), "..", "results")
    os.makedirs(out, exist_ok=True)

    # The harness runs with telemetry on: every entry in
    # results/benchmarks.json carries the metrics the instrumented
    # library paths recorded during that benchmark (registry reset
    # per bench, so counters are per-entry, not cumulative).
    tm.enable()
    results, csv_lines = {}, ["name,seconds,derived"]
    for bench in selected:
        print(f"== {bench.name} ==")
        tm.registry().reset()
        trace_rel = None
        if args.trace:
            trace_rel = os.path.join("trace", f"{bench.name}.jsonl")
            tm.trace_to(os.path.join(out, trace_rel))
        started_at = tm.wall_time()
        t0 = tm.monotonic()
        try:
            with tm.span(f"bench/{bench.name}", quick=args.quick):
                res = bench.run(args.quick)
            dt = tm.monotonic() - t0
            results[bench.name] = {"ok": True, "seconds": dt,
                                   "result": res}
            derived = _derive(bench.name, res)
        except Exception as e:  # pragma: no cover
            dt = tm.monotonic() - t0
            results[bench.name] = {"ok": False, "seconds": dt,
                                   "error": repr(e)}
            derived = f"ERROR:{e!r}"
        if args.trace:
            tm.trace_stop()
        results[bench.name]["started_at"] = started_at
        if bench.seed is not None:
            # The registered workload seed (e.g. serving_load's
            # open-loop arrival process) travels with the entry, so a
            # recorded run is replayable without consulting the code.
            results[bench.name]["seed"] = bench.seed
        results[bench.name]["telemetry"] = {
            "metrics": tm.registry().snapshot(),
            "trace": trace_rel,
        }
        csv_lines.append(f"{bench.name},{dt:.3f},{derived}")
        print()

    print("\n".join(csv_lines))
    path = os.path.join(out, "benchmarks.json")
    # Merge into the existing record so `--only NAME` refreshes one
    # entry instead of clobbering the rest of the matrix.
    merged = {}
    try:
        with open(path) as f:
            merged = json.load(f)
    except (FileNotFoundError, ValueError):
        pass
    if not isinstance(merged, dict):
        merged = {}
    merged.update(results)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1, default=str)

    failed = {k: v["error"] for k, v in results.items() if not v["ok"]}
    if failed:
        # A crashed benchmark must fail the harness (and the nightly
        # lines driving it), not just leave an ERROR cell in the CSV.
        print(f"\nFAILED {len(failed)}/{len(results)} benchmark(s):",
              file=sys.stderr)
        for name, err in failed.items():
            print(f"  {name}: {err}", file=sys.stderr)
        sys.exit(1)


def _derive(name: str, res: dict) -> str:
    try:
        if name == "manhattan_hypothesis_fit":
            return (f"r={res['pearson_r']:.4f};sigma="
                    f"{res['fit_err_std_pct']:.2f}%")
        if name == "nf_reduction":
            best = max(v["reduction_pct"]["mdm"]
                       for k, v in res.items() if isinstance(v, dict)
                       and isinstance(v.get("reduction_pct"), dict))
            return f"best_mdm_nf_reduction={best:.1f}%"
        if name == "accuracy_under_noise":
            eta = max(res["noisy"])
            row = res["noisy"][eta]
            gain = row["baseline"] - row["mdm"]
            return f"ce_gain_mdm_vs_baseline@eta={eta:g}:{gain:.4f}"
        if name == "theorem1_sparsity":
            return "bound_ok=" + str(all(
                v.get("bound_ok") for v in res.values()
                if isinstance(v, dict) and "bound_ok" in v))
        if name == "roofline_table":
            return f"cells_ok={res['ok']}/{res['cells']}"
        if name == "mdm_planning_cost":
            return f"plan_4096x4096={res['plan_4096x4096']['seconds']:.3f}s"
        if name.startswith("solver_throughput"):
            return (f"speedup=x{res['speedup']:.1f};"
                    f"{res['batched_tiles_per_s']:.0f}tiles/s;"
                    f"scaleout=x"
                    f"{res['speedup_scaleout_best_vs_batched_f64']:.2f};"
                    f"sharded_mixed=x"
                    f"{res['speedup_sharded_mixed_vs_batched_f64']:.2f}"
                    f"@{res['sharded_mixed_tiles_per_s']:.0f}tiles/s;"
                    f"mixed_err={res['mixed_max_rel_voltage_err']:.1e}")
        if name == "cim_traffic":
            return (f"kernel_traffic_reduction=x{res['kernel_ratio']:.1f};"
                    f"xla=x{res['xla_ratio']:.2f}")
        if name == "deploy_throughput":
            p = res["planning_64x64"]
            return (f"fused_cold=x{p['speedup_cold']:.1f};"
                    f"cache_hit=x{p['cache_hit_speedup_vs_cold']:.1f};"
                    f"serve_cim="
                    f"{res['serving']['cim_mdm']['tokens_per_s']:.0f}tok/s")
        if name == "fault_tolerance":
            wins = res["fault_aware_beats_mdm"]
            return ("fault_aware_beats_mdm="
                    + ",".join(f"{k}:{v}" for k, v in wins.items())
                    + ";sig_ge_aware="
                    + str(res["sig_weighted_matches_fault_aware_all_rates"]))
        if name == "fault_line_open":
            wins = res["spare_line_beats_fault_aware"]
            return ("spare_line_beats_fault_aware="
                    + ",".join(f"{k}:{v}" for k, v in wins.items())
                    + ";all_rates="
                    + str(res["spare_line_beats_fault_aware_all_rates"]))
        if name == "serving_health":
            return (f"fresh={res['fresh_err']:.3f};"
                    f"unmon_worst={max(res['unmonitored_err']):.3f};"
                    f"mon_worst={max(res['monitored_err']):.3f};"
                    f"all_gates={res['all_gates']}")
        if name == "serving_load":
            caps = res["capacities"]
            t = res["throughput"]
            hot = res["latency"]["2x"]
            return (f"tok/s@c{caps[0]}->c{caps[-1]}="
                    f"{t[str(caps[0])]['tokens_per_s']:.0f}->"
                    f"{t[str(caps[-1])]['tokens_per_s']:.0f};"
                    f"p95@2x={hot['p95_s'] * 1e3:.0f}ms;"
                    f"all_gates={res['all_gates']}")
        if name == "mapping_matrix":
            return (f"best={res['best_cell']}@"
                    f"{res['best_reduction_pct']:.1f}%")
    except Exception as e:
        return f"derive_error:{e!r}"
    return "ok"


if __name__ == "__main__":
    main()
