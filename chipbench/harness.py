"""Run one cell of the on-chip benchmark once.

    python3 -m chipbench --workload CELL --seed N --seconds S --trace 0|1

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) pairs a
configuration (``chipbench/configs/<name>.json``) with a traffic mix
(``chipbench/traffic/<name>.json``).  Metrics are readers of their own,
found by name: end-to-end ones in ``chipbench/metrics/<name>.py``,
per-layer ones in ``chipbench/layer_metrics/<name>.py``; each defines
``read(run)`` and returns a number, or None where it finds nothing.
A mix's ``loop`` names its arrival process, ``chipbench/loops/<loop>.py``;
a configuration's ``devices`` its device model,
``chipbench/devices/<model>.py``; its ``reference`` the plain
reference, ``chipbench/references/<name>.py``.

One run, one process:

1. set-up: weights on the device from the seed (one jitted call), the
   deploy through ``ContinuousEngine`` (a plan cache of the run's own,
   so every run plans alike), and a warm-up of the cell's two shapes,
   one prefill at ``max_prompt`` and one decode at ``capacity``;
2. the window of ``--seconds``: the mix drives ``submit`` and ``step``;
   with ``--trace 1`` under the JAX profiler and the program's spans;
3. after the window: peak device memory, the program's state freed,
   and the correctness check against the configuration's plain
   reference, on a sample of the requests the window finished.

The last stdout line is the result; the last stderr lines are the
numbers compared, each with its limit.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

from chipbench import counts, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class SetupError(RuntimeError):
    """The run cannot start here: no program, no chip, no such cell."""


# ------------------------------------------------------------ discovery


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SetupError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def load_data(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SetupError(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics one run of ``cell`` reports.

    ``--trace 0``: the end-to-end metrics that list the cell, or list
    none.  ``--trace 1``: the per-layer metrics that list the cell, or
    list none and move a metric this cell reports.
    """
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [])
            or ("workloads" not in m and m["moves"] in names)]


# ------------------------------------------------------------ the run


@dataclasses.dataclass
class Rec:
    """One request as the window saw it (times: host clock, seconds)."""

    prompt: object
    max_tokens: int
    due: float                  # scheduled arrival, or submit time
    submitted: float = 0.0
    tokens: list = dataclasses.field(default_factory=list)
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: dict
    cfg: dict
    mix: dict
    peak: dict
    seed: int
    setup_s: float = 0.0
    t_open: float = 0.0         # host clock at the window's opening
    t_close: float = 0.0        # host clock when the last step ended
    recs: dict = dataclasses.field(default_factory=dict)
    due: list = dataclasses.field(default_factory=list)
    flops: float = 0.0          # useful FLOPs of the window's tokens
    deploy_report: dict = dataclasses.field(default_factory=dict)
    served: list = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    notes: dict = dataclasses.field(default_factory=dict)
    spans: list | None = None   # the program's spans (traced run)
    trace: object = None        # chipbench.trace.Trace (traced run)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def tokens_in_window(self) -> int:
        return sum(sum(1 for t in r.times if t <= self.t_close)
                   for r in self.recs.values())


@contextlib.contextmanager
def compile_counter():
    import jax

    n = {"compiles": 0}

    def listen(name, *_a, **_k):
        if name == "/jax/core/compile/backend_compile_duration":
            n["compiles"] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("chipbench." + name)


@dataclasses.dataclass
class Window:
    """What a loop (``chipbench/loops/<loop>.py``) drives the window by."""

    eng: object
    mix: dict
    t_open: float
    t_end: float
    submit: object              # (request, due) -> None
    step: object                # () -> None: one engine step
    wait: object                # (until) -> None: sleep, host clock
    clock: object = time.perf_counter


def _drive(run: Run, eng, seconds: float, vocab: int, traced: bool) -> None:
    """The measured window: the mix's loop drives ``submit`` and
    ``step``; the loop and its traffic are made before it opens."""
    mix = run.mix
    shapes = counts.dense_shapes(run.cfg)
    clock = time.perf_counter

    def on_token(rid, tok, done):
        r = run.recs.get(rid)
        if r is None:
            return
        n = len(r.tokens)
        L = len(r.prompt)
        r.tokens.append(tok)
        r.times.append(clock())
        run.flops += (counts.prefill_flops(shapes, L) if n == 0 else
                      counts.token_flops(shapes, L + n, sampled=True))

    def submit(req, due):
        rid = eng.submit(req.prompt, max_tokens=req.max_tokens,
                         temperature=mix["temperature"], on_token=on_token)
        run.recs[rid] = Rec(req.prompt, req.max_tokens, due, clock())
        run.due.append(rid)

    def step():
        with _annotate(traced, "step"):
            eng.step()

    def wait(until):
        with _annotate(traced, "wait"):
            time.sleep(max(0.0, until - clock()))

    loop = load_module("loops", mix["loop"])
    work = loop.prepare(mix, run.seed, seconds, vocab)
    t0 = clock()
    run.t_open = t0
    loop.drive(work, Window(eng, mix, t0, t0 + seconds, submit, step,
                            wait))
    run.t_close = clock()
    late = [r.submitted - r.due for r in run.recs.values()]
    print(f"chipbench: generator lateness over {len(late)} requests: "
          f"max {max(late, default=0.0) * 1e3:.3f} ms, mean "
          f"{sum(late) / max(1, len(late)) * 1e3:.3f} ms", file=sys.stderr)


def _peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def _read_spans(path: str) -> list[dict]:
    out = []
    with open(path) as f:
        for line in f:
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def compare(run: Run, params, ref_mod, control: tuple = ()) -> dict:
    """The served tokens of a seeded sample of finished requests against
    the plain reference; returns the numbers compared.

    ``control`` names controls of the reference module (never in a
    benchmark run; ``chipbench/control.py``): for each, the gap of the
    token that the control puts first, read at the same positions, goes
    under ``"controls"``.
    """
    import jax.numpy as jnp
    import numpy as np

    done = [rid for rid, r in run.recs.items()
            if len(r.tokens) == r.max_tokens and r.times
            and r.times[-1] <= run.t_close]
    k = min(run.mix["compare_requests"], len(done))
    sample = []
    if done:
        longest = max(done, key=lambda rid: (len(run.recs[rid].prompt)
                                             + run.recs[rid].max_tokens,
                                             -rid))
        rest = [rid for rid in done if rid != longest]
        rng = traffic.rng_for(run.seed, 3)
        pick = rng.choice(len(rest), size=k - 1, replace=False) if k > 1 \
            else []
        sample = [longest] + [rest[j] for j in sorted(pick)]
    ref = ref_mod.build(params, run.cfg)
    gaps_fn = ref_mod.make_gaps(run.cfg)
    ctl_fns = {kind: ref_mod.make_control_gaps(run.cfg, kind)
               for kind in control}
    S = run.mix["max_seq"]
    worst, n_tok = 0.0, 0
    ctl = {kind: 0.0 for kind in control}
    for rid in sample:
        r = run.recs[rid]
        seq = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.tokens, np.int32)])
        tokens = np.zeros(S, np.int32)
        tokens[:len(seq) - 1] = seq[:-1]
        targets = np.zeros(S, np.int32)
        targets[:len(seq) - 1] = seq[1:]
        g = np.asarray(gaps_fn(ref, jnp.asarray(tokens),
                               jnp.asarray(targets)))
        L = len(r.prompt)
        g = g[L - 1:len(seq) - 1]
        worst = max(worst, float(g.max()))
        n_tok += len(g)
        for kind, fn in ctl_fns.items():
            c = np.asarray(fn(ref, jnp.asarray(tokens)))
            ctl[kind] = max(ctl[kind], float(c[L - 1:len(seq) - 1].max()))
    vocab = run.cfg["vocab_size"]
    stray = sum(1 for r in run.recs.values() for t in r.tokens
                if not 0 <= t < vocab)
    out = {"controls": ctl} if control else {}
    return {**out, "logit_gap": {"value": worst,
                                 "limit": run.cfg["limits"]["logit_gap"]},
            "tokens_outside_vocab": {"value": stray, "limit": 0},
            "compared_tokens": {"value": n_tok,
                                "limit": run.mix["compare_min_tokens"]},
            "compared_requests": len(sample)}


def is_correct(checks: dict) -> bool:
    """Gap and stray tokens at most their limits; compared tokens at
    least theirs."""
    return (checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
            and checks["tokens_outside_vocab"]["value"]
            <= checks["tokens_outside_vocab"]["limit"]
            and checks["compared_tokens"]["value"]
            >= checks["compared_tokens"]["limit"])


def control_verdicts(checks: dict) -> dict:
    """``is_correct`` of each control put in the program's place: its
    gap as the logit gap, on the same compared tokens."""
    return {kind: is_correct({**checks, "logit_gap": {
        **checks["logit_gap"], "value": gap}})
        for kind, gap in checks.get("controls", {}).items()}


def run_cell(bench: dict, cell_name: str, seed: int, seconds: float,
             trace: bool, t_start: float, program=None, cfg=None,
             mix=None, control: tuple = (), keep: dict | None = None
             ) -> dict:
    """One run of one cell; returns the result object (not printed).

    ``program``, ``cfg`` and ``mix`` stand in for the program adapter,
    the configuration and the mix in tests on the CPU; ``keep`` receives
    the run's readings under ``"run"``.
    """
    import jax

    from chipbench import trace as trace_mod

    if program is None:
        from chipbench import program
    cell = cell_of(bench, cell_name)
    cfg = cfg or load_data("configs", cell["config"])
    mix = mix or load_data("traffic", cell["traffic"])
    ref_mod = load_module("references", cfg["reference"])
    dev = jax.devices()[0]
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    run = Run(cell, cfg, mix, peaks.get(dev.device_kind, {}), seed)
    if keep is not None:
        keep["run"] = run
    tmp = tempfile.mkdtemp(prefix="chipbench-")
    try:
        params = ref_mod.make_weights(cfg, seed)
        eng = program.make_engine(cfg, mix, params,
                                  os.path.join(tmp, "plans"))
        run.deploy_report = dict(eng.deploy_report or {})
        run.served = program.served_paths(eng)
        vocab = cfg["vocab_size"]
        warm = traffic.rng_for(seed, 4).integers(
            0, vocab, mix["max_prompt"], dtype="int32")
        eng.submit(warm, max_tokens=2)
        eng.run()
        gc.collect()
        gc.freeze()
        if trace:
            from repro import telemetry as tm

            tm.enable()
            tm.trace_to(os.path.join(tmp, "spans.jsonl"))
            jax.profiler.start_trace(os.path.join(tmp, "profile"))
        run.setup_s = time.perf_counter() - t_start
        with compile_counter() as cc, _annotate(trace, "window"):
            _drive(run, eng, seconds, vocab, trace)
        run.compiles_in_window = cc["compiles"]
        if trace:
            jax.profiler.stop_trace()
            tm.trace_stop()
            tm.disable()
            run.spans = _read_spans(os.path.join(tmp, "spans.jsonl"))
            path = trace_mod.find_xplane(os.path.join(tmp, "profile"))
            run.trace = trace_mod.load(path) if path else None
        peak = _peak_bytes()
        gc.unfreeze()
        del eng
        gc.collect()
        checks = compare(run, params, ref_mod, control)
        del params
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in metrics_of(bench, cell_name, trace):
        kind = "layer_metrics" if trace else "metrics"
        value = load_module(kind, m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": is_correct(checks),
        "attempted": len(run.due),
        "failed": 0,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": peak},
    }
    if trace and run.trace is not None:
        result["device"]["busy_s"] = trace_mod.busy_seconds(run.trace)
        result["device"]["window_s"] = trace_mod.window_seconds(run.trace)
        result["breakdown"] = trace_mod.breakdown(run.trace)
    print(f"chipbench: cell {cell_name} seed {seed}: served by "
          f"{','.join(run.served)}; {run.tokens_in_window()} tokens in "
          f"{run.window_s:.3f} s; {len(run.due)} requests; "
          f"{run.compiles_in_window} compiles in the window; deploy "
          f"{run.deploy_report.get('deploy_seconds', 0.0):.3f} s",
          file=sys.stderr)
    for k, v in run.notes.items():
        print(f"chipbench: {k}: {v}", file=sys.stderr)
    result["checks"] = checks
    return result


# ------------------------------------------------------------ entry


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(bench: dict, cell_name: str) -> None:
    """Find the program and the chip; raise SetupError where absent."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SetupError(f"the program (src/repro) is not in {ROOT}")
    if src not in sys.path:
        sys.path.insert(0, src)
    cell = cell_of(bench, cell_name)
    import jax

    # The persistent compile cache lives in the checkout, at a fixed path.
    cache = os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # No eviction: every program of a cell stays cached for its next run.
    jax.config.update("jax_compilation_cache_max_size", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SetupError(f"JAX found no TPU (devices: {devs})")
    if len(devs) < cell["chips"]:
        raise SetupError(f"cell {cell_name} needs {cell['chips']} chips, "
                         f"JAX found {len(devs)}")


def report(result: dict) -> None:
    for name, c in result["checks"].items():
        if isinstance(c, dict) and "limit" in c:
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        bench = load_benchmark()
        prepare(bench, args.workload)
    except SetupError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    report(result)
    return 0
