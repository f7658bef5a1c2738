"""Tier-1 gate for the telemetry subsystem (metrics, spans, pipeline).

Pins the three contracts docs/observability.md promises:

* **disabled is free** — the off path allocates nothing and changes no
  state, and enabling telemetry cannot change a single computed value
  (the serve engine generates bit-identical tokens on vs. off);
* **exposition is deterministic** — golden Prometheus-text and JSON
  snapshots, strict once-only registration (the AUD007 hook);
* **traces reconstruct the run** — span JSONL round-trips through the
  ``repro.telemetry.report`` aggregator and the ``scripts/
  trace_report.py`` CLI with self-times telescoping to the root wall
  time (the >= 95% coverage acceptance gate holds by construction).

All metric-object tests use **local** ``MetricsRegistry`` instances so
the process-global default registry stays exactly what the library
modules declared — the semantic auditor (AUD007) checks that registry
against the static declarations.
"""
from __future__ import annotations

import gc
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import telemetry as tm
from repro.telemetry.metrics import MetricsRegistry
from repro.telemetry.report import aggregate, coverage, load_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry_reset():
    """Every test leaves telemetry off, untraced, and zeroed."""
    yield
    tm.disable()
    tm.trace_stop()
    tm.registry().reset()


# ------------------------------- metrics ----------------------------------


def test_counter_gauge_histogram_basic():
    tm.enable()
    reg = MetricsRegistry()
    c = reg.counter("t_ops_total", "Ops.")
    g = reg.gauge("t_depth", "Depth.")
    h = reg.histogram("t_lat_seconds", "Latency.", buckets=(0.1, 1.0))
    c.inc()
    c.inc(2)
    g.set(5.0)
    g.dec()
    h.observe(0.5)
    h.observe(1.0)  # le bounds are inclusive
    h.observe(5.0)  # overflow -> +Inf only
    snap = reg.snapshot()
    assert snap["t_ops_total"]["values"] == [{"labels": {}, "value": 3.0}]
    assert snap["t_depth"]["values"] == [{"labels": {}, "value": 4.0}]
    hv = snap["t_lat_seconds"]["values"][0]
    assert hv["counts"] == [0, 2, 1]
    assert hv["sum"] == 6.5 and hv["count"] == 3


def test_labels_create_children_and_validate():
    tm.enable()
    reg = MetricsRegistry()
    c = reg.counter("t_req_total", "Reqs.", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="b").inc(4)
    vals = {tuple(v["labels"].items()): v["value"]
            for v in reg.snapshot()["t_req_total"]["values"]}
    assert vals == {(("kind", "a"),): 1.0, (("kind", "b"),): 4.0}
    with pytest.raises(ValueError, match="labels"):
        c.labels(wrong="x")


def test_counter_rejects_negative_and_bad_names():
    tm.enable()
    reg = MetricsRegistry()
    c = reg.counter("t_down_total")
    with pytest.raises(ValueError, match="only go up"):
        c.inc(-1)
    with pytest.raises(ValueError, match="bad metric name"):
        reg.counter("Bad-Name")


def test_registration_is_strict_once_only():
    """The AUD007 hook: one name registers exactly once per registry."""
    reg = MetricsRegistry()
    reg.counter("t_dup_total")
    with pytest.raises(ValueError, match="AUD007"):
        reg.gauge("t_dup_total")


def test_prometheus_exposition_golden():
    tm.enable()
    reg = MetricsRegistry()
    c = reg.counter("g_requests_total", "Requests.", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    reg.gauge("g_temp", "Temp.").set(1.5)
    h = reg.histogram("g_lat_seconds", "Latency.", buckets=(0.1, 1.0))
    for v in (0.5, 1.0, 5.0):
        h.observe(v)
    assert reg.to_prometheus() == (
        "# HELP g_lat_seconds Latency.\n"
        "# TYPE g_lat_seconds histogram\n"
        'g_lat_seconds_bucket{le="0.1"} 0\n'
        'g_lat_seconds_bucket{le="1"} 2\n'
        'g_lat_seconds_bucket{le="+Inf"} 3\n'
        "g_lat_seconds_sum 6.5\n"
        "g_lat_seconds_count 3\n"
        "# HELP g_requests_total Requests.\n"
        "# TYPE g_requests_total counter\n"
        'g_requests_total{kind="a"} 3\n'
        "# HELP g_temp Temp.\n"
        "# TYPE g_temp gauge\n"
        "g_temp 1.5\n")


def test_json_snapshot_round_trips():
    tm.enable()
    reg = MetricsRegistry()
    reg.counter("t_j_total").inc(7)
    assert json.loads(reg.to_json())["t_j_total"]["values"][0][
        "value"] == 7.0


def test_reset_zeroes_values_keeps_registrations():
    tm.enable()
    reg = MetricsRegistry()
    c = reg.counter("t_r_total", labels=("k",))
    c.labels(k="x").inc(3)
    reg.reset()
    assert reg.names() == frozenset({"t_r_total"})
    assert reg.snapshot()["t_r_total"]["values"] == []
    c.labels(k="x").inc()  # children still usable after reset
    assert reg.snapshot()["t_r_total"]["values"][0]["value"] == 1.0


# --------------------------- disabled fast path ---------------------------


def test_disabled_records_nothing():
    tm.disable()
    reg = MetricsRegistry()
    c = reg.counter("t_off_total", labels=("k",))
    h = reg.histogram("t_off_seconds")
    g = reg.gauge("t_off_depth")
    c.inc()
    c.labels(k="x").inc(5)  # shared no-op child, no key created
    h.observe(1.0)
    g.set(9.0)
    snap = reg.snapshot()
    assert snap["t_off_total"]["values"] == []
    assert snap["t_off_seconds"]["values"][0]["count"] == 0
    assert snap["t_off_depth"]["values"][0]["value"] == 0.0


@pytest.mark.parametrize("sink", [False, True])
def test_disabled_fast_path_allocates_nothing(sink, tmp_path):
    """The off path is a flag test + return: zero allocated blocks
    across 10k record calls and spans (small slack for interpreter
    noise), with or without a span sink open."""
    tm.disable()
    if sink:
        tm.trace_to(str(tmp_path / "off.jsonl"))
    reg = MetricsRegistry()
    c = reg.counter("t_alloc_total")
    h = reg.histogram("t_alloc_seconds")
    g = reg.gauge("t_alloc_depth")

    def burst(n):
        for _ in range(n):
            c.inc()
            h.observe(0.5)
            g.set(1.0)
            with tm.span("serve/iteration", it=1):
                pass

    burst(1000)  # warm method caches
    gc.collect()
    before = sys.getallocatedblocks()
    burst(10000)
    gc.collect()
    assert sys.getallocatedblocks() - before <= 16
    assert tm.span("x") is tm.span("y")


def test_disabled_overhead_smoke():
    """30k disabled record calls stay well under 100ms (~us/call)."""
    tm.disable()
    reg = MetricsRegistry()
    c = reg.counter("t_fast_total")
    t0 = tm.monotonic()
    for _ in range(30000):
        c.inc()
    assert tm.monotonic() - t0 < 0.1


def test_enable_after_import_activates_labels():
    """labels() taken at use time honours a later enable()."""
    tm.disable()
    reg = MetricsRegistry()
    c = reg.counter("t_late_total", labels=("k",))
    c.labels(k="x").inc()  # no-op child
    tm.enable()
    c.labels(k="x").inc()
    assert reg.snapshot()["t_late_total"]["values"][0]["value"] == 1.0


# -------------------------------- spans -----------------------------------


def test_span_noop_without_sink_or_enable(tmp_path):
    tm.enable()
    assert not tm.tracing()
    s = tm.span("x")  # no sink open
    assert s is tm.span("y")  # the shared no-op instance
    tm.trace_to(str(tmp_path / "t.jsonl"))
    tm.disable()
    assert tm.span("z") is s  # sink open but disabled


def test_span_jsonl_round_trip_and_coverage(tmp_path):
    tm.enable()
    path = tm.trace_to(str(tmp_path / "t.jsonl"))
    with tm.span("root", runs=1):
        with tm.span("child/a"):
            pass
        with tm.span("child/b", n=2):
            pass
    assert tm.trace_stop() == path
    spans = load_spans(path)
    # spans are written at exit: children first, root last
    assert [s["name"] for s in spans] == ["child/a", "child/b", "root"]
    by = {s["name"]: s for s in spans}
    assert by["root"]["parent"] is None and by["root"]["depth"] == 0
    assert by["child/a"]["parent"] == by["root"]["id"]
    assert by["child/b"]["depth"] == 1
    assert by["child/b"]["attrs"] == {"n": 2}
    assert all(s["dur"] >= 0 and s["t_end"] >= s["t_start"]
               for s in spans)
    stats, wall = aggregate(spans)
    assert wall == pytest.approx(by["root"]["dur"])
    # self-times telescope: the named phases cover the full wall time
    assert coverage(spans) == pytest.approx(1.0, abs=1e-6)
    assert stats["root"]["self"] == pytest.approx(
        by["root"]["dur"] - by["child/a"]["dur"] - by["child/b"]["dur"])


def test_spans_held_in_memory_until_trace_stop(tmp_path):
    tm.enable()
    path = tm.trace_to(str(tmp_path / "t.jsonl"))
    with tm.span("phase/a"):
        pass
    assert os.path.getsize(path) == 0       # nothing written yet
    tm.trace_stop()
    assert [s["name"] for s in load_spans(path)] == ["phase/a"]


def test_replaced_sink_is_written_out(tmp_path):
    tm.enable()
    first = tm.trace_to(str(tmp_path / "a.jsonl"))
    with tm.span("phase/first"):
        pass
    second = tm.trace_to(str(tmp_path / "b.jsonl"))
    with tm.span("phase/second"):
        pass
    tm.trace_stop()
    assert [s["name"] for s in load_spans(first)] == ["phase/first"]
    assert [s["name"] for s in load_spans(second)] == ["phase/second"]
    assert load_spans(second)[0]["id"] == 0


def test_gc_pauses_recorded_as_spans(tmp_path):
    from repro.telemetry.trace import _on_gc

    tm.enable()
    path = tm.trace_to(str(tmp_path / "t.jsonl"))
    assert _on_gc in gc.callbacks
    with tm.span("phase/outer"):
        gc.collect()
    tm.trace_stop()
    assert _on_gc not in gc.callbacks
    spans = load_spans(path)
    outer = next(s for s in spans if s["name"] == "phase/outer")
    pauses = [s for s in spans if s["name"] == "runtime/gc"
              and s["parent"] == outer["id"]]
    assert pauses and pauses[-1]["attrs"]["generation"] == 2
    assert pauses[-1]["attrs"]["collected"] >= 0
    assert pauses[-1]["depth"] == outer["depth"] + 1


def test_spans_need_no_jax(tmp_path):
    """repro.telemetry imports and traces in a process without jax: a
    span then writes no profiler event and needs none."""
    code = ("import sys; from repro import telemetry as tm; "
            "tm.enable(); p = tm.trace_to(sys.argv[1]); "
            "exec('with tm.span(\"phase/a\"):\\n    pass'); "
            "tm.trace_stop(); assert 'jax' not in sys.modules; "
            "print(open(p).read())")
    res = subprocess.run(
        [sys.executable, "-c", code, str(tmp_path / "t.jsonl")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert res.returncode == 0, res.stderr
    assert json.loads(res.stdout)["name"] == "phase/a"


def test_trace_report_cli(tmp_path):
    tm.enable()
    path = tm.trace_to(str(tmp_path / "t.jsonl"))
    with tm.span("phase/outer"):
        with tm.span("phase/inner"):
            pass
    tm.trace_stop()
    res = subprocess.run(
        [sys.executable, os.path.join("scripts", "trace_report.py"),
         path],
        capture_output=True, text=True, cwd=REPO)
    assert res.returncode == 0, res.stderr
    assert "phase/outer" in res.stdout and "phase/inner" in res.stdout
    assert "cover" in res.stdout
    res = subprocess.run(
        [sys.executable, os.path.join("scripts", "trace_report.py"),
         "--json", path],
        capture_output=True, text=True, cwd=REPO)
    data = json.loads(res.stdout)
    assert data[path]["spans"] == 2
    assert set(data[path]["phases"]) == {"phase/outer", "phase/inner"}


def test_trace_report_cli_unreadable_file_fails():
    res = subprocess.run(
        [sys.executable, os.path.join("scripts", "trace_report.py"),
         "no/such/trace.jsonl"],
        capture_output=True, text=True, cwd=REPO)
    assert res.returncode == 1
    assert "cannot read" in res.stderr


def test_load_spans_skips_torn_lines(tmp_path):
    p = tmp_path / "torn.jsonl"
    p.write_text('{"name": "a", "id": 0, "parent": null, "dur": 1.0}\n'
                 'not json\n'
                 '{"other": "record"}\n'
                 '{"name": "b", "id": 1, "parent": 0, "du')
    spans = load_spans(str(p))
    assert [s["name"] for s in spans] == ["a"]


# --------------------- pipeline instrumentation (e2e) ---------------------


SERVE_CFG = None


def _serve_cfg():
    global SERVE_CFG
    if SERVE_CFG is None:
        from repro.configs.base import CimConfig, ModelConfig
        SERVE_CFG = ModelConfig(
            name="cim-telemetry-test", n_layers=2, d_model=32,
            n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
            block_pattern=("attn",), remat="none", dtype="float32",
            attn_chunk=32,
            cim=CimConfig(enabled=True, mode="mdm", rows=16, cols=16,
                          n_bits=4))
    return SERVE_CFG


def _engine(tmp_path):
    from repro.deploy import PlanCache
    from repro.models.model import init_params
    from repro.serve import ServeEngine

    cfg = _serve_cfg()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, max_seq=64,
                       plan_cache=PlanCache(str(tmp_path)))


def test_generation_bit_identical_on_vs_off(tmp_path):
    """Enabling telemetry + tracing must not move a single token."""
    eng = _engine(tmp_path / "cache")
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 128)
    tm.disable()
    off = np.asarray(eng.generate(prompts, 4, seed=3))
    tm.enable()
    tm.trace_to(str(tmp_path / "on.jsonl"))
    on = np.asarray(eng.generate(prompts, 4, seed=3))
    np.testing.assert_array_equal(off, on)


def test_deploy_serve_smoke_metrics_and_trace(tmp_path):
    """The acceptance smoke: telemetry-on deploy + serve produces a
    Prometheus snapshot with the pipeline's metrics and a JSONL trace
    whose phase self-times cover >= 95% of the run's wall time."""
    tm.enable()
    tm.registry().reset()
    path = tm.trace_to(str(tmp_path / "smoke.jsonl"))
    with tm.span("smoke/deploy_serve"):
        eng = _engine(tmp_path / "cache")
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8),
                                     0, 128)
        out = np.asarray(eng.generate(prompts, 3, seed=0))
    tm.trace_stop()
    assert out.shape == (2, 3)

    spans = load_spans(path)
    names = {s["name"] for s in spans}
    assert {"smoke/deploy_serve", "deploy/collect", "deploy/plan",
            "deploy/package", "serve/generate",
            "serve/prefill"} <= names
    assert coverage(spans) >= 0.95

    text = tm.registry().to_prometheus()
    for metric in ("repro_deploy_seconds", "repro_plan_seconds",
                   "repro_plan_cache_probes_total",
                   "repro_serve_prefill_seconds",
                   "repro_serve_decode_step_seconds"):
        assert metric in text, metric
    snap = tm.registry().snapshot()
    assert snap["repro_serve_requests_total"]["values"][0]["value"] == 1
    assert snap["repro_serve_tokens_total"]["values"][0]["value"] == 6
    deployed = {tuple(v["labels"].items()): v["value"] for v in
                snap["repro_deploy_matrices_total"]["values"]}
    assert deployed[(("status", "deployed"),)] > 0


def test_solver_and_mc_metrics_recorded():
    from repro.core.tiling import CrossbarSpec
    from repro.nonideal.models import NonidealModel
    from repro.nonideal.montecarlo import mc_nf

    tm.enable()
    tm.registry().reset()
    spec = CrossbarSpec(rows=16, cols=16, n_bits=8)
    masks = (jax.random.uniform(jax.random.PRNGKey(2), (2, 16, 16))
             < 0.25).astype(np.float32)
    res = mc_nf(masks, spec, NonidealModel(sigma_program=0.05), 2,
                jax.random.PRNGKey(0), precision="f64")
    assert int(res.unconverged) == 0
    snap = tm.registry().snapshot()
    assert snap["repro_mc_samples_total"]["values"][0]["value"] == 4
    assert snap["repro_solver_solves_total"]["values"][0]["value"] == 1
    assert snap["repro_solver_iterations_total"]["values"][0][
        "value"] > 0
    assert snap["repro_mc_nf_mean"]["values"][0]["value"] > 0
    assert snap["repro_mc_sweep_seconds"]["values"][0]["count"] == 1


def test_plan_cache_metrics_hit_and_miss(tmp_path):
    from repro.core.tiling import CrossbarSpec
    from repro.deploy import PlanCache
    from repro.deploy.planner import plan_matrices

    tm.enable()
    tm.registry().reset()
    spec = CrossbarSpec(rows=16, cols=16, n_bits=4)
    mats = {"m": jax.random.normal(jax.random.PRNGKey(0), (32, 32))}
    cache = PlanCache(str(tmp_path))
    plan_matrices(mats, spec, "mdm", cache=cache)
    plan_matrices(mats, spec, "mdm", cache=cache)

    def probes(metric, result):
        vals = {tuple(v["labels"].items()): v["value"] for v in
                tm.registry().snapshot()[metric]["values"]}
        return vals.get((("result", result),), 0.0)

    # first pass: manifest miss + per-entry miss; second pass resolves
    # the whole set from one manifest read (no per-entry probes).
    assert probes("repro_plan_cache_probes_total", "miss") >= 1
    assert probes("repro_plan_cache_manifest_probes_total", "hit") >= 1
    snap = tm.registry().snapshot()
    assert snap["repro_plan_cache_puts_total"]["values"][0]["value"] >= 1
    assert snap["repro_plan_cache_read_bytes_total"]["values"][0][
        "value"] > 0


# ------------------------- continuous serving loop -------------------------

ADMIT_CHILDREN = ["serve/admit_inputs", "serve/prefill_call",
                  "serve/join_call", "serve/first_token_wait"]
DECODE_CHILDREN = ["serve/decode_inputs", "serve/decode_call",
                   "serve/decode_wait"]


def _continuous(max_prompt=16):
    from repro.configs.base import CimConfig, ModelConfig
    from repro.models.model import init_params
    from repro.serve import ContinuousEngine

    cfg = ModelConfig(
        name="continuous-telemetry-test", n_layers=2, d_model=32,
        n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=128,
        block_pattern=("attn",), remat="none", dtype="float32",
        attn_chunk=32, cim=CimConfig(enabled=False))
    return ContinuousEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                            capacity=2, max_seq=32, max_prompt=max_prompt)


def _serve(eng, lengths, temps=None):
    rs = np.random.RandomState(4)
    for i, L in enumerate(lengths):
        eng.submit(rs.randint(0, 128, L), max_tokens=3 + i % 2,
                   temperature=0.0 if temps is None else temps[i],
                   seed=i)
    return eng.run()


def test_continuous_spans_nest_and_reach_the_profiler(tmp_path):
    """Every new serving span is a profiler host event inside a
    ``serve/iteration`` event, and the device programs carry the
    ``serve_*`` names."""
    from jax.profiler import ProfileData

    eng = _continuous()
    _serve(eng, [5])                         # compile outside the trace
    n0 = eng.iterations
    tm.enable()
    path = tm.trace_to(str(tmp_path / "t.jsonl"))
    jax.profiler.start_trace(str(tmp_path / "profile"))
    _serve(eng, [3, 7, 16])
    jax.profiler.stop_trace()
    tm.trace_stop()

    spans = load_spans(path)
    by_id = {s["id"]: s for s in spans}
    parent = {s["name"]: by_id[s["parent"]]["name"] for s in spans
              if s["parent"] is not None and s["name"] != "runtime/gc"}
    for name in ADMIT_CHILDREN:
        assert parent[name] == "serve/admit", name
    for name in DECODE_CHILDREN:
        assert parent[name] == "serve/decode_batch", name
    for name in ("serve/admit", "serve/decode_batch", "serve/stream"):
        assert parent[name] == "serve/iteration", name
    assert parent["serve/evict_call"] == "serve/stream"

    (xplane,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
                 for f in fs if f.endswith(".xplane.pb")]
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(xplane).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    iterations = [(a, b) for n, a, b in host if n == "serve/iteration"]
    assert len(iterations) == eng.iterations - n0
    inner = ADMIT_CHILDREN + DECODE_CHILDREN + [
        "serve/admit", "serve/decode_batch", "serve/stream",
        "serve/evict_call"]
    events = [(n, a, b) for n, a, b in host if n in inner]
    assert {n for n, _, _ in events} == set(inner)
    assert len(events) == sum(1 for s in spans if s["name"] in inner)
    for n, a, b in events:
        assert any(lo <= a and b <= hi for lo, hi in iterations), n
    names = {n for n, _, _ in host}
    # The pool's programs (decode, join, evict) are called as compiled
    # ahead of time, and their host events name their jit.
    for fn in ("serve_prefill", "jit(serve_decode)", "jit(serve_join)",
               "jit(serve_evict)"):
        assert f"PjitFunction({fn})" in names, fn
    assert eng.traces == {"prefill": 1, "decode": 1}


def test_continuous_generation_bit_identical_on_vs_off(tmp_path):
    lengths, temps = [3, 9, 16, 5], [0.0, 0.8, 0.0, 1.3]
    tm.disable()
    off = _serve(_continuous(), lengths, temps)
    tm.enable()
    tm.trace_to(str(tmp_path / "on.jsonl"))
    on = _serve(_continuous(), lengths, temps)
    assert on == off


def test_prefill_padding_attrs_and_counter_exact(tmp_path):
    tm.enable()
    tm.registry().reset()
    path = tm.trace_to(str(tmp_path / "t.jsonl"))
    _serve(_continuous(max_prompt=16), [3, 7, 16])
    tm.trace_stop()
    admits = [s["attrs"] for s in load_spans(path)
              if s["name"] == "serve/admit"]
    assert sorted(a["prompt_tokens"] for a in admits) == [3, 7, 16]
    assert {a["padded_to"] for a in admits} == {16}
    vals = {v["labels"]["kind"]: v["value"] for v in tm.registry()
            .snapshot()["repro_serve_prefill_tokens_total"]["values"]}
    assert vals == {"prompt": 26.0, "padding": 22.0}
