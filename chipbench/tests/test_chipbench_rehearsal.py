"""The harness on the CPU at smoke sizes, in-process: one cell's whole
run but the look for a chip, its refusal without one, and faults
planted under the timed path that ``correct`` must catch."""
from __future__ import annotations

import copy
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import harness, program  # noqa: E402

SEED = 2 ** 31 + 77


def smoke(cell: str):
    """The cell's configuration and mix, cut to a size the CPU runs in
    seconds (the limits stay those of the configuration file)."""
    bench = harness.load_benchmark(ROOT)
    w = harness.cell_of(bench, cell)
    cfg = harness.load_data("configs", w["config"])
    cfg.update(hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, intermediate_size=128,
               vocab_size=256)
    mix = harness.load_data("traffic", w["traffic"])
    # Three requests of at least 4 tokens: never fewer than 12 compared.
    mix.update(capacity=4, max_prompt=32, max_seq=64, set_size=16,
               compare_min_tokens=12, compare_requests=3)
    mix["prompt_tokens"].update(median=8, min=4, max=32)
    mix["output_tokens"].update(median=8, min=4, max=31)
    if mix["loop"] == "open":
        mix["rate_per_s"] = 6.0
    return bench, cfg, mix


def run(cell, trace=False, prog=program, seconds=1.5):
    bench, cfg, mix = smoke(cell)
    return harness.run_cell(bench, cell, SEED, seconds, trace,
                            time.perf_counter(), program=prog, cfg=cfg,
                            mix=mix)


@pytest.mark.parametrize("cell,trace", [("phi3-ideal.batch", False),
                                        ("phi3-ideal.chat", True)])
def test_cell_rehearsal(cell, trace):
    res = run(cell, trace)
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    bench = harness.load_benchmark(ROOT)
    want = {m["name"] for m in harness.metrics_of(bench, cell, trace)}
    got = set(res["metrics"])
    if trace:
        # No TPU plane on the CPU: the trace readers find nothing.
        assert got <= want and "deploy_s" in got
        assert res["device"]["busy_s"] == 0.0
    else:
        assert got == want
        assert all(m["value"] > 0 for m in res["metrics"].values())


class _Faulty:
    """The program adapter with the decode step broken underneath."""

    def __init__(self, fault):
        self.fault = fault

    def __getattr__(self, name):
        return getattr(program, name)

    def make_engine(self, cfg, mix, params, plan_root):
        import jax
        import jax.numpy as jnp

        eng = program.make_engine(cfg, mix, params, plan_root)
        good = eng._decode
        vocab = cfg["vocab_size"]

        def altered(*a, **k):
            tok, st = good(*a, **k)
            return (tok + 1) % vocab, st

        def outside(*a, **k):
            tok, st = good(*a, **k)
            return tok + vocab, st

        def unchanged(params, state, *a, **k):
            keep = jax.tree_util.tree_map(jnp.copy, state)
            tok, _ = good(params, state, *a, **k)
            return tok, keep

        eng._decode = {"token_altered": altered,
                       "token_outside_vocab": outside,
                       "state_unchanged": unchanged}[self.fault]
        return eng


@pytest.mark.parametrize("fault", ["token_altered", "token_outside_vocab",
                                   "state_unchanged"])
def test_fault_under_timed_path_is_not_correct(fault):
    res = run("phi3-ideal.batch", prog=_Faulty(fault))
    assert not res["correct"], res["checks"]


def test_main_refuses_without_tpu(capsys):
    rc = harness.main(["--workload", "phi3-ideal.batch", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "no TPU" in err


def test_alone_with_benchmark_files_refuses(tmp_path):
    """A directory with only BENCHMARK.json and chipbench/ has no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    command = harness.load_benchmark(ROOT)["command"]
    assert command[0] == "python3"
    p = subprocess.run(
        [sys.executable] + command[1:] + [
            "--workload", "phi3-ideal.batch", "--seed", "3", "--seconds",
            "1", "--trace", "0"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "program" in p.stderr


def test_unknown_cell_refuses(capsys):
    rc = harness.main(["--workload", "no-such-cell", "--seed", "1",
                       "--seconds", "1"])
    out, _ = capsys.readouterr()
    assert rc != 0 and out == ""


def test_smoke_keeps_the_configured_limits():
    bench, cfg, mix = smoke("phi3-ideal.batch")
    full = harness.load_data("configs", "phi3-mini-3.8b.ideal")
    assert cfg["limits"] == full["limits"]
    assert copy.deepcopy(mix)["loop"] == "closed"


def test_bfloat16_crossbar_control_reads():
    """The control with the crossbar matmuls on bfloat16 operands gives a
    gap and a verdict through ``is_correct`` (read on the chip)."""
    bench, cfg, mix = smoke("phi3-ideal.batch")
    res = harness.run_cell(bench, "phi3-ideal.batch", 13, 1.5, False,
                           time.perf_counter(), cfg=cfg, mix=mix,
                           control=("bf16",))
    c = res["checks"]
    assert 0.0 <= c["controls"]["bf16"] < float("inf")
    assert set(harness.control_verdicts(c)) == {"bf16"}


@pytest.mark.parametrize("seed", [11, 12, SEED])
def test_float8_control_is_not_correct(seed):
    """The control, the reference in float8 put in the program's place,
    reads a logit gap above the configuration's limit and comes out not
    correct."""
    bench, cfg, mix = smoke("phi3-ideal.batch")
    res = harness.run_cell(bench, "phi3-ideal.batch", seed, 1.5, False,
                           time.perf_counter(), cfg=cfg, mix=mix,
                           control=("fp8",))
    c = res["checks"]
    assert res["correct"], c
    assert c["controls"]["fp8"] > c["logit_gap"]["limit"], c
    assert harness.control_verdicts(c) == {"fp8": False}, c
