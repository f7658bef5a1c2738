"""chip_smoke.py on the CPU: its phases at the smoke preset, in-process,
and its refusal to run anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name="chip_smoke_under_test"):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod        # dataclasses resolve annotations here
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load(os.path.join(ROOT, "chip_smoke.py"))


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(smoke, argv, capsys):
    assert smoke.main(argv) != 0
    out, err = capsys.readouterr()
    assert out == ""                       # no result line
    assert "no TPU" in err


def test_script_alone_refuses(tmp_path, capsys):
    """Copied out of the checkout, the script finds no program."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    alone = _load(str(tmp_path / "chip_smoke.py"), "chip_smoke_alone")
    assert alone.main([]) != 0
    out, err = capsys.readouterr()
    assert out == "" and "repro package" in err


def test_kernel_phase_smoke(smoke):
    out = smoke.phase_kernel(smoke.SMOKE_KERNEL, 0, kernel_impl="interpret")
    assert out["served_ideal"] == "interpret"
    assert out["served_nonideal"] == "xla"
    for row in out["batches"]:
        assert row["err_kernel"] <= smoke.KERNEL_TOL
        assert row["eq17_distortion"] >= 10 * smoke.KERNEL_TOL
    assert out["ok"]


def test_serve_phase_smoke(smoke, tmp_path):
    from repro.deploy import PlanCache

    size = smoke.SMOKE_SERVE
    out = smoke.phase_serve(size, 1, plan_cache=PlanCache(str(tmp_path)))
    assert out["traces"] == {"prefill": 1, "decode": 1}
    assert out["tokens_generated"] == size.n_requests * size.max_tokens
    assert out["logits_err"] <= smoke.LOGITS_TOL
    assert out["logits_eq17_distortion"] > out["logits_err"]
    assert out["served_ideal"] == "xla"    # "auto" off TPU
    assert out["ok"]


def test_solver_phase_smoke(smoke):
    out = smoke.phase_solver(smoke.SMOKE_SOLVER, 0)
    assert out["mesh_devices"] == 4
    assert out["precision"] == "f64"       # the CPU computes real f64
    assert out["unconverged_sharded"] == 0
    assert out["ok"]
