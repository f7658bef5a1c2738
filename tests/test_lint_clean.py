"""Tier-1 gate: the repo lints clean under its own rules.

``src/`` plus the linted tool trees (benchmarks/, scripts/) and
``chip_smoke.py`` must carry
zero unsuppressed findings — every intentional deviation needs an
inline justified suppression.  The budget assertion keeps the linter
honest about its design point: a pure-AST pass that never imports jax
stays fast enough to run on every commit.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

from repro.analysis import run_paths

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT_TREES = [os.path.join(ROOT, d)
              for d in ("src", "benchmarks", "scripts", "chip_smoke.py")]


def test_repo_lints_clean():
    t0 = time.perf_counter()
    findings, files = run_paths(LINT_TREES)
    dt = time.perf_counter() - t0
    unsuppressed = [f.format() for f in findings if not f.suppressed]
    assert unsuppressed == []
    assert files > 50  # the walk actually found the codebase
    assert dt < 5.0, f"lint took {dt:.1f}s; budget is 5s"


def test_every_suppression_has_a_justification():
    findings, _ = run_paths(LINT_TREES)
    for f in (f for f in findings if f.suppressed):
        with open(f.path) as fh:
            line = fh.read().splitlines()[f.line - 1]
        assert "--" in line.split("reprolint:")[1], (
            f"{f.path}:{f.line} suppresses {f.code} without a "
            f"'-- justification'")


def test_cli_exits_zero_and_stays_jax_free():
    """The lint CLI as the nightly runs it: exit 0, no jax import."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint.py"),
         "src", "benchmarks", "scripts"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "unsuppressed" in proc.stdout
    # jax-free is the CLI's speed contract (audit is opt-in via --audit)
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.argv = ['reprolint', 'scripts'];"
         "sys.path.insert(0, 'src');"
         "from repro.analysis.cli import main; main();"
         "assert 'jax' not in sys.modules, 'lint CLI imported jax'"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert probe.returncode == 0, probe.stdout + probe.stderr


def test_cli_select_and_json():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "lint.py"),
         "--json", "--select", "RPL001", "src"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json

    out = json.loads(proc.stdout)
    assert out["unsuppressed"] == 0
    assert all(f["code"] == "RPL001" for f in out["findings"])
