"""The trace reduction against a small trace recorded on a TPU v5e.

``fixtures/small.xplane.pb`` was written by ``record_fixture.py``: one
Pallas ``cim_mvm`` call (M=64, I=256, N=512) and a bf16 matmul, three
times inside ``chipbench.step`` annotations with a 20 ms
``chipbench.wait`` between them, inside one ``chipbench.window``.  The
numbers below were read from the trace by hand (its summary of planes,
lines and events).  The device clock runs ~1 ms behind the host's
annotations in this trace, so the first step's ops fall before the
window opens.
"""
from __future__ import annotations

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import counts, harness, trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "small.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(FIXTURE)


def test_planes_ops_and_annotations(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    assert len(ops) == 15
    assert [o.name for o in ops[:5]] == [
        "copy-start", "convert_element_type.0", "cim_mvm.1", "copy-done",
        "fusion"]
    names = [n for n, _, _ in tr.spans]
    assert names.count("chipbench.step") == 3
    assert names.count("chipbench.wait") == 3
    assert tr.window() == (49701373, 115275263)


def test_busy_and_idle(tr):
    assert trace.window_seconds(tr) == pytest.approx(0.06557389)
    # Ops of steps 2 and 3 only (step 1 precedes the window's opening).
    busy = trace.busy_seconds(tr)
    assert busy == pytest.approx((2 + 402 + 3242 + 2 + 1188
                                  + 2 + 401 + 3481 + 2 + 1382) * 1e-9)
    run = types.SimpleNamespace(trace=tr)
    share = harness.load_module("layer_metrics", "idle_share.batch").read(run)
    assert share == pytest.approx(100 * (1 - busy / 0.06557389))
    assert 99.9 < share < 100.0


def test_kernel_time_by_name(tr):
    ops = trace.kernel_ops(tr, "cim_mvm")
    assert [o.end - o.start for o in ops] == [3242, 3481]
    assert trace.kernel_ops(tr, "cim") == []
    shapes = trace.operand_shapes(ops[0])
    assert shapes[0] == ("f32", (64, 512))       # result (M, N)
    assert shapes[1] == ("f32", (64, 256))       # activations (M, I)
    assert shapes[2] == ("s16", (256, 512))      # signed codes


def test_breakdown_names_ops_and_gaps(tr):
    b = trace.breakdown(tr)
    assert b["device_ops"][0] == ["cim_mvm.1", pytest.approx(6.723e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # The three longest gaps are the host's 20 ms sleeps.
    assert [g[0] for g in b["idle_gaps"][:3]] == ["wait"] * 3
    assert all(0.02 < g[1] < 0.024 for g in b["idle_gaps"][:3])


def test_roofline_from_shapes(tr):
    """least time / kernel time, memory-bound at M=64."""
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        import json
        peak = json.load(f)["TPU v5 lite"]
    cfg = harness.load_data("configs", "phi3-mini-3.8b.ideal")
    run = types.SimpleNamespace(trace=tr, peak=peak, cfg=cfg, notes={})
    value = harness.load_module(
        "layer_metrics", "cim_mvm_roofline.batch").read(run)
    nbytes = 256 * 512 * 9 / 8 + 256 * 64 + 2 * 64 * 256 + 2 * 64 * 512
    least, side = counts.least_seconds(2 * 64 * 256 * 512, nbytes, peak)
    assert side == "memory"
    assert value == pytest.approx(100 * 2 * least / ((3242 + 3481) * 1e-9))
    assert run.notes["cim_mvm_bound"] == "memory"
    assert 0 < value < 100


def test_readers_return_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, peak={}, spans=None, notes={})
    for name in ("idle_share.batch", "idle_share.chat",
                 "cim_mvm_roofline.batch", "admit_ms.chat",
                 "decode_step_ms.batch"):
        assert harness.load_module("layer_metrics", name).read(run) is None
