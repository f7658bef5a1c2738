"""The span readers (``chipbench/spans.py``): the program's JSONL spans
moved onto the profiler's clock, device idle split by the program span
the host was in, device time of the prefill and decode programs, and
the padding share of admissions.

A synthetic run first, built by hand so every number is known: a
profiler clock 7 s ahead of the program's, ``chipbench.step``
annotations 5 us wider than their iterations on each side, and device
stamps 1.5 ms behind the host.  Then ``fixtures/spans.xplane.pb`` and
``fixtures/spans.jsonl``, one run of the serving loop recorded on a TPU
v5e by ``record_spans_fixture.py``.
"""
from __future__ import annotations

import json
import os
import re
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import harness, spans, trace  # noqa: E402

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
MS = 1_000_000                       # ns
CLOCK = 7_000 * MS                   # profiler clock - program clock
SKEW = 1_500_000                     # device stamps behind the host
PAD = 5_000                          # annotation beyond its iteration

# One iteration of 10 ms, host times in ms from its start:
# (name, start, end, parent name).
ITERATION = [
    ("serve/iteration", 0.0, 10.0, None),
    ("serve/admit", 0.1, 3.0, "serve/iteration"),
    ("serve/admit_inputs", 0.1, 0.5, "serve/admit"),
    ("serve/prefill_call", 0.5, 0.8, "serve/admit"),
    ("serve/join_call", 0.8, 0.9, "serve/admit"),
    ("serve/first_token_wait", 0.9, 3.0, "serve/admit"),
    ("serve/decode_batch", 3.1, 9.0, "serve/iteration"),
    ("serve/decode_inputs", 3.1, 3.3, "serve/decode_batch"),
    ("serve/decode_call", 3.3, 3.6, "serve/decode_batch"),
    ("serve/decode_wait", 3.6, 9.0, "serve/decode_batch"),
    ("serve/stream", 9.0, 9.8, "serve/iteration"),
    ("serve/evict_call", 9.5, 9.6, "serve/stream"),
]
# Device work, true host times in ms: prefill, join, decode, evict.
DEVICE = [(0.6, 2.9), (2.9, 2.92), (3.7, 8.9), (9.65, 9.7)]


def synthetic(n_iter: int = 4, gap_ms: float = 2.0):
    """A traced run of ``n_iter`` iterations, ``gap_ms`` apart."""
    recs, ops, steps = [], [], []
    ids = iter(range(10 ** 6))
    for k in range(n_iter):
        t0 = k * (10.0 + gap_ms)
        by_name = {}
        for name, a, b, parent in ITERATION:
            s = {"name": name, "id": next(ids),
                 "parent": by_name[parent]["id"] if parent else None,
                 "depth": 0 if parent is None
                 else by_name[parent]["depth"] + 1,
                 "t_start": (t0 + a) * 1e-3, "t_end": (t0 + b) * 1e-3,
                 "dur": (b - a) * 1e-3}
            if name == "serve/admit":
                s["attrs"] = {"rid": k, "prompt_tokens": 100 + k,
                              "padded_to": 512}
            by_name[name] = s
            recs.append(s)
        a0 = round(t0 * MS) + CLOCK
        steps.append(("chipbench.step", a0 - PAD, a0 + 10 * MS + PAD))
        for a, b in DEVICE:
            ops.append(trace.Op(f"%fusion.{len(ops)} = f32[1]",
                                round((t0 + a) * MS) + CLOCK - SKEW,
                                round((t0 + b) * MS) + CLOCK - SKEW))
    window = ("chipbench.window", CLOCK - MS,
              CLOCK + round(n_iter * (10.0 + gap_ms) * MS))
    tr = trace.Trace({"/device:TPU:0": ops}, [window] + steps)
    return types.SimpleNamespace(trace=tr, spans=recs, notes={})


def read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def test_alignment_and_skew_bounds():
    run = synthetic()
    tl = spans.timeline(run)
    assert len(tl.iterations) == 4
    name, a, b, depth, _ = tl.iterations[1][0]
    assert (name, depth) == ("serve/iteration", 0)
    assert a == CLOCK + 12 * MS and b == a + 10 * MS
    assert tl.align_error_ns == PAD
    # Decode starts 0.1 ms after its call returned and ends 0.1 ms
    # before its wait ended: the bounds sit 0.1 ms either side of the
    # skew.
    assert tl.skew_bounds_ns == (SKEW - 100_000, SKEW + 100_000)
    assert tl.skew_ns == SKEW


def test_step_idle_split_by_innermost_span():
    run = synthetic()
    # Idle inside an iteration: 0-0.6, 2.92-3.7, 8.9-9.65, 9.7-10 ms.
    assert read("step_idle_ms.batch", run) == pytest.approx(2.43)
    split = run.notes["idle_ms_by_span"]
    assert split == pytest.approx({
        "serve/admit_inputs": 0.4, "serve/stream": 0.65,
        "serve/iteration": 0.4, "serve/decode_inputs": 0.2,
        "serve/prefill_call": 0.1, "serve/decode_call": 0.3,
        "serve/evict_call": 0.1, "serve/decode_wait": 0.2,
        "serve/first_token_wait": 0.08})
    assert sum(split.values()) == pytest.approx(2.43)
    # One 0.78 ms gap an iteration, named by its largest part.
    assert run.notes["longest_idle_ms"][:5] == (
        [["serve/decode_call", 0.78]] * 4 + [["serve/stream", 0.75]])
    assert run.notes["device_clock_skew_ms"] == [1.4, 1.6]
    assert read("step_idle_ms.chat", run) == pytest.approx(2.43)


def test_device_time_of_prefill_and_decode():
    run = synthetic()
    assert read("decode_device_ms.batch", run) == pytest.approx(5.2)
    # The join runs inside the first-token wait, after the prefill.
    assert read("prefill_device_ms.chat", run) == pytest.approx(2.32)
    assert read("decode_call_ms.batch", run) == pytest.approx(0.3)


def test_padding_share_from_admit_attributes():
    run = synthetic()
    prompt = sum(100 + k for k in range(4))
    assert read("prefill_padding.chat", run) == pytest.approx(
        100 * (4 * 512 - prompt) / (4 * 512))


def test_steps_and_iterations_that_do_not_pair_read_nothing():
    run = synthetic()
    run.trace.spans.pop()                    # one step annotation lost
    for name in ("step_idle_ms.batch", "decode_device_ms.batch",
                 "prefill_device_ms.chat"):
        assert read(name, run) is None, name


def test_parent_spans_read_what_they_hold():
    """Spans as the parent commit writes them: no calls, waits or admit
    attributes.  The device readers find nothing; the idle split falls
    on the spans there are, and with no decode to read the skew from,
    the device stamps stay 1.5 ms early: 0.9 ms more idle at the
    iteration's end than is missed at its start."""
    run = synthetic()
    kept = {"serve/iteration", "serve/admit", "serve/decode_batch"}
    run.spans = [dict(s, attrs={"rid": 0}) if s["name"] == "serve/admit"
                 else s for s in run.spans if s["name"] in kept]
    for name in ("decode_device_ms.batch", "prefill_device_ms.chat",
                 "decode_call_ms.batch", "prefill_padding.chat"):
        assert read(name, run) is None, name
    assert read("step_idle_ms.batch", run) == pytest.approx(3.33)
    assert run.notes["device_clock_skew_ms"] is None
    assert set(run.notes["idle_ms_by_span"]) <= kept


def test_readers_return_nothing_without_a_trace():
    run = types.SimpleNamespace(trace=None, peak={}, spans=None, notes={})
    for name in ("step_idle_ms.batch", "step_idle_ms.chat",
                 "decode_device_ms.batch", "decode_call_ms.batch",
                 "prefill_device_ms.chat", "prefill_padding.chat"):
        assert read(name, run) is None, name


# ------------------------------------------------ the recorded fixture

PROGRAM_SPAN = re.compile(r"^[a-z_]+/[a-z_]+$")


@pytest.fixture(scope="module")
def recorded():
    """The fixture's run as the readers see it, and the trace's own
    program host events and ``XLA Modules`` events."""
    from jax.profiler import ProfileData

    path = os.path.join(FIXTURES, "spans.xplane.pb")
    with open(os.path.join(FIXTURES, "spans.jsonl")) as f:
        recs = [json.loads(line) for line in f]
    host, modules = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                a = int(e.start_ns)
                ev = (e.name, a, a + int(e.duration_ns))
                if line.name == "XLA Modules":
                    modules.append((e.name.split("(")[0], ev[1], ev[2]))
                elif (plane.name.startswith("/host:")
                      and PROGRAM_SPAN.match(e.name)):
                    host.append(ev)
    run = types.SimpleNamespace(trace=trace.load(path), spans=recs,
                                notes={})
    return run, sorted(host, key=lambda e: e[1]), sorted(
        modules, key=lambda e: e[1])


def test_recorded_programs_carry_stable_names(recorded):
    _, _, modules = recorded
    count = {}
    for name, _, _ in modules:
        count[name] = count.get(name, 0) + 1
    assert {k: count[k] for k in count if k.startswith("jit_serve_")} == {
        "jit_serve_prefill": 6, "jit_serve_join": 6,
        "jit_serve_decode": 11, "jit_serve_evict": 6}


def test_recorded_program_spans_nest_in_iterations(recorded):
    run, host, _ = recorded
    names = [n for n, _, _ in host]
    for name in {s["name"] for s in run.spans}:
        assert names.count(name) == sum(
            1 for s in run.spans if s["name"] == name), name
    iterations = [(a, b) for n, a, b in host if n == "serve/iteration"]
    assert len(iterations) == 11
    for n, a, b in host:
        assert any(lo <= a and b <= hi for lo, hi in iterations), n


def test_recorded_alignment_matches_the_programs_host_events(recorded):
    """The JSONL spans, moved onto the profiler's clock through the
    ``chipbench.step`` annotations, land where the program's own host
    events are, within the alignment error the reader states."""
    run, host, _ = recorded
    tl = spans.timeline(run)
    assert 0 < tl.align_error_ns < 50_000
    for name in {n for n, _, _ in host}:
        ours = sorted((a, b) for it in tl.iterations
                      for n, a, b, _, _ in it if n == name)
        theirs = sorted((a, b) for n, a, b in host if n == name)
        assert len(ours) == len(theirs), name
        for (a, b), (c, d) in zip(ours, theirs):
            assert abs(a - c) <= tl.align_error_ns, name
            assert abs(b - d) <= tl.align_error_ns, name


def test_recorded_clock_skew_and_device_time(recorded):
    """Each decode ran on the device after its call returned and before
    its wait ended: on this trace the device's stamps are 1.38-1.90 ms
    behind the host.  Moved by that skew, the device-busy time of each
    decode's bracket is its program's time on the ``XLA Modules`` line,
    less the slivers between its ops."""
    run, host, modules = recorded
    calls = [(a, b) for n, a, b in host if n == "serve/decode_call"]
    waits = [(a, b) for n, a, b in host if n == "serve/decode_wait"]
    decodes = [(a, b) for n, a, b in modules if n == "jit_serve_decode"]
    low = max(c[1] - m[0] for c, m in zip(calls, decodes))
    high = min(w[1] - m[1] for w, m in zip(waits, decodes))
    assert (low, high) == (1_376_615, 1_903_158)
    tl = spans.timeline(run)
    tl.skew_ns = (low + high) // 2
    pairs = tl.brackets("serve/decode_call", "serve/decode_wait")
    for (a, _, b), (c, d) in zip(pairs, decodes):
        assert 0.95 * (d - c) <= tl.device_ms(a, b) * 1e6 <= d - c


def test_recorded_padding_share(recorded):
    run, _, _ = recorded
    prompt = 5 + 60 + 17 + 33 + 9 + 48
    assert read("prefill_padding.chat", run) == pytest.approx(
        100 * (6 * 64 - prompt) / (6 * 64))
    assert read("decode_call_ms.batch", run) > 0


def test_small_fixture_has_no_program_spans():
    """On the first recorded trace (benchmark annotations only) the new
    readers find nothing and leave the notes alone."""
    run = types.SimpleNamespace(
        trace=trace.load(os.path.join(FIXTURES, "small.xplane.pb")),
        spans=[], notes={})
    for name in ("step_idle_ms.batch", "decode_device_ms.batch",
                 "prefill_device_ms.chat", "prefill_padding.chat"):
        assert read(name, run) is None, name
    assert run.notes == {}
