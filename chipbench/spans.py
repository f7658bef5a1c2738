"""The program's spans on the profiler's clock, and what the device did
under them.

The program (``repro.telemetry``) times its spans by its own monotonic
clock from ``trace_to``, and the profiler trace times its events by its
own.  ``repro.telemetry`` also enters each span as a profiler host
event, but ``chipbench.trace.load`` keeps only the benchmark's own
``chipbench.*`` host events, so the readers here move the program's
JSONL spans onto the profiler's clock:

* the harness wraps each ``eng.step()`` in one ``chipbench.step``
  annotation, and each step opens exactly one ``serve/iteration`` span,
  so the i-th of one pairs with the i-th of the other;
* every span under an iteration moves by that iteration's offset
  (midpoint against midpoint), so a drift between the clocks over the
  window does not enter; the error is at most half of what the
  annotation adds around the span (``span_alignment_error_us``).

The device's op stamps run behind the host's: an op is stamped before
the host call that enqueued it began (PERF.md, "clock skew").  The skew
is read per run from the decodes: the device's busy block of a decode
starts after its ``serve/decode_call`` returned (the runtime enqueues
the program after the call returns) and ends before its
``serve/decode_wait`` ended, which bounds the skew from below and above
(medians over the decodes); the spans move by the middle of the bounds.
The decode's block is the device work that overlaps most of its host
bracket: in the benchmark's cells a decode (38-60 ms) is far the
longest work near it; in a toy run with a decode of microseconds it is
not, and the bounds mean nothing there.

Times are integer ns on the profiler's clock; readers return ms.
"""
from __future__ import annotations

import bisect
import statistics

from chipbench import trace

STEP = trace.ANNOTATION + "step"
ITERATION = "serve/iteration"
# Busy intervals closer than this belong to one block of device work.
BLOCK_GAP_NS = 50_000


class Busy:
    """Merged busy intervals of one device, queried by host interval."""

    def __init__(self, intervals: list[tuple[int, int]]):
        self.starts = [a for a, _ in intervals]
        self.ends = [b for _, b in intervals]
        self.cum = [0]
        for a, b in intervals:
            self.cum.append(self.cum[-1] + b - a)

    def _range(self, lo: int, hi: int) -> tuple[int, int]:
        return (bisect.bisect_right(self.ends, lo),
                bisect.bisect_left(self.starts, hi))

    def within(self, lo: int, hi: int) -> int:
        """Busy ns inside [lo, hi)."""
        i, j = self._range(lo, hi)
        if hi <= lo or j <= i:
            return 0
        return (self.cum[j] - self.cum[i] - max(0, lo - self.starts[i])
                - max(0, self.ends[j - 1] - hi))

    def idle(self, lo: int, hi: int) -> list[tuple[int, int]]:
        """The idle intervals inside [lo, hi), in order."""
        i, j = self._range(lo, hi)
        out, cur = [], lo
        for k in range(i, j):
            if self.starts[k] > cur:
                out.append((cur, self.starts[k]))
            cur = max(cur, self.ends[k])
        if cur < hi:
            out.append((cur, hi))
        return out

    def blocks(self) -> list[tuple[int, int]]:
        """Busy intervals joined across gaps under ``BLOCK_GAP_NS``."""
        out: list[list[int]] = []
        for a, b in zip(self.starts, self.ends):
            if out and a - out[-1][1] < BLOCK_GAP_NS:
                out[-1][1] = b
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]


class Timeline:
    """A traced run's device busy time and program spans, one clock."""

    def __init__(self, busy: list[Busy], iterations: list[list[tuple]],
                 align_error_ns: int):
        self.busy = busy
        # Per iteration: (name, start, end, depth, span record), the
        # iteration's own first; host times on the profiler's clock.
        self.iterations = iterations
        self.align_error_ns = align_error_ns
        self.skew_bounds_ns = self._skew_bounds()
        self.skew_ns = (0 if self.skew_bounds_ns is None
                        else sum(self.skew_bounds_ns) // 2)

    def brackets(self, call: str, wait: str
                 ) -> list[tuple[int, int, int]]:
        """(start and end of each ``call`` span, end of the ``wait``
        span that follows it under the same parent)."""
        out = []
        for it in self.iterations:
            pending: dict = {}
            for name, a, b, _, rec in sorted(it, key=lambda s: s[1]):
                if name == call:
                    pending[rec["parent"]] = (a, b)
                elif name == wait and rec["parent"] in pending:
                    out.append((*pending.pop(rec["parent"]), b))
        return out

    def _skew_bounds(self) -> tuple[int, int] | None:
        pairs = self.brackets("serve/decode_call", "serve/decode_wait")
        if not pairs or not self.busy:
            return None
        blocks = self.busy[0].blocks()
        starts = [a for a, _ in blocks]
        lows, highs = [], []
        for call, call_end, wait in pairs:
            # The decode's block: the one overlapping most of the host
            # bracket, widened 5 ms back for the device's lag.
            lo = call - 5_000_000
            k0 = max(0, bisect.bisect_right(starts, lo) - 1)
            k1 = bisect.bisect_left(starts, wait)
            best = max(range(k0, k1), default=None,
                       key=lambda k: min(blocks[k][1], wait)
                       - max(blocks[k][0], lo))
            if best is None:
                continue
            a, b = blocks[best]
            lows.append(call_end - a)
            highs.append(wait - b)
        if not lows:
            return None
        return int(statistics.median(lows)), int(statistics.median(highs))

    def device_ms(self, lo: int, hi: int) -> float:
        """Busy ms inside host interval [lo, hi), averaged over chips."""
        return sum(b.within(lo - self.skew_ns, hi - self.skew_ns)
                   for b in self.busy) / len(self.busy) * 1e-6


def timeline(run) -> Timeline | None:
    """The run's timeline, or None where the trace has no device, the
    run no program spans, or the steps and iterations do not pair."""
    tr, recs = getattr(run, "trace", None), getattr(run, "spans", None)
    if tr is None or not tr.devices or not recs:
        return None
    steps = sorted((a, b) for n, a, b in tr.spans if n == STEP)
    its = sorted((s for s in recs if s.get("name") == ITERATION),
                 key=lambda s: s["t_start"])
    if not its or len(its) != len(steps):
        return None
    by_id = {s["id"]: s for s in recs}
    index = {s["id"]: k for k, s in enumerate(its)}

    def root(s):
        seen = s
        while seen is not None and seen["id"] not in index:
            seen = by_id.get(seen.get("parent"))
        return None if seen is None else index[seen["id"]]

    offsets, err = [], 0
    for (a, b), s in zip(steps, its):
        t0, t1 = round(s["t_start"] * 1e9), round(s["t_end"] * 1e9)
        offsets.append((a + b - t0 - t1) // 2)
        err = max(err, ((b - a) - (t1 - t0)) // 2)
    groups: list[list[tuple]] = [[] for _ in its]
    for s in recs:
        k = root(s)
        if k is None:
            continue
        off = offsets[k]
        groups[k].append((s["name"], round(s["t_start"] * 1e9) + off,
                          round(s["t_end"] * 1e9) + off, s["depth"],
                          s))
    for g in groups:
        g.sort(key=lambda x: x[3])      # the iteration first
    lo, hi = tr.window()
    busy = [Busy(trace.busy_intervals(ops, lo, hi))
            for ops in tr.devices.values()]
    return Timeline(busy, groups, err)


def _innermost(spans: list[tuple]) -> list[tuple[int, int, str]]:
    """Elementary segments of an iteration, each with the name of the
    deepest span covering it."""
    cuts = sorted({t for s in spans for t in (s[1], s[2])})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        covering = [s for s in spans if s[1] <= a and b <= s[2]]
        if covering:
            out.append((a, b, max(covering, key=lambda s: s[3])[0]))
    return out


def step_idle_ms(run) -> float | None:
    """Device idle inside the ``serve/iteration`` spans, ms per
    iteration.  Notes the split by innermost program span (ms per
    iteration; the parts add up to the reading), the longest idle gaps
    with their span, the skew and the alignment error."""
    tl = timeline(run)
    if tl is None or not tl.iterations:
        return None
    by_span: dict[str, float] = {}
    gaps: list[tuple[float, str]] = []
    total = 0
    for it in tl.iterations:
        segs = _innermost([(n, a - tl.skew_ns, b - tl.skew_ns, d)
                           for n, a, b, d, _ in it])
        if not segs:
            continue
        lo, hi = segs[0][0], segs[-1][1]
        for busy in tl.busy:
            k = 0
            for x, y in busy.idle(lo, hi):
                total += y - x
                longest = (0, "")
                while k < len(segs) and segs[k][1] <= x:
                    k += 1
                j = k
                while j < len(segs) and segs[j][0] < y:
                    part = min(y, segs[j][1]) - max(x, segs[j][0])
                    if part > 0:
                        name = segs[j][2]
                        by_span[name] = by_span.get(name, 0) + part
                        longest = max(longest, (part, name))
                    j += 1
                gaps.append(((y - x) * 1e-6, longest[1]))
    n = len(tl.iterations) * len(tl.busy)
    run.notes["idle_ms_by_span"] = {
        k: round(v * 1e-6 / n, 4)
        for k, v in sorted(by_span.items(), key=lambda kv: -kv[1])}
    run.notes["longest_idle_ms"] = [
        [name, round(ms, 3)] for ms, name in sorted(gaps, reverse=True)[:8]]
    run.notes["device_clock_skew_ms"] = (
        None if tl.skew_bounds_ns is None
        else [round(t * 1e-6, 4) for t in tl.skew_bounds_ns])
    run.notes["span_alignment_error_us"] = round(tl.align_error_ns * 1e-3,
                                                 1)
    return total * 1e-6 / n


def bracket_device_ms(run, call: str, wait: str) -> float | None:
    """Mean device-busy ms from the start of each ``call`` span to the
    end of the ``wait`` span after it: the program the call enqueued,
    and the tail of any join or evict still queued ahead of it."""
    tl = timeline(run)
    if tl is None or tl.skew_bounds_ns is None:
        return None
    pairs = tl.brackets(call, wait)
    if not pairs:
        return None
    return sum(tl.device_ms(a, b) for a, _, b in pairs) / len(pairs)


def padding_share(run) -> float | None:
    """Padding tokens over all the tokens the window's admissions
    prefilled, percent, from the ``serve/admit`` attributes."""
    admits = [s.get("attrs", {}) for s in run.spans or ()
              if s.get("name") == "serve/admit"]
    admits = [a for a in admits if "padded_to" in a]
    padded = sum(a["padded_to"] for a in admits)
    if not padded:
        return None
    return 100.0 * (padded - sum(a["prompt_tokens"] for a in admits)) \
        / padded
