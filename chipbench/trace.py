"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

* device ops: the events of each TPU plane's ``XLA Ops`` line;
* busy time: the union of their intervals inside the traced window,
  averaged over the chips traced; idle share = 1 - busy / window;
* kernel time: the summed device durations of the ops of one name;
* the window and what the host was doing: the benchmark's own
  ``chipbench.*`` annotations (``jax.profiler.TraceAnnotation``) on the
  host planes, which share the trace's clock;
* breakdown: the device ops that took most time, and the longest idle
  gaps, each named by the innermost benchmark annotation that covers
  its middle.

An event's name on the ``XLA Ops`` line is the op's HLO text
(``%cim_mvm.3 = f32[64,8192]{...} custom-call(f32[64,3072]{...} ...)``):
the op's name is what precedes `` = ``, and the shapes follow it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

OPS_LINE = "XLA Ops"
ANNOTATION = "chipbench."
# Ops whose events span the ops they run (a layer scan's ``while``):
# busy time takes them in its union, the op table leaves them out.
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    text: str      # the event's name: the op's HLO text
    start: int     # ns
    end: int       # ns

    @property
    def name(self) -> str:
        """The HLO op's name, e.g. ``cim_mvm.3`` or ``while.28``."""
        return self.text.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    devices: dict[str, list[Op]]            # plane name -> ops, by start
    spans: list[tuple[str, int, int]]       # benchmark annotations

    def window(self) -> tuple[int, int]:
        """The ``chipbench.window`` annotation, else the ops' extent."""
        win = [(a, b) for n, a, b in self.spans
               if n == ANNOTATION + "window"]
        if win:
            return win[0]
        ops = [o for v in self.devices.values() for o in v]
        return (min(o.start for o in ops), max(o.end for o in ops))


def find_xplane(root: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    devices: dict[str, list[Op]] = {}
    spans: list[tuple[str, int, int]] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    ops.append(Op(e.name, s, s + int(e.duration_ns)))
            ops.sort(key=lambda o: o.start)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION):
                        s = int(e.start_ns)
                        spans.append((e.name, s, s + int(e.duration_ns)))
    return Trace(devices, spans)


def _clip(ops: list[Op], lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi]


def busy_intervals(ops: list[Op], lo: int, hi: int
                   ) -> list[tuple[int, int]]:
    """Union of the ops' intervals inside [lo, hi), merged and sorted."""
    merged: list[list[int]] = []
    for a, b in sorted(_clip(ops, lo, hi)):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_seconds(tr: Trace) -> float:
    """Device-busy seconds in the window, averaged over the chips."""
    lo, hi = tr.window()
    if not tr.devices:
        return 0.0
    tot = sum(sum(b - a for a, b in busy_intervals(ops, lo, hi))
              for ops in tr.devices.values())
    return tot / len(tr.devices) * 1e-9


def window_seconds(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) * 1e-9


def kernel_ops(tr: Trace, name: str) -> list[Op]:
    """Ops of one kernel: ``name`` itself or ``name.<n>``, in the window."""
    lo, hi = tr.window()
    pat = re.compile(rf"^{re.escape(name)}(\.\d+)?$")
    return [o for ops in tr.devices.values() for o in ops
            if pat.match(o.name) and o.end > lo and o.start < hi]


def _host_doing(tr: Trace, t: int) -> str:
    """Innermost benchmark annotation covering time ``t``."""
    best = None
    for n, a, b in tr.spans:
        if a <= t < b and n != ANNOTATION + "window":
            if best is None or (b - a) < (best[2] - best[1]):
                best = (n, a, b)
    return best[0][len(ANNOTATION):] if best else "outside annotations"


def breakdown(tr: Trace, top: int = 10) -> dict:
    """Top device ops by total time (loops and calls, which contain
    other ops, left out), and the longest idle gaps."""
    lo, hi = tr.window()
    per_op: dict[str, float] = {}
    for ops in tr.devices.values():
        for a, b, o in ((max(o.start, lo), min(o.end, hi), o)
                        for o in ops if o.end > lo and o.start < hi
                        and not o.name.startswith(CONTAINERS)):
            per_op[o.name] = per_op.get(o.name, 0.0) + (b - a) * 1e-9
    n_dev = max(1, len(tr.devices))
    device_ops = sorted(([k, v / n_dev] for k, v in per_op.items()),
                        key=lambda kv: -kv[1])[:top]
    gaps = []
    for ops in tr.devices.values():
        cur = lo
        for a, b in busy_intervals(ops, lo, hi) + [(hi, hi)]:
            if a > cur:
                gaps.append((a - cur, (a + cur) // 2))
            cur = max(cur, b)
    gaps.sort(key=lambda g: -g[0])
    idle_gaps = [[_host_doing(tr, mid), dur * 1e-9]
                 for dur, mid in gaps[:top]]
    return {"device_ops": device_ops, "idle_gaps": idle_gaps}


_SHAPE = re.compile(r"\b(bf16|f32|f16|s8|s16|s32|u8|u32|pred)\[([\d,]*)\]")


def operand_shapes(op: Op) -> list[tuple[str, tuple[int, ...]]]:
    """Typed shapes in an op's HLO text, the result's first."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(op.text)]
