"""Structured trace spans: nested, monotonic, JSONL.

``with span("deploy/plan", matrices=12):`` times one phase of a run.
Spans nest through a thread-local stack (a span opened inside another
records it as its parent), carry JSON-serialisable attributes, and are
kept in memory at exit and written to the sink by :func:`trace_stop`,
one JSON line each::

    {"name": "deploy/plan", "id": 3, "parent": 2, "depth": 1,
     "t_start": 0.0123, "t_end": 0.8711, "dur": 0.8588,
     "attrs": {"matrices": 12}}

Timestamps are :func:`repro.telemetry.monotonic` reads relative to the
``trace_to`` call — monotonic by construction, never wall-clock.  Span
ids are sequential integers in the order spans open: deterministic for
a deterministic call order, no PRNG contact (the determinism contract
telemetry shares with the code it instruments).

Spans are active only while a sink is open (:func:`trace_to`) *and*
telemetry is enabled; otherwise :func:`span` returns a shared no-op
context manager — no object allocated per call, nothing timed.  The
``REPRO_TRACE`` environment variable opens a sink at import time (and
writes it at exit), so ``REPRO_TELEMETRY=1 REPRO_TRACE=out.jsonl
python -m ...`` traces any entry point without code changes.

An active span is also a host event of the JAX profiler: it enters a
``jax.profiler.TraceAnnotation`` of the span's bare name, so a profiler
trace (``jax.profiler.start_trace``) shows the program's spans on the
clock of its device events.  jax is taken from ``sys.modules`` only if
it is already imported: this module stays importable without it.
While a sink is open, Python garbage-collection pauses are recorded as
``runtime/gc`` spans (``gc.callbacks``), nested in whatever span they
interrupted.

``repro.telemetry.report`` aggregates a trace file into the per-phase
wall/self-time table behind ``scripts/trace_report.py``.
"""
from __future__ import annotations

import atexit
import gc
import itertools
import json
import os
import sys
import threading

from repro.telemetry.metrics import enabled, monotonic

_LOCK = threading.Lock()
_LOCAL = threading.local()


class _TraceState:
    __slots__ = ("sink", "path", "t0", "ids", "records")

    def __init__(self):
        self.sink = None
        self.path = None
        self.t0 = 0.0
        self.ids = itertools.count()
        self.records: list[dict] = []


_TRACE = _TraceState()


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def _write(f, records: list[dict]) -> None:
    f.writelines(json.dumps(r) + "\n" for r in records)
    f.close()


def trace_to(path: str) -> str:
    """Open ``path`` as the JSONL span sink (replacing any prior one,
    which is written out first).

    Resets the relative clock and the span-id sequence, so every trace
    file starts at ``t_start ~ 0`` with ids from 0.  Returns the path.
    """
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    f = open(path, "w", encoding="utf-8")
    with _LOCK:
        old, old_records = _TRACE.sink, _TRACE.records
        _TRACE.sink = f
        _TRACE.path = path
        _TRACE.t0 = monotonic()
        _TRACE.ids = itertools.count()
        _TRACE.records = []
    if old is not None:
        _write(old, old_records)
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return path


def trace_stop() -> str | None:
    """Write the spans held since :func:`trace_to` and close the sink;
    returns the finished trace's path (or None)."""
    with _LOCK:
        f, path, records = _TRACE.sink, _TRACE.path, _TRACE.records
        _TRACE.sink = None
        _TRACE.path = None
        _TRACE.records = []
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    if f is not None:
        _write(f, records)
    return path


def tracing() -> bool:
    """Is a span sink currently open?"""
    return _TRACE.sink is not None


def trace_path() -> str | None:
    """Path of the open sink, or None."""
    return _TRACE.path


def _coerce(v):
    """Attribute values must be JSON-serialisable and deterministic."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    try:
        return float(v)  # host scalar (incl. 0-d device arrays)
    except (TypeError, ValueError):
        return str(v)


class _NoopSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


def _annotation(name: str):
    """A profiler host event named ``name``, or None without jax."""
    prof = sys.modules.get("jax.profiler")
    cls = getattr(prof, "TraceAnnotation", None)
    return cls(name) if cls is not None else None


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "depth", "t_start",
                 "ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        # next() on itertools.count is atomic under the GIL: no lock on
        # the hot path, and none a GC callback could find held.
        self.id = next(_TRACE.ids)
        self.parent = stack[-1].id if stack else None
        self.depth = len(stack)
        stack.append(self)
        self.ann = _annotation(self.name)
        if self.ann is not None:
            self.ann.__enter__()
        self.t_start = monotonic()
        return self

    def __exit__(self, *exc):
        t_end = monotonic()
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        rec = {"name": self.name, "id": self.id, "parent": self.parent,
               "depth": self.depth,
               "t_start": round(self.t_start - _TRACE.t0, 9),
               "t_end": round(t_end - _TRACE.t0, 9),
               "dur": round(t_end - self.t_start, 9)}
        if self.attrs:
            rec["attrs"] = self.attrs
        if _TRACE.sink is not None:
            _TRACE.records.append(rec)
        return False


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: one ``runtime/gc`` span per collection."""
    if phase == "start":
        if _TRACE.sink is not None and enabled():
            s = _Span("runtime/gc", {"generation": info["generation"]})
            s.__enter__()
            _LOCAL.gc = s
        return
    s = getattr(_LOCAL, "gc", None)
    if s is not None:
        _LOCAL.gc = None
        s.attrs["collected"] = info["collected"]
        s.__exit__(None, None, None)


def span(name: str, **attrs):
    """Context manager timing one named phase (no-op when inactive)."""
    if _TRACE.sink is None or not enabled():
        return _NOOP_SPAN
    return _Span(name, {k: _coerce(v) for k, v in attrs.items()})


_env_trace = os.environ.get("REPRO_TRACE", "")
if _env_trace:
    try:
        trace_to(_env_trace)
        atexit.register(trace_stop)
    except OSError:  # unwritable path must not break the import
        pass
