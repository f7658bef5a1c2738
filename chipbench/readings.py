"""Arithmetic shared by the metric readers."""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation), None when empty."""
    return float(np.percentile(values, q)) if len(values) else None


def span_mean_ms(run, name: str) -> float | None:
    """Mean duration of the program's spans of one name, in ms."""
    durs = [s["dur"] for s in run.spans or () if s.get("name") == name]
    return 1e3 * sum(durs) / len(durs) if durs else None


def idle_share(run) -> float | None:
    """Percent of the traced window with no op running on the device."""
    from chipbench import trace

    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_seconds(run.trace)
                    / trace.window_seconds(run.trace))
