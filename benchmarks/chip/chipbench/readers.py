"""Arithmetic shared by the metric readers in `metrics/`.  Each reader
returns None where its run has nothing for it to read."""
from __future__ import annotations

from typing import Optional

import numpy as np


def percentile_ms(run, kind: str, p: float) -> Optional[float]:
    lat = run.latencies_ms(kind)
    return float(np.percentile(lat, p)) if len(lat) else None


def module_ms(run, prefix: str) -> Optional[float]:
    """Mean device milliseconds of one call of the modules named `prefix`."""
    if run.trace is None:
        return None
    n, s = run.trace.module(prefix)
    return 1e3 * s / n if n else None


def idle_pct(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    t = run.trace
    if t is None or not t.devices or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def mean_wait_ms(run, kind: str) -> Optional[float]:
    """Mean scheduler queue wait of the `kind` tasks that completed in the
    window, from the scheduler's cumulative per-kind counters."""
    a, b = run.sched0.get(kind), run.sched1.get(kind)
    if not b:
        return None
    n0, w0 = (a["n"], a["mean_wait_ms"] * a["n"]) if a else (0, 0.0)
    n = b["n"] - n0
    return (b["mean_wait_ms"] * b["n"] - w0) / n if n > 0 else None
