"""Arithmetic shared by the metric readers (kept with the benchmark)."""

from __future__ import annotations

from typing import List, Optional

import numpy as np


def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), or None for no values."""
    return float(np.percentile(np.asarray(values, np.float64), q)) if len(values) else None


def token_gaps(rec: dict) -> List[float]:
    """Every gap between consecutive tokens of a request whose later
    token reached the host inside the window."""
    end = rec["seconds"]
    out = []
    for r in rec["requests"]:
        t = r["times"]
        out.extend(b - a for a, b in zip(t, t[1:]) if b <= end)
    return out


def delta(rec: dict, name: str, a: str = "open", b: str = "close") -> float:
    """Growth of registry counter ``name`` between two snapshots."""
    c = rec["counters"]
    return float(c[b].get(name, 0) - c[a].get(name, 0))


def device_seconds(rec: dict, modules) -> Optional[float]:
    """Device time of the named XLA modules in the traced stretch, or
    None where the trace holds none of them."""
    tr = rec.get("trace")
    if not tr:
        return None
    hits = [tr["modules"][m] for m in modules if m in tr["modules"]]
    return float(sum(hits)) if hits else None


def traced_ticks(rec: dict) -> float:
    return delta(rec, "decode_ticks_total", "trace_open", "trace_close")
