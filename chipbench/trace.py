"""Reduction of a profiler trace (``.xplane.pb``) to device time per XLA
module, device busy time, and the longest idle gaps, each named by what
the host was doing in it.

Only JAX's own reader (``jax.profiler.ProfileData``) is used.  Device
planes are those named ``/device:TPU:<n>``; on each, module executions
are the events of the ``XLA Modules`` line and operations those of the
``XLA Ops`` line.  Host spans are the benchmark's own
``TraceAnnotation`` events, whose names start with ``SPAN_PREFIX``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

SPAN_PREFIX = "cb:"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def module_name(event_name: str) -> str:
    """``jit_tick_p(1234)`` -> ``jit_tick_p``."""
    return event_name.split("(", 1)[0].strip()


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(profile, n_gaps: int = 10) -> dict:
    """Reduce a ``ProfileData`` to::

        {"chips": n, "modules": {name: seconds}, "module_counts": {name: n},
         "busy_s": seconds (mean over chips), "span_ns": [first, last],
         "gaps": [[label, seconds], ...] longest first}

    Times are device times summed over the chips' module events; busy
    time is the union of each chip's operation intervals."""
    modules: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    busy_total, chips = 0.0, 0
    merged_all: List[Tuple[int, int]] = []
    spans: List[Tuple[int, int, str]] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mod_line, ops_line = lines.get(MODULE_LINE), lines.get(OPS_LINE)
            if mod_line is None and ops_line is None:
                continue
            chips += 1
            intervals = []
            for ev in (mod_line.events if mod_line is not None else []):
                name = module_name(ev.name)
                modules[name] = modules.get(name, 0.0) + ev.duration_ns * 1e-9
                counts[name] = counts.get(name, 0) + 1
                intervals.append((int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
            if ops_line is not None:
                intervals = [(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                             for ev in ops_line.events]
            merged = _union(intervals)
            busy_total += sum(e - s for s, e in merged) * 1e-9
            if chips == 1:
                merged_all = merged
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                                      ev.name[len(SPAN_PREFIX):]))
    gaps = []
    for (s0, e0), (s1, _) in zip(merged_all, merged_all[1:]):
        if s1 > e0:
            gaps.append((s1 - e0, e0, s1))
    gaps.sort(reverse=True)
    labelled = []
    for dur, a, b in gaps[:n_gaps]:
        mid = (a + b) // 2
        cover = [(e - s, name) for s, e, name in spans if s <= mid <= e]
        label = "host:" + (min(cover)[1] if cover else "outside-spans")
        labelled.append([label, dur * 1e-9])
    return {
        "chips": chips,
        "modules": modules,
        "module_counts": counts,
        "busy_s": busy_total / chips if chips else 0.0,
        "span_ns": [merged_all[0][0], merged_all[-1][1]] if merged_all else None,
        "gaps": labelled,
    }

