"""Published per-chip peaks, keyed by ``jax.Device.device_kind``, and
the least time a piece of work could take on a chip.

A device that is not in ``peaks.json`` is an error, never a default:
a roofline against the wrong chip's peaks is a wrong number.  The work
itself (operations and bytes) is counted by each configuration's own
``configs/<name>_flops.py``.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str, table=None) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind the
    table does not hold."""
    table = json.loads(PEAKS_FILE.read_text()) if table is None else table
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"chipbench/peaks.json holds {sorted(table)}")
    return table[device_kind]


def least_time(flops: float, nbytes: float, rung: str, peaks: dict) -> (float, str):
    """The least time the chip could take for ``flops`` operations and
    ``nbytes`` bytes, and which bound sets it.  The f32 rung is held to
    the bfloat16 matrix peak (the fastest the chip multiplies), q16_16 to
    the int8 peak."""
    peak = peaks["int8_ops_per_s"] if rung == "q16_16" else peaks["bf16_flops_per_s"]
    t_c, t_b = flops / peak, nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bytes")
