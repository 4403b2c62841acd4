"""lanes_per_tick: tokens produced by decode ticks in the window over the
ticks run (the batch a tick carries)."""

from chipbench.metrics._common import delta


def read(rec):
    ticks = delta(rec, "decode_ticks_total")
    if not ticks:
        return None
    end = rec["seconds"]
    n = sum(1 for r in rec["requests"]
            for t, tick in zip(r["times"], r["from_tick"]) if tick and 0.0 <= t <= end)
    return n / ticks
