"""level_passes_per_tick: compiled decode passes per decode tick in the
window (``level_passes_total`` over ``decode_ticks_total``): 1 where a
tick's lanes share a rung, 2 where f32 and q16_16 lanes mix."""

from chipbench.metrics._common import delta


def read(rec):
    ticks = delta(rec, "decode_ticks_total")
    if not ticks:
        return None
    passes = sum(delta(rec, k) for k in rec["counters"]["close"]
                 if k.startswith("level_passes_total{"))
    return passes / ticks
