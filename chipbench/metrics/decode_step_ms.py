"""decode_step_ms: device time of the decode modules (the fused
single-rung tick, the mixed-rung passes, sampling and the state update)
per decode tick, in the traced stretch (ms)."""

from chipbench.metrics._common import device_seconds, traced_ticks

MODULES = ("jit_tick_p", "jit_pool_pass_p", "jit_finish", "jit_step_update")


def read(rec):
    t, ticks = device_seconds(rec, MODULES), traced_ticks(rec)
    if t is None or not ticks:
        return None
    return 1000.0 * t / ticks
