"""decode_mfu: algorithmic operations of every decode token in the
traced stretch over the decode modules' device time times the chip's
bfloat16 matrix peak (%): the whole decode step's share of the chip's
peak, whatever its kernels are."""

from chipbench.metrics._common import device_seconds
from chipbench.metrics.decode_step_ms import MODULES


def read(rec):
    t = device_seconds(rec, MODULES)
    if t is None or not rec["passes"] or not rec.get("peaks"):
        return None
    ops = sum(rec["counts"].decode_flops_per_token(rec["spec"], c)
              for _, lanes in rec["passes"] for c in lanes)
    return 100.0 * ops / (t * rec["peaks"]["bf16_flops_per_s"])
