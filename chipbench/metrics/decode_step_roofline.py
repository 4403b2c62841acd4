"""decode_step_roofline: the least time the chip could take for the
traced stretch's decode passes -- each pass the larger of its
algorithmic operations over the matrix peak and its bytes (weights at
the rung's declared width, live KV rows) over HBM bandwidth, as the
configuration's ``configs/<name>_flops.py`` counts them -- over the
device time of the decode modules (%).  The bound that sets most of the
least time is logged."""

import sys

from chipbench import peaks
from chipbench.metrics._common import device_seconds
from chipbench.metrics.decode_step_ms import MODULES


def read(rec):
    t = device_seconds(rec, MODULES)
    if t is None or not rec["passes"] or not rec.get("peaks"):
        return None
    least, by = 0.0, {"compute": 0.0, "bytes": 0.0}
    for rung, lanes in rec["passes"]:
        f, b = rec["counts"].decode_pass(rec["spec"], rung, lanes)
        lt, bound = peaks.least_time(f, b, rung, rec["peaks"])
        least += lt
        by[bound] += lt
    print(f"decode_step_roofline: least time {least:.6f}s of {t:.6f}s device time; "
          f"bound by {max(by, key=by.get)} ({by})", file=sys.stderr)
    return 100.0 * least / t
