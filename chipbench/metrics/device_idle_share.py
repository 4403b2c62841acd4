"""device_idle_share: share of the traced stretch in which no operation
ran on the device, averaged over the chips (%)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["chips"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
