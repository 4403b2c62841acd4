"""peak_hbm_share: ``peak_bytes_in_use`` of the fullest chip, read after
the window, over the chip's HBM from chipbench/peaks.json (%)."""


def read(rec):
    if rec.get("memory_peak_bytes") is None or not rec.get("peaks"):
        return None
    return 100.0 * rec["memory_peak_bytes"] / rec["peaks"]["hbm_bytes"]
