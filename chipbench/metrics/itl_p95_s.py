"""itl_p95_s: 95th percentile of the same gaps as itl_p50_s (s)."""

from chipbench.metrics._common import percentile, token_gaps


def read(rec):
    return percentile(token_gaps(rec), 95)
