"""itl_p50_s: median of every gap between consecutive tokens of a
request, over all requests, for tokens reaching the host in the window
(s)."""

from chipbench.metrics._common import percentile, token_gaps


def read(rec):
    return percentile(token_gaps(rec), 50)
