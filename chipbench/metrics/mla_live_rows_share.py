"""mla_live_rows_share: of the cache rows that the decode passes'
latent-attention layers ran over in the traced stretch (every lane of
the batch x the cache view's length, a pass and a layer at a time), the
share that its member lanes attend (each one's position + 1) (%): the
server's ``attn_rows_total{kind=mla,rows=live}`` over
``{...,rows=computed}``, between the trace's opening and closing
snapshots.  None where the
program keeps no such counter or ran no latent-attention pass."""

from chipbench.metrics._common import delta

LIVE = "attn_rows_total{kind=mla,rows=live}"
COMPUTED = "attn_rows_total{kind=mla,rows=computed}"


def read(rec):
    c = rec["counters"]
    if COMPUTED not in c.get("trace_close", {}):
        return None
    computed = delta(rec, COMPUTED, "trace_open", "trace_close")
    if not computed:
        return None
    return 100.0 * delta(rec, LIVE, "trace_open", "trace_close") / computed
