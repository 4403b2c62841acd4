"""prefill_chunk_ms: device time of the chunked-prefill module per chunk
dispatched, in the traced stretch (ms)."""

from chipbench.metrics._common import delta, device_seconds

MODULES = ("jit_chunk_admit",)


def read(rec):
    t = device_seconds(rec, MODULES)
    chunks = delta(rec, "prefill_chunks_total", "trace_open", "trace_close")
    if t is None or not chunks:
        return None
    return 1000.0 * t / chunks
