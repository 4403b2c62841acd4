"""Serving example: continuous batching with per-request precision
(paper §7.2's hybrid strategy, applied per request: each request names
the ladder rung it runs at, and one batch mixes rungs).

Run:  PYTHONPATH=src python examples/serve_batched.py
"""

import jax

from repro.configs import smoke
from repro.models import init_params
from repro.runtime.scheduler import Request
from repro.runtime.serve import ContinuousBatchingServer, ServingConfig


def main():
    cfg = smoke("gemma2_2b")  # local/global alternating + softcaps
    params = init_params(cfg, jax.random.PRNGKey(0))
    # page size 4 divides the smoke config's 8-row sliding window
    srv = ContinuousBatchingServer(cfg, params, ServingConfig(n_slots=4, max_len=64, page_size=4))

    prompts = [[1, 2, 3, 4], [9, 8, 7], [5, 5, 5, 5, 5], [2, 4, 6, 8, 10, 12]]
    print("PRECISE (f32) generations:")
    for i, seq in enumerate(srv.generate(prompts, max_new=12, level="f32")):
        print(f"  req{i}: {seq}")
    print("FAST (q16_16, int8 W8A8) generations:")
    for i, seq in enumerate(srv.generate(prompts, max_new=12, level="q16_16")):
        print(f"  req{i}: {seq}")

    # one batch, two rungs: every request decodes at its own level
    levels = ["f32", "q16_16", "f32", "q16_16"]
    reqs = [Request(rid=srv.next_rid(), prompt=p, max_new=12, level=lv)
            for p, lv in zip(prompts, levels)]
    fins = srv.serve(reqs)
    print("mixed batch:")
    for r in reqs:
        print(f"  req{r.rid} [{r.level}]: {fins[r.rid].tokens}")
    print(f"stats: {srv.stats}")


if __name__ == "__main__":
    main()
