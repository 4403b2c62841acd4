"""Serving-throughput benchmark on the CPU: the continuous-batching
engine on a MIXED workload (mixed prompt lengths, mixed per-request
decode budgets) — the traffic shape the scheduler exists for — and the
paged pool's prefix sharing on a long-prompt shared-prefix workload.

Useful-token accounting: a request contributes its own generated
tokens (at most its ``max_new``).

``serving_json()`` is the ``BENCH_serving.json`` payload recorded per
PR (benchmarks/run.py --json); benchmarks/check_serving_regression.py
gates CI on it.  Times here are the CPU's, not a device's.
"""

from __future__ import annotations

import time

import jax
import numpy as np

#: (prompt_len, max_new) per request — mixed lengths, bimodal budgets
#: (short lookups interleaved with long generations).
WORKLOAD = (
    (8, 2), (5, 24), (11, 2), (4, 24),
    (7, 2), (9, 24), (6, 2), (10, 24),
)

N_SLOTS = 4
MAX_LEN = 64
#: cache rows per page: divides gemma2's 8-row sliding window
PAGE_SIZE = 8
SERVE_LEVEL = "q16_16"   # FAST: exercises the quantized-weight cache +
                         # fused SwiGLU decode path under request churn

#: shared-prefix workload: long prompts that all open with the same
#: PREFIX_LEN tokens (system prompt / few-shot header traffic).  The
#: paged pool with prefix sharing prefills the header ONCE and attaches
#: its pages to every later request.
PREFIX_LEN = 48
SP_TAILS = ((2, 8), (5, 8), (9, 8), (3, 8), (7, 8), (11, 8),
            (4, 8), (6, 8), (10, 8), (8, 8), (2, 8), (5, 8))
SP_MAX_LEN = 128
SP_PAGE = 16


def _requests(server):
    from repro.runtime.scheduler import Request

    rng = np.random.default_rng(7)
    return [
        Request(rid=server.next_rid(), prompt=rng.integers(1, 100, size=plen).tolist(),
                max_new=max_new, level=SERVE_LEVEL)
        for plen, max_new in WORKLOAD
    ]


def _build(cfg_name: str = "gemma2_2b"):
    """Smoke-family config scaled up so a decode step is compute-bound,
    not python dispatch noise (at d_model=64 a step is all dispatch)."""
    import dataclasses

    from repro.configs import smoke
    from repro.models import init_params

    cfg = smoke(cfg_name)
    cfg = dataclasses.replace(cfg, name=cfg.name + "-bench", d_model=256, d_ff=1024)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _continuous_runner(cfg, params):
    """Workload closure for the continuous engine (one persistent
    server — the pool is allocated once; timed passes reuse the warm
    jit cache exactly like a long-lived serving process would).

    Telemetry runs ENABLED here on purpose: the recorded tokens/s is
    what a production deployment with profiling on would see, the trace
    becomes the CI artifact, and check_telemetry_overhead.py separately
    bounds the on-vs-off delta."""
    from repro.runtime.config import ServingConfig
    from repro.runtime.serve import ContinuousBatchingServer
    from repro.runtime.telemetry import TelemetryConfig

    srv = ContinuousBatchingServer(
        cfg, params,
        ServingConfig(n_slots=N_SLOTS, max_len=MAX_LEN, page_size=PAGE_SIZE,
                      default_level=SERVE_LEVEL,
                      telemetry=TelemetryConfig(enabled=True, trace=True)),
    )

    def run():
        fins = srv.serve(_requests(srv))
        return sum(f.n_generated for f in fins.values())

    return run, srv


def _shared_prefix_requests(srv):
    from repro.runtime.scheduler import Request

    rng = np.random.default_rng(11)
    prefix = rng.integers(1, 100, size=PREFIX_LEN).tolist()
    return [
        Request(rid=srv.next_rid(),
                prompt=prefix + rng.integers(1, 100, size=tail).tolist(),
                max_new=max_new, level=SERVE_LEVEL)
        for tail, max_new in SP_TAILS
    ]


def _shared_prefix_runner(cfg, params):
    """Shared-prefix workload through one persistent server with prefix
    sharing on."""
    from repro.runtime.config import ServingConfig
    from repro.runtime.serve import ContinuousBatchingServer

    srv = ContinuousBatchingServer(
        cfg, params,
        ServingConfig(
            n_slots=N_SLOTS, max_len=SP_MAX_LEN, default_level=SERVE_LEVEL,
            page_size=SP_PAGE, prefix_sharing=True,
        ),
    )

    def run():
        fins = srv.serve(_shared_prefix_requests(srv))
        return sum(f.n_generated for f in fins.values())

    return run, srv


def shared_prefix_json(repeats: int = 3) -> dict:
    """The ``shared_prefix`` section of the serving payload: tokens/s
    with prefix sharing on the long-prompt workload, plus the page-pool
    capacity numbers (high-water pages vs a slot-contiguous pool's
    footprint)."""
    cfg, params = _build("deepseek_7b")  # full-context attn: shareable
    run_p, srv_p = _shared_prefix_runner(cfg, params)
    run_p()  # warm: compiles + primes the prefix cache

    p_walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        p_toks = run_p()
        p_walls.append(time.perf_counter() - t0)
    p_wall = sorted(p_walls)[len(p_walls) // 2]

    report = srv_p.cache_ops.report()
    full = report["groups"][f"L{SP_MAX_LEN}"]
    return {
        "workload": {"prefix_len": PREFIX_LEN, "tails": list(SP_TAILS),
                     "n_slots": N_SLOTS, "max_len": SP_MAX_LEN,
                     "page_size": SP_PAGE},
        "paged_tokens_per_s": p_toks / p_wall,
        "prefix_hits": srv_p.stats["prefix_hits"],
        "prefix_tokens_reused": srv_p.stats["prefix_tokens_reused"],
        "prefill_chunks": srv_p.stats["prefill_chunks"],
        "memory": {
            "page_size": SP_PAGE,
            "high_water_pages": full["high_water"],
            "contiguous_pages_equiv": full["contiguous_pages_equiv"],
            "capacity_ratio": full["high_water"] / full["contiguous_pages_equiv"],
        },
    }


def serving_json(repeats: int = 3, trace_out=None, metrics_out=None) -> dict:
    """``trace_out`` / ``metrics_out``: optional paths; when given, the
    server's Chrome trace and Prometheus exposition are written there
    after the timed passes (CI uploads both as artifacts)."""
    cfg, params = _build()
    run_c, srv_c = _continuous_runner(cfg, params)
    run_c()  # warm: pays every compile

    c_walls = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        c_toks = run_c()
        c_walls.append(time.perf_counter() - t0)
    c_wall = sorted(c_walls)[len(c_walls) // 2]
    stats = dict(srv_c.stats)
    if trace_out is not None:
        srv_c.telemetry.write_trace(trace_out)
    if metrics_out is not None:
        with open(metrics_out, "w") as f:
            f.write(srv_c.render_prometheus())
    return {
        "bench": "serving",
        "model": "gemma2_2b-smoke",
        "level": SERVE_LEVEL,
        "workload": {"requests": list(WORKLOAD), "n_slots": N_SLOTS,
                     "max_len": MAX_LEN, "page_size": PAGE_SIZE},
        "useful_tokens": {"continuous": c_toks},
        "continuous_tokens_per_s": c_toks / c_wall,
        "continuous_stats": stats,
        "telemetry": srv_c.metrics_snapshot(),
        "shared_prefix": shared_prefix_json(repeats),
    }


def bench_serving():
    """CSV rows for benchmarks/run.py."""
    p = serving_json()
    return [
        ("serving.continuous_tok_s", 0.0,
         f"tokens_per_s={p['continuous_tokens_per_s']:.1f},"
         f"decode_steps={p['continuous_stats']['decode_steps']}"),
        ("serving.paged_shared_prefix_tok_s", 0.0,
         f"tokens_per_s={p['shared_prefix']['paged_tokens_per_s']:.1f},"
         f"prefix_hits={p['shared_prefix']['prefix_hits']},"
         f"capacity_ratio={p['shared_prefix']['memory']['capacity_ratio']:.2f}"),
    ]


if __name__ == "__main__":
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
    print(json.dumps(serving_json(), indent=2))
