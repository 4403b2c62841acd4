"""CI gate: the telemetry profiler tier must stay cheap.

Runs the bench_serving mixed workload through TWO continuous servers —
telemetry fully enabled (profiler + tracer) vs fully disabled — with
interleaved timed passes, and FAILS (exit 1) when the enabled side's
median tokens/s drops more than ``--tolerance`` (default 5%) below the
disabled side.  This is the enforcement half of the overhead contract
in docs/observability.md: the registry tier is always on (plain dict
increments, same cost as the ad-hoc counters it replaced), and the
span/timer tier must cost < 5% even when fully on.

The gate also asserts the two servers emit IDENTICAL token streams —
telemetry that changes tokens is a correctness bug, not an overhead
bug (tests/test_telemetry.py pins the same invariant at smoke scale).

Usage:
    PYTHONPATH=src python benchmarks/check_telemetry_overhead.py \
        [--repeats 5] [--tolerance 0.05]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent))

from _gate import GateRow, emit  # noqa: E402
from bench_serving import (  # noqa: E402
    MAX_LEN, N_SLOTS, PAGE_SIZE, SERVE_LEVEL, _build, _requests,
)


def _runner(cfg, params, enabled: bool):
    from repro.runtime.config import ServingConfig
    from repro.runtime.serve import ContinuousBatchingServer
    from repro.runtime.telemetry import TelemetryConfig

    srv = ContinuousBatchingServer(
        cfg, params,
        ServingConfig(n_slots=N_SLOTS, max_len=MAX_LEN, page_size=PAGE_SIZE,
                      default_level=SERVE_LEVEL,
                      telemetry=TelemetryConfig(enabled=enabled, trace=enabled)),
    )

    def run():
        fins = srv.serve(_requests(srv))
        toks = sum(f.n_generated for f in fins.values())
        streams = [f.tokens for f in sorted(fins.values(), key=lambda f: f.rid)]
        return toks, streams

    return run


def measure(repeats: int = 5):
    cfg, params = _build()
    run_off = _runner(cfg, params, enabled=False)
    run_on = _runner(cfg, params, enabled=True)
    _, off_streams = run_off()
    _, on_streams = run_on()  # warm: pays every compile on both servers
    identical = True

    off_walls, on_walls = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        off_toks, s_off = run_off()
        off_walls.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        on_toks, s_on = run_on()
        on_walls.append(time.perf_counter() - t0)
        identical = identical and (s_off == s_on)
    off_wall = sorted(off_walls)[len(off_walls) // 2]
    on_wall = sorted(on_walls)[len(on_walls) // 2]
    return {
        "off_tokens_per_s": off_toks / off_wall,
        "on_tokens_per_s": on_toks / on_wall,
        "identical_tokens": identical and (off_streams == on_streams),
    }


def check(m: dict, tolerance: float):
    on, off = m["on_tokens_per_s"], m["off_tokens_per_s"]
    return [
        GateRow(
            key="telemetry_overhead",
            passed=on >= off * (1.0 - tolerance),
            value=f"{on / off:.3f}x",
            bound=f">= {1.0 - tolerance:.2f}x disabled",
            detail=f"profiler tier costs more than {tolerance:.0%}: "
                   f"{on:.1f} (on) vs {off:.1f} (off) tokens/s "
                   f"= {1.0 - on / off:.1%} overhead",
        ),
        GateRow(
            key="identical_tokens",
            passed=bool(m["identical_tokens"]),
            value=str(m["identical_tokens"]),
            bound="True",
            detail="telemetry on/off produced DIFFERENT token streams — "
                   "instrumentation is perturbing decode",
        ),
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=0.05)
    args = ap.parse_args(argv)

    m = measure(args.repeats)
    title = (
        f"telemetry overhead: disabled={m['off_tokens_per_s']:.1f} tok/s, "
        f"enabled={m['on_tokens_per_s']:.1f} tok/s "
        f"({m['on_tokens_per_s'] / m['off_tokens_per_s']:.3f}x)"
    )
    return emit(title, check(m, args.tolerance), "TELEMETRY OVERHEAD FAIL")


if __name__ == "__main__":
    raise SystemExit(main())
