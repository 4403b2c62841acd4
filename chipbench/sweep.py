#!/usr/bin/env python3
"""Find a chat cell's knee once, by hand, on the chip: the highest offered
rate at which completed requests keep pace with arrivals over the window.

    python3 chipbench/sweep.py --workload ds7b-chat-mixed --rates 0.25,0.3,0.35 --seeds 7,8 --seconds 90

One process builds the cell's server once (weights from the first seed)
and runs one open-loop window per rate and traffic seed with the cell's
traffic file, its rate replaced; each window drains before the next.
Per window it prints one JSON line: requests due, finished inside the window, left at the
close, time to first token (median, 95th percentile, and 95th
percentile of the first and of the last third of arrivals), the gaps
between tokens, and tokens/s served against offered.  A window keeps pace
where the second half of the window serves at least 0.9 of the offered
tokens/s and the last third's time to first token has not grown past
twice the first third's; the knee is the highest rate at which every
seed's window keeps pace.  Not a benchmark run: its numbers go into
PERF.md as the sweep.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", default="7")
    args = ap.parse_args(argv)

    import numpy as np

    from chipbench.cell import Cell, build_server
    from chipbench.loadgen import OpenLoop, output_lengths, schedule
    from chipbench.run import check_devices, enable_compile_cache

    bench = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cell = Cell(args.workload, bench)
    why_not = check_devices(1)
    if why_not:
        print(f"sweep: {why_not}", file=sys.stderr)
        return 2
    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    seeds = [int(s) for s in args.seeds.split(",")]
    lengths = set()
    for r in rates:
        lengths |= set(output_lengths(dict(cell.traffic, rate_rps=r), args.seconds))
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    _, srv = build_server(cell, seeds[0], sorted(lengths), T_START, log)
    for r, seed in ((r, s) for r in rates for s in seeds):
        traffic = dict(cell.traffic, rate_rps=r)
        plan = schedule(traffic, seed, args.seconds, cell.mcfg.vocab)
        loop = OpenLoop(srv, plan, args.seconds, "all", drain_limit_s=120.0)
        served = loop.run()
        end = args.seconds
        sent = [s for s in served if s.sent is not None]
        ttft = [s.times[0] - loop.t0 - s.planned.due for s in sent if s.times]
        third = max(1, len(sent) // 3)
        first = [s.times[0] - loop.t0 - s.planned.due for s in sent[:third] if s.times]
        last = [s.times[0] - loop.t0 - s.planned.due for s in sent[-third:] if s.times]
        gaps = [b - a for s in sent for a, b in zip(s.times, s.times[1:]) if b - loop.t0 <= end]
        done_in = sum(1 for s in sent if s.times and len(s.times) == s.planned.max_new
                      and s.times[-1] - loop.t0 <= end)
        row = {
            "rate_rps": r, "seed": seed, "due": len(plan), "finished_in_window": done_in,
            "left_at_close": len(sent) - done_in,
            "ttft_p50_s": float(np.percentile(ttft, 50)), "ttft_p95_s": float(np.percentile(ttft, 95)),
            "ttft_p95_first_third_s": float(np.percentile(first, 95)),
            "ttft_p95_last_third_s": float(np.percentile(last, 95)),
            "itl_p50_s": float(np.percentile(gaps, 50)), "itl_p95_s": float(np.percentile(gaps, 95)),
            "tokens_per_s": sum(1 for s in sent for t in s.times if t - loop.t0 <= end) / end,
            "tokens_per_s_second_half": sum(1 for s in sent for t in s.times
                                            if end / 2 < t - loop.t0 <= end) / (end / 2),
            "offered_tokens_per_s": r * sum(p.max_new for p in plan) / len(plan),
            "drain_s": time.perf_counter() - loop.t_close,
        }
        row["keeps_pace"] = bool(
            row["tokens_per_s_second_half"] >= 0.9 * row["offered_tokens_per_s"]
            and row["ttft_p95_last_third_s"] <= 2 * row["ttft_p95_first_third_s"])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
