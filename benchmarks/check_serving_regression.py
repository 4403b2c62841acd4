"""CI gate for the serving engine.

Reads a fresh ``BENCH_serving.json`` (written by ``benchmarks/run.py
--json``) and FAILS (exit 1) when a serving invariant breaks.  Every
invariant is printed as a PASS/FAIL table row (shared plumbing:
``_gate.py``):

1. **shared-prefix hits**: on the long-prompt shared-prefix workload
   the prefix cache must record hits (a counted invariant, not a time);
2. **trajectory** (only with ``--absolute``): continuous-batching
   tokens/s must not regress more than ``--tolerance`` (default 20%)
   against the checked-in baseline.  Absolute tokens/s are
   host-dependent, so compare only against a baseline recorded on
   identical hardware.

Refreshing the baseline after an intentional change: copy the CI
artifact (or a local ``--json`` run's output) over
``benchmarks/baselines/BENCH_serving.json`` and commit it.

Usage:
    python benchmarks/check_serving_regression.py \
        --current BENCH_serving.json \
        [--baseline benchmarks/baselines/BENCH_serving.json] \
        [--tolerance 0.2] [--absolute]
"""

from __future__ import annotations

from pathlib import Path
from typing import List

from _gate import GateRow, emit, load_current_and_baseline, make_parser

DEFAULT_BASELINE = Path(__file__).parent / "baselines" / "BENCH_serving.json"


def check(current: dict, baseline: dict, tolerance: float,
          absolute: bool) -> List[GateRow]:
    sp = current["shared_prefix"]
    rows = [GateRow(
        key="shared_prefix_hits",
        passed=sp["prefix_hits"] > 0,
        value=str(sp["prefix_hits"]),
        bound="> 0",
        detail="prefix cache recorded ZERO hits on the shared-prefix "
               "workload — sharing is not engaging",
    )]
    if absolute:
        base, cur = baseline["continuous_tokens_per_s"], current["continuous_tokens_per_s"]
        rows.append(GateRow(
            key="trajectory_absolute",
            passed=cur >= base * (1.0 - tolerance),
            value=f"{cur:.3f}",
            bound=f">= {base:.3f} * {1 - tolerance:.2f}",
            detail=f"continuous tokens/s regressed >{tolerance:.0%} vs baseline: "
                   f"{cur:.3f} < {base:.3f} * {1 - tolerance:.2f}",
        ))
    return rows


def main(argv=None) -> int:
    args = make_parser(DEFAULT_BASELINE).parse_args(argv)
    current, baseline = load_current_and_baseline(args)

    sp = current["shared_prefix"]
    mem = sp["memory"]
    title = (
        f"serving: continuous={current['continuous_tokens_per_s']:.1f} tok/s"
        f"\nshared-prefix: paged={sp['paged_tokens_per_s']:.1f} tok/s "
        f"(hits {sp['prefix_hits']}, pages {mem['high_water_pages']}/"
        f"{mem['contiguous_pages_equiv']} = {mem['capacity_ratio']:.2f} of a "
        f"slot-contiguous pool)"
    )
    rows = check(current, baseline, args.tolerance, args.absolute)
    return emit(title, rows, "SERVING PERF FAIL")


if __name__ == "__main__":
    raise SystemExit(main())
