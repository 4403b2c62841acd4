"""Benchmark harness entrypoint: one section per paper table/figure +
the roofline cell summary.  Prints ``name,us_per_call,derived`` CSV.

Run:  PYTHONPATH=src python -m benchmarks.run [--section trig|universal|mul|matmul|switch|fused_mlp|serving|roofline|all]

``--json`` additionally records the perf trajectories: writes
``BENCH_fused_mlp.json`` (fused/unfused/precise medians at the
configs/ MLP shapes + smoke-model decode tokens/s),
``BENCH_serving.json`` (continuous-batching tokens/s on the
mixed-length serving workload and prefix-sharing hits on a
shared-prefix one — gated in CI by
benchmarks/check_serving_regression.py) AND
``BENCH_speculative.json`` (ladder-speculative vs vanilla f32 greedy
tokens/s — gated in CI by benchmarks/check_speculative_regression.py)
next to the CSV output, so successive PRs accumulate comparable
numbers.  The serving run also drops ``trace.json`` (Chrome
``trace_event`` profile of the continuous engine — open in Perfetto)
and ``metrics.prom`` (Prometheus text exposition) beside the JSONs;
CI uploads all of them as artifacts (see docs/observability.md).
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).parent.parent))

from benchmarks import bench_paper_tables, bench_serving, bench_speculative, roofline  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all")
    ap.add_argument(
        "--json", nargs="?", const="BENCH_fused_mlp.json", default=None,
        metavar="PATH",
        help="also write the fused-MLP medians + decode tokens/s as JSON "
             "(default path: BENCH_fused_mlp.json); BENCH_serving.json is "
             "written next to it",
    )
    args = ap.parse_args()

    sections = {
        "trig": bench_paper_tables.bench_trig,
        "universal": bench_paper_tables.bench_universal_family,
        "mul": bench_paper_tables.bench_scalar_mul,
        "matmul": bench_paper_tables.bench_matmul_crossover,
        "switch": bench_paper_tables.bench_switch,
        "ladder": bench_paper_tables.bench_ladder_switch,
        "fused_mlp": bench_paper_tables.bench_fused_mlp,
        "serving": bench_serving.bench_serving,
        "speculative": bench_speculative.bench_speculative,
        "footprint": bench_paper_tables.bench_footprint,
        "deferred": bench_paper_tables.bench_deferred_error,
        "roofline": roofline.run,
    }

    if args.json is not None or args.section == "json-only":
        payload = bench_paper_tables.fused_mlp_json()
        out_path = args.json or "BENCH_fused_mlp.json"
        Path(out_path).write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {out_path}", file=sys.stderr)
        out_dir = Path(out_path).parent
        serving_payload = bench_serving.serving_json(
            trace_out=str(out_dir / "trace.json"),
            metrics_out=str(out_dir / "metrics.prom"),
        )
        print(f"wrote {out_dir / 'trace.json'} and {out_dir / 'metrics.prom'}",
              file=sys.stderr)
        serving_path = Path(out_path).parent / "BENCH_serving.json"
        serving_path.write_text(json.dumps(serving_payload, indent=2) + "\n")
        print(f"wrote {serving_path}", file=sys.stderr)
        spec_payload = bench_speculative.speculative_json()
        spec_path = Path(out_path).parent / "BENCH_speculative.json"
        spec_path.write_text(json.dumps(spec_payload, indent=2) + "\n")
        print(f"wrote {spec_path}", file=sys.stderr)
        if args.section == "json-only":
            return
        # the JSON payloads already ran those suites — don't pay for
        # them twice in the same invocation
        sections.pop("fused_mlp", None)
        sections.pop("serving", None)
        sections.pop("speculative", None)
        if args.section in ("fused_mlp", "serving", "speculative"):
            return

    todo = sections.values() if args.section == "all" else [sections[args.section]]

    print("name,us_per_call,derived")
    for fn in todo:
        for name, us, derived in fn():
            print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
