#!/usr/bin/env python3
"""The chip benchmark's one entry point.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of standard output, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: every number compared with the reference, beside its limit
(also the last lines on standard error).

It refuses to run -- exit code 2, no result -- where JAX finds no TPU
or fewer chips than the cell asks for.  JAX's persistent compilation
cache is kept where ``JAX_COMPILATION_CACHE_DIR`` says, or else at the
fixed ``.jax_cache`` of the checkout, so that only a checkout's first
run of a cell compiles.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(CHECKOUT))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int) -> str:
    """None where JAX holds at least ``chips`` TPU chips, else why not."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        return f"JAX found no usable backend: {e}"
    if devs[0].platform != "tpu":
        return f"needs a TPU; JAX found {devs[0].platform}"
    if len(devs) < chips:
        return f"the cell asks for {chips} chips; JAX found {len(devs)}"
    return None


def enable_compile_cache() -> str:
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # the eviction slices and other small programs are worth keeping too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    args = parse_args(argv)
    bench_file = CHECKOUT / "BENCHMARK.json"
    if not bench_file.exists():
        print(f"chipbench: no {bench_file}", file=sys.stderr)
        return 2
    bench = json.loads(bench_file.read_text())
    from chipbench.cell import run_cell, workload_entry

    try:
        entry = workload_entry(bench, args.workload)
    except KeyError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    why_not = check_devices(int(entry["chips"]))
    if why_not:
        print(f"chipbench: {why_not}; no result", file=sys.stderr)
        return 2
    print(f"chipbench: compile cache {enable_compile_cache()}", file=sys.stderr, flush=True)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, bench=bench)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
