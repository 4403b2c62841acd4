#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's sound runs and the
control's, on many seeds, in one process on the chip.

    python3 chipbench/control.py --workload ds7b-chat-mixed --seeds 11,12,13 --seconds 20

Per seed: weights and traffic from the seed, the cell's server, one
open-loop window at the cell's own load (drained to its end), then the
comparison of ``compare.py`` twice over the same sampled requests: the
program's served tokens against the reference (the lower readings), and
the control -- the reference computed one step below each rung's
precision, ``high`` for f32 and ``int4`` for q16_16 -- in the program's
place (the upper readings).  One JSON line per seed, with the verdict
of ``compare.judge`` on the program's readings against all the cell's
limits, and on each rung's control against that rung's own limits
(``rung_limits``): the program has to come out correct and every
control not.  Run by hand; the limits files record what it printed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))

#: the control of each rung: the reference one step below its precision
CONTROL = {"f32": "high", "q16_16": "int4"}


def rung_limits(limits: dict, level: str) -> dict:
    """The limits of the numbers that belong to rung ``level``."""
    from chipbench.compare import SHORT

    prefix = SHORT.get(level, level) + "_"
    return {k: v for k, v in limits.items() if k.startswith(prefix)}


def readings_for_seed(cell, seed: int, seconds: float, log) -> dict:
    from chipbench import compare
    from chipbench.cell import SAMPLE_REQUESTS, SAMPLE_TOKENS, build_server
    from chipbench.loadgen import OpenLoop, output_lengths, schedule

    plan = schedule(cell.traffic, seed, seconds, cell.mcfg.vocab)
    weights, srv = build_server(cell, seed, output_lengths(cell.traffic, seconds),
                                time.perf_counter(), log)
    drain = cell.traffic.get("drain", "all")
    served = OpenLoop(srv, plan, seconds, drain, drain_limit_s=180.0).run()
    level_names = srv.level_names
    del srv
    gc.collect()
    sent = [s for s in served if s.sent is not None and not s.withdrawn]
    picks = compare.sample(sent, level_names, seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    rows = cell.traffic["output"]["max"]
    out = {"seed": seed, "unfinished": sum(1 for s in sent if s.tokens is None),
           "program": compare.readings(cell.reference, cell.spec, weights, picks,
                                       cell.scfg.max_len, rows)}
    out["program_correct"] = compare.judge(out["program"], cell.limits, out["unfinished"])[0]
    out["control"], out["control_correct"] = {}, {}
    for lv, reqs in picks.items():
        ctl = compare.readings(cell.reference, cell.spec, weights, {lv: reqs},
                               cell.scfg.max_len, rows, control=CONTROL[lv])
        out["control"].update(ctl)
        out["control_correct"][lv] = compare.judge(ctl, rung_limits(cell.limits, lv), 0)[0]
    del weights
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from chipbench.cell import Cell
    from chipbench.run import check_devices, enable_compile_cache

    cell = Cell(args.workload, json.loads((HERE.parent / "BENCHMARK.json").read_text()))
    why_not = check_devices(1)
    if why_not:
        print(f"control: {why_not}", file=sys.stderr)
        return 2
    enable_compile_cache()
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings_for_seed(cell, seed, args.seconds, log)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
