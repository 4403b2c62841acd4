#!/usr/bin/env python3
"""Compile a configuration's served steps for a described TPU v5e, at a
cell's sizes, on a machine without the chip; print each program's memory.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --config deepseek_7b [--slots 10]

Run by hand before a chip run; it is not a test.  Nothing runs and no
array of the real size is made: the server's step functions are built on
an object that skips the server's own set-up (which would allocate the
weights and the pool), and lowered with shapes on one chip of a
described ``v5e:2x2``.  What the chip's compiler refuses fails here.
Peak is arguments + temporaries + outputs not aliased to an argument,
from ``compiled.memory_analysis()``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE.parent))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def bare_server(mcfg, scfg):
    """A ``ContinuousBatchingServer`` with its jitted steps built and no
    device state allocated."""
    from repro.core.precision import MathEngine
    from repro.runtime.cachepool import PagedCachePool
    from repro.runtime.config import SERVE_CACHE_DTYPE, SERVE_STEP_LEVELS
    from repro.runtime.serve import ContinuousBatchingServer
    from repro.runtime.telemetry import Telemetry

    srv = object.__new__(ContinuousBatchingServer)
    srv.cfg, srv.scfg = mcfg, scfg
    srv.level_names = tuple(lv for lv, _ in SERVE_STEP_LEVELS)
    srv.telemetry = Telemetry(scfg.telemetry)
    srv._declare_metrics(srv.telemetry.registry)
    srv.engine = MathEngine(scfg.default_level)
    srv.paged = True
    srv.cache_ops = PagedCachePool(mcfg, scfg.n_slots, scfg.max_len, scfg.page_size,
                                   dtype=SERVE_CACHE_DTYPE, n_pages=scfg.n_pages,
                                   registry=srv.telemetry.registry)
    srv._build()
    return srv


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--max-len", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.cell import load_module
    from repro.core.quantization import QuantizedWeightCache
    from repro.models import init_params
    from repro.models.layers import attach_quantized_weights

    jax.config.update("jax_enable_compilation_cache", False)
    spec = json.loads((HERE / "configs" / f"{args.config}.json").read_text())
    if args.slots:
        spec["serving"]["n_slots"] = args.slots
    if args.max_len:
        spec["serving"]["max_len"] = args.max_len
    if args.layers:
        spec["num_hidden_layers"] = args.layers
    binding = load_module(HERE / "configs" / f"{args.config}.py", "binding")
    mcfg, scfg = binding.program_config(spec), binding.serving_config(spec)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    shapes = jax.eval_shape(lambda k: init_params(mcfg, k), jax.random.PRNGKey(0))
    params = on_chip(jax.eval_shape(
        lambda p: attach_quantized_weights(p, QuantizedWeightCache(), level="q16_16"), shapes))
    srv = bare_server(mcfg, scfg)
    pool = srv.cache_ops
    state = on_chip(jax.eval_shape(pool.alloc))
    B, C = scfg.n_slots, scfg.resolved_chunk
    i32, f32 = jnp.int32, jnp.float32

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    tables = {gk: sd((B, g["nb"]), i32) for gk, g in pool.groups.items()}
    slot_tables = {gk: sd((g["nb"],), i32) for gk, g in pool.groups.items()}
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    L = scfg.max_len
    steps = {
        "tick_p": (srv._tick_p, (sd((), i32), params, sd((B,), i32), sd((B,), i32), state, tables,
                                 sd((B,), bool), key, sd((B, L), i32), sd((B,), i32), sd((B, 2), f32))),
        "pool_pass_p": (srv._pool_pass_p, (sd((), i32), params, sd((B, 1), i32), sd((B,), i32), state,
                                           tables, sd((B,), bool), sd((B, mcfg.vocab), f32))),
        "chunk_admit": (srv._chunk_admit, (sd((), i32), params, sd((1, C), i32), sd((1, C), i32), state,
                                           slot_tables, slot_tables, sd((), i32), sd((1,), i32),
                                           sd((1,), i32))),
    }
    hbm = 16 * 1024 ** 3
    report = {"config": args.config, "layers": mcfg.n_layers, "slots": B, "max_len": L,
              "chunk": C, "steps": {}}
    for name, (fn, arg_shapes) in steps.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*arg_shapes).compile()
        ma = compiled.memory_analysis()
        peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes
                - ma.alias_size_in_bytes)
        report["steps"][name] = {
            "compile_s": round(time.perf_counter() - t0, 1),
            "arguments_gb": round(ma.argument_size_in_bytes / 1e9, 3),
            "temporaries_gb": round(ma.temp_size_in_bytes / 1e9, 3),
            "peak_gb": round(peak / 1e9, 3),
            "peak_share_of_16gib": round(peak / hbm, 4),
        }
        print(name, json.dumps(report["steps"][name]), flush=True)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
