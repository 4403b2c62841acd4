"""One run of one cell: set up, measure, check, and reduce to metrics.

``run_cell`` does everything but the look for a chip (``run.py`` does
that), so the CPU tests can drive it at a tiny size.  The steps:

1. set-up (``setup_s``, from process start to the window's opening):
   the weights, made on the device from the seed; the program's server,
   which quantizes its int8 copies; a warm-up that serves a few requests
   at both rungs (so every step the window runs is compiled: chunked
   prefill, the fused single-rung tick, the mixed-rung passes, sampling)
   and reads back one generated-token slice of every length the cell's
   traffic will evict;
2. the window: ``--seconds`` of open-loop traffic (``loadgen.OpenLoop``),
   then the drain;
3. ``memory_peak_bytes``, then the server is freed;
4. the comparison with the plain reference (``compare.py``), which
   decides ``correct``;
5. metrics: each of the cell's metrics (``--trace 0``: end-to-end,
   ``--trace 1``: per-layer) is read from the run's record by its own
   reader, ``chipbench/metrics/<name>.py`` (``reader_path``).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

ROOT = Path(__file__).resolve().parent
#: the compared sample: per rung, requests until this many served tokens
SAMPLE_TOKENS = 400
SAMPLE_REQUESTS = 8
#: longest traced stretch of the window, centred in it
TRACE_SECONDS = 10.0

_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
}


class CompileClock:
    """Sums JAX's own compile events while open -- tracing, lowering,
    backend compilation or loading from the persistent cache -- and
    counts lowerings (one per program newly specialised, whether it is
    then compiled or read from the cache).  Copied from the program's
    ``chip_smoke.py``."""

    def __init__(self):
        self.totals = {name: 0.0 for name in _COMPILE_EVENTS.values()}
        self.lowerings = 0
        self.cache_hits = 0

    def _on_duration(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.totals[_COMPILE_EVENTS[event]] += secs
            if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.lowerings += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader_path(name: str) -> Path:
    """The reader of metric ``name``: ``metrics/<name>.py``, or for a
    quantity split by the end-to-end metric it moves in different cells
    (``decode_step_ms.batch``, ``decode_step_ms.chat``) and with no file
    of its own, the reader of the part before the first dot."""
    path = ROOT / "metrics" / f"{name}.py"
    return path if path.exists() else ROOT / "metrics" / f"{name.split('.')[0]}.py"


def workload_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


def _counters(srv) -> dict:
    """The server's registry as ``{"name{labels}": number}`` (histograms
    left out)."""
    flat = {}
    for name, val in srv.metrics_snapshot().items():
        items = val.items() if isinstance(val, dict) else [("", val)]
        for k, v in items:
            if isinstance(v, (int, float)):
                flat[f"{name}{{{k}}}" if k else name] = v
    return flat


def _annotate(obj, attr: str, label: str, prefix: str, undo: list):
    """Wrap ``obj.attr`` (an instance attribute or bound method) in a
    host span named ``prefix + label``; ``undo`` collects restorers."""
    from jax.profiler import TraceAnnotation

    had = attr in vars(obj)
    orig = getattr(obj, attr)

    def wrapped(*a, **kw):
        with TraceAnnotation(prefix + label):
            return orig(*a, **kw)

    setattr(obj, attr, wrapped)
    undo.append(lambda: setattr(obj, attr, orig) if had else delattr(obj, attr))


def _log_passes(srv, log: list, undo: list):
    """Record every decode pass the window dispatches: its rung and the
    attended context (position + 1) of each lane in it, from the host's
    own state at dispatch time."""
    sched, arb = srv.scheduler, srv.arbiter
    names = srv.level_names
    state = {"tick": None, "k": 0}

    def lanes_of(rung_idx=None):
        act = sched.active_mask()
        out = []
        for s in np.nonzero(act)[0]:
            if rung_idx is None or int(arb.idx[s]) == rung_idx:
                out.append(sched.position(int(s)) + 1)
        return out

    for attr in ("_tick_p", "_pool_pass_p"):
        orig = getattr(srv, attr)
        undo.append(lambda attr=attr, orig=orig: setattr(srv, attr, orig))

        def wrapped(*a, _orig=orig, _attr=attr, **kw):
            tick = srv._m_decode_ticks.value()
            if _attr == "_tick_p":
                present = sorted({int(arb.idx[s]) for s in np.nonzero(sched.active_mask())[0]})
                log.append((names[present[0]], lanes_of()))
            else:
                if state["tick"] != tick:
                    state["tick"], state["k"] = tick, 0
                present = sorted({int(arb.idx[s]) for s in np.nonzero(sched.active_mask())[0]})
                ri = present[state["k"]]
                state["k"] += 1
                log.append((names[ri], lanes_of(ri)))
            return _orig(*a, **kw)

        setattr(srv, attr, wrapped)


def warm_up(srv, levels, out_lengths) -> None:
    """Compile every program the window runs, at the cell's shapes only:
    serve three short requests (two rungs in one batch, then one rung
    alone), and read back one generated-token slice of every length the
    traffic can evict (the server's eviction pull compiles per length)."""
    import jax.numpy as jnp
    from repro.runtime.scheduler import Request

    chunk = srv.scfg.resolved_chunk
    prompt = [1 + (i % 97) for i in range(chunk + 3)]
    reqs = [Request(rid=srv.next_rid(), prompt=prompt, max_new=3, level=lv) for lv in levels]
    reqs.append(Request(rid=srv.next_rid(), prompt=prompt[:5], max_new=7, level=levels[-1]))
    srv.serve(reqs)
    buf = srv._gen_buf
    for n in out_lengths:
        np.asarray(buf[0, :n])
    jnp.zeros(()).block_until_ready()


def _device_info():
    import jax

    devs = jax.local_devices()
    peak = None
    for d in devs:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class Cell:
    """A cell's files, loaded: configuration, program binding, reference,
    operation and byte counts, traffic and limits."""

    def __init__(self, workload: str, bench: dict, spec_override=None, traffic_override=None):
        from chipbench import compare

        entry = workload_entry(bench, workload)
        self.workload, self.config = workload, entry["config"]
        self.spec = spec_override or json.loads(
            (ROOT / "configs" / f"{self.config}.json").read_text())
        self.traffic = traffic_override or json.loads(
            (ROOT / "traffic" / f"{entry['traffic']}.json").read_text())
        self.binding = load_module(ROOT / "configs" / f"{self.config}.py",
                                   f"chipbench_cfg_{self.config}")
        self.reference = load_module(ROOT / "configs" / f"{self.config}_reference.py",
                                     f"chipbench_ref_{self.config}")
        self.counts = load_module(ROOT / "configs" / f"{self.config}_flops.py",
                                  f"chipbench_flops_{self.config}")
        self.limits = compare.load_limits(ROOT, workload)
        self.mcfg = self.binding.program_config(self.spec)
        self.scfg = self.binding.serving_config(self.spec)


def build_server(cell: Cell, seed: int, out_lengths, t_start: float, log: Callable):
    """The set-up: weights from the seed, the program's server, warm-up.
    Returns (weights, server)."""
    import jax
    from chipbench import weights as weights_mod
    from repro.models import init_params
    from repro.runtime.serve import ContinuousBatchingServer

    mcfg = cell.mcfg
    with CompileClock() as clock:
        shapes = jax.eval_shape(lambda k: init_params(mcfg, k), jax.random.PRNGKey(0))
        weights = weights_mod.make_weights(shapes, seed)
        t_w = time.perf_counter()
        srv = ContinuousBatchingServer(mcfg, weights, cell.scfg)
        t_srv = time.perf_counter()
        warm_up(srv, sorted(cell.traffic["levels"]), out_lengths)
    log(f"setup: weights {t_w - t_start:.3f}s server {t_srv - t_w:.3f}s "
        f"warm-up {time.perf_counter() - t_srv:.3f}s compile {json.dumps(clock.totals)} "
        f"cache_hits {clock.cache_hits} lowerings {clock.lowerings}")
    return weights, srv


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench: Optional[dict] = None, spec_override: Optional[dict] = None,
             traffic_override: Optional[dict] = None, log: Callable = None) -> dict:
    """One run; returns the result object (the contract's last line).

    ``spec_override`` / ``traffic_override`` replace the cell's files
    (tests at a tiny size)."""
    import jax

    from chipbench import compare, peaks
    from chipbench import trace as trace_mod
    from chipbench.loadgen import OpenLoop, output_lengths, schedule

    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    bench = bench if bench is not None else json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    cell = Cell(workload, bench, spec_override, traffic_override)
    mcfg, scfg, traffic, spec = cell.mcfg, cell.scfg, cell.traffic, cell.spec
    plan = schedule(traffic, seed, seconds, mcfg.vocab)
    log(f"cell {workload}: {cell.config} layers={mcfg.n_layers} d_model={mcfg.d_model} "
        f"slots={scfg.n_slots} max_len={scfg.max_len} requests={len(plan)} seed={seed}")
    weights, srv = build_server(cell, seed, output_lengths(traffic, seconds), t_start, log)

    record = {"seconds": float(seconds), "spec": spec, "counts": cell.counts, "counters": {},
              "passes": [], "trace": None}
    undo: list = []
    tdir = None
    events = {}
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
        t_len = min(TRACE_SECONDS, float(seconds))
        t_a = (float(seconds) - t_len) / 2.0
        prefix = trace_mod.SPAN_PREFIX
        for obj, attr, label in (
                (srv, "_admit", "admit"), (srv, "_finish_slot", "evict"),
                (srv, "_tick_p", "tick_p"), (srv, "_pool_pass_p", "pool_pass_p"),
                (srv, "_finish", "finish"), (srv, "_step_update", "step_update"),
                (srv, "_chunk_admit", "chunk_admit"), (srv.cache_ops, "ensure_rows", "ensure_rows"),
                (srv.cache_ops, "device_tables", "device_tables")):
            _annotate(obj, attr, label, prefix, undo)
        passes: list = []

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # a traced Python call stack slows the host loop
        opts.enable_hlo_proto = False

        def trace_open():
            jax.profiler.start_trace(tdir, profiler_options=opts)
            record["counters"]["trace_open"] = _counters(srv)
            record["trace_t"] = [time.perf_counter(), None]
            _log_passes(srv, passes, undo)

        def trace_close():
            if record.get("trace_t") is None or record["trace_t"][1] is not None:
                return
            record["trace_t"][1] = time.perf_counter()   # before stop_trace writes the file
            record["counters"]["trace_close"] = _counters(srv)
            jax.profiler.stop_trace()
            record["passes"] = list(passes)
            passes.clear()

        events = {t_a: trace_open, t_a + t_len: trace_close}
    record["counters"]["open"] = _counters(srv)
    drain = traffic.get("drain", "all")

    def on_close():
        record["counters"]["close"] = _counters(srv)

    loop = OpenLoop(srv, plan, seconds, drain, events=events, on_close=on_close)
    setup_s = time.perf_counter() - t_start
    with CompileClock() as win_clock:
        served = loop.run()
    if trace:
        trace_close()
    for fn in reversed(undo):
        fn()
    record["counters"]["end"] = _counters(srv)
    record["n_pages"] = {gk: g["alloc"].n_pages for gk, g in srv.cache_ops.groups.items()}
    dev = _device_info()
    t0, t_close = loop.t0, loop.t_close
    attempted = [s for s in served if not s.withdrawn and s.sent is not None]
    failed = [s for s in attempted if s.tokens is None]
    lag = np.percentile([s.sent - t0 - s.planned.due for s in attempted], [50, 95]) \
        if attempted else None
    log(f"window: {len(attempted)} attempted, {len(failed)} unfinished, "
        f"drain {time.perf_counter() - t_close:.3f}s, lowerings in window {win_clock.lowerings}, "
        f"generator lag p50/p95 {lag} s")

    level_names = srv.level_names
    max_len = scfg.max_len
    del srv, loop
    gc.collect()

    # the comparison with the plain reference, on the freed chip
    t_ref = time.perf_counter()
    picks = compare.sample(attempted, level_names, seed, SAMPLE_TOKENS, SAMPLE_REQUESTS)
    numbers = compare.readings(cell.reference, spec, weights, picks, max_len,
                               traffic["output"]["max"])
    correct, checks = compare.judge(numbers, cell.limits, len(failed),
                                    compare.off_rung(attempted, level_names))
    log(f"reference: {time.perf_counter() - t_ref:.3f}s over "
        f"{ {lv: len(r) for lv, r in picks.items()} } requests; {json.dumps(numbers)}")

    record.update({
        "setup_s": setup_s,
        "compiles_in_window": win_clock.lowerings,
        "memory_peak_bytes": dev["memory_peak_bytes"],
        "requests": [{
            "level": s.planned.level, "due": s.planned.due,
            "sent": None if s.sent is None else s.sent - t0,
            "times": [t - t0 for t in s.times], "from_tick": s.from_tick,
            "finished": s.tokens is not None, "withdrawn": s.withdrawn,
        } for s in served if s.sent is not None or s.withdrawn],
    })
    if trace:
        record["peaks"] = peaks.lookup(dev["kind"]) if dev["platform"] == "tpu" else None
        t_r = time.perf_counter()
        try:
            prof = jax.profiler.ProfileData.from_file(trace_mod.find_xplane(tdir))
            red = trace_mod.reduce(prof)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        a, b = record["trace_t"]
        red["window_s"] = b - a
        record["trace"] = red
        log(f"trace: read in {time.perf_counter() - t_r:.3f}s; busy {red['busy_s']:.6f}s "
            f"of {red['window_s']:.6f}s over {red['chips']} chips")
    elif dev["platform"] == "tpu":
        peaks.lookup(dev["kind"])

    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        reader = load_module(reader_path(m["name"]), f"chipbench_metric_{m['name']}")
        value = reader.read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": len(attempted), "failed": len(failed),
              "metrics": metrics, "device": {k: dev[k] for k in ("platform", "kind", "count",
                                                                  "memory_peak_bytes")}}
    if trace:
        red = record["trace"]
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        top = sorted(red["modules"].items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                               "idle_gaps": red["gaps"][:10]}
    result["checks"] = checks
    return result

