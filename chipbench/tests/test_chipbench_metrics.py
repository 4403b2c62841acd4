"""The metric readers on a synthetic run record."""

import json
from pathlib import Path

import pytest

from chipbench import peaks
from chipbench.cell import cell_metrics, load_module, reader_path

ROOT = Path(__file__).resolve().parents[1]
COUNTS = load_module(ROOT / "configs" / "deepseek_7b_flops.py", "m_flops_deepseek_7b")
SPEC = {"hidden_size": 4096, "intermediate_size": 11008, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_hidden_layers": 4, "vocab_size": 102400}
PEAKS = json.loads((ROOT / "peaks.json").read_text())["TPU v5 lite"]


def read(name, rec):
    return load_module(reader_path(name), f"m_{name}").read(rec)


def record():
    # 20 requests due 0.5 s apart in a 10 s window; request i sent 0.01*i
    # late, first token 0.1*(i+1) after due, then tokens every 0.02 s
    reqs = []
    for i in range(20):
        due = 0.5 * i
        first = due + 0.1 * (i + 1)
        times = [first + 0.02 * k for k in range(5)]
        reqs.append({"level": "f32" if i % 4 == 0 else "q16_16", "due": due,
                     "sent": due + 0.01 * i, "times": times,
                     "from_tick": [False] + [True] * 4, "finished": True, "withdrawn": False})
    reqs.append({"level": "q16_16", "due": 0.0, "sent": None, "times": [], "from_tick": [],
                 "finished": False, "withdrawn": True})
    counters = {
        "open": {"decode_ticks_total": 100, "level_passes_total{level=f32}": 50,
                 "level_passes_total{level=q16_16}": 100, "prefill_chunks_total": 10},
        "close": {"decode_ticks_total": 300, "level_passes_total{level=f32}": 150,
                  "level_passes_total{level=q16_16}": 300, "prefill_chunks_total": 90},
        "trace_open": {"decode_ticks_total": 150, "prefill_chunks_total": 20},
        "trace_close": {"decode_ticks_total": 250, "prefill_chunks_total": 60},
        "end": {"pages_high_water{group=L1536}": 300},
    }
    return {"seconds": 10.0, "setup_s": 42.5, "compiles_in_window": 0, "requests": reqs,
            "counters": counters, "n_pages": {"L1536": 1153}, "spec": SPEC, "counts": COUNTS, "peaks": PEAKS,
            "memory_peak_bytes": 8 * 1024 ** 3,
            "passes": [("f32", [100, 200]), ("q16_16", [300])] * 100,
            "trace": {"chips": 1, "busy_s": 7.5, "window_s": 10.0, "gaps": [],
                      "modules": {"jit_tick_p": 1.0, "jit_pool_pass_p": 2.5, "jit_finish": 0.3,
                                  "jit_step_update": 0.2, "jit_chunk_admit": 2.0,
                                  "jit_other": 9.0}}}


def test_end_to_end_readers():
    rec = record()
    assert read("setup_s", rec) == 42.5
    # 4 gaps of 0.02 per request, all before the window end except the
    # last requests' (due 9.5, first token 11.5 -> outside)
    assert read("itl_p50_s", rec) == pytest.approx(0.02)
    assert read("itl_p95_s", rec) == pytest.approx(0.02)
    inside = sum(1 for r in rec["requests"] for t in r["times"] if t <= 10.0)
    assert read("tokens_per_s", rec) == pytest.approx(inside / 10.0)


def test_counter_readers():
    rec = record()
    assert read("compiles_in_window", rec) == 0
    assert read("level_passes_per_tick", rec) == pytest.approx(300 / 200)
    inside_tick = sum(1 for r in rec["requests"] for t, k in zip(r["times"], r["from_tick"])
                      if k and t <= 10.0)
    assert read("lanes_per_tick", rec) == pytest.approx(inside_tick / 200)
    assert read("kv_pages_peak_share", rec) == pytest.approx(100 * 300 / 1152)


def test_trace_readers():
    rec = record()
    decode_s = 1.0 + 2.5 + 0.3 + 0.2
    assert read("decode_step_ms", rec) == pytest.approx(1000 * decode_s / 100)
    assert read("prefill_chunk_ms", rec) == pytest.approx(1000 * 2.0 / 40)
    assert read("device_idle_share", rec) == pytest.approx(25.0)
    assert read("peak_hbm_share", rec) == pytest.approx(50.0)
    least = 0.0
    ops = 0.0
    for rung, lanes in rec["passes"]:
        f, b = COUNTS.decode_pass(SPEC, rung, lanes)
        least += peaks.least_time(f, b, rung, PEAKS)[0]
        ops += f
    assert read("decode_step_roofline", rec) == pytest.approx(100 * least / decode_s)
    assert read("decode_mfu", rec) == pytest.approx(100 * ops / (decode_s * 197e12))
    assert 0 < read("decode_step_roofline", rec) <= 100
    # a quantity split by the end-to-end metric it moves reads alike in both cells
    for name in ("decode_step_ms", "decode_step_roofline", "decode_mfu", "device_idle_share"):
        assert read(f"{name}.chat", rec) == read(f"{name}.batch", rec) == read(name, rec)


def test_readers_without_a_trace_return_nothing():
    rec = dict(record(), trace=None, passes=[], peaks=None)
    for name in ("decode_step_ms", "prefill_chunk_ms", "decode_step_roofline", "decode_mfu",
                 "device_idle_share", "peak_hbm_share"):
        assert read(name, rec) is None


def test_every_benchmark_metric_has_a_reader_and_cells_report_enough():
    bench = json.loads((ROOT.parent / "BENCHMARK.json").read_text())
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert reader_path(m["name"]).exists(), m["name"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        names = {m["name"] for m in cell_metrics(bench, w["name"], False)}
        assert "setup_s" in names and len(names) >= 2
        layer = cell_metrics(bench, w["name"], True)
        assert layer
        for m in layer:
            assert m["moves"] in names, (w["name"], m["name"])
        assert names <= e2e
