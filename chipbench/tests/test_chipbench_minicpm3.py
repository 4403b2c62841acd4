"""The minicpm3_4b configuration's benchmark files: operation and byte
counts against a hand count, the latent-rows reader on a synthetic
record, and the comparison that decides ``correct`` at a tiny size on the
CPU, as ``test_chipbench_faults.py`` runs it for deepseek_7b: a sound run
of ``mcpm3-chat-mixed`` is correct, each rung's control is not."""

import json
import time
from pathlib import Path

import pytest

from chipbench import cell as cell_mod
from chipbench import compare
from chipbench.cell import load_module, reader_path

ROOT = Path(__file__).resolve().parents[1]
WORKLOAD = "mcpm3-chat-mixed"
SEED = 2**31 + 1515
SECONDS = 1.0
FULL = json.loads((ROOT / "configs" / "minicpm3_4b.json").read_text())
flops = load_module(ROOT / "configs" / "minicpm3_4b_flops.py", "chipbench_flops_minicpm3_test")


def test_decode_flops_hand_count():
    # per layer: wq_a 2560 x 768 = 1,966,080; wq_b 768 x 40 x 96 = 2,949,120;
    # wkv_a 2560 x 288 = 737,280; wo 2560 x 2560 = 6,553,600; MLP 3 x 2560 x
    # 6400 = 49,152,000: 61,358,080 (+ wkv_b 256 x 40 x 128 = 1,310,720)
    proj = 1_966_080 + 2_949_120 + 737_280 + 6_553_600 + 49_152_000
    assert flops.shapes(FULL)["proj_params"] == proj == 61_358_080
    ctx = 1000
    absorb = 2 * 40 * 64 * 256 + 2 * 40 * 256 * 64          # = 2 x wkv_b's parameters
    assert absorb == 2 * 1_310_720
    attn = 2 * 40 * 288 * ctx + 2 * 40 * 256 * ctx
    want = 20 * (2 * proj + absorb + attn) + 2 * 2560 * 73448
    assert flops.decode_flops_per_token(FULL, ctx) == want


def test_decode_pass_bytes_by_rung():
    proj, wkv_b, head = 61_358_080, 1_310_720, 2560 * 73448
    lanes = [10, 20]
    f, b = flops.decode_pass(FULL, "f32", lanes)
    assert b == 20 * (proj * 4 + wkv_b * 4) + head * 4 + 30 * 20 * 288 * 4
    assert f == sum(flops.decode_flops_per_token(FULL, c) for c in lanes)
    _, b16 = flops.decode_pass(FULL, "q16_16", lanes)
    assert b16 == 20 * (proj * 1 + wkv_b * 4) + head * 2 + 30 * 20 * 288 * 4


def test_mla_live_rows_share_reader():
    read = load_module(reader_path("mla_live_rows_share.mcpm3"), "m_mla_live").read
    live, comp = "attn_rows_total{kind=mla,rows=live}", "attn_rows_total{kind=mla,rows=computed}"
    rec = {"counters": {"trace_open": {live: 100, comp: 1000},
                        "trace_close": {live: 400, comp: 5000}}}
    assert read(rec) == pytest.approx(100 * 300 / 4000)
    # a program without the counter, or a stretch with no pass: no reading
    assert read({"counters": {"trace_open": {}, "trace_close": {}}}) is None
    assert read({"counters": {"open": {}, "close": {}}}) is None
    rec["counters"]["trace_close"] = dict(rec["counters"]["trace_open"])
    assert read(rec) is None


def tiny_spec():
    spec = json.loads(json.dumps(FULL))
    spec.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                num_key_value_heads=4, num_hidden_layers=2, vocab_size=256,
                q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
                v_head_dim=8, dim_model_base=6.4)
    f = spec["rope_scaling"]["short_factor"]
    spec["rope_scaling"]["short_factor"] = [f[i * len(f) // 4] for i in range(4)]
    spec["serving"] = dict(spec["serving"], n_slots=4, max_len=128)
    return spec


def tiny_traffic():
    t = json.loads((ROOT / "traffic" / f"{WORKLOAD}.json").read_text())
    t.update(rate_rps=16.0, prompt=dict(t["prompt"], median=24, min=8, max=48),
             output=dict(t["output"], median=8, min=4, max=16))
    return t


@pytest.fixture(scope="module")
def built():
    from chipbench.loadgen import output_lengths

    c = cell_mod.Cell(WORKLOAD, json.loads((ROOT.parent / "BENCHMARK.json").read_text()),
                      tiny_spec(), tiny_traffic())
    return c, cell_mod.build_server(c, SEED, output_lengths(c.traffic, SECONDS),
                                    time.perf_counter(), lambda m: None)


def test_sound_run_is_correct(built, monkeypatch):
    c, (weights, srv) = built
    monkeypatch.setattr(cell_mod, "build_server", lambda *a, **k: (weights, srv))
    r = cell_mod.run_cell(WORKLOAD, SEED, SECONDS, False, t_start=time.perf_counter(),
                          spec_override=c.spec, traffic_override=c.traffic, log=lambda m: None)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(c.limits) <= set(r["checks"])


def test_control_is_not_correct(built):
    from chipbench.control import CONTROL, rung_limits
    from chipbench.loadgen import OpenLoop, schedule

    c, (weights, srv) = built
    plan = schedule(c.traffic, SEED + 1, SECONDS, c.mcfg.vocab)
    served = OpenLoop(srv, plan, SECONDS, "all").run()
    picks = compare.sample(served, srv.level_names, SEED, cell_mod.SAMPLE_TOKENS,
                           cell_mod.SAMPLE_REQUESTS)
    rows = c.traffic["output"]["max"]
    sound = compare.readings(c.reference, c.spec, weights, picks, c.scfg.max_len, rows)
    assert compare.judge(sound, c.limits, 0)[0], sound
    for lv, reqs in picks.items():
        ctl = compare.readings(c.reference, c.spec, weights, {lv: reqs}, c.scfg.max_len, rows,
                               control=CONTROL[lv])
        own = rung_limits(c.limits, lv)
        assert own, lv
        ok, checks = compare.judge(ctl, own, 0)
        assert not ok, (lv, checks)
