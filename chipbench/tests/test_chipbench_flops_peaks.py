"""Operation and byte counts against hand counts; the peaks table."""

from pathlib import Path

import pytest

from chipbench import peaks
from chipbench.cell import load_module

flops = load_module(Path(__file__).resolve().parents[1] / "configs" / "deepseek_7b_flops.py",
                    "chipbench_flops_deepseek_7b_test")

DS7B = {"hidden_size": 4096, "intermediate_size": 11008, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_hidden_layers": 4, "vocab_size": 102400}


def test_decode_flops_hand_count():
    # per layer: q,k,v,o 4 x 4096^2 = 67,108,864; gate, up, down 3 x 4096 x 11008
    # = 135,266,304; total 202,375,168 parameters.  Head 4096 x 102400.
    proj = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert proj == 202_375_168
    ctx = 1000
    attn = 4 * 32 * 128 * ctx
    want = 4 * (2 * proj + attn) + 2 * 4096 * 102400
    assert flops.decode_flops_per_token(DS7B, ctx) == want


def test_decode_pass_bytes_by_rung():
    proj, head = 202_375_168, 4096 * 102400
    kv_row = 2 * 32 * 128 * 4          # k and v, f32, one layer
    lanes = [10, 20]
    f, b = flops.decode_pass(DS7B, "f32", lanes)
    assert b == 4 * proj * 4 + head * 4 + 30 * 4 * kv_row
    assert f == sum(flops.decode_flops_per_token(DS7B, c) for c in lanes)
    _, b16 = flops.decode_pass(DS7B, "q16_16", lanes)
    assert b16 == 4 * proj * 1 + head * 2 + 30 * 4 * kv_row


def test_least_time_picks_the_bound():
    pk = peaks.lookup("TPU v5 lite")
    t, bound = peaks.least_time(1e9, 819e9, "f32", pk)
    assert bound == "bytes" and t == pytest.approx(1.0)
    t, bound = peaks.least_time(393e12, 1.0, "q16_16", pk)
    assert bound == "compute" and t == pytest.approx(1.0)


def test_peaks_known_and_unknown():
    pk = peaks.lookup("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        peaks.lookup("TPU v9 imaginary")
