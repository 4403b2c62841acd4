"""MiniCPM3 as served agrees with its plain reference, and needs all of
its published mathematics to do so.

The program's smoke MiniCPM3 (smoke widths, the LongRoPE factors cut to
the smoke rope width, the published scale constants) serves two
requests through ``ContinuousBatchingServer`` with the paged pool: one
at f32 and one at q16_16, so every tick runs both rungs' pool passes and
samples through ``_finish``, whose logits are recorded, as are the last
chunk's logits of each admission (chunked prefill).  Each recorded row is
compared with the plain float32 reference
(``chipbench/configs/minicpm3_4b_reference.py``, expanded latent
attention) over the request's prompt and served tokens, in units of the
reference row's spread (standard deviation over the vocabulary).

Tolerances, in spreads: f32 ``F32_TOL``, the served path's f32 against
the reference's f32 at ``highest``, which differ only in accumulation
order and in the absorbed against the expanded attention (reading
1.0e-6: 100x room); q16_16 ``Q16_TOL``, 8-bit weights and activations
with per-tensor activation exponents (reading 0.071).  A reference that
leaves out one scaling, or the factors, reads 0.50 (factors) to 3.6
(embedding scale) against the served f32 rows: the test asks for more
than 1000 x ``F32_TOL``.
"""

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import weights as weights_mod
from chipbench.cell import load_module
from repro.configs import get_config, smoke
from repro.core.cordic import rope_inv_freq_q64
from repro.models import init_params
from repro.models.layers import rope_tables
from repro.runtime.config import ServingConfig
from repro.runtime.scheduler import Request
from repro.runtime.serve import ContinuousBatchingServer

REF = load_module(Path(__file__).resolve().parents[1] / "chipbench" / "configs"
                  / "minicpm3_4b_reference.py", "minicpm3_ref_test")
CFG = smoke("minicpm3_4b")
MAX_LEN = 64
F32_TOL = 1e-4
Q16_TOL = 0.25
PUBLISHED_LAYERS = 62


def spec_of(cfg):
    """The reference's view of the smoke model, in the published keys."""
    m = cfg.mla
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": m.kv_lora_rank, "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_base,
        "max_position_embeddings": 32768,
        "rope_scaling": {"original_max_position_embeddings": 32768,
                         "short_factor": list(cfg.rope_factors)},
        "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": cfg.d_model / 10,
        "published": {"num_hidden_layers": PUBLISHED_LAYERS},
    }


SPEC = spec_of(CFG)
#: each of the published scalings, and the factors, taken out of the reference alone
WITHOUT = {
    "scale_emb": {"scale_emb": 1},
    "residual_scale": {"scale_depth": math.sqrt(PUBLISHED_LAYERS)},
    "head_divisor": {"dim_model_base": CFG.d_model},
    "rope_factors": {"rope_scaling": {"original_max_position_embeddings": 32768,
                                      "short_factor": [1.0] * len(CFG.rope_factors)}},
}


def test_smoke_config_carries_the_published_constants():
    assert CFG.scale_emb == 12.0
    assert CFG.residual_scale == pytest.approx(1.4 / math.sqrt(62))
    assert CFG.head_divisor == pytest.approx(10.0)
    assert len(CFG.rope_factors) == CFG.rope_dim // 2
    full = get_config("minicpm3_4b").rope_factors
    assert CFG.rope_factors[0] == full[0] and CFG.rope_factors[-1] in full


@pytest.fixture(scope="module")
def served():
    """Weights, the two served requests (prompt, tokens, rung) and the
    recorded logits rows of each request, in order of position, and the
    server's registry afterwards."""
    shapes = jax.eval_shape(lambda k: init_params(CFG, k), jax.random.PRNGKey(0))
    weights = weights_mod.make_weights(shapes, 2**31 + 15)
    scfg = ServingConfig(n_slots=2, max_len=MAX_LEN, page_size=8,
                         prefill_chunk=8, eos_id=None, temperature=0.0,
                         default_level="f32", seed=0)
    srv = ContinuousBatchingServer(CFG, weights, scfg)
    rows, slot_of = {0: [], 1: []}, {}
    admit, finish = srv._admit, srv._finish
    admitting = []

    def rec_admit(slot, req):
        slot_of[req.rid] = slot
        admitting.append(slot)
        admit(slot, req)
        admitting.pop()

    def rec_finish(logits, key):
        # (1, V) from an admission's last prefill chunk; (B, V) from a tick
        host = np.asarray(logits)
        for s, row in zip(admitting or sorted(rows), host):
            rows[s].append(row)
        return finish(logits, key)

    srv._admit, srv._finish = rec_admit, rec_finish
    rng = np.random.default_rng(3)
    reqs = [Request(rid=0, prompt=[int(t) for t in rng.integers(0, CFG.vocab, 21)],
                    max_new=9, level="f32"),
            Request(rid=1, prompt=[int(t) for t in rng.integers(0, CFG.vocab, 13)],
                    max_new=9, level="q16_16")]
    out = srv.serve(reqs)
    got = []
    for r in reqs:
        toks = out[r.rid].tokens[len(r.prompt):]
        got.append((r.prompt, toks, r.level, np.stack(rows[slot_of[r.rid]][: len(toks)])))
    return weights, got, srv.metrics_snapshot()


def reference_rows(weights, prompt, toks, spec):
    seq = list(prompt) + list(toks)
    return np.asarray(REF.logits(weights, np.asarray(seq[:-1], np.int32),
                                 len(prompt) - 1 + np.arange(len(toks)), spec))


def distance(weights, prompt, toks, served_rows, spec):
    """Widest distance of a served logits row from the reference's, in
    spreads of the reference row."""
    ref = reference_rows(weights, prompt, toks, spec)
    return float(np.max(np.abs(served_rows - ref) / ref.std(axis=-1, keepdims=True)))


def test_served_logits_agree_with_the_reference(served):
    weights, got, _ = served
    for prompt, toks, level, rows in got:
        assert len(toks) == 9 and rows.shape == (9, CFG.vocab)
        d = distance(weights, prompt, toks, rows, SPEC)
        assert d < (F32_TOL if level == "f32" else Q16_TOL), (level, d)


@pytest.mark.parametrize("left_out", sorted(WITHOUT))
def test_reference_without_a_scaling_disagrees(served, left_out):
    weights, got, _ = served
    spec = dict(SPEC, **WITHOUT[left_out])
    prompt, toks, level, rows = got[0]
    assert level == "f32"
    assert distance(weights, prompt, toks, rows, spec) > 1000 * F32_TOL


@pytest.mark.parametrize("mode", ["precise", "fast"])
def test_unit_factors_give_the_old_tables_bit_for_bit(mode):
    pos = jnp.arange(0, 3000, 7, dtype=jnp.int32)
    old = rope_tables(pos, 32, 10000.0, mode)
    new = rope_tables(pos, 32, 10000.0, mode, (1.0,) * 16)
    for a, b in zip(old, new):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(rope_inv_freq_q64(32), rope_inv_freq_q64(32, factors=[1.0] * 16)):
        np.testing.assert_array_equal(a, b)


def test_fast_and_precise_tables_with_factors_agree():
    """The CORDIC tables with the published factors against the f32 ones
    (positions of a served context) and against float64 at the longest
    published context, within the CORDIC rope test's 1e-3."""
    factors = get_config("minicpm3_4b").rope_factors
    pos = jnp.arange(0, 2048, 3, dtype=jnp.int32)
    fs, fc = rope_tables(pos, 32, 10000.0, "fast", factors)
    ps, pc = rope_tables(pos, 32, 10000.0, "precise", factors)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(ps), atol=1e-3)
    np.testing.assert_allclose(np.asarray(fc), np.asarray(pc), atol=1e-3)
    far = np.array([32766, 32767], np.int32)
    fs, fc = rope_tables(jnp.asarray(far), 32, 10000.0, "fast", factors)
    for i, p in enumerate(far):
        for j in range(16):
            angle = math.fmod(int(p) * 10000.0 ** (-2.0 * j / 32) / factors[j], 2 * math.pi)
            assert float(fs[i, j]) == pytest.approx(math.sin(angle), abs=1e-3)
            assert float(fc[i, j]) == pytest.approx(math.cos(angle), abs=1e-3)


def test_latent_rows_counter(served):
    """Every tick ran both rungs' passes; each pass computes all lanes x
    ``MAX_LEN`` rows in each MLA layer and its member lane attends
    position + 1 of them: at tick t a request decodes the token at
    position len(prompt) + t."""
    _, got, snap = served
    rows = snap["attn_rows_total"]
    ticks = len(got[0][1]) - 1              # the first token comes from the prefill
    layers = CFG.n_layers
    assert rows["kind=mla,rows=computed"] == layers * 2 * ticks * 2 * MAX_LEN
    live = sum(len(p) + t + 1 for p, _, _, _ in got for t in range(ticks))
    assert rows["kind=mla,rows=live"] == layers * live


#: train_loss runs its matmuls in bf16 (mode "precise"): its mean cross-entropy
#: reads 2.2e-4 from the reference's; leaving a scaling out of the reference
#: moves the reference's by 0.013 (residual scale) to 0.35 (head divisor)
TRAIN_CE_TOL = 1e-3


def _ce(weights, toks, spec=None):
    """Mean next-token cross-entropy over ``toks`` (S,): ``train_loss``'s
    with ``spec`` None, else the reference's under ``spec``."""
    from repro.models import train_loss

    if spec is None:
        batch = {"tokens": jnp.asarray(toks[None, :-1]), "labels": jnp.asarray(toks[None, 1:])}
        return float(train_loss(weights, batch, CFG, mode="precise", z_coef=0.0)[1]["ce"])
    n = len(toks) - 1
    lg = np.asarray(REF.logits(weights, toks[:-1], np.arange(n), spec), np.float64)
    top = lg.max(-1)
    lse = np.log(np.exp(lg - top[:, None]).sum(-1)) + top
    return float(np.mean(lse - lg[np.arange(n), toks[1:]]))


def test_train_loss_applies_the_scalings(served):
    weights = served[0]
    toks = np.random.default_rng(5).integers(0, CFG.vocab, 33).astype(np.int32)
    ce = _ce(weights, toks)
    assert abs(ce - _ce(weights, toks, SPEC)) < TRAIN_CE_TOL
    for left_out in ("scale_emb", "residual_scale", "head_divisor"):
        other = _ce(weights, toks, dict(SPEC, **WITHOUT[left_out]))
        assert abs(ce - other) > 10 * TRAIN_CE_TOL, left_out
