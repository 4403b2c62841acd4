"""Fused FAST-path SwiGLU (kernels/fused_mlp) + QuantizedWeightCache.

Covers the PR-3 acceptance contract:

* the Pallas kernel matches the NumPy-int64 oracle on the shared body
  (integer intermediates bit-exact, float epilogue at f32 rounding);
* the fused path tracks the unfused ``dot_fast_int8`` + ``psilu``
  composition and the f32 reference within quantization tolerance;
* ``dot_fast_int8`` with a pre-quantized weight operand is bit-exact
  vs. the per-call-quantization path, and still differentiable (STE);
* QuantizedWeightCache: quantize-once counting, coherence across
  ``set_level`` / ``engine.at``, barrier-mediated invalidation;
* the decode step with attached weights performs ZERO weight
  quantizations (counting hook on ``quantize_pow2``);
* vectorized server sampling: greedy unchanged, EOS trimming,
  temperature path.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _reference import stepwise
from repro.core import quantization
from repro.core.quantization import QuantizedWeightCache, quantize_pow2
from repro.kernels.fused_mlp.fused_mlp import fused_swiglu_kernel_call
from repro.kernels.fused_mlp.ops import fused_swiglu, fused_swiglu_parts, fused_swiglu_xla
from repro.kernels.fused_mlp.ref import fused_swiglu_ref
from repro.models.layers import (
    attach_quantized_weights,
    dot_fast_int8,
    psilu,
    swiglu_mlp,
)


def rand_int8(rng, shape):
    return rng.integers(-127, 128, size=shape, dtype=np.int8)


SHAPES = [
    (8, 128, 128),
    (16, 256, 384),
    (100, 200, 300),    # non-multiples: exercises padding
    (1, 128, 128),
    (257, 129, 511),    # awkward primes
]


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape", SHAPES)
def test_fused_kernel_matches_oracle(rng, shape):
    M, K, F = shape
    x = rand_int8(rng, (M, K))
    wg = rand_int8(rng, (K, F))
    wu = rand_int8(rng, (K, F))
    ea = np.int32(-9)
    eg = rng.integers(-12, -5, size=(F,), dtype=np.int32)
    eu = rng.integers(-12, -5, size=(F,), dtype=np.int32)

    got = np.asarray(
        fused_swiglu_kernel_call(x, wg, wu, ea, eg, eu, bm=128, bn=128, bk=128)
    )
    want, gate_ref, sig_ref = fused_swiglu_ref(x, wg, wu, ea, eg, eu, return_parts=True)

    # shared-body integer contract: BIT-exact (XLA form == kernel == oracle)
    out_x, gate_x, sig_x = (np.asarray(v) for v in fused_swiglu_parts(x, wg, wu, ea, eg, eu))
    np.testing.assert_array_equal(gate_x, gate_ref)
    np.testing.assert_array_equal(sig_x, sig_ref)
    np.testing.assert_array_equal(out_x, got)  # kernel == XLA form, bitwise

    # float epilogue: one f32 rounding event vs the float64 oracle
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=3e-6 * max(scale, 1.0), rtol=3e-6)


def test_fused_kernel_block_sweep(rng):
    M, K, F = 300, 700, 260
    x = rand_int8(rng, (M, K))
    wg = rand_int8(rng, (K, F))
    wu = rand_int8(rng, (K, F))
    ea = np.int32(-8)
    eg = np.full((F,), -9, np.int32)
    eu = np.full((F,), -10, np.int32)
    want = fused_swiglu_ref(x, wg, wu, ea, eg, eu)
    for bm, bn, bk in [(128, 128, 128), (256, 128, 256), (512, 512, 512)]:
        got = np.asarray(
            fused_swiglu_kernel_call(x, wg, wu, ea, eg, eu, bm=bm, bn=bn, bk=bk)
        )
        np.testing.assert_allclose(got, want, rtol=3e-6, atol=3e-6 * np.abs(want).max())


def test_fused_float_boundary_vs_unfused_composition(rng):
    """silu(x@Wg) * (x@Wu): fused single-correction path vs the
    three-dispatch composition vs the f32 reference."""
    M, K, F = 32, 256, 192
    x = rng.uniform(-1, 1, (M, K)).astype(np.float32)
    wg = (rng.uniform(-1, 1, (K, F)) * 0.1).astype(np.float32)
    wu = (rng.uniform(-1, 1, (K, F)) * 0.1).astype(np.float32)

    fused = np.asarray(fused_swiglu(jnp.asarray(x), jnp.asarray(wg), jnp.asarray(wu)))

    gate = dot_fast_int8(jnp.asarray(x), jnp.asarray(wg))
    up = dot_fast_int8(jnp.asarray(x), jnp.asarray(wu))
    unfused = np.asarray(psilu(gate.astype(jnp.float32), "fast") * up)

    ref = jax.nn.silu(x.astype(np.float64) @ wg) * (x.astype(np.float64) @ wu)
    ref = np.asarray(ref)
    scale = np.abs(ref).max()

    err_fused = np.abs(fused - ref).max()
    err_unfused = np.abs(unfused - ref).max()
    # both sit on the same int8 quantization grid; the fused path must
    # not be worse than ~the composition (it removes rounding events)
    assert err_fused < 0.05 * scale + 1e-3, (err_fused, scale)
    assert err_fused < 2.0 * err_unfused + 1e-4, (err_fused, err_unfused)


# ---------------------------------------------------------------------------
# dot_fast_int8 with pre-quantized weights (XLA FAST path satellite)
# ---------------------------------------------------------------------------


def test_dot_fast_cached_bit_exact(rng):
    x = rng.uniform(-2, 2, (16, 96)).astype(np.float32)
    w = rng.uniform(-1, 1, (96, 64)).astype(np.float32)
    wq = quantize_pow2(w, bits=8, axis=1)
    base = np.asarray(dot_fast_int8(jnp.asarray(x), jnp.asarray(w)))
    cached = np.asarray(dot_fast_int8(jnp.asarray(x), jnp.asarray(w), wq=wq))
    as_dict = np.asarray(
        dot_fast_int8(jnp.asarray(x), jnp.asarray(w), wq={"q": wq.q, "exp": wq.exp})
    )
    np.testing.assert_array_equal(base, cached)
    np.testing.assert_array_equal(base, as_dict)


def test_dot_fast_cached_gradient(rng):
    """The cached forward keeps the STE backward of the uncached path."""
    x = rng.uniform(-1, 1, (8, 32)).astype(np.float32)
    w = rng.uniform(-1, 1, (32, 16)).astype(np.float32)
    wq = quantize_pow2(w, bits=8, axis=1)

    def loss_cached(x, w):
        return jnp.sum(dot_fast_int8(x, w, wq=wq) ** 2)

    def loss_plain(x, w):
        return jnp.sum(dot_fast_int8(x, w) ** 2)

    gx_c, gw_c = jax.grad(loss_cached, argnums=(0, 1))(x, w)
    gx_p, gw_p = jax.grad(loss_plain, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx_c), np.asarray(gx_p), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gw_c), np.asarray(gw_p), rtol=1e-6)


# ---------------------------------------------------------------------------
# QuantizedWeightCache semantics
# ---------------------------------------------------------------------------


def test_cache_quantizes_once(rng):
    w = jnp.asarray(rng.uniform(-1, 1, (32, 48)), jnp.float32)
    cache = QuantizedWeightCache()
    a = cache.get("mlp/w_gate", w, axis=1)
    b = cache.get("mlp/w_gate", w, axis=1)
    assert cache.quantize_calls == 1 and cache.hits == 1
    assert a.q is b.q
    # a different level is a different entry
    cache.get("mlp/w_gate", w, level="q8_8", axis=1)
    assert cache.quantize_calls == 2
    # bit-identical to direct quantization
    direct = quantize_pow2(w, bits=8, axis=1)
    np.testing.assert_array_equal(np.asarray(a.q), np.asarray(direct.q))


def test_cache_coherent_across_level_switches(rng):
    """set_level / scoped engine.at never drop entries (they are
    per-level immutable); only barrier-mediated invalidation clears."""
    from repro.core.precision import MathEngine

    eng = MathEngine("f32")
    w = jnp.asarray(rng.uniform(-1, 1, (16, 24)), jnp.float32)
    eng.weight_cache.get("blk/w_up", w, level="q16_16", axis=1)
    assert len(eng.weight_cache) == 1

    eng.set_level("q16_16")
    eng.set_level("f32")
    with eng.at("q8_24"):
        assert len(eng.weight_cache) == 1   # scoping does not invalidate
    assert len(eng.weight_cache) == 1
    assert eng.weight_cache.quantize_calls == 1

    n_events = len(eng._barrier.events)
    lat = eng.invalidate_weights()
    assert lat >= 0.0
    assert len(eng.weight_cache) == 0
    assert len(eng._barrier.events) == n_events + 1  # went through the barrier

    # named invalidation only drops that param (all its levels)
    eng.weight_cache.get("a/w", w, level="q16_16", axis=1)
    eng.weight_cache.get("a/w", w, level="q8_8", axis=1)
    eng.weight_cache.get("b/w", w, level="q16_16", axis=1)
    eng.invalidate_weights("a/w")
    assert "a/w" not in eng.weight_cache
    assert "b/w" in eng.weight_cache


def test_attach_quantized_weights_swiglu(rng):
    """swiglu_mlp with attached weights = fused path; tracks both the
    unfused FAST path and the precise path within quantization error."""
    d, f, M = 64, 192, 24
    params = {
        "norm": jnp.zeros((d,)),
        "w_gate": jnp.asarray(rng.uniform(-1, 1, (d, f)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.uniform(-1, 1, (d, f)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.uniform(-1, 1, (f, d)) * 0.1, jnp.float32),
    }
    x = jnp.asarray(rng.uniform(-1, 1, (2, M, d)), jnp.float32)

    cache = QuantizedWeightCache()
    qparams = attach_quantized_weights(params, cache)
    assert {"w_gate_q", "w_up_q", "w_down_q"} <= set(qparams)
    assert cache.quantize_calls == 3

    fused = np.asarray(swiglu_mlp(qparams, x, "fast"), np.float32)
    unfused = np.asarray(swiglu_mlp(params, x, "fast"), np.float32)
    precise = np.asarray(swiglu_mlp(params, x, "precise"), np.float32)
    scale = np.abs(precise).max()
    assert np.abs(fused - precise).max() < 0.1 * scale + 1e-3
    assert np.abs(fused - unfused).max() < 0.1 * scale + 1e-3


def test_attach_stacked_and_moe_shapes(rng):
    """Exponent axes follow 'everything but the contraction axis' so
    scanned slices broadcast: (P,d,f) -> (P,1,f); (P,E,d,f) -> (P,E,1,f)."""
    cache = QuantizedWeightCache()
    params = {
        "w_gate": jnp.asarray(rng.uniform(-1, 1, (3, 8, 16)), jnp.float32),
        "nested": {"w_down": jnp.asarray(rng.uniform(-1, 1, (3, 2, 16, 8)), jnp.float32)},
    }
    q = attach_quantized_weights(params, cache)
    assert q["w_gate_q"]["exp"].shape == (3, 1, 16)
    assert q["nested"]["w_down_q"]["exp"].shape == (3, 2, 1, 8)
    # per-(stack, channel) exponents equal slicewise 2-D quantization
    sl = quantize_pow2(params["w_gate"][1], bits=8, axis=1)
    np.testing.assert_array_equal(np.asarray(q["w_gate_q"]["q"][1]), np.asarray(sl.q))


# ---------------------------------------------------------------------------
# MoE fused expert path
# ---------------------------------------------------------------------------


def test_moe_fused_expert_path(rng):
    from repro.configs.mixtral_8x22b import CONFIG
    from repro.models.config import smoke_config
    from repro.models.layers import init_from_specs
    from repro.models.moe import moe_forward, moe_specs

    cfg = smoke_config(CONFIG)
    params = init_from_specs(moe_specs(cfg), jax.random.PRNGKey(0))
    x = jnp.asarray(rng.uniform(-1, 1, (2, 16, cfg.d_model)), jnp.float32)

    qparams = attach_quantized_weights(params, QuantizedWeightCache())
    fused, aux_f = moe_forward(qparams, x, cfg, "fast")
    unfused, aux_u = moe_forward(params, x, cfg, "fast")
    precise, _ = moe_forward(params, x, cfg, "precise")

    f, u, p = (np.asarray(v, np.float32) for v in (fused, unfused, precise))
    scale = max(np.abs(p).max(), 1e-6)
    assert np.abs(f - p).max() < 0.15 * scale + 1e-3
    assert np.abs(f - u).max() < 0.15 * scale + 1e-3
    np.testing.assert_allclose(np.asarray(aux_f), np.asarray(aux_u), rtol=1e-5)


# ---------------------------------------------------------------------------
# decode: zero weight quantizations (the counting hook)
# ---------------------------------------------------------------------------


def _count_quantize_calls(monkeypatch):
    calls = {"weight": 0, "act": 0}
    orig = quantization.quantize_pow2

    def counting(x, bits=8, axis=None):
        calls["weight" if axis is not None else "act"] += 1
        return orig(x, bits=bits, axis=axis)

    monkeypatch.setattr(quantization, "quantize_pow2", counting)
    return calls


def test_decode_step_no_weight_requant(rng, monkeypatch):
    """The FAST decode graph with attached weights contains ZERO weight
    quantizations — asserted by counting quantize_pow2(axis != None)
    calls while tracing a fresh decode step.  The unfused graph
    requantizes every projection (the regression this PR removes)."""
    from repro.configs.gemma2_2b import CONFIG
    from repro.models import decode_step, init_caches, init_params, prefill_step
    from repro.models.config import smoke_config

    cfg = smoke_config(CONFIG)
    params = init_params(cfg, jax.random.PRNGKey(1))
    qparams = attach_quantized_weights(params, QuantizedWeightCache())
    caches = init_caches(cfg, 1, 32)
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    _, caches = jax.jit(lambda p, t, c: prefill_step(p, t, c, cfg, mode="fast"))(
        qparams, toks, caches
    )

    calls = _count_quantize_calls(monkeypatch)
    tok = jnp.asarray([[5]], jnp.int32)
    pos = jnp.asarray([4], jnp.int32)

    fn_cached = jax.jit(lambda p, t, s, c: decode_step(p, t, s, c, cfg, mode="fast"))
    jax.block_until_ready(fn_cached(qparams, tok, pos, caches)[0])
    assert calls["weight"] == 0, f"cached decode quantized weights: {calls}"
    assert calls["act"] > 0  # activations still quantize per call

    calls["weight"] = calls["act"] = 0
    fn_plain = jax.jit(lambda p, t, s, c: decode_step(p, t, s, c, cfg, mode="fast"))
    jax.block_until_ready(fn_plain(params, tok, pos, caches)[0])
    assert calls["weight"] > 0  # the old path requantizes in-graph


#: page size of the gemma2 servers below: it divides the smoke config's
#: 8-row sliding window
PAGE = 4


def test_server_weight_cache_populated_once():
    """Server build quantizes each weight exactly once; generate()
    never grows the count (per-step requantization is gone)."""
    from repro.configs.gemma2_2b import CONFIG
    from repro.models import init_params
    from repro.models.config import smoke_config
    from repro.runtime.serve import ContinuousBatchingServer, ServingConfig

    cfg = smoke_config(CONFIG)
    params = init_params(cfg, jax.random.PRNGKey(2))
    srv = ContinuousBatchingServer(
        cfg, params, ServingConfig(n_slots=1, max_len=32, page_size=PAGE,
                                   default_level="q16_16")
    )
    cache = srv.engine.weight_cache
    built = cache.quantize_calls
    assert built > 0 and cache.hits == 0
    srv.generate([[1, 2, 3]], max_new=4)
    srv.generate([[4, 5, 6]], max_new=4)
    assert cache.quantize_calls == built


# ---------------------------------------------------------------------------
# vectorized sampling / host-sync removal
# ---------------------------------------------------------------------------


def test_server_greedy_matches_teacher_forcing_fast_level():
    """Greedy decode at the FAST level (fused path) must equal the FAST
    rung's own step-by-step re-derivation: a FAST prefill of the prompt,
    then one FAST decode step per token, with the weights quantized in
    the graph (the server quantizes them once at build).

    The reference is not a prefill of the growing sequence: FAST
    activations share one power-of-two exponent per tensor
    (``_quant_dims``), so a whole-sequence prefill puts the last
    position on the grid of the sequence's largest activation, while a
    one-token decode uses that token's own.  The two derivations differ
    by up to one grid step, enough to flip a near-tied argmax."""
    from repro.configs.gemma2_2b import CONFIG
    from repro.models import init_params
    from repro.models.config import smoke_config
    from repro.runtime.serve import ContinuousBatchingServer, ServingConfig

    cfg = smoke_config(CONFIG)
    params = init_params(cfg, jax.random.PRNGKey(3))
    prompt = list(range(1, 8))
    srv = ContinuousBatchingServer(
        cfg, params, ServingConfig(n_slots=1, max_len=64, page_size=PAGE,
                                   default_level="q16_16")
    )
    out = srv.generate([prompt], max_new=4)[0]
    seq = stepwise(cfg, params, prompt, 4, "fast")
    assert out == seq, (out, seq)


def test_server_eos_trimming():
    from repro.configs.gemma2_2b import CONFIG
    from repro.models import init_params
    from repro.models.config import smoke_config
    from repro.runtime.serve import ContinuousBatchingServer, ServingConfig

    cfg = smoke_config(CONFIG)
    params = init_params(cfg, jax.random.PRNGKey(4))
    srv = ContinuousBatchingServer(
        cfg, params, ServingConfig(n_slots=2, max_len=32, page_size=PAGE))
    ref = srv.generate([[1, 2, 3], [3, 2, 1]], max_new=6)
    first_new = ref[0][3]
    srv2 = ContinuousBatchingServer(
        cfg, params,
        ServingConfig(n_slots=2, max_len=32, page_size=PAGE, eos_id=int(first_new)),
    )
    out = srv2.generate([[1, 2, 3], [3, 2, 1]], max_new=6)
    # row 0 stops right at its first token == eos
    assert out[0] == [1, 2, 3, int(first_new)]
    # rows never exceed prompt + max_new, and eos appears at most once at the end
    for o, p in zip(out, [[1, 2, 3], [3, 2, 1]]):
        assert len(o) <= len(p) + 6
        assert int(first_new) not in o[len(p):-1]


def test_server_temperature_sampling_on_device():
    from repro.configs.gemma2_2b import CONFIG
    from repro.models import init_params
    from repro.models.config import smoke_config
    from repro.runtime.serve import ContinuousBatchingServer, ServingConfig

    cfg = smoke_config(CONFIG)
    params = init_params(cfg, jax.random.PRNGKey(5))

    def fresh():
        return ContinuousBatchingServer(
            cfg, params,
            ServingConfig(n_slots=2, max_len=32, page_size=PAGE, temperature=0.8,
                          seed=7),
        )

    outs = fresh().generate([[1, 2, 3], [4, 5]], max_new=4)
    assert all(len(o) > 0 for o in outs)
    for o in outs:
        assert all(0 <= t < cfg.vocab for t in o)
    # deterministic under a fixed seed: the sampling key runs on across
    # serve() calls, so the same seed means a fresh server
    outs2 = fresh().generate([[1, 2, 3], [4, 5]], max_new=4)
    assert outs == outs2


# ---------------------------------------------------------------------------
# interpret auto-detection
# ---------------------------------------------------------------------------


def test_default_interpret_off_tpu(rng):
    from repro.kernels import default_interpret

    assert default_interpret() is (jax.default_backend() != "tpu")
    # interpret=None flows through every kernel entrypoint
    x = rand_int8(rng, (8, 128))
    wg = rand_int8(rng, (128, 128))
    out = fused_swiglu_kernel_call(
        x, wg, wg, np.int32(-7), np.full((128,), -7, np.int32),
        np.full((128,), -7, np.int32), interpret=None,
    )
    assert out.shape == (8, 128)
