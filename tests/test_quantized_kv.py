"""Q-format int8 KV cache (FAST serving): correctness vs the bf16 cache
and bounded quantization error — the paper's C1 applied to resident
serving state."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import smoke
from repro.models import decode_step, init_caches, init_params, prefill_step


@pytest.mark.parametrize(
    "arch",
    [
        pytest.param(
            "deepseek_7b",
            marks=pytest.mark.xfail(
                reason="pre-existing: dense-GQA int8-KV logit error 0.73 > 0.45 bound "
                "on this toolchain.  Measured per-(layer, kv-head) dequant error is "
                "UNIFORM and already at the int8 pow2 floor (k: 0.40/0.41/0.64/0.71%, "
                "v: 0.69/0.66/0.65/0.40% of head amax; grid step is 0.39-0.79%), so "
                "finer per-head exponents cannot close it — the excess is cross-layer "
                "amplification of near-tied logits on the random-init smoke model "
                "(per-step logit diffs 0.12/0.11/0.73/0.20).  See ROADMAP "
                "'Known-failing tier-1 tests'",
                strict=False,
            ),
        ),
        "gemma2_2b",
        "mixtral_8x22b",
    ],
)
def test_quantized_decode_close_to_bf16(arch):
    """Greedy decode logits through the int8 cache track the bf16-cache
    logits within Q-format error (int8 grid ~ 0.8% of slot amax)."""
    cfg = smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(7))
    B, S = 2, 24
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)))

    # teacher-forced: SAME token stream for both cache formats (greedy
    # feedback on a random-init model flips near-tied argmaxes and the
    # trajectories diverge chaotically — that would test chaos, not
    # quantization)
    forced = jnp.asarray(rng.integers(0, cfg.vocab, (4, B, 1)))
    prefill = jax.jit(lambda p, t, c: prefill_step(p, t, c, cfg))
    decode = jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, cfg))
    outs = {}
    for quantized in (False, True):
        caches = init_caches(cfg, B, 64, quantized=quantized)
        logits, caches = prefill(params, toks, caches)
        pos = jnp.full((B,), S, jnp.int32)
        seq_logits = [np.asarray(logits, np.float32)]
        for i in range(4):
            logits, caches = decode(params, forced[i], pos, caches)
            seq_logits.append(np.asarray(logits, np.float32))
            pos = pos + 1
        outs[quantized] = np.stack(seq_logits)

    diff = np.abs(outs[True] - outs[False]).max()
    scale = np.abs(outs[False]).max()
    assert diff < 0.08 * scale + 0.15, (arch, diff, scale)


def test_kv_quantization_is_core_pow2_kept_axes():
    """The KV-cache quantizer IS quantize_pow2's kept-axes form: one
    exponent per (batch, seq, kv-head) slice, bit-identical payloads —
    cache quantization and weight/activation quantization share a
    single grid definition."""
    from repro.core.quantization import quantize_pow2
    from repro.models.attention import _q8

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(2, 5, 3, 16)) * 10.0, jnp.float32)
    q, e = _q8(x, axes=(3,))
    assert q.dtype == jnp.int8 and e.shape == (2, 5, 3)
    qt = quantize_pow2(x, bits=8, axis=(0, 1, 2))
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qt.q))
    np.testing.assert_array_equal(np.asarray(e), np.asarray(qt.exp).reshape(2, 5, 3))
    # per-head independence: rescaling ONE head leaves every other
    # head's payload and exponent untouched
    y = x.at[:, :, 1].multiply(64.0)
    q2, e2 = _q8(y, axes=(3,))
    np.testing.assert_array_equal(np.asarray(q2[:, :, [0, 2]]), np.asarray(q[:, :, [0, 2]]))
    np.testing.assert_array_equal(np.asarray(e2[:, :, [0, 2]]), np.asarray(e[:, :, [0, 2]]))
    np.testing.assert_array_equal(np.asarray(e2[:, :, 1]), np.asarray(e[:, :, 1]) + 6)
    # round-trip error bounded by half a grid step per head
    deq = np.asarray(q, np.float32) * np.exp2(np.asarray(e, np.float32))[..., None]
    amax = np.abs(np.asarray(x)).max(axis=3)
    assert (np.abs(deq - np.asarray(x)).max(axis=3) <= np.exp2(np.asarray(e)) / 2 + 1e-6).all()
    assert (amax / np.exp2(np.asarray(e, np.float64)) <= 127.0 + 0.5).all()


def test_quantized_cache_layout():
    cfg = smoke("deepseek_7b")
    c = init_caches(cfg, 2, 32, quantized=True)
    k = jax.tree.leaves({"k": c})[0]
    flat = jax.tree_util.tree_flatten_with_path(c)[0]
    names = {"/".join(str(getattr(p, "key", p)) for p in path) for path, _ in flat}
    assert any("k_exp" in n for n in names)
    # int8 payloads
    for path, leaf in flat:
        tail = str(getattr(path[-1], "key", path[-1]))
        if tail in ("k", "v"):
            assert leaf.dtype == jnp.int8, tail


def test_quantized_cache_halves_bytes():
    def nbytes(c):
        return sum(
            int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(c)
        )
    # smoke dims (hd=16): per-head exponent overhead is 4/16/2 = 12.5%
    cfg = smoke("deepseek_7b")
    full = nbytes(init_caches(cfg, 2, 64, quantized=False))
    quant = nbytes(init_caches(cfg, 2, 64, quantized=True))
    assert quant < 0.75 * full, (quant, full)

    # production dims (hd=128): overhead 1.6% -> true halving.
    # eval_shape only — no allocation of the 32k cache.
    from repro.configs import get_config
    prod = get_config("deepseek_7b")
    full_p = nbytes(jax.eval_shape(lambda: init_caches(prod, 8, 32768, quantized=False)))
    quant_p = nbytes(jax.eval_shape(lambda: init_caches(prod, 8, 32768, quantized=True)))
    assert quant_p < 0.53 * full_p, (quant_p, full_p)
