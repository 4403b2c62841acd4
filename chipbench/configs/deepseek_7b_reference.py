"""Plain reference for deepseek_7b (a llama-architecture decoder), from
the published description: pre-norm RMSNorm, rotary embeddings in the
half-split convention on every head, multi-head causal attention,
SwiGLU MLP, untied head.  Straightforward ``jax.numpy`` in float32: no
cache, no batching, no kernels.  It imports nothing of the program.

It reads the benchmark's weight arrays (``chipbench/weights.py``) by
their keys: ``embed`` (V, d); per layer, stacked on a leading axis,
``periods/pos0/mixer/{norm, wq, wk, wv, wo}`` and
``periods/pos0/ffn/{norm, w_gate, w_up, w_down}``; ``final_norm``;
``lm_head`` (d, V).  Norm gains are stored as offsets from 1.

``matmul`` picks the arithmetic of every matrix product:

* ``"highest"``: float32 (six bfloat16 passes on a TPU) -- the
  reference;
* ``"high"``: three bfloat16 passes (hi x hi + hi x lo + lo x hi, each
  operand split into a bfloat16 part and a bfloat16 remainder, summed in
  float32), written out so that it means the same on every backend --
  the control one step below the f32 rung's ``highest``;
* ``"int4"``: 4-bit operands on a power-of-two grid, one exponent per
  activation tensor and per weight output channel -- the control one
  step below the q16_16 rung's 8-bit operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _bf16x3(x, w, dims):
    """Three-pass bfloat16 product of ``x`` and ``w`` contracted over
    ``dims`` (``lax.dot_general`` dimension numbers)."""
    def split(a):
        # reduce_precision, not an astype round trip: the TPU compiler may
        # drop a float32 -> bfloat16 -> float32 pair, leaving lo = 0
        hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)

    (xh, xl), (wh, wl) = split(x), split(w)
    dot = lambda a, b: jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)  # noqa: E731
    return dot(xh, wh) + dot(xh, wl) + dot(xl, wh)


def _contract(a, b, dims, matmul):
    """``lax.dot_general`` at the reference's (``highest``) or the f32
    control's (``high``) arithmetic."""
    if matmul == "high":
        return _bf16x3(a, b, dims)
    return jax.lax.dot_general(a, b, dims, precision=HIGHEST, preferred_element_type=jnp.float32)


def _pow2_quant(x, bits, axes):
    """Round ``x`` onto a signed ``bits``-wide grid scaled by a power of
    two, one exponent per slice over ``axes``; returns the dequantized
    values."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    e = jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30))) - (bits - 1)
    e = jnp.where(amax > 0, e, 0.0)
    qmax = 2.0 ** (bits - 1) - 1
    return jnp.clip(jnp.round(x * jnp.exp2(-e)), -qmax - 1, qmax) * jnp.exp2(e)


def _mm(x, w, matmul):
    """x (S, k) @ w (k, n) at the run's arithmetic."""
    if matmul == "int4":
        x = _pow2_quant(x, 4, axes=None)
        w = _pow2_quant(w, 4, axes=0)
        matmul = "highest"
    return _contract(x, w, (((1,), (0,)), ((), ())), matmul)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, positions, theta):
    """x (S, H, D): rotate the two halves of each head."""
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv            # (S, D/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(h, w, spec, matmul):
    S, d = h.shape
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = d // H
    eps = spec["rms_norm_eps"]
    pos = jnp.arange(S)
    a = w["mixer"]
    x = _rms(h, a["norm"], eps)
    q = _rope(_mm(x, a["wq"], matmul).reshape(S, H, hd), pos, spec["rope_theta"])
    k = _rope(_mm(x, a["wk"], matmul).reshape(S, KV, hd), pos, spec["rope_theta"])
    v = _mm(x, a["wv"], matmul).reshape(S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    att = "highest" if matmul == "int4" else matmul
    # scores (H, S, S): contract head_dim, batch over heads
    s = _contract(q.transpose(1, 0, 2), k.transpose(1, 0, 2), (((2,), (2,)), ((0,), (0,))), att)
    s = s / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _contract(p, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))), att)   # (H, S, hd)
    o = o.transpose(1, 0, 2).reshape(S, H * hd)
    h = h + _mm(o, a["wo"], matmul)
    f = w["ffn"]
    x = _rms(h, f["norm"], eps)
    g = _mm(x, f["w_gate"], matmul)
    u = _mm(x, f["w_up"], matmul)
    return h + _mm(jax.nn.silu(g) * u, f["w_down"], matmul)


@functools.partial(jax.jit, static_argnames=("spec_items", "matmul"))
def _logits(weights, tokens, rows, *, spec_items, matmul):
    spec = dict(spec_items)
    h = weights["embed"][tokens].astype(jnp.float32)
    layers = weights["periods"]["pos0"]

    def body(h, w):
        return _layer(h, w, spec, matmul), None

    h, _ = jax.lax.scan(body, h, layers)
    x = _rms(h[rows], weights["final_norm"], spec["rms_norm_eps"])
    return _mm(x, weights["lm_head"], matmul)


SPEC_KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps", "rope_theta")


def logits(weights, tokens, rows, spec: dict, matmul: str = "highest"):
    """Logits (len(rows), V) predicting the token after each position in
    ``rows`` of the sequence ``tokens`` (S,).  ``tokens`` may be padded
    at the end: attention is causal, so padding changes no row before
    it."""
    items = tuple((k, spec[k]) for k in SPEC_KEYS)
    return _logits(weights, jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
                   spec_items=items, matmul=matmul)
