"""Plain reference for minicpm3_4b, from the published description
(the MiniCPM3-4B config.json and modelling code): pre-norm RMSNorm,
multi-head latent attention, SwiGLU MLP, untied head, with MiniCPM's
three scalings and LongRoPE.  Straightforward ``jax.numpy`` in float32:
no cache, no batching, no kernels.  It imports nothing of the program.

Attention is the *expanded* form of the published description, not the
program's absorbed decode: per layer,

* ``q = wq_b(rms(wq_a x))``, split per head into nope (64) and rope (32);
* ``[c, k_pe] = wkv_a x``, ``c = rms(c)`` (the 256-wide latent),
  ``k_pe`` one 32-wide rotary key shared by every head;
* ``[k_nope, v] = wkv_b c``: full per-head keys and values;
* scores ``[q_nope, q_pe] . [k_nope, k_pe] / sqrt(96)``, causal softmax.

so the program's absorbed decode (scores and values in latent space) is
checked against an independent formulation.

MiniCPM's scalings: the embedding rows x ``scale_emb``; each residual
branch x ``scale_depth / sqrt(published num_hidden_layers)`` (the
published depth, whatever depth is run); the head's input, after the
final norm, / (hidden_size / dim_model_base).  LongRoPE: angle_i = pos x
theta^(-2i/32) / factor_i with the short factors, and cos and sin x
sqrt(1 + ln(s) / ln(original)), s = max_position_embeddings / original
(1 here, so the multiplier is 1 exactly); rotate-half convention.

It reads the benchmark's weight arrays (``chipbench/weights.py``) by
their keys: ``embed`` (V, d); per layer, stacked on a leading axis,
``periods/pos0/mixer/{norm, wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b,
wo}`` and ``periods/pos0/ffn/{norm, w_gate, w_up, w_down}``;
``final_norm``; ``lm_head`` (d, V).  Departures from the published
model: norm gains are stored as offsets from 1 (the layer applies
``1 + w``); weights are random from the seed.

``matmul`` picks the arithmetic of every matrix product, with the same
meanings as in ``deepseek_7b_reference.py`` (whose helpers it uses):
``"highest"`` (the reference), ``"high"`` (three bfloat16 passes, the
f32 rung's control) and ``"int4"`` (4-bit operands, the q16_16 rung's
control; attention's own contractions stay at ``highest``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.configs import deepseek_7b_reference as base

_contract, _mm, _rms = base._contract, base._mm, base._rms


def _rope(x, positions, theta, factors, mscale):
    """x (S, H, D): LongRoPE, rotate the two halves of each head."""
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    inv = inv / jnp.asarray(factors, jnp.float32)
    ang = positions[:, None].astype(jnp.float32) * inv            # (S, D/2)
    sin = (jnp.sin(ang) * mscale)[:, None, :]
    cos = (jnp.cos(ang) * mscale)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(h, w, spec, matmul):
    S, d = h.shape
    H = spec["num_attention_heads"]
    r, nope = spec["kv_lora_rank"], spec["qk_nope_head_dim"]
    rope, vd = spec["qk_rope_head_dim"], spec["v_head_dim"]
    eps = spec["rms_norm_eps"]
    res = spec["residual_scale"]
    rot = functools.partial(_rope, positions=jnp.arange(S), theta=spec["rope_theta"],
                            factors=spec["rope_factors"], mscale=spec["rope_mscale"])
    pos = jnp.arange(S)
    a = w["mixer"]
    x = _rms(h, a["norm"], eps)
    q = _mm(_rms(_mm(x, a["wq_a"], matmul), a["q_norm"], eps), a["wq_b"], matmul)
    q = q.reshape(S, H, nope + rope)
    kv = _mm(x, a["wkv_a"], matmul)
    c = _rms(kv[:, :r], a["kv_norm"], eps)
    k_pe = rot(kv[:, None, r:])                                    # (S, 1, rope)
    kvb = _mm(c, a["wkv_b"], matmul).reshape(S, H, nope + vd)      # expanded per head
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe, (S, H, rope))], axis=-1)
    v = kvb[..., nope:]
    q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], axis=-1)
    att = "highest" if matmul == "int4" else matmul
    s = _contract(q.transpose(1, 0, 2), k.transpose(1, 0, 2), (((2,), (2,)), ((0,), (0,))), att)
    s = s / jnp.sqrt(jnp.float32(nope + rope))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _contract(p, v.transpose(1, 0, 2), (((2,), (1,)), ((0,), (0,))), att)   # (H, S, vd)
    o = o.transpose(1, 0, 2).reshape(S, H * vd)
    h = h + _mm(o, a["wo"], matmul) * res
    f = w["ffn"]
    x = _rms(h, f["norm"], eps)
    g = _mm(x, f["w_gate"], matmul)
    u = _mm(x, f["w_up"], matmul)
    return h + _mm(jax.nn.silu(g) * u, f["w_down"], matmul) * res


@functools.partial(jax.jit, static_argnames=("spec_items", "matmul"))
def _logits(weights, tokens, rows, *, spec_items, matmul):
    spec = dict(spec_items)
    h = weights["embed"][tokens].astype(jnp.float32) * spec["scale_emb"]
    layers = weights["periods"]["pos0"]

    def body(h, w):
        return _layer(h, w, spec, matmul), None

    h, _ = jax.lax.scan(body, h, layers)
    x = _rms(h[rows], weights["final_norm"], spec["rms_norm_eps"]) / spec["head_divisor"]
    return _mm(x, weights["lm_head"], matmul)


def derived(spec: dict) -> tuple:
    """The numbers the forward pass reads, as hashable (key, value) pairs:
    the widths as given, and the scalings and rope terms computed from
    the published keys."""
    rs = spec["rope_scaling"]
    s = spec["max_position_embeddings"] / rs["original_max_position_embeddings"]
    mscale = math.sqrt(1 + math.log(s) / math.log(rs["original_max_position_embeddings"])) \
        if s > 1 else 1.0
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, spec[k]) for k in keys) + (
        ("scale_emb", float(spec["scale_emb"])),
        ("residual_scale",
         spec["scale_depth"] / math.sqrt(spec["published"]["num_hidden_layers"])),
        ("head_divisor", spec["hidden_size"] / spec["dim_model_base"]),
        ("rope_factors", tuple(float(f) for f in rs["short_factor"])),
        ("rope_mscale", mscale),
    )


def logits(weights, tokens, rows, spec: dict, matmul: str = "highest"):
    """Logits (len(rows), V) predicting the token after each position in
    ``rows`` of the sequence ``tokens`` (S,).  ``tokens`` may be padded
    at the end: attention is causal, so padding changes no row before
    it."""
    return _logits(weights, jnp.asarray(tokens, jnp.int32), jnp.asarray(rows, jnp.int32),
                   spec_items=derived(spec), matmul=matmul)
