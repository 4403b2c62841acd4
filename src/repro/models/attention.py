"""Attention: GQA / sliding-window / local-global / MLA, with a
memory-safe chunked online-softmax formulation (scan over KV blocks) so
32k-token prefill never materializes an S x S score matrix.

Decode (single query against a cache) materializes the (B, H, S_kv)
score row directly — it is linear in S_kv and small.

Sliding-window caches are rolling buffers of size ``window`` with an
explicit per-slot position tensor (mask handles wrap-around), so
mixtral's 32k/500k decode memory is window-bounded.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import LayerSpec, ModelConfig
from repro.models.layers import (
    Spec,
    apply_rope,
    attn_norm_spec,
    pdot,
    rms_norm,
    rope_tables,
    softcap,
)

__all__ = [
    "attn_specs",
    "mla_specs",
    "attention_forward",
    "mla_forward",
    "init_attn_cache",
    "init_mla_cache",
    "reset_attn_cache_slot",
    "reset_mla_cache_slot",
    "truncate_attn_cache_slot",
]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


def attn_specs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "norm": attn_norm_spec(d),
        "wq": Spec((d, h * hd), ("embed", "heads")),
        "wk": Spec((d, kv * hd), ("embed", "kv")),
        "wv": Spec((d, kv * hd), ("embed", "kv")),
        "wo": Spec((h * hd, d), ("heads", "embed")),
    }


def mla_specs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    m = cfg.mla
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "norm": attn_norm_spec(d),
        "wq_a": Spec((d, m.q_lora_rank), ("embed", None)),
        "q_norm": Spec((m.q_lora_rank,), (None,), init="zeros"),
        "wq_b": Spec((m.q_lora_rank, h * qk), (None, "heads")),
        "wkv_a": Spec((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None)),
        "kv_norm": Spec((m.kv_lora_rank,), (None,), init="zeros"),
        "wkv_b": Spec((m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)), (None, "heads")),
        "wo": Spec((h * m.v_head_dim, d), ("heads", "embed")),
    }


# ---------------------------------------------------------------------------
# chunked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------


def _gqa_scores(q, k):
    """q: (B,S,KV,G,D), k: (B,Ck,KV,D) -> (B,KV,G,S,Ck) fp32."""
    return jnp.einsum("bskgd,bckd->bkgsc", q, k, preferred_element_type=jnp.float32)


def chunked_attention(
    q,
    k,
    v,
    *,
    q_positions,
    kv_len: Optional[int] = None,
    causal: bool = True,
    window: Optional[int] = None,
    cap: Optional[float] = None,
    chunk: int = 1024,
):
    """q: (B,S,H,Dq); k: (B,Skv,KV,Dq); v: (B,Skv,KV,Dv).

    Online softmax over KV chunks: memory O(S * chunk) instead of
    O(S * Skv).  Keys are assumed contiguous from position 0 (training
    and prefill), so key positions are derived from the chunk index
    *inside* the scanned body — this keeps the mask loop-variant (XLA
    would otherwise hoist an O(n_chunks * S * chunk) mask tensor out of
    the loop) — and the body is checkpointed, so the backward pass
    recomputes scores/masks instead of saving them (flash-attention
    memory behavior, pure JAX).
    """
    B, S, H, Dq = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KV
    scale = 1.0 / math.sqrt(Dq)
    kv_len = Skv if kv_len is None else kv_len

    if Skv >= 32768:
        chunk = min(chunk, 128)  # bound the f32 score buffers at 32k prefill
    elif Skv >= 16384:
        chunk = min(chunk, 512)
    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))

    qr = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(B, S, KV, G, Dq)
    k_c = k.reshape(B, n_chunks, chunk, KV, Dq).swapaxes(0, 1)
    v_c = v.reshape(B, n_chunks, chunk, KV, Dv).swapaxes(0, 1)

    def step(carry, blk):
        m, l, acc = carry
        idx, k_blk, v_blk = blk  # (), (B,chunk,KV,D), (B,chunk,KV,Dv)
        s = _gqa_scores(qr, k_blk)  # (B,KV,G,S,chunk) f32
        s = softcap(s, cap)
        # key positions derived from the chunk index (loop-variant)
        kp = idx * chunk + jax.lax.iota(jnp.int32, chunk)          # (chunk,)
        qp = q_positions[:, None, None, :, None]                   # (B,1,1,S,1)
        kpb = kp[None, None, None, None, :]
        valid = kpb < kv_len
        if causal:
            valid = valid & (kpb <= qp)
        if window is not None:
            valid = valid & (kpb > qp - window)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum(
            "bkgsc,bckd->bkgsd", p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    a0 = jnp.zeros((B, KV, G, S, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(step, prevent_cse=False),
        (m0, l0, a0),
        (jnp.arange(n_chunks, dtype=jnp.int32), k_c, v_c),
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, Dv).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, kp, *, q_position, window=None, cap=None,
                     k_exp=None, v_exp=None):
    """Single-token decode: q (B,1,H,Dq) vs cache (B,L,KV,D); kp (B,L)
    slot positions (-1 = unwritten).

    Q-format caches (k_exp/v_exp per slot): the int8 payload enters the
    dot via a fused convert; the power-of-two exponents fold into the
    scores / probabilities (shift-only, C1's deferred correction)."""
    B, _, H, Dq = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(Dq)
    qr = (q[:, 0] * scale).reshape(B, KV, G, Dq)
    s = jnp.einsum(
        "bkgd,blkd->bkgl", qr.astype(jnp.float32), k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if k_exp is not None:  # (B, L, KV) -> (B, KV, 1, L)
        s = s * jnp.exp2(k_exp.astype(jnp.float32)).transpose(0, 2, 1)[:, :, None, :]
    s = softcap(s, cap)
    qp = q_position[:, None, None, None]
    kpb = kp[:, None, None, :]
    valid = (kpb >= 0) & (kpb <= qp)
    if window is not None:
        valid &= kpb > qp - window
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_exp is not None:
        p = p * jnp.exp2(v_exp.astype(jnp.float32)).transpose(0, 2, 1)[:, :, None, :]
    out = jnp.einsum("bkgl,blkd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, -1).astype(q.dtype)


def decode_attention_multi(q, k_cache, v_cache, kp, *, q_positions, window=None,
                           cap=None, k_exp=None, v_exp=None):
    """Segment decode: S queries against a cache (the speculative-verify
    / chunked-continuation path).  q (B,S,H,Dq) vs cache (B,L,KV,D);
    kp (B,L) slot positions (-1 = unwritten); q_positions (B,S).

    Each query position masks keys by its OWN position (kp <= qp_s), so
    within-segment causality holds after the whole segment's k/v have
    been written to the cache.  The (B,KV,G,S,L) score tensor is small
    for decode-length segments (S = k+1 speculative drafts)."""
    B, S, H, Dq = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(Dq)
    qr = (q.astype(jnp.float32) * scale).reshape(B, S, KV, G, Dq)
    s = jnp.einsum(
        "bskgd,blkd->bkgsl", qr, k_cache.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    if k_exp is not None:  # (B, L, KV) -> (B, KV, 1, 1, L)
        s = s * jnp.exp2(k_exp.astype(jnp.float32)).transpose(0, 2, 1)[:, :, None, None, :]
    s = softcap(s, cap)
    qp = q_positions[:, None, None, :, None]                   # (B,1,1,S,1)
    kpb = kp[:, None, None, None, :]                           # (B,1,1,1,L)
    valid = (kpb >= 0) & (kpb <= qp)
    if window is not None:
        valid &= kpb > qp - window
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if v_exp is not None:
        p = p * jnp.exp2(v_exp.astype(jnp.float32)).transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum("bkgsl,blkd->bskgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, S, H, -1).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA layer forward (train/prefill and decode)
# ---------------------------------------------------------------------------


def init_attn_cache(
    cfg: ModelConfig, layer: LayerSpec, batch: int, max_len: int,
    dtype=jnp.bfloat16, quantized: bool = False,
):
    """quantized=True: the paper's Q-format applied to the KV cache —
    int8 payloads with a per-(batch, slot) power-of-two exponent
    (shift-only rescale, C1 faithful).  Halves resident cache bytes;
    the dequant scales fold into the attention dots."""
    L = min(layer.window, max_len) if layer.window else max_len
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    out = {
        "k": jnp.zeros((batch, L, kv, hd), jnp.int8 if quantized else dtype),
        "v": jnp.zeros((batch, L, kv, hd), jnp.int8 if quantized else dtype),
        "pos": jnp.full((batch, L), -1, jnp.int32),
    }
    if quantized:
        # per-(slot, kv-head) exponents: finer than per-slot, still
        # negligible overhead (L x KV int32 vs L x KV x hd int8 payload)
        out["k_exp"] = jnp.zeros((batch, L, kv), jnp.int32)
        out["v_exp"] = jnp.zeros((batch, L, kv), jnp.int32)
    return out


def reset_attn_cache_slot(cache: dict, slot) -> dict:
    """Reset one batch slot of a KV cache for continuous-batching
    admission.  Payloads zero; the per-slot position tensor goes back
    to -1 (unwritten) so the next occupant's decode mask cannot attend
    to the evicted request's residue.  ``slot`` may be traced."""
    out = {}
    for k, v in cache.items():
        fill = jnp.full(v.shape[1:], -1, v.dtype) if k == "pos" else jnp.zeros(v.shape[1:], v.dtype)
        out[k] = v.at[slot].set(fill)
    return out


def reset_mla_cache_slot(cache: dict, slot) -> dict:
    """MLA variant of :func:`reset_attn_cache_slot` (latent ckv/krope
    payloads + the same -1 position sentinel)."""
    return reset_attn_cache_slot(cache, slot)


def truncate_attn_cache_slot(cache: dict, slot, keep_pos) -> dict:
    """Truncate-to-position form of :func:`reset_attn_cache_slot`:
    entries of ONE batch slot whose position is ``>= keep_pos`` go back
    to the pristine fill (payloads zero, pos sentinel -1); entries below
    the boundary are untouched BIT-FOR-BIT.  This is the speculative-
    decoding rollback for position-indexed caches (GQA k/v and MLA
    ckv/krope both carry the same per-slot ``pos`` tensor, so one
    implementation serves both).  ``slot`` and ``keep_pos`` may be
    traced — jit-safe.

    NOTE: this restores a *pristine* fill, which equals the pre-write
    contents only while the rolling buffer has not wrapped (position
    ``>= keep_pos`` was never previously occupied by an OLDER live
    entry).  Wrapped sliding-window rollback needs the before/after
    merge in :func:`repro.models.model.commit_segment`, which keeps the
    overwritten entries."""
    out = {}
    drop = cache["pos"][slot] >= keep_pos                      # (L,)
    for k, v in cache.items():
        row = v[slot]
        fill = jnp.asarray(-1 if k == "pos" else 0, v.dtype)
        mask = drop.reshape((-1,) + (1,) * (row.ndim - 1))
        out[k] = v.at[slot].set(jnp.where(mask, fill, row))
    return out


def _q8(x, axes):
    """int8 KV-cache quantization on the paper's pow2 grid: one
    exponent per kept slice — per (batch[, seq], kv-head), with the
    reduced ``axes`` spanning head_dim.  Defers to the core
    quantizer's kept-axes form so cache payloads and weight/activation
    quantization share a single grid definition.  Returns
    ``(int8 payload, exponents)`` with the exponents' reduced axes
    squeezed away (the cache's ``k_exp``/``v_exp`` layout)."""
    from repro.core.quantization import quantize_pow2

    red = {a % x.ndim for a in axes}
    keep = tuple(i for i in range(x.ndim) if i not in red)
    qt = quantize_pow2(x, bits=8, axis=keep)
    return qt.q, qt.exp.reshape([x.shape[i] for i in keep])


def attention_forward(
    params,
    x,
    cfg: ModelConfig,
    layer: LayerSpec,
    *,
    positions,
    mode: str = "precise",
    cache=None,
    prefill: bool = False,
    constrain=lambda x, kind: x,
):
    """x: (B,S,d).

    cache=None             -> training forward (no cache out)
    cache given, prefill   -> chunked attention + cache populated [0:S)
    cache given, S==1      -> single-token decode against the cache;
                              returns the new ROWS (B, ...), not a cache
    cache given, S>1       -> segment decode; returns the cache with the
                              segment written
    """
    B, S, _ = x.shape
    h = rms_norm(x, params["norm"], cfg.rms_eps)
    q = pdot(h, params["wq"], mode, wq=params.get("wq_q")).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = pdot(h, params["wk"], mode, wq=params.get("wk_q")).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = pdot(h, params["wv"], mode, wq=params.get("wv_q")).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)

    sin, cos = rope_tables(positions, cfg.head_dim, cfg.rope_base, mode)
    # head-sharded (TP) layout through attention: keeps every KV chunk
    # local to its device; the seq<->heads reshard happens ONCE per
    # layer, outside the chunk loop (see launch/steps._make_constrain)
    q = constrain(apply_rope(q, sin, cos), "heads4d")
    k = constrain(apply_rope(k, sin, cos), "heads4d")
    v = constrain(v, "heads4d")

    if cache is None or prefill:
        out = chunked_attention(
            q, k, v,
            q_positions=positions,
            causal=True,
            window=layer.window,
            cap=cfg.attn_softcap,
        )
        new_cache = None
        if prefill:
            new_cache = _prefill_cache(cache, k, v, positions, layer.window)
    elif S == 1:
        # decode: the new row is selected in at its slot where the
        # attention reads the cache, and only the rows come back -- the
        # caller writes them (``repro.models.write_rows``, the paged
        # commit), so no layer produces a whole new cache
        slot = positions[:, 0] % cache["k"].shape[1]  # rolling for SWA
        if "k_exp" in cache:
            qk, e_k = _q8(k[:, 0], axes=(2,))            # exps (B, KV)
            qv, e_v = _q8(v[:, 0], axes=(2,))
            new_cache = {"k": qk, "v": qv, "pos": positions[:, 0],
                         "k_exp": e_k, "v_exp": e_v}
        else:
            new_cache = {"k": k[:, 0], "v": v[:, 0], "pos": positions[:, 0]}
        seen = {name: _with_row(cache[name], row, slot) for name, row in new_cache.items()}
        out = decode_attention(
            q, seen["k"], seen["v"], seen["pos"],
            q_position=positions[:, 0],
            window=layer.window,
            cap=cfg.attn_softcap,
            k_exp=seen.get("k_exp"), v_exp=seen.get("v_exp"),
        )
    else:
        # segment decode (speculative verify): S tokens against the
        # cache with per-query causal masks.  Requires S <= L so the
        # segment cannot overwrite its own earlier writes.
        L = cache["k"].shape[1]
        if S > L:
            raise ValueError(f"segment length {S} exceeds cache length {L}")
        quantized = "k_exp" in cache
        k_cache, v_cache = cache["k"], cache["v"]
        kp = cache["pos"]
        ek_c = cache.get("k_exp")
        ev_c = cache.get("v_exp")

        def store_one(s_i):
            nonlocal k_cache, v_cache, kp, ek_c, ev_c
            slot = positions[:, s_i] % L
            if quantized:
                qk, e_k = _q8(k[:, s_i], axes=(2,))      # exps (B, KV)
                qv, e_v = _q8(v[:, s_i], axes=(2,))
                k_cache = _store(k_cache, qk, slot)
                v_cache = _store(v_cache, qv, slot)
                ek_c = _store(ek_c, e_k, slot)
                ev_c = _store(ev_c, e_v, slot)
            else:
                k_cache = _store(k_cache, k[:, s_i], slot)
                v_cache = _store(v_cache, v[:, s_i], slot)
            kp = _store(kp, positions[:, s_i], slot)

        if layer.window is None:
            # full attention: positions stay below L, so no in-segment
            # write can land on a slot an earlier query needs — write the
            # whole segment, then batch the S queries (bit-matches the
            # sequential decode order: same slots, same masked set).
            for s_i in range(S):
                store_one(s_i)
            out = decode_attention_multi(
                q, k_cache, v_cache, kp,
                q_positions=positions,
                window=None,
                cap=cfg.attn_softcap,
                k_exp=ek_c, v_exp=ev_c,
            )
        else:
            # SWA rolling buffer: a later segment write can WRAP onto a
            # slot an earlier query's window still covers.  Interleave
            # store/query exactly as sequential decode does (S is static
            # and small — at most k+1 speculative positions).
            outs = []
            for s_i in range(S):
                store_one(s_i)
                outs.append(decode_attention(
                    q[:, s_i : s_i + 1], k_cache, v_cache, kp,
                    q_position=positions[:, s_i],
                    window=layer.window,
                    cap=cfg.attn_softcap,
                    k_exp=ek_c, v_exp=ev_c,
                ))
            out = jnp.concatenate(outs, axis=1)
        new_cache = {"k": k_cache, "v": v_cache, "pos": kp}
        if quantized:
            new_cache["k_exp"] = ek_c
            new_cache["v_exp"] = ev_c

    out = pdot(out.reshape(B, S, cfg.n_heads * cfg.head_dim), params["wo"], mode, wq=params.get("wo_q"))
    return out, new_cache


def _prefill_cache(cache, k, v, positions, window):
    """Populate cache buffers from a prefill segment starting at pos 0.

    Full attention: write k/v at [0:S).  SWA: keep the last ``window``
    tokens, rolled so each lands at slot ``pos % window``.  Quantized
    caches get per-position Q-format exponents.
    """
    B, S = k.shape[0], k.shape[1]
    L = cache["k"].shape[1]
    dt = cache["k"].dtype
    quantized = "k_exp" in cache
    if quantized:
        k, e_k = _q8(k, axes=(3,))                       # exps (B, S, KV)
        v, e_v = _q8(v, axes=(3,))

    def place(buf, seg, fill_dtype):
        if window is None or L >= S:
            return jax.lax.dynamic_update_slice_in_dim(buf, seg.astype(fill_dtype), 0, axis=1)
        return jnp.roll(seg[:, S - L :].astype(fill_dtype), S % L, axis=1)

    out = {
        "k": place(cache["k"], k, dt),
        "v": place(cache["v"], v, dt),
        "pos": place(cache["pos"], positions, jnp.int32),
    }
    if quantized:
        out["k_exp"] = place(cache["k_exp"], e_k, jnp.int32)
        out["v_exp"] = place(cache["v_exp"], e_v, jnp.int32)
    return out


def _store(buf, val, slot):
    """buf (B, L, ...) <- val (B, ...) at per-batch slot (B,)."""
    idx = slot[:, None]  # (B,1)
    oh = jax.nn.one_hot(slot, buf.shape[1], dtype=buf.dtype)  # (B, L)
    oh = oh.reshape(oh.shape + (1,) * (buf.ndim - 2))
    return buf * (1 - oh) + oh * val[:, None]


def _with_row(buf, val, slot):
    """buf (B, L, ...) as read with val (B, ...) at per-batch slot (B,):
    the same values as :func:`_store`, as a select the attention read
    consumes (dtypes promote as the blend's do, so an f32 row is read
    unrounded from a bf16 cache)."""
    at = jnp.arange(buf.shape[1], dtype=slot.dtype) == slot[:, None]  # (B, L)
    return jnp.where(at.reshape(at.shape + (1,) * (buf.ndim - 2)), val[:, None], buf)


# ---------------------------------------------------------------------------
# MLA (MiniCPM3 / deepseek-family latent attention)
# ---------------------------------------------------------------------------


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
        "pos": jnp.full((batch, max_len), -1, jnp.int32),
    }


def mla_forward(params, x, cfg: ModelConfig, *, positions, mode="precise", cache=None, prefill: bool = False, constrain=lambda x, kind: x):
    """Latent attention; decode (S == 1) returns the new rows, as
    :func:`attention_forward` does.  Each path's attention runs under a named scope
    (``mla_prefill`` for training and prefill, ``mla_decode`` for one
    token, ``mla_segment`` for a prefill chunk or a verify segment), so a
    device trace can find it."""
    B, S, _ = x.shape
    m = cfg.mla
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    h = rms_norm(x, params["norm"], cfg.rms_eps)
    q_lat = rms_norm(pdot(h, params["wq_a"], mode, wq=params.get("wq_a_q")), params["q_norm"], cfg.rms_eps)
    q = pdot(q_lat, params["wq_b"], mode, wq=params.get("wq_b_q")).reshape(B, S, H, nope + rope_d)
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = pdot(h, params["wkv_a"], mode, wq=params.get("wkv_a_q"))
    ckv = rms_norm(kv_a[..., : m.kv_lora_rank], params["kv_norm"], cfg.rms_eps)
    k_rope = kv_a[..., m.kv_lora_rank :]  # (B,S,rope_d) shared across heads

    sin, cos = rope_tables(positions, rope_d, cfg.rope_base, mode, cfg.rope_factors)
    q_rope = apply_rope(q_rope, sin, cos)
    k_rope = apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0]

    w_b = params["wkv_b"].reshape(m.kv_lora_rank, H, nope + vd)
    w_uk, w_uv = w_b[..., :nope], w_b[..., nope:]

    if cache is None or prefill:
        with jax.named_scope("mla_prefill"):
            k_nope = jnp.einsum("bsr,rhd->bshd", ckv, w_uk).astype(x.dtype)
            v = jnp.einsum("bsr,rhd->bshd", ckv, w_uv).astype(x.dtype)
            k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope_d)).astype(x.dtype)
            k = constrain(jnp.concatenate([k_nope, k_rope_b], axis=-1), "heads4d")
            v = constrain(v, "heads4d")
            q_full = constrain(jnp.concatenate([q_nope, q_rope], axis=-1), "heads4d")
            out = chunked_attention(
                q_full, k, v,
                q_positions=positions, causal=True,
            )
            new_cache = None
            if prefill:
                dt = cache["ckv"].dtype
                new_cache = {
                    "ckv": jax.lax.dynamic_update_slice_in_dim(
                        cache["ckv"], ckv.astype(dt), 0, axis=1
                    ),
                    "krope": jax.lax.dynamic_update_slice_in_dim(
                        cache["krope"], k_rope.astype(dt), 0, axis=1
                    ),
                    "pos": jax.lax.dynamic_update_slice_in_dim(
                        cache["pos"], positions.astype(jnp.int32), 0, axis=1
                    ),
                }
    elif S == 1:
        with jax.named_scope("mla_decode"):
            # decode: absorbed form — score via latent space, cache stays rank-sized
            slot = positions[:, 0] % cache["ckv"].shape[1]
            new_cache = {"ckv": ckv[:, 0], "krope": k_rope[:, 0], "pos": positions[:, 0]}
            ckv_c, kr_c, kp = (_with_row(cache[n], new_cache[n], slot)
                               for n in ("ckv", "krope", "pos"))
            # q_eff[h] = q_nope[h] @ w_uk[h] : (B,H,rank)
            q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
            s = jnp.einsum("bhr,blr->bhl", q_eff.astype(jnp.float32), ckv_c.astype(jnp.float32))
            s = s + jnp.einsum(
                "bhd,bld->bhl", q_rope[:, 0].astype(jnp.float32), kr_c.astype(jnp.float32)
            )
            s = s / math.sqrt(nope + rope_d)
            valid = (kp[:, None, :] >= 0) & (kp[:, None, :] <= positions[:, 0][:, None, None])
            s = jnp.where(valid, s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bhl,blr->bhr", p, ckv_c.astype(jnp.float32))  # (B,H,rank)
            out = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv.astype(jnp.float32))
            out = out[:, None].astype(x.dtype)  # (B,1,H,vd)
    else:
        with jax.named_scope("mla_segment"):
            # segment decode: absorbed form with S queries, per-query masks
            L = cache["ckv"].shape[1]
            if S > L:
                raise ValueError(f"segment length {S} exceeds cache length {L}")
            ckv_c, kr_c, kp = cache["ckv"], cache["krope"], cache["pos"]
            for s_i in range(S):
                slot = positions[:, s_i] % L
                ckv_c = _store(ckv_c, ckv[:, s_i], slot)
                kr_c = _store(kr_c, k_rope[:, s_i], slot)
                kp = _store(kp, positions[:, s_i], slot)
            q_eff = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
            s = jnp.einsum("bshr,blr->bshl", q_eff.astype(jnp.float32), ckv_c.astype(jnp.float32))
            s = s + jnp.einsum(
                "bshd,bld->bshl", q_rope.astype(jnp.float32), kr_c.astype(jnp.float32)
            )
            s = s / math.sqrt(nope + rope_d)
            valid = (kp[:, None, None, :] >= 0) & (kp[:, None, None, :] <= positions[:, :, None, None])
            s = jnp.where(valid, s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o_lat = jnp.einsum("bshl,blr->bshr", p, ckv_c.astype(jnp.float32))
            out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv.astype(jnp.float32))
            out = out.astype(x.dtype)  # (B,S,H,vd)
            new_cache = {"ckv": ckv_c, "krope": kr_c, "pos": kp}

    out = pdot(out.reshape(B, S, H * vd), params["wo"], mode, wq=params.get("wo_q"))
    return out, new_cache
