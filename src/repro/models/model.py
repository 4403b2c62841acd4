"""The language model: embeddings -> scanned periods -> head.

Public step functions (all pure, jit/pjit-ready):

``train_loss``    — causal LM loss with sequence-chunked cross-entropy
                    (never materializes (B, S, V) logits), MoE aux
                    losses, z-loss; remat over periods.
``prefill_step``  — segment forward, returns last-position logits and
                    populated caches.
``decode_rows``   — one token against caches, returning the rows it
                    adds (``write_rows`` writes them).
``decode_step``   — one token against caches, returning the caches.

Modality stubs (phi-3-vision, musicgen): ``extra_embeds`` (B, P, d) are
pre-computed patch/frame embeddings added onto the first P token
positions — the backbone is the assigned architecture; the frontend is
out of scope per the assignment.
"""

from __future__ import annotations

import functools
import inspect
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import Spec, init_from_specs, rms_norm, softcap
from repro.models.transformer import init_period_cache, period_forward, period_specs

__all__ = [
    "param_specs",
    "init_params",
    "init_caches",
    "cache_layout",
    "train_loss",
    "prefill_step",
    "decode_rows",
    "decode_step",
    "write_rows",
    "segment_step",
    "commit_segment",
    "write_cache_slot",
    "truncate_cache_slot",
]

Constrain = Callable[[jnp.ndarray, str], jnp.ndarray]
_id: Constrain = lambda x, kind: x


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def _stack_spec(s: Spec, n: int) -> Spec:
    return Spec((n,) + s.shape, ("layer",) + s.axes, s.dtype, s.init, s.scale)


def param_specs(cfg: ModelConfig) -> dict:
    period = period_specs(cfg)
    stacked = jax.tree.map(
        lambda s: _stack_spec(s, cfg.n_periods),
        period,
        is_leaf=lambda x: isinstance(x, Spec),
    )
    out = {
        "embed": Spec((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "periods": stacked,
        "final_norm": Spec((cfg.d_model,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = Spec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return out


# Alias used by config.param_count()
param_shapes = param_specs


def init_params(cfg: ModelConfig, key, dtype=jnp.float32):
    params = init_from_specs(param_specs(cfg), key)
    return jax.tree.map(lambda x: x.astype(dtype), params)


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
                quantized: bool = False):
    """Stacked (n_periods, ...) cache pytree.  quantized=True stores
    attention KV in Q-format int8 (+ per-slot exponents)."""
    one = init_period_cache(cfg, batch, max_len, dtype, quantized=quantized)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_periods,) + x.shape).copy(), one
    )


def cache_layout(cfg: ModelConfig, max_len: int):
    """The model's cache-memory layout, one entry per period position:
    ``(key, kind, L)`` where ``key`` is the cache-tree key (``pos{i}``),
    ``kind`` is the layer kind and ``L`` is the POSITION-INDEXED cache
    length (``min(window, max_len)`` for sliding-window attention,
    ``max_len`` for full attention / MLA) — or ``None`` for cumulative
    state (SSM), which is O(1) per slot and position-free.

    This is the single source of truth the paged
    :class:`~repro.runtime.cachepool.PagedCachePool` builds its page
    groups from: position-indexed caches page; cumulative caches stay
    slot-contiguous.
    """
    out = []
    for i, spec in enumerate(cfg.period):
        if spec.kind in ("attn",):
            L = min(spec.window, max_len) if spec.window else max_len
            out.append((f"pos{i}", spec.kind, L))
        elif spec.kind == "mla":
            out.append((f"pos{i}", "mla", max_len))
        else:
            out.append((f"pos{i}", spec.kind, None))
    return out


def truncate_cache_slot(pool, cfg: ModelConfig, slot, keep_pos, ssm_snapshot=None):
    """Roll ONE batch slot of a stacked cache pool back so only entries
    at positions ``< keep_pos`` survive.  Position-indexed caches
    (attn/mla) drop the rejected entries in place; SSM caches are
    cumulative, so their rollback needs ``ssm_snapshot`` — a mapping
    ``pos{i} -> {"state", "conv"}`` with leaves ``(n_periods, ...)``
    holding the slot's cache contents as of ``keep_pos`` (e.g. the
    per-position states from :func:`segment_step`'s ``seg_aux``).  Raises if the
    model has SSM layers and no snapshot is given.  ``slot`` and
    ``keep_pos`` may be traced — jit-safe."""
    from repro.models import attention as attn

    out = {}
    for i, spec in enumerate(cfg.period):
        key = f"pos{i}"
        if spec.kind in ("attn", "mla"):
            out[key] = jax.vmap(
                lambda c: attn.truncate_attn_cache_slot(c, slot, keep_pos)
            )(pool[key])
        else:
            if ssm_snapshot is None or key not in ssm_snapshot:
                raise ValueError(
                    "truncate_cache_slot: SSM caches are cumulative and "
                    f"need an ssm_snapshot entry for {key}"
                )
            snap = ssm_snapshot[key]
            out[key] = {
                k: pool[key][k].at[:, slot].set(snap[k].astype(pool[key][k].dtype))
                for k in pool[key]
            }
    return out


def write_cache_slot(pool, single, slot):
    """Scatter a single-request cache tree (leaves (n_periods, 1, ...))
    into batch slot ``slot`` of a stacked pool — the admission write of
    a freshly prefilled request.  The single cache is fully populated
    from a zero init, so the write itself is also a complete reset of
    the slot.  ``slot`` may be traced — jit-safe."""
    return jax.tree.map(
        lambda p, s: p.at[:, slot].set(s[:, 0].astype(p.dtype)), pool, single
    )


# ---------------------------------------------------------------------------
# backbone
# ---------------------------------------------------------------------------


def _embed(params, tokens, cfg: ModelConfig, extra_embeds=None):
    """Training embedding: cast the table BEFORE the gather, so the FSDP
    all-gather of a sharded table (and the row gather itself) moves
    bf16, not the f32 master copy.  The serving steps run on one chip,
    where that cast would rewrite the whole table per dispatch to read a
    few rows: they gather first (:func:`_embed_rows`)."""
    x = jnp.take(params["embed"].astype(jnp.bfloat16), tokens, axis=0)
    return _add_stub_prefix(x, cfg, extra_embeds)


def _embed_rows(params, tokens, cfg: ModelConfig, extra_embeds=None):
    """Serving embedding: gather the token rows from the table, then
    round just those rows to bf16 -- the same bits as :func:`_embed`,
    since rounding is per element.  The rounding is
    ``lax.reduce_precision``: the TPU compiler may drop an f32 -> bf16 ->
    f32 ``astype`` round trip, which the exact rung's upcast would make."""
    rows = jnp.take(params["embed"], tokens, axis=0)
    rows = jax.lax.reduce_precision(rows, exponent_bits=8, mantissa_bits=7)
    return _add_stub_prefix(rows.astype(jnp.bfloat16), cfg, extra_embeds)


def _scale_embed(x, cfg: ModelConfig):
    """Embedding output times ``cfg.scale_emb`` where the model has one
    (MiniCPM)."""
    return x if cfg.scale_emb is None else x * cfg.scale_emb


def _serve_embed(params, tokens, cfg: ModelConfig, mode: str, extra_embeds=None):
    """The serving steps' embedding: :func:`_embed_rows`, upcast to f32 on
    the exact rung (so the scale below is not rounded to bf16 there),
    then :func:`_scale_embed`."""
    x = _embed_rows(params, tokens, cfg, extra_embeds)
    if mode == "exact":
        x = x.astype(jnp.float32)
    return _scale_embed(x, cfg)


def _add_stub_prefix(x, cfg: ModelConfig, extra_embeds):
    if extra_embeds is not None and cfg.stub_prefix_len:
        P = cfg.stub_prefix_len
        x = jnp.concatenate(
            [x[:, :P] + extra_embeds.astype(x.dtype), x[:, P:]], axis=1
        )
    return x


def _backbone_train(params, x, cfg: ModelConfig, positions, mode, constrain, remat: bool):
    def body(carry, period_params):
        h, aux = carry
        h2, _, a = period_forward(
            period_params, h, cfg, positions=positions, mode=mode, constrain=constrain
        )
        return (h2, aux + a), None

    fn = jax.checkpoint(body, prevent_cse=False) if remat else body
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((2,), jnp.float32)), params["periods"])
    return x, aux


def _final_norm(params, x, cfg: ModelConfig):
    """The final RMSNorm, then the head-input divisor where the model has
    one (MiniCPM: hidden size / dim_model_base)."""
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    return x if cfg.head_divisor is None else x / cfg.head_divisor


def _lm_head(params, cfg: ModelConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# training loss (chunked CE)
# ---------------------------------------------------------------------------


def _chunked_ce(hidden, head, labels, mask, cfg: ModelConfig, chunk: int = 256,
                mode: str = "precise"):
    """hidden (B,S,d), head (d,V), labels (B,S) -> (sum_loss, sum_zloss, count).

    Scans sequence chunks; the (B, chunk, V) logits are transient.
    """
    B, S, d = hidden.shape
    chunk = min(chunk, S)
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))

    h_c = hidden.reshape(B, n, chunk, d).swapaxes(0, 1)
    l_c = labels.reshape(B, n, chunk).swapaxes(0, 1)
    m_c = mask.reshape(B, n, chunk).swapaxes(0, 1)

    def step(carry, blk):
        loss_s, z_s, cnt = carry
        h, lab, m = blk
        logits = jnp.dot(
            h.astype(jnp.bfloat16), head.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        logits = softcap(logits, cfg.final_softcap, mode)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * m
        return (loss_s + ce.sum(), z_s + ((lse * m) ** 2).sum(), cnt + m.sum()), None

    init = (jnp.float32(0), jnp.float32(0), jnp.float32(0))
    (loss_s, z_s, cnt), _ = jax.lax.scan(step, init, (h_c, l_c, m_c))
    return loss_s, z_s, cnt


def train_loss(
    params,
    batch: dict,
    cfg: ModelConfig,
    mode: str = "precise",
    constrain: Constrain = _id,
    remat: bool = True,
    z_coef: float = 1e-4,
):
    """batch: tokens (B,S), labels (B,S), optional loss_mask, extra_embeds.

    Returns (loss, metrics dict).
    """
    tokens = batch["tokens"]
    labels = batch["labels"]
    mask = batch.get("loss_mask", jnp.ones_like(labels, jnp.float32))
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    x = _scale_embed(_embed(params, tokens, cfg, batch.get("extra_embeds")), cfg)
    x = constrain(x, "residual")
    x, aux = _backbone_train(params, x, cfg, positions, mode, constrain, remat)
    x = _final_norm(params, x, cfg)

    loss_s, z_s, cnt = _chunked_ce(x, _lm_head(params, cfg), labels, mask, cfg, mode=mode)
    ce = loss_s / jnp.maximum(cnt, 1.0)
    z_loss = z_coef * z_s / jnp.maximum(cnt, 1.0)
    loss = ce + z_loss
    metrics = {"ce": ce, "z_loss": z_loss, "tokens": cnt}
    if cfg.moe is not None:
        lb, rz = aux[0] / cfg.n_periods, aux[1] / cfg.n_periods
        loss = loss + cfg.moe.aux_loss_coef * lb + cfg.moe.router_z_coef * rz
        metrics.update({"moe_lb": lb, "moe_z": rz})
    return loss, metrics


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------


def _scan_with_caches(params, x, caches, cfg, positions, mode, constrain, *,
                      prefill, collect_aux: bool = False):
    """Scan periods with the stacked cache in the CARRY, updated in
    place via dynamic_update_index — ONE cache buffer end to end, for
    the steps that write whole segments (prefill, segment decode).
    Single-token decode writes one row a layer and scans with
    :func:`_scan_rows` instead.

    (Passing caches as scan xs/ys double-buffers them: the stacked ys
    output is distinct from the xs input, costing a full extra cache
    per device — fatal for 32k decode cells.  Measured in EXPERIMENTS.md
    §Perf iteration P2.)

    ``collect_aux=True`` (segment decode): each period's segment
    rollback state rides out as scan ys, stacked to leaves of shape
    ``(n_periods, ...)`` — a third return value.
    """

    def body(carry, xs):
        h, all_caches = carry
        period_params, i = xs
        cache_i = jax.tree.map(
            lambda c: jax.lax.dynamic_index_in_dim(c, i, 0, keepdims=False), all_caches
        )
        seg_aux = {} if collect_aux else None
        h2, new_cache, _ = period_forward(
            period_params, h, cfg,
            positions=positions, mode=mode, caches=cache_i, prefill=prefill,
            constrain=constrain, seg_aux=seg_aux,
        )
        all_caches = jax.tree.map(
            lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n.astype(c.dtype), i, 0),
            all_caches, new_cache,
        )
        return (h2, all_caches), seg_aux

    (x, new_caches), aux = jax.lax.scan(
        body, (x, caches),
        (params["periods"], jnp.arange(cfg.n_periods, dtype=jnp.int32)),
    )
    if collect_aux:
        return x, new_caches, aux
    return x, new_caches


def _scan_rows(params, x, caches, cfg, positions, mode, constrain):
    """The single-token decode scan: the stacked cache is a read-only
    scan operand, and each period's new rows (one per position-indexed
    cache and lane, and each SSM layer's new state) are the scan's
    outputs, leaves ``(n_periods, B, ...)`` in the cache's dtypes (as
    the cache stores them, so every precision rung returns the same
    types).  Nothing cache-sized is written, so the outputs cost a row
    per lane a layer, not a cache."""

    def body(h, xs):
        period_params, cache_i = xs
        h2, rows, _ = period_forward(
            period_params, h, cfg,
            positions=positions, mode=mode, caches=cache_i, constrain=constrain,
        )
        return h2, jax.tree.map(lambda r, c: r.astype(c.dtype), rows, cache_i)

    return jax.lax.scan(body, x, (params["periods"], caches))


def write_rows(caches, rows, position, cfg: ModelConfig):
    """Write :func:`decode_rows`' rows into a stacked cache (leaves
    ``(n_periods, B, ...)``): lane ``b``'s row of a position-indexed
    cache lands at ``position[b] % L`` (one ``.at[:, b, slot]`` scatter
    a leaf); SSM leaves take the new state."""
    B = position.shape[0]
    lanes = jnp.arange(B)
    out = {}
    for i, spec in enumerate(cfg.period):
        key = f"pos{i}"
        c, r = caches[key], rows[key]
        if spec.kind in ("attn", "mla"):
            slot = position % c["pos"].shape[2]
            out[key] = {
                n: c[n].at[:, lanes, slot].set(r[n].astype(c[n].dtype), mode="drop")
                for n in c
            }
        else:
            out[key] = {n: r[n].astype(c[n].dtype) for n in c}
    return out


def _exact_at_highest(step):
    """Run a serving step's matmuls at ``highest`` precision when it is
    called with mode="exact", and at the backend default otherwise.

    On a TPU an f32 matmul at default precision rounds both operands to
    bf16 (one MXU pass).  That turns the f32-scale accumulation-order
    noise between a prefill and a decode derivation of the same prefix
    into bf16-ulp steps, and near-tied logits then rank differently.
    The precision is set while the step is traced and only for the
    exact rung, so the cheaper rungs keep the default.
    """
    sig = inspect.signature(step)

    @functools.wraps(step)
    def run(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        if bound.arguments["mode"] != "exact":
            return step(*args, **kwargs)
        with jax.default_matmul_precision("highest"):
            return step(*args, **kwargs)

    return run


@_exact_at_highest
def prefill_step(
    params,
    tokens,
    caches,
    cfg: ModelConfig,
    mode: str = "precise",
    constrain: Constrain = _id,
    extra_embeds=None,
):
    """tokens (B,S) from position 0; returns (last_logits (B,V), caches').

    mode="exact" (serving): f32 residual stream and f32 head so the
    prefill and decode derivations of the same prefix agree to f32
    noise — bf16 rounding of an O(1e3) hybrid residual stream costs a
    full ulp (O(10)) per store and broke jamba's greedy consistency.
    """
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    x = _serve_embed(params, tokens, cfg, mode, extra_embeds)
    x, new_caches = _scan_with_caches(params, x, caches, cfg, positions, mode, constrain, prefill=True)
    x = _final_norm(params, x[:, -1:], cfg)
    head_dt = jnp.float32 if mode == "exact" else jnp.bfloat16
    logits = jnp.dot(
        x[:, 0].astype(head_dt),
        _lm_head(params, cfg).astype(head_dt),
        preferred_element_type=jnp.float32,
    )
    return softcap(logits, cfg.final_softcap, mode), new_caches


@_exact_at_highest
def decode_rows(
    params,
    token,
    position,
    caches,
    cfg: ModelConfig,
    mode: str = "precise",
    constrain: Constrain = _id,
    lane_mask=None,
):
    """token (B,1) at scalar-per-batch ``position`` (B,) -> (logits, rows).

    ``rows`` has the cache tree's keys with leaves ``(n_periods, B,
    ...)``: the one row each position-indexed cache gains (it lands at
    ``position % L``) and each SSM layer's new state.  Each layer
    attends over its cache with its new row selected in at that slot,
    so the logits are :func:`decode_step`'s bit for bit; the caches are
    only read.  The serving steps hand the rows to the cache's commit
    (:func:`write_rows`, ``PagedCachePool.commit_rows``).

    mode="exact": see :func:`prefill_step` — the serving-consistency
    f32 path.

    ``lane_mask`` (B,) zeroes non-member lanes at the embedding.  The
    continuous-batching server passes its slot mask here: the FAST
    path's PER-TENSOR activation exponents take their amax over the
    whole batch, so without the mask an f32 neighbor's activations
    would perturb a q16_16 request's quantization — masked, a pass's
    input tensor is independent of what the other lanes hold, which is
    what makes a slot's output identical to running it alone.
    """
    B = token.shape[0]
    positions = position.reshape(B, 1).astype(jnp.int32)
    x = _serve_embed(params, token, cfg, mode)
    if lane_mask is not None:
        x = x * lane_mask.astype(x.dtype)[:, None, None]
    x, rows = _scan_rows(params, x, caches, cfg, positions, mode, constrain)
    x = _final_norm(params, x, cfg)
    head_dt = jnp.float32 if mode == "exact" else jnp.bfloat16
    logits = jnp.dot(
        x[:, 0].astype(head_dt),
        _lm_head(params, cfg).astype(head_dt),
        preferred_element_type=jnp.float32,
    )
    return softcap(logits, cfg.final_softcap, mode), rows


def decode_step(
    params,
    token,
    position,
    caches,
    cfg: ModelConfig,
    mode: str = "precise",
    constrain: Constrain = _id,
    lane_mask=None,
):
    """token (B,1) at scalar-per-batch ``position`` (B,) -> (logits,
    caches'): :func:`decode_rows`, then :func:`write_rows` on every
    lane."""
    logits, rows = decode_rows(
        params, token, position, caches, cfg, mode=mode, constrain=constrain,
        lane_mask=lane_mask,
    )
    return logits, write_rows(caches, rows, position.reshape(-1).astype(jnp.int32), cfg)


@_exact_at_highest
def segment_step(
    params,
    tokens,
    positions,
    caches,
    cfg: ModelConfig,
    mode: str = "exact",
    constrain: Constrain = _id,
    lane_mask=None,
):
    """Mid-sequence segment forward: ``tokens`` (B,S) at explicit
    ``positions`` (B,S) against populated caches — the speculative-
    verify pass.  Returns ``(logits (B,S,V), caches', seg_aux)``.

    All S positions are scored in ONE pass (this is where speculative
    decoding's verification throughput comes from); the caches come
    back with the whole segment committed, and ``seg_aux`` holds the
    per-position SSM rollback candidates for
    :func:`commit_segment` to roll rejected suffixes back.

    mode="exact": the f32 serving-consistency path — required for the
    token-exactness contract (verification logits must match what
    vanilla f32 decode would have produced).
    """
    B, S = tokens.shape
    x = _serve_embed(params, tokens, cfg, mode)
    if lane_mask is not None:
        x = x * lane_mask.astype(x.dtype)[:, None, None]
    x, new_caches, seg_aux = _scan_with_caches(
        params, x, caches, cfg, positions.astype(jnp.int32), mode, constrain,
        prefill=False, collect_aux=True,
    )
    x = _final_norm(params, x, cfg)
    head_dt = jnp.float32 if mode == "exact" else jnp.bfloat16
    logits = jnp.einsum(
        "bsd,dv->bsv",
        x.astype(head_dt),
        _lm_head(params, cfg).astype(head_dt),
        preferred_element_type=jnp.float32,
    )
    return softcap(logits, cfg.final_softcap, mode), new_caches, seg_aux


def commit_segment(before, after, seg_aux, cfg: ModelConfig, *,
                   keep_pos, keep_count, active):
    """Merge a verified segment into the cache pool, rolling REJECTED
    positions back bit-for-bit.

    ``before``/``after``: the stacked cache pool as of before/after
    :func:`segment_step` (leaves ``(n_periods, B, ...)``).
    ``seg_aux``: the third return of :func:`segment_step`.
    ``keep_pos`` (B,): last accepted position — cache entries at
    positions ``> keep_pos`` revert to their pre-segment contents
    (which correctly restores even wrapped sliding-window slots the
    segment overwrote).  ``keep_count`` (B,): number of accepted
    segment positions (>= 1 for active lanes).  ``active`` (B,) bool:
    lanes not in the segment keep their ``before`` caches untouched.
    """
    out = {}
    for i, spec in enumerate(cfg.period):
        key = f"pos{i}"
        b, a = before[key], after[key]
        if spec.kind in ("attn", "mla"):
            rejected = (a["pos"] > keep_pos[None, :, None]) | (~active[None, :, None])
            merged = {}
            for name, av in a.items():
                mask = rejected.reshape(rejected.shape + (1,) * (av.ndim - 3))
                merged[name] = jnp.where(mask, b[name], av)
            out[key] = merged
        else:  # mamba: cumulative state — select the per-position candidates
            states = seg_aux[key]["states"]          # (P,B,S,nh,ds,hd) f32
            conv_hist = seg_aux[key]["conv_hist"]    # (P,B,K-1+S,C)
            S = states.shape[2]
            Km1 = conv_hist.shape[2] - S
            idx = jnp.clip(keep_count - 1, 0, S - 1).astype(jnp.int32)
            sel = jnp.take_along_axis(
                states, idx.reshape(1, -1, 1, 1, 1, 1), axis=2
            )[:, :, 0]
            rows = (
                jnp.clip(keep_count, 0, S).astype(jnp.int32).reshape(1, -1, 1, 1)
                + jnp.arange(Km1, dtype=jnp.int32).reshape(1, 1, -1, 1)
            )
            conv = jnp.take_along_axis(conv_hist, jnp.broadcast_to(
                rows, conv_hist.shape[:2] + (Km1, conv_hist.shape[3])), axis=2)
            am = active.reshape(1, -1, 1, 1, 1)
            out[key] = {
                "state": jnp.where(am, sel.astype(b["state"].dtype), b["state"]),
                "conv": jnp.where(am[..., 0], conv.astype(b["conv"].dtype), b["conv"]),
            }
    return out
