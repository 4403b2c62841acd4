"""Mamba-2 (SSD — state-space duality) mixer layer.

Training/prefill uses the chunked dual form: within a chunk the
recurrence is expressed as a masked-attention-like quadratic product;
across chunks a sequential ``lax.scan`` carries the (heads, d_state,
head_dim) state — O(S) total work, O(chunk^2) intra-chunk.

Decode is the pure recurrence: ``h = exp(dt*A) h + dt * B (x)``,
``y = C h + D x`` — one token, no sequence dimension, which is what
makes the 500k-context cell trivially sub-quadratic for this family.

Jamba's mamba layers reuse this block with d_state=16 (noted in
DESIGN.md: Jamba ships Mamba-1 layers; we adapt to the SSD form with
matching state size — same state capacity, TPU-friendlier compute).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import Spec, attn_norm_spec, pdot, rms_norm

__all__ = ["ssm_specs", "ssm_forward", "init_ssm_cache"]


def ssm_specs(cfg: ModelConfig) -> dict:
    d = cfg.d_model
    s = cfg.ssm
    d_in = s.d_inner(d)
    nh = s.n_heads(d)
    gs = s.n_groups * s.d_state
    conv_dim = d_in + 2 * gs
    return {
        "norm": attn_norm_spec(d),
        "wz": Spec((d, d_in), ("embed", "ssm")),
        "wx": Spec((d, d_in), ("embed", "ssm")),
        "wB": Spec((d, gs), ("embed", None)),
        "wC": Spec((d, gs), ("embed", None)),
        "wdt": Spec((d, nh), ("embed", None)),
        "conv_w": Spec((s.d_conv, conv_dim), (None, "ssm"), scale=0.5),
        "conv_b": Spec((conv_dim,), ("ssm",), init="zeros"),
        "A_log": Spec((nh,), (None,), init="uniform", scale=1.0),
        "D": Spec((nh,), (None,), init="ones"),
        "dt_bias": Spec((nh,), (None,), init="zeros"),
        "out_norm": Spec((d_in,), ("ssm",), init="zeros"),
        "wo": Spec((d_in, d), ("ssm", "embed")),
    }


def init_ssm_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    d_in = s.d_inner(cfg.d_model)
    nh = s.n_heads(cfg.d_model)
    gs = s.n_groups * s.d_state
    conv_dim = d_in + 2 * gs
    return {
        "state": jnp.zeros((batch, nh, s.d_state, s.head_dim), dtype),
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
    }


def _causal_depthwise_conv(x, w, b, carry: Optional[jnp.ndarray] = None):
    """x: (B, S, C) with window w: (K, C).  carry: (B, K-1, C) history
    (decode) or None (train: zero left-pad)."""
    K = w.shape[0]
    if carry is None:
        carry = jnp.zeros((x.shape[0], K - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([carry, x], axis=1)
    out = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(K)
    )
    new_carry = xp[:, -(K - 1) :, :] if K > 1 else carry
    return out + b[None, None, :], new_carry


def _ssd_chunked(x, dt, A, B_, C_, chunk: int, initial_state=None):
    """SSD dual form.

    x:  (B, S, nh, hd)   inputs per head
    dt: (B, S, nh)       positive step sizes
    A:  (nh,)            negative decay rates
    B_: (B, S, ds)       input projections (n_groups=1, broadcast to heads)
    C_: (B, S, ds)       output projections
    initial_state: optional (B, nh, ds, hd) carried-in state (mid-sequence
    continuation: speculative verify segments, chunked prefill) — zeros
    when omitted (training / prefill from position 0).
    Returns (y: (B, S, nh, hd), final_state).
    """
    Bb, S, nh, hd = x.shape
    ds = B_.shape[-1]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_ = jnp.pad(B_, ((0, 0), (0, pad), (0, 0)))
        C_ = jnp.pad(C_, ((0, 0), (0, pad), (0, 0)))

    # chunked views, scan axis first: (nc, B, chunk, ...)
    xc = x.reshape(Bb, nc, chunk, nh, hd).swapaxes(0, 1)
    dtc = dt.reshape(Bb, nc, chunk, nh).swapaxes(0, 1)
    Bc = B_.reshape(Bb, nc, chunk, ds).swapaxes(0, 1)
    Cc = C_.reshape(Bb, nc, chunk, ds).swapaxes(0, 1)

    def step(state, blk):
        xb, dtb, Bb_, Cb = (v.astype(jnp.float32) for v in blk)
        dA = dtb * A[None, None, :]             # (B,L,nh) negative
        l = jnp.cumsum(dA, axis=1)              # within-chunk log-decay
        # intra-chunk: scores[b,h,i,j] = C_i . B_j * exp(l_i - l_j) * dt_j, j <= i
        logdiff = l[:, :, None, :] - l[:, None, :, :]          # (B,L,L,nh)
        causal = jnp.tril(jnp.ones((logdiff.shape[1], logdiff.shape[1]), bool))
        # mask BEFORE exp: above-diagonal logdiff is positive and can
        # overflow to inf, which would poison gradients through where.
        logdiff = jnp.where(causal[None, :, :, None], logdiff, -jnp.inf)
        decay = jnp.exp(logdiff)
        cb = jnp.einsum("bid,bjd->bij", Cb, Bb_)               # (B,L,L)
        w = cb[..., None] * decay * dtb[:, None, :, :]         # (B,L,L,nh)
        y_intra = jnp.einsum("bijh,bjhd->bihd", w, xb)
        # inter-chunk: contribution of carried state
        y_inter = jnp.einsum(
            "bid,bhdp,bih->bihp", Cb, state, jnp.exp(l)
        )
        # state update: decay whole chunk + inject this chunk's inputs
        total = l[:, -1, :]                                    # (B,nh)
        inj = jnp.einsum(
            "bjd,bjhp,bjh->bhdp", Bb_, xb, jnp.exp(total[:, None, :] - l) * dtb
        )
        state = state * jnp.exp(total)[:, :, None, None] + inj
        return state, y_intra + y_inter

    if initial_state is None:
        state0 = jnp.zeros((Bb, nh, ds, hd), jnp.float32)
    else:
        state0 = initial_state.astype(jnp.float32)
    # keep the scanned views in their storage dtype; each step upcasts
    # its own chunk (full-sequence f32 copies were 2x the buffer cost)
    final_state, yc = jax.lax.scan(step, state0, (xc, dtc, Bc, Cc))
    y = yc.swapaxes(0, 1).reshape(Bb, nc * chunk, nh, hd)
    return y[:, :S], final_state


def _ssd_segment(xs, dt, A, Bp, Cp, state0):
    """Sequential recurrence over a short decode segment, emitting the
    state AFTER EVERY position (the rollback candidates for speculative
    verification).  Each scan step performs exactly the einsums of the
    single-token decode branch, so the per-position states match what a
    sequence of single-token decodes would have produced from the same
    layer inputs.

    xs (B,S,nh,hd), dt (B,S,nh), Bp/Cp (B,S,ds), state0 (B,nh,ds,hd)
    -> (y (B,S,nh,hd) f32, states (B,S,nh,ds,hd) f32)
    """

    def step(state, blk):
        xb, dtb, Bb_, Cb = blk
        dA = jnp.exp(dtb * A[None, :])                               # (B,nh)
        inj = jnp.einsum("bd,bhp,bh->bhdp", Bb_, xb, dtb)
        state = state * dA[:, :, None, None] + inj
        y = jnp.einsum("bd,bhdp->bhp", Cb, state)
        return state, (y, state)

    _, (ys, states) = jax.lax.scan(
        step,
        state0,
        (xs.swapaxes(0, 1), dt.swapaxes(0, 1), Bp.swapaxes(0, 1), Cp.swapaxes(0, 1)),
    )
    return ys.swapaxes(0, 1), states.swapaxes(0, 1)


def ssm_forward(
    params,
    x,
    cfg: ModelConfig,
    *,
    mode: str = "precise",
    cache: Optional[dict] = None,
    prefill: bool = False,
    constrain=lambda x, kind: x,
    seg_aux: Optional[dict] = None,
) -> Tuple[jnp.ndarray, Optional[dict]]:
    """x: (B, S, d). cache given + prefill -> populate state from the
    segment; cache given, S==1 -> single-step recurrence decode;
    cache given, S>1, not prefill -> mid-sequence SEGMENT decode (the
    speculative-verify / chunked-continuation path): the recurrence
    continues from the cached state, and — because the SSM state is
    cumulative rather than position-indexed — ``seg_aux`` (a dict the
    caller owns) receives the per-position rollback candidates:
    ``states`` (B,S,nh,ds,hd) and ``conv_hist`` (B,K-1+S,conv_dim)."""
    B, S, d = x.shape
    s = cfg.ssm
    d_in = s.d_inner(d)
    nh = s.n_heads(d)
    gs = s.n_groups * s.d_state

    h = rms_norm(x, params["norm"], cfg.rms_eps)
    z = pdot(h, params["wz"], mode, wq=params.get("wz_q"))
    xs = pdot(h, params["wx"], mode, wq=params.get("wx_q"))
    Bp = pdot(h, params["wB"], mode, wq=params.get("wB_q"))
    Cp = pdot(h, params["wC"], mode, wq=params.get("wC_q"))
    dt_raw = pdot(h, params["wdt"], mode, wq=params.get("wdt_q"))

    conv_in = jnp.concatenate([xs, Bp, Cp], axis=-1)
    conv_out, new_conv = _causal_depthwise_conv(
        conv_in, params["conv_w"], params["conv_b"],
        carry=None if (cache is None or prefill) else cache["conv"],
    )
    # silu in f32, stored bf16: at S=32k the (B, S, conv_dim) buffers
    # are GiB-scale per mamba layer (7/period for jamba) — §Perf P6.
    # "exact" (serving) skips the bf16 round-trip so decode's conv
    # output is bit-aligned with prefill's (decode S=1 buffers are tiny).
    conv_dt = jnp.float32 if mode == "exact" else jnp.bfloat16
    conv_out = jax.nn.silu(conv_out.astype(jnp.float32)).astype(conv_dt)
    xs = constrain(conv_out[..., :d_in].reshape(B, S, nh, s.head_dim), "heads4d")
    Bp = conv_out[..., d_in : d_in + gs]
    Cp = conv_out[..., d_in + gs :]

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    if cache is None or prefill:
        y, final_state = _ssd_chunked(xs, dt, A, Bp, Cp, chunk=s.chunk)
        new_cache = None
        if prefill:
            new_cache = {
                "state": final_state.astype(cache["state"].dtype),
                "conv": new_conv.astype(cache["conv"].dtype),
            }
    elif S == 1:
        state = cache["state"]
        dA = jnp.exp(dt[:, 0, :] * A[None, :])                       # (B,nh)
        inj = jnp.einsum("bd,bhp,bh->bhdp", Bp[:, 0], xs[:, 0], dt[:, 0])
        state = state * dA[:, :, None, None] + inj
        y = jnp.einsum("bd,bhdp->bhp", Cp[:, 0], state)[:, None]     # (B,1,nh,hd)
        new_cache = {"state": state, "conv": new_conv}
    else:
        # mid-sequence segment decode (speculative verify): sequential
        # recurrence from the cached state, per-position states kept
        # as rollback candidates
        y, states = _ssd_segment(
            xs.astype(jnp.float32), dt, A,
            Bp.astype(jnp.float32), Cp.astype(jnp.float32),
            cache["state"].astype(jnp.float32),
        )
        if seg_aux is not None:
            seg_aux["states"] = states
            # the conv input window history: carry ++ this segment's
            # conv inputs — the carry after accepting ``a`` tokens is
            # rows [a : a+K-1]
            seg_aux["conv_hist"] = jnp.concatenate(
                [cache["conv"].astype(conv_in.dtype), conv_in], axis=1
            )
        new_cache = {"state": states[:, -1], "conv": new_conv}

    y = y + xs.astype(jnp.float32) * params["D"][None, None, :, None]
    y = y.reshape(B, S, d_in)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(y.astype(x.dtype), params["out_norm"], cfg.rms_eps)
    out = pdot(y, params["wo"], mode, wq=params.get("wo_q"))
    return out, new_cache
