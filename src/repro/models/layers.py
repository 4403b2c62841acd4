"""Shared layers: parameter specs with logical sharding axes, RMSNorm,
SwiGLU MLP, rotary embeddings (precise fp32 or fast CORDIC fixed-point),
and the precision-dispatched matmul ``pdot`` — the paper's dispatch
table 𝒟 applied at the op level inside models.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "Spec",
    "init_from_specs",
    "rms_norm",
    "softcap",
    "ptanh",
    "psigmoid",
    "psilu",
    "pdot",
    "dot_fast_int8",
    "FAST_MODES",
    "is_fast_mode",
    "snap_q8_8",
    "rope_tables",
    "apply_rope",
    "swiglu_mlp",
    "mlp_specs",
    "attn_norm_spec",
    "WEIGHT_KEYS",
    "attach_quantized_weights",
]


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Spec:
    """Declares one parameter: shape + logical axes + init law.

    ``axes`` are *logical* names ('embed', 'heads', 'mlp', 'vocab',
    'expert', 'ssm', None) resolved to mesh axes by
    repro.distributed.sharding rules at launch time.
    """

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: jnp.dtype = jnp.float32
    init: str = "normal"       # 'normal' | 'zeros' | 'ones' | 'uniform'
    scale: Optional[float] = None  # default: 1/sqrt(fan_in)

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)

    def initializer(self, key):
        if self.init == "zeros":
            return jnp.zeros(self.shape, self.dtype)
        if self.init == "ones":
            return jnp.ones(self.shape, self.dtype)
        fan_in = self.shape[0] if len(self.shape) > 1 else self.shape[-1]
        scale = self.scale if self.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        if self.init == "uniform":
            return jax.random.uniform(key, self.shape, self.dtype, -scale, scale)
        return jax.random.normal(key, self.shape, self.dtype) * scale


def init_from_specs(specs, key):
    """Materialize a pytree of Specs into parameters (smoke scale only)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, Spec))
    keys = jax.random.split(key, len(leaves))
    vals = [s.initializer(k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------

#: model-layer dispatch strings that run the Q-format integer path.
#: "fast" is the paper's Q16.16 rung (W8A8 + CORDIC activations);
#: "fast8" is the q8_8 draft rung used by ladder-speculative decoding —
#: same int8 weight payloads, but activations are first rounded onto
#: the Q8.8 grid, a genuinely coarser datapath (values below 2^-8 are
#: lost, headroom saturates at +/-128).
FAST_MODES = ("fast", "fast8")


def is_fast_mode(mode: str) -> bool:
    """True for any Q-format rung ("fast", "fast8")."""
    return mode in FAST_MODES


def snap_q8_8(x):
    """Round onto the Q8.8 grid: 16-bit fixed point, 8 fractional bits,
    saturating at +/-(2^7).  This is the activation coarsening of the
    q8_8 draft rung — applied BEFORE the W8A8 int8 path, it emulates a
    16-bit fixed-point datapath feeding the paper's deferred-correction
    matmul."""
    xf = x.astype(jnp.float32) * 256.0
    xf = jnp.clip(jnp.round(xf), -32768.0, 32767.0)
    return (xf * (1.0 / 256.0)).astype(x.dtype)


def rms_norm(x, weight, eps: float = 1e-5):
    """RMSNorm with fp32 accumulation (precise-path op by policy: norms
    stay on f^F even in FAST mode — the paper's per-op dispatch)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + weight.astype(jnp.float32))).astype(dtype)


# ---------------------------------------------------------------------------
# precision-dispatched activations: 𝒟[tanh] / 𝒟[sigmoid] inside models
# ---------------------------------------------------------------------------
#
# The FAST paths run the universal-CORDIC Q16.16 forward (core/cordic)
# with an analytic-derivative straight-through backward — the same
# custom_vjp pattern as dot_fast_int8 below, so FAST training steps stay
# differentiable even though the forward is integer shift-add.


@jax.custom_vjp
def _tanh_fast(x):
    from repro.core.cordic import cordic_tanh

    return cordic_tanh(x)


def _tanh_fast_fwd(x):
    y = _tanh_fast(x)
    return y, y


def _tanh_fast_bwd(y, g):
    return (g * (1.0 - y * y),)


_tanh_fast.defvjp(_tanh_fast_fwd, _tanh_fast_bwd)


@jax.custom_vjp
def _sigmoid_fast(x):
    from repro.core.cordic import cordic_sigmoid

    return cordic_sigmoid(x)


def _sigmoid_fast_fwd(x):
    y = _sigmoid_fast(x)
    return y, y


def _sigmoid_fast_bwd(y, g):
    return (g * y * (1.0 - y),)


_sigmoid_fast.defvjp(_sigmoid_fast_fwd, _sigmoid_fast_bwd)


def ptanh(x, mode: str = "precise"):
    """𝒟[tanh]: FAST -> Q16.16 CORDIC (|eps| <= 6e-5, STE backward);
    PRECISE -> IEEE-754.  Inputs are expected in f32."""
    if is_fast_mode(mode):
        return _tanh_fast(x)
    return jnp.tanh(x)


def psigmoid(x, mode: str = "precise"):
    """𝒟[sigmoid]: FAST -> Q16.16 CORDIC (|eps| <= 5e-5, STE backward)."""
    if is_fast_mode(mode):
        return _sigmoid_fast(x)
    return jax.nn.sigmoid(x)


def psilu(x, mode: str = "precise"):
    """𝒟[silu]: x * sigmoid(x) with the sigmoid precision-dispatched;
    the product rule composes with the sigmoid STE under autodiff."""
    if is_fast_mode(mode):
        return x * _sigmoid_fast(x)
    return jax.nn.silu(x)


def softcap(x, cap: Optional[float], mode: str = "precise"):
    """Gemma-2 logit soft-capping: cap * tanh(x / cap), with the tanh
    precision-dispatched.  Attention-*score* capping call sites stay
    PRECISE by policy (like rms_norm: tiny f32 internals where a
    format boundary would cost more than it saves)."""
    if cap is None:
        return x
    return (cap * ptanh(x.astype(jnp.float32) / cap, mode)).astype(x.dtype)


# ---------------------------------------------------------------------------
# precision-dispatched matmul (the per-op 𝒟 inside models)
# ---------------------------------------------------------------------------


def _quant_dims(x, w):
    """per-tensor activation exponent, per-out-channel weight exponents."""
    from repro.core.quantization import quantize_pow2

    xq = quantize_pow2(x, bits=8, axis=None)
    wq = quantize_pow2(w, bits=8, axis=w.ndim - 1)
    return xq, wq


@jax.custom_vjp
def _dot_fast(x, w):
    return _dot_fast_fwd_impl(x, w)


def _dot_fast_fwd_impl(x, w):
    xq, wq = _quant_dims(x, w)
    acc = jax.lax.dot_general(
        xq.q,
        wq.q,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    e = (xq.exp + wq.exp.reshape(-1)).astype(jnp.float32)
    return acc.astype(jnp.float32) * jnp.exp2(e)


def _dot_fast_fwd(x, w):
    return _dot_fast_fwd_impl(x, w), (x, w)


def _dot_fast_bwd(res, g):
    x, w = res
    g = g.astype(jnp.float32)
    gx = jax.lax.dot_general(
        g, w.astype(jnp.float32), (((g.ndim - 1,), (1,)), ((), ()))
    ).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2 = g.reshape(-1, g.shape[-1])
    gw = jnp.matmul(x2.T, g2).astype(w.dtype)
    return gx, gw


_dot_fast.defvjp(_dot_fast_fwd, _dot_fast_bwd)


def _wq_parts(wq):
    """Normalize a pre-quantized weight operand: QTensor or the
    ``{"q": int8, "exp": int32}`` dict stored in augmented param trees."""
    if isinstance(wq, dict):
        return wq["q"], wq["exp"]
    return wq.q, wq.exp


@jax.custom_vjp
def _dot_fast_cached(x, w, q, e):
    return _dot_fast_cached_impl(x, q, e)


def _dot_fast_cached_impl(x, q, e):
    from repro.core.quantization import quantize_pow2

    xq = quantize_pow2(x, bits=8, axis=None)
    acc = jax.lax.dot_general(
        xq.q,
        q,
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    ee = (xq.exp + jnp.asarray(e, jnp.int32).reshape(-1)).astype(jnp.float32)
    return acc.astype(jnp.float32) * jnp.exp2(ee)


def _dot_fast_cached_fwd(x, w, q, e):
    import numpy as np

    # integer operands carry float0 cotangents; stash them concrete
    zeros = (
        np.zeros(q.shape, jax.dtypes.float0),
        np.zeros(e.shape, jax.dtypes.float0),
    )
    return _dot_fast_cached_impl(x, q, e), (x, w, zeros)


def _dot_fast_cached_bwd(res, g):
    x, w, (zq, ze) = res
    gx, gw = _dot_fast_bwd((x, w), g)
    return gx, gw, zq, ze


_dot_fast_cached.defvjp(_dot_fast_cached_fwd, _dot_fast_cached_bwd)


def dot_fast_int8(x, w, wq=None):
    """W8A8 matmul, kernel-equivalent XLA form: int8 x int8 -> int32 MXU
    accumulation, ONE deferred power-of-two rescale (paper C3).

    This is the exact computation the Pallas kernel
    (kernels/qmatmul) performs on real TPU; expressed as
    ``lax.dot_general(..., preferred_element_type=int32)`` it lowers on
    every backend and is what the multi-pod dry-run compiles.  Backward
    is the straight-through estimator (float grads).

    ``wq`` (optional) is a pre-quantized weight operand (QTensor or the
    ``{"q", "exp"}`` dict a :class:`~repro.core.quantization.\
QuantizedWeightCache` attaches to param trees): the per-call weight
    quantization is skipped entirely — bit-identical to the uncached
    path for the same weights, but the decode loop never requantizes.
    """
    if wq is None:
        return _dot_fast(x, w)
    q, e = _wq_parts(wq)
    return _dot_fast_cached(x, w, q, e)


def pdot(x, w, mode: str = "precise", wq=None):
    """𝒟[matmul]: FAST -> W8A8 deferred-rescale path; PRECISE -> bf16
    MXU (per-device f32 accumulation is implicit in the TPU MXU);
    EXACT -> f32 end-to-end (serving-consistency mode, see below).

    Deliberately bf16-in/bf16-out on the PRECISE path, with NO
    preferred_element_type=f32 + downcast: that pattern pins every TP
    partial-sum all-reduce and every backward reshard to fp32 (XLA
    cannot commute the convert through the reduction), doubling
    collective bytes.  Cross-device partial sums in bf16 are the
    Megatron-standard trade.

    EXACT is the *serving* precise path (runtime/serve maps the ``f32``
    ladder level here): a bf16-rounded output quantizes the tiny
    shape-dependent accumulation differences between a (B, S) prefill
    gemm and a (B, 1) decode gemm up to a full bf16 ulp — and at
    hybrid-depth residual magnitudes one residual-stream ulp is O(10),
    which is what made jamba's decode drift from its own prefill
    re-derivation (ROADMAP "Known-failing tier-1 tests").  Keeping the
    serving matmuls in f32 keeps that noise at f32 scale, so greedy
    decode agrees with prefill re-derivation across all families.

    ``wq``: optional cached int8 weights — used by the FAST path only.
    """
    if is_fast_mode(mode):
        if mode == "fast8":
            x = snap_q8_8(x)
        return dot_fast_int8(x, w, wq=wq).astype(jnp.bfloat16)
    dt = jnp.float32 if mode == "exact" else jnp.bfloat16
    return jax.lax.dot_general(
        x.astype(dt),
        w.astype(dt),
        dimension_numbers=(((x.ndim - 1,), (0,)), ((), ())),
    )


# ---------------------------------------------------------------------------
# rotary embeddings: 𝒟[sin/cos]
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("rope_dim", "base", "mode", "factors"))
def rope_tables(positions, rope_dim: int, base: float = 10000.0, mode: str = "precise",
                factors: Optional[Tuple[float, ...]] = None):
    """(… ) int positions -> (…, rope_dim//2) sin/cos tables.

    PRECISE: fp32 ``jnp.sin/cos`` of ``pos * inv_freq``.
    FAST: exact Q0.64 phase accumulation + 16-iteration CORDIC
    (core/cordic) — integer-only, and *more accurate* than the fp32
    path at long-context positions (tests/test_cordic.py).

    ``factors`` (LongRoPE, rope_dim//2 values): frequency i is divided
    by ``factors[i]`` -- on the fast path folded into the host-side
    Q0.64 frequencies, so the phase stays exact integer arithmetic.
    """
    half = rope_dim // 2
    if is_fast_mode(mode):
        from repro.core.cordic import exact_rope_phase_q16, cordic_sincos_q16, rope_inv_freq_q64
        from repro.core.qformat import Q16_16, from_fixed

        f_hi, f_lo = rope_inv_freq_q64(rope_dim, base, factors)
        theta_q = exact_rope_phase_q16(
            positions[..., None], jnp.asarray(f_hi)[None, :], jnp.asarray(f_lo)[None, :]
        )
        sin_q, cos_q = cordic_sincos_q16(theta_q)
        return from_fixed(sin_q, Q16_16), from_fixed(cos_q, Q16_16)
    inv_freq = (base ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rope_dim))
    if factors is not None:
        inv_freq = inv_freq / jnp.asarray(factors, jnp.float32)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.sin(angle), jnp.cos(angle)


def apply_rope(x, sin, cos):
    """x: (..., S, H, D); sin/cos: (..., S, D//2) broadcast over heads.
    Half-split (llama) convention."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    sin = sin[..., None, :]  # add head axis
    cos = cos[..., None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_specs(d_model: int, d_ff: int) -> dict:
    return {
        "norm": Spec((d_model,), ("embed",), init="zeros"),
        "w_gate": Spec((d_model, d_ff), ("embed", "mlp")),
        "w_up": Spec((d_model, d_ff), ("embed", "mlp")),
        "w_down": Spec((d_ff, d_model), ("mlp", "embed")),
    }


def attn_norm_spec(d_model: int) -> Spec:
    return Spec((d_model,), ("embed",), init="zeros")


def _fused_swiglu_fast(h, wgq, wuq):
    """Fused FAST hidden stage on cached int8 weights: one x
    quantization feeding both matmuls, then the kernel-equivalent XLA
    form (kernels/fused_mlp.fused_swiglu_xla — CORDIC sigmoid on the
    Q16.16 gate accumulator, ONE combined power-of-two correction).
    Inference-only: the int8 dots have no VJP; training keeps the
    per-call STE path below.
    """
    from repro.core.quantization import quantize_pow2
    from repro.kernels.fused_mlp.ops import fused_swiglu_xla

    gq, ge = _wq_parts(wgq)
    uq, ue = _wq_parts(wuq)
    xq = quantize_pow2(h, bits=8, axis=None)
    return fused_swiglu_xla(xq.q, gq, uq, xq.exp, ge, ue)


def swiglu_mlp(params, x, mode: str = "precise", eps: float = 1e-5):
    """SwiGLU MLP with the paper's per-op dispatch.

    FAST with cached quantized weights attached (``w_gate_q`` etc., see
    :func:`attach_quantized_weights`): the fused hidden stage — one
    activation quantization, no weight requantization, the activation
    never round-tripping through bf16 — then the down-projection on the
    cached int8 ``w_down`` (one more deferred correction).  Otherwise
    the original three-dispatch path (the training/default route).
    """
    h = rms_norm(x, params["norm"], eps)
    if is_fast_mode(mode) and "w_gate_q" in params:
        if mode == "fast8":
            h = snap_q8_8(h)
        act = _fused_swiglu_fast(h, params["w_gate_q"], params["w_up_q"])
        act = act.astype(jnp.bfloat16)
        return pdot(act, params["w_down"], mode, wq=params["w_down_q"])
    gate = pdot(h, params["w_gate"], mode)
    up = pdot(h, params["w_up"], mode)
    act = psilu(gate.astype(jnp.float32), mode).astype(up.dtype) * up
    return pdot(act, params["w_down"], mode)


# ---------------------------------------------------------------------------
# quantize-once weight attachment (serving FAST path)
# ---------------------------------------------------------------------------

#: param-dict keys consumed through ``pdot`` / the fused MLP-MoE paths.
#: (MLA's ``wkv_b`` is read through absorbed-decode einsums, not pdot,
#: so it stays float.)
WEIGHT_KEYS = frozenset({
    "w_gate", "w_up", "w_down",            # MLP + MoE experts
    "wq", "wk", "wv", "wo",                # attention projections
    "wq_a", "wq_b", "wkv_a",               # MLA low-rank projections
    "wz", "wx", "wB", "wC", "wdt",         # Mamba-2 projections
})


def attach_quantized_weights(params, cache, *, level: str = "q16_16"):
    """Return ``params`` with ``<key>_q = {"q": int8, "exp": int32}``
    entries added next to every :data:`WEIGHT_KEYS` matrix, quantized
    ONCE through ``cache`` (a QuantizedWeightCache — normally
    ``engine.weight_cache``).

    The exponent axes are "everything except the contraction axis"
    (``ndim-2``): per out-channel for 2-D weights, additionally per
    period for scanned stacks, per (period, expert) for MoE — so the
    scanned slice of every added leaf broadcasts exactly like the
    per-call quantization it replaces.  Float leaves are left in place
    (precise path, STE backward, and re-attachment after
    ``engine.invalidate_weights`` all still need them).
    """
    def walk(node, path):
        if isinstance(node, dict):
            out = {k: walk(v, f"{path}/{k}") for k, v in node.items()}
            for k in sorted(WEIGHT_KEYS & node.keys()):
                w = node[k]
                if not hasattr(w, "ndim") or w.ndim < 2:
                    continue
                axis = tuple(i for i in range(w.ndim) if i != w.ndim - 2)
                qt = cache.get(f"{path}/{k}", w, level=level, axis=axis)
                out[k + "_q"] = {"q": qt.q, "exp": qt.exp}
            return out
        return node

    return walk(params, "")
