"""Pallas TPU kernel: fused FAST-path SwiGLU with ONE deferred correction.

The paper's C3 kernel defers its correction so each output element sees
one rounding event (Eq. 18).  The model-layer FAST path used to undo
that win a layer up: ``swiglu_mlp`` ran three independent
quantize -> int8-dot -> rescale round trips plus a separate CORDIC
activation dispatch, bouncing the gate activation through HBM and f32
between every stage.  This kernel applies the same "keep intermediates
in fast memory, correct once" principle to the whole hidden stage:

* one streamed ``x`` tile feeds BOTH int8xint8 MXU accumulations
  (``x @ Wg`` and ``x @ Wu``) — the activations are quantized once,
  not once per matmul;
* the CORDIC ``sigmoid_q16_body`` (core/cordic, Walther hyperbolic
  mode) is applied to the gate accumulator *inside* the kernel, in
  Q16.16, straight off the VMEM scratch — the pre-activation never
  round-trips through HBM or f32;
* the epilogue applies ONE combined power-of-two correction:
  ``out = acc_g * acc_u * sigmoid(g_q16) * 2**(e_g + e_u - 16)``.
  Both ``exp2`` factors are exact; the only rounding events per output
  element are the single deferred shift of the gate into Q16.16 (the
  sigmoid operand) and the final f32 mantissa round.

K-budget note — the ``@ Wd`` down-projection is NOT fused: contracting
over d_ff needs the full activation row resident, and at the assigned
shapes (gemma2 d_ff=9216, mixtral expert 16384) a ``(bm, d_ff)`` f32
row tile alone exceeds the VMEM budget that double-buffering leaves.
The wired model path instead quantizes the activation once and runs the
down-projection through the cached-weight int8 path (one more deferred
correction — two per layer total vs. three plus an activation bounce).

Grid: ``(M/bm, F/bn, K/bk)`` with K innermost ("arbitrary" semantics);
the two int32 accumulators live in VMEM scratch persisting across the K
steps of one (i, j) tile, exactly like kernels/qmatmul.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import default_interpret
from repro.core.cordic import sigmoid_q16_body

__all__ = [
    "swiglu_body_q16",
    "fused_swiglu_kernel_call",
    "DEFAULT_BM",
    "DEFAULT_BN",
    "DEFAULT_BK",
]

# (bm*bk + 2*bk*bn) int8 + 2*bm*bn int32 acc + bm*bn f32 out ~= 1.1 MiB
# single-buffered — well under VMEM with the Pallas pipeline's x2.
DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512

_RAW_MAX = (1 << 31) - 1


def swiglu_body_q16(acc_g, acc_u, e_g, e_u, *, return_parts: bool = False):
    """The shared element contract of the fused epilogue.

    ``acc_g``/``acc_u``: exact int32 MXU accumulators of the int8 gate /
    up products; ``e_g``/``e_u``: combined power-of-two exponents
    (activation + per-channel weight), broadcastable against the
    accumulators.  Three steps, fixed order (the oracle in ref.py and
    the XLA form in ops.py replay exactly this):

    1. deferred shift of ``acc_g`` into Q16.16 — saturating on the
       left-shift side (sigmoid is flat there anyway), round-half-up on
       the right-shift side: the single integer rounding event;
    2. ``sigmoid_q16_body`` on the Q16.16 gate (integer shift-add);
    3. one combined correction in f32:
       ``(acc_g * acc_u) * sig * 2**(e_g + e_u - 16)`` — both scales
       exact powers of two, silu(g) = g * sigmoid(g) recovered from the
       RAW accumulator so step 1's quantization only touches the
       sigmoid operand.
    """
    acc_g = jnp.asarray(acc_g, jnp.int32)
    acc_u = jnp.asarray(acc_u, jnp.int32)
    e_g = jnp.asarray(e_g, jnp.int32)
    e_u = jnp.asarray(e_u, jnp.int32)

    s = e_g + 16
    sr = jnp.minimum(jnp.maximum(-s, 0), 31)
    sl = jnp.minimum(jnp.maximum(s, 0), 31)
    half = jnp.where(sr > 0, jnp.int32(1) << jnp.maximum(sr - 1, 0), 0)
    shifted_r = (acc_g + half) >> sr
    lim = jnp.int32(_RAW_MAX) >> sl
    shifted_l = jnp.where(
        acc_g > lim,
        jnp.int32(_RAW_MAX),
        jnp.where(acc_g < -lim, jnp.int32(-_RAW_MAX), acc_g << sl),
    )
    gate_q16 = jnp.where(s >= 0, shifted_l, shifted_r)

    sig = sigmoid_q16_body(gate_q16)

    comb = jnp.exp2((e_g + e_u - 16).astype(jnp.float32))
    out = (
        acc_g.astype(jnp.float32) * acc_u.astype(jnp.float32)
    ) * sig.astype(jnp.float32) * comb
    if return_parts:
        return out, gate_q16, sig
    return out


def _kernel(x_ref, wg_ref, wu_ref, ea_ref, eg_ref, eu_ref, out_ref,
            accg_ref, accu_ref, *, nk: int):
    """One (i, j, k) grid step.

    x_ref:  (bm, bk) int8      activation tile (shared by both matmuls)
    wg_ref: (bk, bn) int8      gate-weight tile
    wu_ref: (bk, bn) int8      up-weight tile
    ea_ref: (1, 1)   int32     activation exponent (per-tensor)
    eg_ref: (1, bn)  int32     gate-weight exponents (per-channel)
    eu_ref: (1, bn)  int32     up-weight exponents (per-channel)
    out_ref:(bm, bn) f32       silu(x@Wg) * (x@Wu) tile
    accg_ref/accu_ref: (bm, bn) int32 VMEM scratch accumulators
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    x = x_ref[...]
    dims = (((1,), (0,)), ((), ()))
    accg_ref[...] += jax.lax.dot_general(
        x, wg_ref[...], dimension_numbers=dims, preferred_element_type=jnp.int32
    )
    accu_ref[...] += jax.lax.dot_general(
        x, wu_ref[...], dimension_numbers=dims, preferred_element_type=jnp.int32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        e_g = (ea_ref[0, 0] + eg_ref[0, :])[None, :]
        e_u = (ea_ref[0, 0] + eu_ref[0, :])[None, :]
        out_ref[...] = swiglu_body_q16(accg_ref[...], accu_ref[...], e_g, e_u)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def fused_swiglu_kernel_call(
    x_q,
    wg_q,
    wu_q,
    ea,
    eg,
    eu,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    interpret: Optional[bool] = None,
):
    """Invoke the fused kernel on padded int8 operands.

    x_q: (M, K) int8;  wg_q/wu_q: (K, F) int8
    ea:  () or (1,1) int32 per-tensor activation exponent
    eg/eu: (F,) int32 per-channel weight exponents
    Returns (M, F) float32 ``silu(x@Wg) * (x@Wu)``.

    Zero padding is total for the body: padded accumulators are 0, the
    up factor is 0, so padded outputs are exactly 0 and sliced away.
    """
    if interpret is None:
        interpret = default_interpret()
    M, K = x_q.shape
    K2, F = wg_q.shape
    assert K == K2 and wu_q.shape == wg_q.shape, (x_q.shape, wg_q.shape, wu_q.shape)
    bm_, bn_, bk_ = min(bm, _rup(M, 8)), min(bn, _rup(F, 128)), min(bk, _rup(K, 128))

    Mp, Fp, Kp = _rup(M, bm_), _rup(F, bn_), _rup(K, bk_)
    x_p = jnp.pad(x_q, ((0, Mp - M), (0, Kp - K)))
    wg_p = jnp.pad(wg_q, ((0, Kp - K), (0, Fp - F)))
    wu_p = jnp.pad(wu_q, ((0, Kp - K), (0, Fp - F)))
    eg_p = jnp.pad(jnp.asarray(eg, jnp.int32).reshape(1, F), ((0, 0), (0, Fp - F)))
    eu_p = jnp.pad(jnp.asarray(eu, jnp.int32).reshape(1, F), ((0, 0), (0, Fp - F)))
    ea_ = jnp.asarray(ea, jnp.int32).reshape(1, 1)

    nk = Kp // bk_
    grid = (Mp // bm_, Fp // bn_, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Fp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm_, bn_), jnp.int32),
            pltpu.VMEM((bm_, bn_), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x_p, wg_p, wu_p, ea_, eg_p, eu_p)
    return out[:M, :F]


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
