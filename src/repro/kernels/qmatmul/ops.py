"""Public ops for the quantized matmul kernel.

``qmatmul``      — float in / float out W8A8 matmul through the Pallas
                   kernel (quantize -> int8 MXU -> deferred rescale).
``qmatmul_q16``  — Q16.16-raw output variant (the paper's native type).
``qmatmul_int16``— W8A16: activations as hi/lo int8 limbs (two kernel
                   passes + shift-combine), the paper's §8.1 "paired
                   registers" answer to the missing wide multiplier.
``qdot_ste``     — differentiable wrapper (straight-through estimator)
                   used by the FAST training path: quantized forward,
                   float backward.

``interpret=None`` auto-detects via ``repro.kernels.default_interpret``:
compiled Mosaic kernels on TPU, interpreter everywhere else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import dequantize_pow2, quantize_pow2
from repro.kernels.qmatmul.qmatmul import qmatmul_kernel_call

__all__ = ["qmatmul", "qmatmul_q16", "qmatmul_int16", "qdot_ste"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmatmul(a, b, interpret: Optional[bool] = None):
    """float (M,K) x (K,N) -> float32 (M,N) via the W8A8 fast path."""
    aq = quantize_pow2(a, bits=8, axis=None)
    bq = quantize_pow2(b, bits=8, axis=1)  # per-output-channel
    return qmatmul_kernel_call(
        aq.q, bq.q, aq.exp, bq.exp.reshape(-1), epilogue="float", interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmatmul_q16(a, b, interpret: Optional[bool] = None):
    """float x float -> raw Q16.16 int32 output (paper-native type)."""
    aq = quantize_pow2(a, bits=8, axis=None)
    bq = quantize_pow2(b, bits=8, axis=1)
    return qmatmul_kernel_call(
        aq.q, bq.q, aq.exp, bq.exp.reshape(-1), epilogue="q16", interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmatmul_int16(a, b, interpret: Optional[bool] = None):
    """W8A16: 16-bit activations split into int8 limbs (paper §8.1).

    a is quantized to int16 with a per-tensor pow2 scale, then split:
        a16 = a_hi * 2**8 + a_lo,  a_hi = asr(a16, 8) in [-128, 127],
        a_lo = a16 & 0xFF in [0, 255].
    The unsigned low limb is made MXU-friendly (int8) by the standard
    zero-point trick: a_lo - 128, corrected with a column-sum term.
    Two kernel passes accumulate exactly; ONE deferred rescale total.
    """
    aq = quantize_pow2(a, bits=16, axis=None)
    bq = quantize_pow2(b, bits=8, axis=1)
    a16 = aq.q.astype(jnp.int32)
    a_hi = (a16 >> 8).astype(jnp.int8)
    a_lo_u = (a16 & 0xFF).astype(jnp.int32)
    a_lo = (a_lo_u - 128).astype(jnp.int8)

    zero_e = jnp.zeros((), jnp.int32)
    eb = bq.exp.reshape(-1)
    hi = qmatmul_kernel_call(a_hi, bq.q, zero_e, eb * 0, epilogue="int32", interpret=interpret)
    lo = qmatmul_kernel_call(a_lo, bq.q, zero_e, eb * 0, epilogue="int32", interpret=interpret)
    # zero-point correction: sum_k 128 * b[k, n]
    col = 128 * jnp.sum(bq.q.astype(jnp.int32), axis=0)  # (N,)
    acc = (hi << 8) + lo + col[None, :]
    scale = jnp.exp2((aq.exp + bq.exp.reshape(1, -1)).astype(jnp.float32))
    return acc.astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def qdot_ste(a, b, interpret: Optional[bool] = None):
    """Quantized forward / float backward (straight-through estimator)."""
    return qmatmul(a, b, interpret=interpret)


def _qdot_fwd(a, b, interpret):
    return qmatmul(a, b, interpret=interpret), (a, b)


def _qdot_bwd(interpret, res, g):
    a, b = res
    return (
        jnp.matmul(g, b.T.astype(g.dtype)),
        jnp.matmul(a.T.astype(g.dtype), g),
    )


qdot_ste.defvjp(_qdot_fwd, _qdot_bwd)
