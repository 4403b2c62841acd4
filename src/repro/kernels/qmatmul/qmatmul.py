"""Pallas TPU kernel: tiled Q-format int8 matmul with deferred rescale.

This is the paper's C3 (cache-aware tiled matmul with deferred-shift
accumulation, Listing 3) re-derived for the TPU memory hierarchy:

* The paper sizes its tile from the ESP32 SRAM bank (``4 b**2 < 8 KB``
  => b = 32).  Here the BlockSpec tile is sized from the VMEM budget
  (``(bm*bk + bk*bn + 2*bm*bn) bytes`` within a few MiB, double
  buffered by the Pallas pipeline) and aligned to the MXU lane width
  (128).  Loop tiling IS BlockSpec — the index maps below are the
  paper's I/J/K block loops.
* The paper accumulates a K-tile in ``int64_t`` and shifts once.  The
  MXU accumulates int8xint8 products *natively and exactly* in int32
  (safe for K <= 2**17), and the single deferred correction is applied
  in the epilogue at the last K step: ONE rounding event per output
  element (paper Eq. 18), versus one per multiply in a
  quantize-per-product scheme.
* Q formats are per-channel powers of two (core/quantization.py), so
  the correction is a shift (q16 epilogue) or an exact exp2 scale
  (float epilogue) — never a true division.

Grid: ``(M/bm, N/bn, K/bk)`` with K innermost ("arbitrary" semantics,
revisiting the same output/accumulator block); A/B blocks stream
through VMEM; the int32 accumulator lives in a VMEM scratch that
persists across the K steps of one (i, j) tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import default_interpret

__all__ = ["qmatmul_kernel_call", "DEFAULT_BM", "DEFAULT_BN", "DEFAULT_BK"]

# Derived from a ~2.5 MiB single-buffer working set (x2 for pipeline
# double-buffering stays well under VMEM), 128-aligned:
#   bm*bk + bk*bn (int8) + bm*bn (int32 acc + int32/f32 out)
DEFAULT_BM = 512
DEFAULT_BN = 512
DEFAULT_BK = 512


def _kernel(a_ref, b_ref, ea_ref, eb_ref, out_ref, acc_ref, *, nk: int, epilogue: str):
    """One (i, j, k) grid step.

    a_ref:  (bm, bk) int8      A tile
    b_ref:  (bk, bn) int8      B tile
    ea_ref: (1, 1)   int32     activation exponent (per-tensor)
    eb_ref: (1, bn)  int32     weight exponents (per-channel)
    out_ref:(bm, bn) int32/f32 output tile
    acc_ref:(bm, bn) int32     VMEM scratch accumulator
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # MXU: int8 x int8 -> exact int32 accumulation (the paper's widened
    # accumulator, natively).
    acc_ref[...] += jax.lax.dot_general(
        a_ref[...],
        b_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        acc = acc_ref[...]
        e = ea_ref[0, 0] + eb_ref[0, :]  # (bn,) combined exponent
        if epilogue == "float":
            # exact power-of-two scale: one multiply, no rounding
            out_ref[...] = acc.astype(jnp.float32) * jnp.exp2(e.astype(jnp.float32))[None, :]
        elif epilogue == "q16":
            # deferred shift to Q16.16: raw = acc * 2**(e + 16)
            s = e + 16
            # s >= 0: left shift (exact); s < 0: round-half-up right shift
            sr = jnp.maximum(-s, 0)
            sl = jnp.maximum(s, 0)
            half = jnp.where(sr > 0, jnp.int32(1) << jnp.maximum(sr - 1, 0), 0)
            shifted = (acc + half[None, :]) >> sr[None, :]
            out_ref[...] = jnp.where(
                (s >= 0)[None, :], acc << sl[None, :], shifted
            ).astype(jnp.int32)
        else:  # 'int32' — raw accumulator (caller rescales)
            out_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("bm", "bn", "bk", "epilogue", "interpret"),
)
def qmatmul_kernel_call(
    a_q,
    b_q,
    ea,
    eb,
    *,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    epilogue: str = "float",
    interpret: Optional[bool] = None,
):
    """Invoke the Pallas kernel on padded int8 operands.

    a_q: (M, K) int8;  b_q: (K, N) int8
    ea:  () or (1,1) int32 per-tensor activation exponent
    eb:  (N,) int32 per-channel weight exponents
    Returns (M, N) float32 (epilogue='float') or int32 Q16.16
    (epilogue='q16') or raw int32 (epilogue='int32').
    ``interpret=None`` auto-detects (compiled on TPU, interpreter off-TPU).
    """
    if interpret is None:
        interpret = default_interpret()
    M, K = a_q.shape
    K2, N = b_q.shape
    assert K == K2, (a_q.shape, b_q.shape)
    bm_, bn_, bk_ = min(bm, _rup(M, 8)), min(bn, _rup(N, 128)), min(bk, _rup(K, 128))

    Mp, Np, Kp = _rup(M, bm_), _rup(N, bn_), _rup(K, bk_)
    a_p = jnp.pad(a_q, ((0, Mp - M), (0, Kp - K)))
    b_p = jnp.pad(b_q, ((0, Kp - K), (0, Np - N)))
    eb_p = jnp.pad(jnp.asarray(eb, jnp.int32).reshape(1, N), ((0, 0), (0, Np - N)))
    ea_ = jnp.asarray(ea, jnp.int32).reshape(1, 1)

    nk = Kp // bk_
    out_dtype = jnp.float32 if epilogue == "float" else jnp.int32

    grid = (Mp // bm_, Np // bn_, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk, epilogue=epilogue),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk_, bn_), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((1, bn_), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_p, b_p, ea_, eb_p)
    return out[:M, :N]


def _rup(x: int, m: int) -> int:
    return -(-x // m) * m
