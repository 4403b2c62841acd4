"""Pallas TPU kernel: universal CORDIC (Walther) on int32 blocks.

Generalizes ``kernels/cordic/cordic.py`` from circular rotation to the
full mode table — circular vectoring (atan2), hyperbolic vectoring
(sqrt, log), hyperbolic rotation (exp), and the composed tanh/sigmoid
paths (hyperbolic rotation + linear-vectoring division).  Each grid
step loads a (rows, 128) block of Q16.16 operands into VMEM and runs
the fully-unrolled shift-add iteration on the VPU; the atan/atanh
tables are baked in as immediates, exactly like the sincos kernel.

The op bodies are the *same functions* as ``repro.core.cordic`` — the
kernel adds only blocking/padding — so the NumPy-int64 oracles in
``ref.py`` pin down one bit-exact contract for both layers.  All ops
are total on the padding value 0 (atan2(0,0)=0, sqrt(0)=0, exp(0)=1,
log(0)=Q16.16 min, tanh(0)=0), so the tail padding is safe.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu
from repro.core.cordic import (
    HYPER_STAGES,
    atan2_q16_body,
    div_q16_body,
    exp_q16_body,
    log_q16_body,
    sigmoid_q16_body,
    sqrt_q16_body,
    tanh_q16_body,
)
from repro.kernels import default_interpret
from repro.kernels.cordic.cordic import DEFAULT_BLOCK_ROWS, LANE

__all__ = ["UNARY_OPS", "universal_kernel_call", "atan2_kernel_call", "div_kernel_call"]

#: op name -> elementwise Q16.16 body (shared with repro.core.cordic)
UNARY_OPS = {
    "sqrt": sqrt_q16_body,
    "exp": exp_q16_body,
    "log": log_q16_body,
    "tanh": tanh_q16_body,
    "sigmoid": sigmoid_q16_body,
}


def _unary_kernel(in_ref, out_ref, *, op: str, stages: int):
    out_ref[...] = UNARY_OPS[op](in_ref[...], stages)


def _atan2_kernel(y_ref, x_ref, out_ref, *, iterations: int, frac_bits: int):
    out_ref[...] = atan2_q16_body(y_ref[...], x_ref[...], iterations, frac_bits)


def _div_kernel(num_ref, den_ref, out_ref, *, iterations: int):
    out_ref[...] = div_q16_body(num_ref[...], den_ref[...], iterations)


def _blocked_call(kernel, inputs, *, block_rows: int, interpret: Optional[bool]):
    """Flatten int32 operands to (rows, 128) blocks, pad the tail with
    zeros, run the 1-output kernel over a parallel grid, restore shape."""
    if interpret is None:
        interpret = default_interpret()
    shape = inputs[0].shape
    flats = [jnp.ravel(jnp.asarray(v, jnp.int32)) for v in inputs]
    n = flats[0].shape[0]
    per_block = block_rows * LANE
    padded = -(-n // per_block) * per_block
    rows = padded // LANE
    flats = [jnp.pad(f, (0, padded - n)).reshape(rows, LANE) for f in flats]

    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(flats),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*flats)
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(
    jax.jit, static_argnames=("op", "stages", "block_rows", "interpret")
)
def universal_kernel_call(
    w_q,
    *,
    op: str,
    stages: int = HYPER_STAGES,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
):
    """Apply a unary universal-CORDIC op (sqrt/exp/log/tanh/sigmoid) to
    a Q16.16 int32 array of any shape."""
    if op not in UNARY_OPS:
        raise ValueError(f"unknown universal op {op!r}; have {sorted(UNARY_OPS)}")
    kernel = functools.partial(_unary_kernel, op=op, stages=stages)
    return _blocked_call(kernel, [w_q], block_rows=block_rows, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("iterations", "frac_bits", "block_rows", "interpret")
)
def atan2_kernel_call(
    y_q,
    x_q,
    *,
    iterations: int = 16,
    frac_bits: int = 16,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
):
    """atan2(y, x) on Q(m.n) int32 arrays of any (matching) shape.
    ``frac_bits`` selects the output angle format (24 = the Q8.24
    ladder rung; operands are scale-invariant)."""
    kernel = functools.partial(_atan2_kernel, iterations=iterations, frac_bits=frac_bits)
    return _blocked_call(kernel, [y_q, x_q], block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("iterations", "block_rows", "interpret"))
def div_kernel_call(
    num_q,
    den_q,
    *,
    iterations: int = 17,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
):
    """Full-range linear-vectoring division num/den on Q16.16 int32
    arrays (div(0, 0) = 0, so the zero tail padding is safe)."""
    kernel = functools.partial(_div_kernel, iterations=iterations)
    return _blocked_call(kernel, [num_q, den_q], block_rows=block_rows, interpret=interpret)
