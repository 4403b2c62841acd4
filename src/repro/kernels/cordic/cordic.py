"""Pallas TPU kernel: 16-iteration CORDIC sincos on int32 blocks.

The paper's C2 on the vector unit: each grid step loads a (rows, 128)
block of Q16.16 angles into VMEM and runs the fully-unrolled shift-add
iteration on the VPU — integer adds, arithmetic shifts and selects
only, exactly the instruction mix the paper uses on the Xtensa integer
pipeline.  The quadrant normalization is branchless (selects), which is
the paper's §8.2 future-work item and is *free* on a SIMD datapath.

The atan table is baked into the kernel as immediates (64 bytes of
constants — the paper's §4.3.2 footprint), not streamed from HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu
from repro.core.cordic import (
    HALF_PI_Q16,
    PI_Q16,
    TWO_PI_Q16,
    atan_table,
    gain_inverse,
)
from repro.kernels import default_interpret

__all__ = ["cordic_kernel_call", "LANE", "DEFAULT_BLOCK_ROWS"]

LANE = 128               # TPU lane width: minor dim of every block
DEFAULT_BLOCK_ROWS = 256  # (256, 128) int32 x 3 live arrays ~= 384 KiB VMEM


def _kernel(theta_ref, sin_ref, cos_ref, *, iterations: int):
    table = [int(v) for v in atan_table(iterations)]
    k_inv = gain_inverse(iterations)

    theta = theta_ref[...]
    # branchless range reduction to [-pi, pi), then fold to [-pi/2, pi/2]
    r = jnp.remainder(theta + PI_Q16, TWO_PI_Q16) - PI_Q16
    hi = r > HALF_PI_Q16
    lo = r < -HALF_PI_Q16
    z = jnp.where(hi, r - PI_Q16, jnp.where(lo, r + PI_Q16, r))
    negate = hi | lo

    x = jnp.full_like(theta, k_inv)
    y = jnp.zeros_like(theta)
    for i in range(iterations):  # static unroll (paper relies on -O2)
        d_pos = z >= 0
        xs = x >> i
        ys = y >> i
        x, y, z = (
            jnp.where(d_pos, x - ys, x + ys),
            jnp.where(d_pos, y + xs, y - xs),
            jnp.where(d_pos, z - table[i], z + table[i]),
        )

    cos_ref[...] = jnp.where(negate, -x, x)
    sin_ref[...] = jnp.where(negate, -y, y)


@functools.partial(jax.jit, static_argnames=("iterations", "block_rows", "interpret"))
def cordic_kernel_call(
    theta_q,
    *,
    iterations: int = 16,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: Optional[bool] = None,
):
    """sin/cos of a Q16.16 int32 array of any shape.

    Flattens to (rows, 128) blocks; pads the tail; restores the shape.
    """
    if interpret is None:
        interpret = default_interpret()
    shape = theta_q.shape
    flat = jnp.ravel(jnp.asarray(theta_q, jnp.int32))
    n = flat.shape[0]
    per_block = block_rows * LANE
    padded = -(-n // per_block) * per_block
    rows = padded // LANE
    flat = jnp.pad(flat, (0, padded - n)).reshape(rows, LANE)

    grid = (rows // block_rows,)
    sin_q, cos_q = pl.pallas_call(
        functools.partial(_kernel, iterations=iterations),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANE), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(flat)
    return (
        sin_q.reshape(-1)[:n].reshape(shape),
        cos_q.reshape(-1)[:n].reshape(shape),
    )
