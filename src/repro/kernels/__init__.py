"""Pallas TPU kernels for the paper's compute hot-spots.

qmatmul/   — C3: tiled int8 matmul with deferred power-of-two rescale
cordic/    — C2: 16-iteration shift-add sincos on VPU blocks
flashattn/ — C3's tiling discipline applied to attention: fused
             online-softmax forward (the named remedy for the dominant
             memory term measured in EXPERIMENTS.md §Roofline)

Each subpackage: <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper), ref.py (NumPy-int64 oracle). Validated in
tests/test_kernel_*.py with interpret=True shape/dtype sweeps.
"""

from __future__ import annotations

import functools

import jax

__all__ = ["default_interpret"]


@functools.lru_cache(maxsize=None)
def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled kernels on TPU backends,
    interpreter everywhere else (the CPU test suite).

    Every kernel entrypoint takes ``interpret=None`` and resolves it
    here, so real hardware runs compiled Mosaic kernels without any
    call-site changes.  Cached: the backend cannot change mid-process.
    Entry points that must run on the chip (``chip_smoke.py``) assert
    this is ``False`` rather than trusting it.
    """
    return jax.default_backend() != "tpu"
