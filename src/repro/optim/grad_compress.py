"""Q-format gradient compression for data-parallel reduction
(paper C1 + §8.6 "distributed multi-node linear algebra").

A plain f32 ring all-reduce moves ~2 x size(f32) per device.  The
compressed reducer moves int8 Q-format payloads instead:

    flatten -> [pmax exponent] -> quantize int8 (shared pow2 scale)
      -> all_to_all (each device owns 1/n of the vector)
      -> local int32 sum (exact: n <= 2**24 summands of |q| <= 127)
      -> requantize int8 -> all_gather

Wire bytes: 2 x size(int8) = size(f32)/2 per device — a 4x reduction
versus the f32 ring — visible in the dry-run's collective term (s8
all-to-all / all-gather ops in the HLO).  Error feedback recirculates
the quantization error so SGD convergence is preserved (EF-SGD); the
error-feedback state lives in the optimizer state pytree.

Use inside ``jax.shard_map`` over the DP axes (see
make_dp_train_step); the model axes stay automatic.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["compressed_mean", "make_dp_train_step"]


def _compress_leaf(g, r, axis_name: str, n_dev: int, bits: int):
    """One leaf: returns (mean_gradient, new_residual)."""
    g32 = g.astype(jnp.float32) + r
    flat = g32.reshape(-1)
    n = flat.shape[0]
    pad = -n % n_dev
    if pad:
        flat = jnp.pad(flat, (0, pad))

    # shared power-of-two exponent (paper C1: shift-only rescale)
    amax = jnp.max(jnp.abs(flat))
    amax = jax.lax.pmax(amax, axis_name)
    e = jnp.where(
        amax > 0,
        jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30))).astype(jnp.int32) - (bits - 1),
        0,
    )
    scale = jnp.exp2(-e.astype(jnp.float32))
    qmax = 2 ** (bits - 1) - 1
    q = jnp.clip(jnp.round(flat * scale), -qmax - 1, qmax).astype(jnp.int8)

    # error feedback BEFORE the wire (local quantization error)
    deq_local = q.astype(jnp.float32) * jnp.exp2(e.astype(jnp.float32))
    new_r = (flat - deq_local)[:n].reshape(g.shape)

    # reduce: int8 all_to_all -> exact int32 local sum -> int8 all_gather
    chunks = q.reshape(n_dev, -1)
    recv = jax.lax.all_to_all(chunks, axis_name, split_axis=0, concat_axis=0, tiled=False)
    local_sum = jnp.sum(recv.astype(jnp.int32), axis=0)  # exact
    # requantize the sum (one extra rounding event, bounded by 2**e2).
    # local_sum is in units of the 2**e grid, so the requantization
    # shift is RELATIVE: e2 - e = ceil(log2(n_dev)) — a pure bit shift,
    # the paper's deferred single-shift correction on the wire.
    shift = int(np.ceil(np.log2(n_dev)))
    e2 = e + shift
    q2 = jnp.clip(
        jnp.round(local_sum.astype(jnp.float32) * jnp.float32(2.0 ** -shift)),
        -qmax - 1, qmax,
    ).astype(jnp.int8)
    gathered = jax.lax.all_gather(q2, axis_name, axis=0, tiled=True)
    mean = gathered.astype(jnp.float32) * (jnp.exp2(e2.astype(jnp.float32)) / n_dev)
    return mean[:n].reshape(g.shape), new_r


def compressed_mean(grads, residuals, axis_name: str, n_dev: int, bits: int = 8):
    """Tree-wise compressed DP mean with error feedback.

    grads/residuals: matching pytrees (residuals f32, zeros at init).
    Returns (mean_grads, new_residuals).
    """
    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    out = [_compress_leaf(g, r, axis_name, n_dev, bits) for g, r in zip(flat_g, flat_r)]
    return treedef.unflatten([o[0] for o in out]), treedef.unflatten([o[1] for o in out])


def make_dp_train_step(cfg, opt_cfg, mesh, *, compress_bits: Optional[int] = 8, mode="precise"):
    """Data-parallel train step with explicit (optionally compressed)
    gradient reduction, shard_map'd over the 'data' axis.

    Returns step(params, opt_state, residuals, batch) ->
    (params, opt_state, residuals, metrics).  Parameters replicated
    across 'data' (pure DP); combine with TP by leaving other mesh
    axes automatic.
    """
    from jax.sharding import PartitionSpec as P

    from repro.models.model import train_loss
    from repro.optim.adamw import adamw_update

    n_dev = mesh.shape["data"]

    def local_step(params, opt_state, residuals, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: train_loss(p, batch, cfg, mode=mode), has_aux=True
        )(params)
        if compress_bits is not None:
            grads, residuals = compressed_mean(grads, residuals, "data", n_dev, compress_bits)
        else:
            grads = jax.lax.pmean(grads, "data")
        params, opt_state, om = adamw_update(opt_cfg, params, grads, opt_state)
        metrics = dict(metrics, loss=jax.lax.pmean(loss, "data"), **om)
        return params, opt_state, residuals, metrics

    rep = P()
    bspec = {"tokens": P("data"), "labels": P("data")}
    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(rep, rep, rep, bspec),
            out_specs=(rep, rep, rep, rep),
            check_vma=False,
        )
    )
