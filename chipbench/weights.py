"""Model weights made on the device from the run's seed, in one jitted
call, in the types the program serves them in.

The program decides only the *layout* (which leaves, which shapes); the
values are the benchmark's: a fixed law per kind of leaf, drawn from
the seed.  The plain references read the same arrays.

Laws, by the last key of a leaf's path:

* ``embed``: normal x 0.02, rounded to bfloat16 values (the program
  gathers embedding rows in bfloat16 on every rung, so a bfloat16-exact
  table makes that gather lossless and the f32 rung comparable to an f32
  reference).  The rounding is ``lax.reduce_precision``: a round trip
  through ``astype(bfloat16)`` may be removed by the TPU compiler;
* any key holding ``norm``: normal x 0.1 -- a gain *offset*: the layer
  applies ``1 + w``;
* every other leaf (projections, stacked per layer): normal /
  sqrt(fan_in), fan_in being the second-to-last axis.
"""

from __future__ import annotations

import math

import numpy as np


def seed32(seed: int) -> int:
    """A 32-bit key for any whole-number seed (the driver's exceed 2**31)."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def _law(path: str, shape):
    last = path.rsplit("/", 1)[-1]
    if last == "embed":
        return "embed", 0.02
    if "norm" in last:
        return "norm", 0.1
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return "matrix", 1.0 / math.sqrt(max(fan_in, 1))


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _paths(tree[k], f"{prefix}/{k}")
    else:
        yield prefix, tree


def make_weights(shape_tree: dict, seed: int):
    """Arrays shaped like ``shape_tree`` (a dict tree of
    ``ShapeDtypeStruct``), drawn from ``seed`` on the default device."""
    import jax
    import jax.numpy as jnp

    specs = list(_paths(shape_tree))

    def build(key):
        flat = {}
        for i, (path, sd) in enumerate(specs):
            kind, scale = _law(path, sd.shape)
            x = jax.random.normal(jax.random.fold_in(key, i), sd.shape, jnp.float32) * scale
            if kind == "embed":
                x = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
            flat[path] = x.astype(sd.dtype)
        return flat

    flat = jax.jit(build)(jax.random.PRNGKey(seed32(seed)))

    def rebuild(tree, prefix=""):
        if isinstance(tree, dict):
            return {k: rebuild(v, f"{prefix}/{k}") for k, v in tree.items()}
        return flat[prefix]

    return jax.block_until_ready(rebuild(shape_tree))
