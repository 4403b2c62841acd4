"""Compile the serving path's kernels, and one decode step, for a TPU
v5e that is described, not attached (one chip of a ``v5e:2x2``
topology), at deepseek_7b's published widths.

What the chip's compiler refuses -- a block not aligned to the tiling,
more fast memory than a kernel may use, a program larger than the
chip's memory -- fails here on the CPU.  Nothing runs: arguments are
shapes only.

The topology is described inside a module fixture and never while a
module is imported: only one process at a time may load the TPU
library, and every pytest-xdist worker imports this file.  The
persistent compilation cache is off around these compiles, since an
entry written for a described chip cannot be read back without one.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

D_MODEL, D_FF, HEAD_DIM, N_HEADS = 4096, 11008, 128, 32
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m", [8, 32, 512])
def test_qmatmul_compiles(one_chip, m):
    from repro.kernels.qmatmul.qmatmul import qmatmul_kernel_call

    s = lambda shape, dt: _shape(one_chip, shape, dt)
    compiled = qmatmul_kernel_call.lower(
        s((m, D_MODEL), jnp.int8), s((D_MODEL, D_FF), jnp.int8),
        s((), jnp.int32), s((D_FF,), jnp.int32), interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("m", [8, 32, 512])
def test_fused_swiglu_compiles(one_chip, m):
    from repro.kernels.fused_mlp.fused_mlp import fused_swiglu_kernel_call

    s = lambda shape, dt: _shape(one_chip, shape, dt)
    compiled = fused_swiglu_kernel_call.lower(
        s((m, D_MODEL), jnp.int8), s((D_MODEL, D_FF), jnp.int8),
        s((D_MODEL, D_FF), jnp.int8), s((), jnp.int32),
        s((D_FF,), jnp.int32), s((D_FF,), jnp.int32), interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("seq", [1, 512])
def test_flash_attention_compiles(one_chip, seq):
    from repro.kernels.flashattn.flashattn import flash_attention_call

    qkv = _shape(one_chip, (N_HEADS, seq, HEAD_DIM), jnp.bfloat16)
    compiled = flash_attention_call.lower(
        qkv, qkv, qkv, scale=1.0 / math.sqrt(HEAD_DIM), interpret=False,
    ).compile()
    _assert_kernel(compiled)


def test_cordic_compiles(one_chip):
    from repro.kernels.cordic.cordic import cordic_kernel_call

    # rope phases of a 2048-position window: (positions, head_dim / 2)
    theta = _shape(one_chip, (2048, HEAD_DIM // 2), jnp.int32)
    _assert_kernel(cordic_kernel_call.lower(theta, interpret=False).compile())


def test_universal_compiles(one_chip):
    from repro.kernels.cordic.universal import universal_kernel_call

    w = _shape(one_chip, (8, D_FF), jnp.int32)
    _assert_kernel(universal_kernel_call.lower(w, op="tanh", interpret=False).compile())


@pytest.mark.parametrize("mode", ["exact", "fast"])
def test_one_layer_decode_step_compiles(one_chip, mode):
    """One decode step of a one-layer deepseek_7b at its published
    widths, at each served rung (``exact`` = f32, ``fast`` = q16_16 on
    the int8 weights a server attaches), for 8 lanes against a
    2048-position f32 cache: the compiler accepts it and it fits one
    chip's memory."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.quantization import QuantizedWeightCache
    from repro.models import decode_step, init_caches, init_params
    from repro.models.layers import attach_quantized_weights

    cfg = dataclasses.replace(get_config("deepseek_7b"), n_layers=1)
    place = lambda tree: jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype), tree)
    params = place(jax.eval_shape(lambda: attach_quantized_weights(
        init_params(cfg, jax.random.PRNGKey(0)), QuantizedWeightCache())))
    caches = place(jax.eval_shape(lambda: init_caches(cfg, 8, 2048, dtype=jnp.float32)))
    tok = _shape(one_chip, (8, 1), jnp.int32)
    pos = _shape(one_chip, (8,), jnp.int32)

    step = jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, cfg, mode=mode),
                   donate_argnums=(3,))
    compiled = step.lower(params, tok, pos, caches).compile()
    # the f32 rung's matmuls run at full precision, the q16_16 rung's not
    assert ("operand_precision={highest,highest}" in compiled.as_text()) == (mode == "exact")
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES
