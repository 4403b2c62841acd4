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
import re

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


def _whole_table_bf16(hlo_text, vocab, d_model):
    """Instructions outside every fused computation's body (the entry,
    branches of a switch, loop bodies) whose result holds a bfloat16
    array of ``vocab x d_model`` elements: a whole-table convert that
    runs as its own pass over memory.  Inside a fusion (the head's cast
    feeding its dot) it is streamed, and allowed."""
    fused = set(re.findall(r"\bfusion\(.*?calls=(%[\w.\-]+)", hlo_text))
    found, comp = [], None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            comp = line.split(" ", 2)[1] if line.startswith("ENTRY") else line.split(" ", 1)[0]
            continue
        if comp in fused or " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        result = re.match(r"(.*?)\s[a-z][\w\-]*\(", rhs)
        for dims in re.findall(r"bf16\[([\d,]*)\]", result.group(1) if result else ""):
            if dims and math.prod(int(n) for n in dims.split(",")) == vocab * d_model:
                found.append(line.strip()[:160])
    return found


def _one_layer_model(sharding, batch, max_len):
    """A one-layer deepseek_7b at its published widths, as shapes on the
    described chip: params with the int8 weights a server attaches, and
    an f32 cache of ``batch`` lanes x ``max_len`` positions."""
    import dataclasses

    from repro.configs import get_config
    from repro.core.quantization import QuantizedWeightCache
    from repro.models import init_caches, init_params
    from repro.models.layers import attach_quantized_weights

    cfg = dataclasses.replace(get_config("deepseek_7b"), n_layers=1)
    place = lambda tree: jax.tree.map(
        lambda x: _shape(sharding, x.shape, x.dtype), tree)
    params = place(jax.eval_shape(lambda: attach_quantized_weights(
        init_params(cfg, jax.random.PRNGKey(0)), QuantizedWeightCache())))
    caches = place(jax.eval_shape(lambda: init_caches(cfg, batch, max_len, dtype=jnp.float32)))
    return cfg, params, caches


def _assert_fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES


@pytest.mark.parametrize("mode", ["exact", "fast"])
def test_one_layer_decode_step_compiles(one_chip, mode):
    """One decode step of a one-layer deepseek_7b at its published
    widths, at each served rung (``exact`` = f32, ``fast`` = q16_16 on
    the int8 weights a server attaches), for 8 lanes against a
    2048-position f32 cache: the compiler accepts it, it fits one
    chip's memory, and it converts no whole embedding table to bf16."""
    from repro.models import decode_step

    cfg, params, caches = _one_layer_model(one_chip, 8, 2048)
    tok = _shape(one_chip, (8, 1), jnp.int32)
    pos = _shape(one_chip, (8,), jnp.int32)

    step = jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, cfg, mode=mode),
                   donate_argnums=(3,))
    compiled = step.lower(params, tok, pos, caches).compile()
    text = compiled.as_text()
    # the f32 rung's matmuls run at full precision, the q16_16 rung's not
    assert ("operand_precision={highest,highest}" in text) == (mode == "exact")
    assert _whole_table_bf16(text, cfg.vocab, cfg.d_model) == []
    _assert_fits_one_chip(compiled)


def test_one_layer_segment_step_compiles(one_chip):
    """One 16-token prefill chunk (``segment_step``, as the paged
    server's chunked admission runs it) of a one-layer deepseek_7b at
    the q16_16 rung, against a one-lane 1536-position f32 cache: it
    fits one chip and converts no whole embedding table to bf16."""
    from repro.models import segment_step

    cfg, params, caches = _one_layer_model(one_chip, 1, 1536)
    tokens = _shape(one_chip, (1, 16), jnp.int32)
    positions = _shape(one_chip, (1, 16), jnp.int32)

    step = jax.jit(lambda p, t, q, c: segment_step(p, t, q, c, cfg, mode="fast"),
                   donate_argnums=(3,))
    compiled = step.lower(params, tokens, positions, caches).compile()
    assert _whole_table_bf16(compiled.as_text(), cfg.vocab, cfg.d_model) == []
    _assert_fits_one_chip(compiled)
