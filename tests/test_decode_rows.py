"""The rows form of single-token decode (``decode_rows``) against the
full-cache decode it replaced.

The full-cache decode blended each layer's new row into the layer's
whole cache (``attention._store``, a one-hot blend), attended over the
blended cache, and carried the whole stacked cache out of the step.  The
reference here is that algorithm: the layers read through the blend
(``_with_row`` swapped for ``_store`` while the reference is traced),
and the step's rows are blended into the stacked cache.  The rows form
must give the same logits bit for bit, write the same bits, and pick the
same greedy tokens step after step.

Structural contract: the rows step returns no leaf with a cache's
position axis, and the server's paged tick writes no whole stacked cache
view.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke
from repro.models import (
    attention,
    decode_rows,
    decode_step,
    init_caches,
    init_params,
    prefill_step,
    write_cache_slot,
)
from repro.runtime.config import ServingConfig
from repro.runtime.serve import ContinuousBatchingServer

MAX_LEN = 32
# two lanes at different positions; gemma2's smoke window is 8, so both
# lanes' windowed caches have wrapped before the first decode step
PROMPT_LENS = (10, 13)
STEPS = 4

CASES = [
    # (arch, mode, quantized KV)
    ("deepseek_7b", "exact", False),     # GQA, the f32 rung
    ("deepseek_7b", "fast", False),      # GQA, the q16_16 rung
    ("minicpm3_4b", "exact", False),     # latent attention
    ("gemma2_2b", "exact", False),       # sliding window (wrapped), softcap
    ("jamba_v01_52b", "exact", False),   # SSM state + attention
    ("deepseek_7b", "precise", True),    # int8 KV payloads + exponents
]


def _ids(case):
    arch, mode, quantized = case
    return f"{arch}-{mode}" + ("-q8kv" if quantized else "")


def _setup(arch, quantized):
    cfg = smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(11)
    caches = init_caches(cfg, len(PROMPT_LENS), MAX_LEN, dtype=jnp.float32,
                         quantized=quantized)
    tok = []
    for b, n in enumerate(PROMPT_LENS):
        single = init_caches(cfg, 1, MAX_LEN, dtype=jnp.float32, quantized=quantized)
        prompt = jnp.asarray(rng.integers(0, cfg.vocab, (1, n)), jnp.int32)
        logits, single = prefill_step(params, prompt, single, cfg, mode="exact")
        caches = write_cache_slot(caches, single, b)
        tok.append(int(jnp.argmax(logits[0])))
    return cfg, params, caches, jnp.asarray(tok, jnp.int32), jnp.asarray(PROMPT_LENS, jnp.int32)


def _blend_decode(cfg, mode):
    """The full-cache decode: blend reads, blend writes."""

    def step(params, tok, pos, caches):
        logits, rows = decode_rows(params, tok[:, None], pos, caches, cfg, mode=mode)
        out = {}
        for i, spec in enumerate(cfg.period):
            key = f"pos{i}"
            c, r = caches[key], rows[key]
            if spec.kind == "mamba":
                out[key] = {n: r[n].astype(c[n].dtype) for n in c}
                continue
            slot = pos % c["pos"].shape[2]
            blend = jax.vmap(attention._store, in_axes=(0, 0, None))
            out[key] = {n: blend(c[n], r[n], slot).astype(c[n].dtype) for n in c}
        return logits, out

    return step


def _bitwise(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("case", CASES, ids=_ids)
def test_rows_form_matches_full_cache_decode(case, monkeypatch):
    arch, mode, quantized = case
    cfg, params, caches, tok, pos = _setup(arch, quantized)
    with monkeypatch.context() as m:
        m.setattr(attention, "_with_row", attention._store)
        # traced (and compiled) while the layers read through the blend
        ref_step = jax.jit(_blend_decode(cfg, mode)).lower(params, tok, pos, caches).compile()
    rows_step = jax.jit(lambda p, t, q, c: decode_rows(p, t[:, None], q, c, cfg, mode=mode))
    new_step = jax.jit(lambda p, t, q, c: decode_step(p, t[:, None], q, c, cfg, mode=mode))

    ref_c, new_c, ref_t, new_t = caches, caches, tok, tok
    ref_toks, new_toks = [], []
    for s in range(STEPS):
        p = pos + s
        ref_logits, ref_c2 = ref_step(params, ref_t, p, ref_c)
        new_logits, new_c2 = new_step(params, new_t, p, new_c)
        if s == 0:
            # same inputs: bit-identical logits, caches and rows
            assert _bitwise(new_logits, ref_logits)
            _, rows = rows_step(params, new_t, p, new_c)
            for i, spec in enumerate(cfg.period):
                key = f"pos{i}"
                for n, leaf in ref_c2[key].items():
                    assert _bitwise(new_c2[key][n], leaf), (key, n)
                    got = rows[key][n].astype(leaf.dtype)
                    if spec.kind == "mamba":
                        want = leaf
                    else:
                        slot = p % leaf.shape[2]
                        want = leaf[:, jnp.arange(len(PROMPT_LENS)), slot]
                    assert _bitwise(got, want), (key, n)
        ref_t = jnp.argmax(ref_logits, axis=-1).astype(jnp.int32)
        new_t = jnp.argmax(new_logits, axis=-1).astype(jnp.int32)
        ref_toks.append(np.asarray(ref_t).tolist())
        new_toks.append(np.asarray(new_t).tolist())
        ref_c, new_c = ref_c2, new_c2
    assert new_toks == ref_toks


@pytest.mark.parametrize("arch", ["deepseek_7b", "minicpm3_4b", "gemma2_2b", "jamba_v01_52b"])
def test_rows_have_no_position_axis(arch):
    """``decode_rows`` returns a row per lane a layer: each leaf is the
    cache leaf without its position axis (SSM: the state's own shape)."""
    cfg = smoke(arch)
    params = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: init_caches(cfg, 3, MAX_LEN, dtype=jnp.float32))
    tok = jax.ShapeDtypeStruct((3, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((3,), jnp.int32)
    _, rows = jax.eval_shape(
        lambda p, t, q, c: decode_rows(p, t, q, c, cfg, mode="exact"), params, tok, pos, caches)
    for i, spec in enumerate(cfg.period):
        key = f"pos{i}"
        for n, c in caches[key].items():
            want = c.shape if spec.kind == "mamba" else c.shape[:2] + c.shape[3:]
            assert rows[key][n].shape == want, (key, n, rows[key][n].shape, c.shape)


def test_paged_tick_writes_no_stacked_cache_view():
    """The lowered ``tick_p`` holds no dynamic-update-slice whose result
    has the shape of a stacked cache view leaf ``(n_periods, B, L, ...)``
    — the whole-cache rewrite a layer used to make."""
    cfg = smoke("deepseek_7b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    srv = ContinuousBatchingServer(
        cfg, params, ServingConfig(n_slots=2, max_len=MAX_LEN, page_size=4))
    pool = srv.cache_ops
    tables = pool.device_tables()
    view = pool.device_view(srv.pool, tables)
    view_shapes = {tuple(leaf.shape) for leaf in jax.tree.leaves(view)}
    B = srv.scfg.n_slots
    text = srv._tick_p.lower(
        jnp.int32(0), srv.params, srv._tok, srv._pos, srv.pool, tables,
        jnp.ones((B,), bool), jax.random.PRNGKey(0), srv._gen_buf, srv._gen_count,
        srv._health,
    ).as_text()
    dus = re.findall(r"stablehlo\.dynamic_update_slice.*->\s*tensor<([0-9x]+)x[a-z]", text)
    dus_shapes = {tuple(int(d) for d in s.split("x")) for s in dus}
    assert not dus_shapes & view_shapes, dus_shapes & view_shapes
    # the view is gathered, so the check would see a rewrite of it
    assert any(len(s) >= 3 and s[2] == MAX_LEN for s in view_shapes)
