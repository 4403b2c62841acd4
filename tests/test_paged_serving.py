"""Serving through the paged cache pool: exactness, isolation, chunked
prefill (zero retraces), prefix sharing, capacity admission, and the
ServingConfig consolidation.

Contracts pinned here:

* a request served through the paged pool in a BATCH (mixed lengths,
  mixed levels, slot churn) emits exactly the tokens it emits served
  ALONE through a paged pool — the gather/scatter adapters preserve the
  lane-isolation contract of the contiguous engine;
* speculative serving through the paged pool equals paged vanilla f32
  (page-granular rollback is bit-exact);
* prefix sharing ON equals prefix sharing OFF token-for-token (shared
  pages are bit-identical to the pages a cold prefill would write);
* admitting a burst of mixed-length prompts triggers ZERO chunk-step
  retraces after warmup (the fixed-shape chunked-prefill contract);
* the page pool drains to empty after every request finishes, across
  slot-reuse churn;
* the deprecated config shims still construct working servers.
"""

import warnings

import jax
import numpy as np
import pytest

from repro.configs import smoke
from repro.models import init_params
from repro.runtime.config import ServingConfig
from repro.runtime.scheduler import Request
from repro.runtime.serve import (
    BatchedServer,
    ContinuousBatchingServer,
    ContinuousServerConfig,
    ServerConfig,
)
from repro.runtime.speculative import SpeculativeConfig

MAX_LEN = 32
PROMPTS = [
    [1, 2, 3, 4, 5],
    [7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17],
    [3, 1, 4],
    [2, 7, 1, 8, 2, 8, 1, 8, 2, 8],
]


def _paged(n_slots=2, **kw):
    kw.setdefault("max_len", MAX_LEN)
    return ServingConfig(n_slots=n_slots, cache="paged", page_size=4, **kw)


@pytest.fixture(scope="module")
def model():
    """``model(arch) -> (cfg, params)``, built once per file."""
    built = {}

    def get(arch):
        if arch not in built:
            cfg = smoke(arch)
            built[arch] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
        return built[arch]

    return get


@pytest.fixture(scope="module")
def server(model):
    """``server(arch, n_slots=2, cache="paged")``: one compiled server per
    key, shared by the file's tests (jit compiles dominate runtime).
    Every request a test sends finishes, so each test finds the pool
    drained; tests assert on their own outputs and on counter
    differences, never on a shared server's totals."""
    built = {}

    def get(arch, n_slots=2, cache="paged"):
        key = (arch, n_slots, cache)
        if key not in built:
            cfg, params = model(arch)
            scfg = (_paged(n_slots=n_slots) if cache == "paged"
                    else ServingConfig(n_slots=n_slots, max_len=MAX_LEN))
            built[key] = ContinuousBatchingServer(cfg, params, scfg)
        return built[key]

    return get


@pytest.fixture(scope="module")
def serve_alone(server):
    """Reference output: the prompt served by itself, the only request
    in the shared 2-slot paged server."""
    def run(arch, prompt, max_new, level):
        return server(arch).generate([prompt], max_new=max_new, level=level)[0]

    return run


# ---------------------------------------------------------------------------
# exactness / isolation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-7b", "gemma2-2b", "jamba-v0.1-52b"])
def test_paged_batch_equals_alone(arch, server, serve_alone):
    """Mixed-length batch through the paged pool == each request served
    alone, across attention families (full GQA, SWA, hybrid SSM)."""
    srv = server(arch)
    outs = srv.generate(PROMPTS, max_new=6, level="f32")
    for p, o in zip(PROMPTS, outs):
        assert o == serve_alone(arch, p, 6, "f32")
    # every request finished -> every page returned to the free list
    for g in srv.cache_ops.groups.values():
        assert g["alloc"].live() == []


def test_paged_mixed_levels_equal_alone(server, serve_alone):
    """Per-request precision through the paged pool: each lane's output
    equals serving it alone AT ITS LEVEL (isolation holds through the
    gather/scatter path and the pristine-masked mixed-level pass)."""
    srv = server("deepseek-7b", n_slots=4)
    levels = ["q16_16", "f32", "q16_16", "f32"]
    reqs = [
        Request(rid=srv.next_rid(), prompt=p, max_new=5, level=lv)
        for p, lv in zip(PROMPTS, levels)
    ]
    fins = srv.serve(reqs)
    for r, lv in zip(reqs, levels):
        assert fins[r.rid].tokens == serve_alone("deepseek-7b", r.prompt, 5, lv)


def test_paged_speculative_equals_vanilla_f32(model, server):
    """Ladder-speculative serving through the paged pool is
    token-identical to paged vanilla f32 — k+1-row scatter including
    the rolled-back rejected rows is a bit-exact page restore."""
    cfg, params = model("deepseek-7b")
    spec = SpeculativeConfig(k=3, max_len=MAX_LEN)
    s_spec = ContinuousBatchingServer(
        cfg, params, _paged(n_slots=2, speculative=spec)
    )
    o_spec = s_spec.generate(PROMPTS, max_new=6, speculative=True)
    o_van = server("deepseek-7b").generate(PROMPTS, max_new=6, level="f32")
    assert o_spec == o_van
    assert s_spec.stats["spec_rounds"] > 0
    for g in s_spec.cache_ops.groups.values():
        assert g["alloc"].live() == []


def test_paged_slot_churn_and_reuse(server, serve_alone):
    """Many more requests than slots: slots recycle through
    free_slot/re-admission and late requests still match serving
    alone (no residue from prior occupants' pages)."""
    prompts = [[(7 * i + j) % 120 + 1 for j in range(3 + (5 * i) % 9)]
               for i in range(7)]
    srv = server("gemma2-2b")
    outs = srv.generate(prompts, max_new=4, level="f32")
    for p, o in zip(prompts, outs):
        assert o == serve_alone("gemma2-2b", p, 4, "f32")
    for g in srv.cache_ops.groups.values():
        assert g["alloc"].live() == []


def test_paged_eos_mode(model, server):
    """EOS-checked serving (per-step host pull) through the paged pool:
    finishes match the contiguous engine's."""
    cfg, params = model("deepseek-7b")
    o_base = server("deepseek-7b", cache="contiguous").generate(
        PROMPTS, max_new=8, level="f32")
    eos = int(o_base[0][len(PROMPTS[0]) + 1])  # force an early EOS for req 0
    s_c = ContinuousBatchingServer(
        cfg, params, ServingConfig(n_slots=2, max_len=MAX_LEN, eos_id=eos)
    )
    s_p = ContinuousBatchingServer(
        cfg, params, _paged(n_slots=2, eos_id=eos)
    )
    assert s_c.generate(PROMPTS, max_new=8, level="f32") == \
        s_p.generate(PROMPTS, max_new=8, level="f32")


# ---------------------------------------------------------------------------
# chunked prefill: fixed shapes, zero retraces
# ---------------------------------------------------------------------------


def test_chunked_prefill_zero_retraces_across_lengths(server):
    """The counting hook: the chunk step traces once per ladder level
    during warmup and NEVER again, whatever prompt lengths arrive —
    the per-length retrace cost of the contiguous prefill is gone."""
    srv = server("deepseek-7b")
    chunks0 = srv.stats["prefill_chunks"]
    srv.generate([[1, 2, 3]], max_new=2, level="f32")  # warmup
    traced = srv._chunk_traces
    assert traced == len(srv.level_names)  # one switch trace covers all rungs
    burst = [[(i * 13 + j) % 120 + 1 for j in range(1 + i)] for i in range(10)]
    srv.generate(burst, max_new=2, level="f32")
    srv.generate(burst[::-1], max_new=2, level="q16_16")
    assert srv._chunk_traces == traced  # ZERO new traces across the burst
    # and the chunk ledger matches ceil(len/C) per admission
    C = srv.scfg.resolved_chunk
    expect = -(-3 // C) + 2 * sum(-(-len(p) // C) for p in burst)
    assert srv.stats["prefill_chunks"] - chunks0 == expect


def test_chunk_size_config(model, serve_alone):
    """prefill_chunk is honored (and validated: must divide max_len;
    prefix sharing pins chunk == page_size)."""
    cfg, params = model("deepseek-7b")
    srv = ContinuousBatchingServer(
        cfg, params,
        ServingConfig(n_slots=1, max_len=MAX_LEN, cache="paged",
                      page_size=4, prefill_chunk=8),
    )
    out = srv.generate([PROMPTS[1]], max_new=4, level="f32")[0]
    assert out == serve_alone("deepseek-7b", PROMPTS[1], 4, "f32")
    assert srv.stats["prefill_chunks"] == -(-len(PROMPTS[1]) // 8)
    with pytest.raises(ValueError, match="divide max_len"):
        ServingConfig(cache="paged", max_len=32, page_size=4, prefill_chunk=5)
    with pytest.raises(ValueError, match="prefill_chunk == page_size"):
        ServingConfig(cache="paged", max_len=32, page_size=4,
                      prefill_chunk=8, prefix_sharing=True)


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------


def test_prefix_sharing_token_identical_and_counted(model, server):
    """Sharing ON == sharing OFF token-for-token, with hits recorded
    and fewer chunk dispatches (the reused prefix is never re-run)."""
    cfg, params = model("deepseek-7b")
    shared = list(range(1, 13))  # 3 full pages of 4
    prompts = [shared + [50 + i, 70 + i] for i in range(4)]
    s_off = server("deepseek-7b")
    chunks_off = s_off.stats["prefill_chunks"]
    o_off = s_off.generate(prompts, max_new=5, level="f32")
    chunks_off = s_off.stats["prefill_chunks"] - chunks_off
    s_on = ContinuousBatchingServer(
        cfg, params, _paged(n_slots=2, prefix_sharing=True)
    )
    o_on = s_on.generate(prompts, max_new=5, level="f32")
    assert o_on == o_off
    assert s_on.stats["prefix_hits"] == 3         # every admission after the first
    assert s_on.stats["prefix_tokens_reused"] == 3 * 12
    assert s_on.stats["prefill_chunks"] < chunks_off
    # slots drained; only prefix-cache entries keep pages resident
    g = s_on.cache_ops.groups[f"L{MAX_LEN}"]
    assert (g["table"] == 0).all()
    assert len(s_on.cache_ops.prefix) > 0
    s_on.cache_ops.prefix.drop_all()
    assert g["alloc"].live() == []


def test_prefix_sharing_speculative_still_exact(model, server):
    """Sharing + speculative composed: still equals vanilla f32."""
    cfg, params = model("deepseek-7b")
    shared = list(range(1, 9))
    prompts = [shared + [40 + i] for i in range(3)]
    spec = SpeculativeConfig(k=2, max_len=MAX_LEN)
    s = ContinuousBatchingServer(
        cfg, params,
        _paged(n_slots=2, prefix_sharing=True, speculative=spec),
    )
    o = s.generate(prompts, max_new=5, speculative=True)
    assert o == server("deepseek-7b").generate(prompts, max_new=5, level="f32")
    assert s.stats["prefix_hits"] > 0


def test_prefix_sharing_rejected_for_unshareable_models(model):
    cfg, params = model("gemma2-2b")
    with pytest.raises(ValueError, match="prefix_sharing"):
        ContinuousBatchingServer(
            cfg, params, _paged(n_slots=2, prefix_sharing=True)
        )


# ---------------------------------------------------------------------------
# capacity admission
# ---------------------------------------------------------------------------


def test_tight_pool_queues_admission_but_serves_all(model, serve_alone):
    """A page pool far smaller than slots x max_len: ``can_admit``
    holds requests in the queue instead of over-committing pages;
    every request still finishes and matches serving alone.

    Sizing: 8 usable pages; each 10-token prompt needs 3 blocks at
    admission and grows to 4 by its last decode write, so at most two
    of the four slots can be resident at once."""
    cfg, params = model("deepseek-7b")
    scfg = ServingConfig(
        n_slots=4, max_len=MAX_LEN, cache="paged", page_size=4, n_pages=9,
    )
    srv = ContinuousBatchingServer(cfg, params, scfg)
    prompts = [[(11 * i + j) % 120 + 1 for j in range(10)] for i in range(6)]
    outs = srv.generate(prompts, max_new=4, level="f32")
    for p, o in zip(prompts, outs):
        assert o == serve_alone("deepseek-7b", p, 4, "f32")
    for g in srv.cache_ops.groups.values():
        assert g["alloc"].live() == []
    assert srv.cache_ops.groups[f"L{MAX_LEN}"]["alloc"].high_water <= 8


# ---------------------------------------------------------------------------
# ServingConfig consolidation + deprecation shims
# ---------------------------------------------------------------------------


def test_serving_config_validation():
    with pytest.raises(ValueError, match="cache"):
        ServingConfig(cache="mmap")
    with pytest.raises(ValueError, match="divide max_len"):
        ServingConfig(cache="paged", max_len=30, page_size=4)
    with pytest.raises(ValueError, match="requires cache='paged'"):
        ServingConfig(prefill_chunk=8)
    with pytest.raises(ValueError, match="requires cache='paged'"):
        ServingConfig(prefix_sharing=True)
    with pytest.raises(ValueError, match="n_pages"):
        ServingConfig(cache="paged", max_len=32, page_size=4, n_pages=3)
    assert ServingConfig(cache="paged", page_size=8).resolved_chunk == 8
    assert ServingConfig().resolved_chunk is None


def test_deprecated_shims_warn_and_work(model, server):
    cfg, params = model("deepseek-7b")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        old = ContinuousServerConfig(n_slots=2, max_len=MAX_LEN)
        assert any(issubclass(x.category, DeprecationWarning) for x in w)
    assert isinstance(old, ServingConfig)  # pure alias
    srv_old = ContinuousBatchingServer(cfg, params, old)
    srv_new = server("deepseek-7b", cache="contiguous")
    assert srv_old.generate(PROMPTS[:2], max_new=4) == \
        srv_new.generate(PROMPTS[:2], max_new=4)

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        bcfg = ServerConfig(max_batch=2, max_len=MAX_LEN, max_new=4)
        assert any(issubclass(x.category, DeprecationWarning) for x in w)
    srv_b = BatchedServer(cfg, params, bcfg)
    assert srv_b.scfg.n_slots == 2  # mapped through to_serving()
    srv_b2 = BatchedServer(
        cfg, params, ServingConfig(n_slots=2, max_len=MAX_LEN, max_new=4)
    )
    same_len = [[1, 2, 3], [4, 5, 6]]
    assert srv_b.generate(same_len) == srv_b2.generate(same_len)


def test_batched_server_rejects_paged(model):
    cfg, params = model("deepseek-7b")
    with pytest.raises(ValueError, match="contiguous"):
        BatchedServer(cfg, params, _paged())
