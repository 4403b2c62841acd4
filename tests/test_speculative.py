"""Ladder-speculative decoding: the acceptance simulator, config
validation, and the continuous-batching server integration (spec slots
exact under churn and in mixed traffic).

The contract under test: drafting at a cheap rung and verifying at f32
changes HOW FAST tokens appear, never WHICH tokens — the speculative
stream is token-for-token identical to vanilla f32 greedy decode, the
caches after a round are bit-identical to sequentially decoding only
the accepted tokens, and the acceptance accounting matches a NumPy
reference simulator.  The exactness sweep over every cache
architecture (SWA, hybrid SSM, MLA) x draft rungs x seeds runs one
family per file: ``tests/test_speculative_<family>.py`` over
``tests/spec_sweep.py``.
"""

import jax
import numpy as np
import pytest

from repro.models import init_params
from repro.runtime.scheduler import Request
from repro.runtime.serve import ContinuousBatchingServer, ContinuousServerConfig
from repro.runtime.speculative import (
    SPEC_DRAFT_LEVELS,
    SpeculativeConfig,
    register_spec_steps,
)
from repro.core.precision import MathEngine

from spec_harness import family_config, make_prompts, simulate_acceptance
from spec_sweep import harness

# ---------------------------------------------------------------------------
# property 3 (edge): the simulator itself, on hand-built traces
# ---------------------------------------------------------------------------


def test_simulator_hand_built_rounds():
    k = 3
    trace = [
        {  # lane0: all k accepted; lane1: first draft wrong; lane2 inactive
            "drafts": np.array([[5, 6, 7], [5, 6, 7], [1, 1, 1]]),
            "preds": np.array([[5, 6, 7, 8], [9, 6, 7, 8], [1, 1, 1, 1]]),
            "active": np.array([True, True, False]),
        },
        {  # agreement only resumes counting from the start (prefix!)
            "drafts": np.array([[4, 4, 4], [2, 9, 9], [1, 1, 1]]),
            "preds": np.array([[9, 4, 4, 4], [2, 9, 0, 0], [1, 1, 1, 1]]),
            "active": np.array([True, True, False]),
        },
    ]
    sim = simulate_acceptance(trace, k)
    assert sim["rounds"] == 2
    assert sim["drafted"] == 4 * k
    # round1: 3 + 0; round2: 0 (first mismatch) + 2
    assert sim["accepted"] == 5
    assert sim["n_commit"][0].tolist() == [4, 1, 0]
    assert sim["n_commit"][1].tolist() == [1, 3, 0]


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_speculative_config_validation():
    with pytest.raises(ValueError, match="k must be >= 1"):
        SpeculativeConfig(k=0)
    with pytest.raises(ValueError, match="not a draft rung"):
        SpeculativeConfig(draft_level="f32")  # verify rung can't draft
    with pytest.raises(ValueError, match="not a draft rung"):
        SpeculativeConfig(draft_level="nope")


def test_k_must_fit_smallest_attention_window():
    """A verify segment wider than the rolling KV window would wrap
    onto positions the verify still attends to — rejected at build."""
    cfg = family_config("gemma2_2b")  # smoke window = 8
    w = min(l.window for l in cfg.period if l.window is not None)
    with pytest.raises(ValueError, match="smallest attention window"):
        register_spec_steps(MathEngine("q8_8"), cfg, k=w)


def test_generate_rejects_insufficient_headroom():
    h = harness("gemma2_2b")
    dec = h.decoder("q8_8")
    with pytest.raises(ValueError, match="headroom"):
        dec.generate([[1, 2, 3]], max_new=200)


# ---------------------------------------------------------------------------
# serving integration: spec slots under continuous-batching churn
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def spec_server_model():
    cfg = family_config("gemma2_2b")
    params = init_params(cfg, jax.random.PRNGKey(5))
    return cfg, params


def test_server_speculative_matches_vanilla_f32_serving(spec_server_model):
    """5 requests on 3 slots (continuous churn): every speculative
    request's output equals the vanilla f32 server's, and the server
    actually speculated (accepted drafts > 0)."""
    cfg, params = spec_server_model
    prompts = make_prompts(cfg.vocab, 7) + make_prompts(cfg.vocab, 8)[:2]

    ref = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=3, max_len=64)
    ).generate(prompts, max_new=12, level="f32")

    srv = ContinuousBatchingServer(
        cfg, params,
        ContinuousServerConfig(
            n_slots=3, max_len=64,
            speculative=SpeculativeConfig(k=3, draft_level="q8_8", max_len=64),
        ),
    )
    got = srv.generate(prompts, max_new=12, speculative=True)
    assert got == ref
    assert srv.stats["spec_rounds"] > 0
    assert 0 < srv.stats["spec_accepted"] <= srv.stats["spec_drafted"]


def test_server_mixed_spec_and_vanilla_traffic(spec_server_model):
    """Speculative and vanilla requests share the same slot pool; the
    spec lanes still emit exactly the vanilla f32 stream."""
    cfg, params = spec_server_model
    prompts = make_prompts(cfg.vocab, 9)
    ref = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=2, max_len=64)
    ).generate(prompts, max_new=8, level="f32")

    srv = ContinuousBatchingServer(
        cfg, params,
        ContinuousServerConfig(
            n_slots=2, max_len=64,
            speculative=SpeculativeConfig(k=3, draft_level="q8_8", max_len=64),
        ),
    )
    reqs = [
        Request(rid=i, prompt=list(p), max_new=8,
                speculative=(i % 2 == 0),
                level=None if i % 2 == 0 else "q16_16")
        for i, p in enumerate(prompts)
    ]
    fins = srv.serve(reqs)
    for i, p in enumerate(prompts):
        if i % 2 == 0:
            assert fins[i].tokens == ref[i], f"spec lane {i} diverged"
        else:
            assert fins[i].n_generated == 8  # vanilla lanes still served


def test_server_rejects_spec_request_without_spec_config(spec_server_model):
    cfg, params = spec_server_model
    srv = ContinuousBatchingServer(
        cfg, params, ContinuousServerConfig(n_slots=1, max_len=64)
    )
    with pytest.raises(ValueError, match="speculative"):
        srv.serve([Request(rid=0, prompt=[1, 2], max_new=2, speculative=True)])
    assert not srv.scheduler.has_work()  # nothing stranded


def test_server_low_acceptance_escalates_draft_rung(spec_server_model):
    """The measured acceptance rate is a live precision signal: a slot
    whose drafts keep missing has its DRAFT rung escalated by the
    draft arbiter (verify rung stays f32 — exactness is never at stake)."""
    cfg, params = spec_server_model
    from repro.core.arbiter import SlotArbiterConfig

    srv = ContinuousBatchingServer(
        cfg, params,
        ContinuousServerConfig(
            n_slots=1, max_len=64,
            speculative=SpeculativeConfig(k=3, draft_level="q8_8", max_len=64),
            arbiter=SlotArbiterConfig(
                n_levels=2, accept_threshold=1.01,  # every round is "low"
                accept_patience=1, cooldown_steps=1, stable_steps=10**6,
            ),
        ),
    )
    names = tuple(lv for lv, _ in SPEC_DRAFT_LEVELS)
    assert srv.draft_arbiter.idx[0] == names.index("q8_8")
    fins = srv.serve([Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new=10,
                              speculative=True)])
    assert fins[0].n_generated == 10
    assert srv.draft_arbiter.idx[0] == names.index("q16_16")  # escalated
    assert any(reason == "acceptance" for *_, reason in srv.draft_arbiter.switches)
