"""Ladder-speculative exactness sweep (tests/spec_sweep.py) for the
hybrid attention+SSM+MoE family, jamba, and the EOS semantics checked
on it."""

import pytest

from repro.runtime.speculative import LadderSpeculativeDecoder, SpeculativeConfig

from spec_harness import make_prompts
from spec_sweep import (  # noqa: F401  (collected here, in this order)
    test_token_exactness,
    test_acceptance_rates_vary_across_rungs_and_families,
    test_rollback_cache_bit_identity,
    test_rollback_sweep_includes_real_rejections,
)
from spec_sweep import harness


@pytest.fixture(scope="module")
def family():
    return "jamba_v01_52b"


def test_eos_truncates_like_vanilla():
    """With an EOS id that actually fires, the speculative stream must
    stop exactly where vanilla stops — even when the EOS token was
    committed mid-round with further verified tokens behind it."""
    h = harness("jamba_v01_52b")
    rep = h.run_exactness("q8_8", seed=2, max_new=16)
    ref = rep.vanilla
    # pick an EOS id that appears in some reference stream (not at the
    # very start); fall back to a non-appearing id (pure budget stop)
    eos = None
    for toks in ref:
        for t in toks[1:]:
            eos = t
            break
        if eos is not None:
            break
    dec = LadderSpeculativeDecoder(
        h.cfg, h.params,
        SpeculativeConfig(k=3, draft_level="q8_8", max_len=64, eos_id=eos),
    )
    got = dec.generate(make_prompts(h.cfg.vocab, 2), max_new=16)
    for g, r in zip(got, ref):
        if eos in r:
            assert g == r[: r.index(eos) + 1]  # EOS kept, nothing after
        else:
            assert g == r


