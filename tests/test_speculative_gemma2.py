"""Ladder-speculative exactness sweep (tests/spec_sweep.py) for the
sliding-window local/global attention family, gemma2, and the draft
length ``k`` checked on it."""

import pytest

from spec_sweep import (  # noqa: F401  (collected here, in this order)
    test_token_exactness,
    test_acceptance_rates_vary_across_rungs_and_families,
    test_rollback_cache_bit_identity,
    test_rollback_sweep_includes_real_rejections,
)
from spec_sweep import harness


@pytest.fixture(scope="module")
def family():
    return "gemma2_2b"


def test_k_variation_token_exactness():
    """k=1 (degenerate: one draft per round) and k=5 must both match
    k=3's output exactly — k is a throughput knob, not a semantics one."""
    base = harness("gemma2_2b").run_exactness("q16_16", seed=0)
    for k in (1, 5):
        rep = harness("gemma2_2b", k).run_exactness("q16_16", seed=0)
        assert rep.tokens_ok
        assert rep.speculative == base.speculative, f"k={k} changed tokens"
        assert rep.accounting_ok
