"""The ladder-speculative exactness sweep over one model family: draft
rungs x seeds, driven by tests/spec_harness.py.

The families are sliding-window local/global attention (gemma2),
hybrid attention+SSM+MoE (jamba) and latent attention (minicpm3 MLA):
every cache kind the rollback must handle.  Each
``tests/test_speculative_<family>.py`` imports these tests and defines
the module fixture ``family``, so each family's compiled harness lives
in one test file (one worker under ``--dist loadfile``).
"""

import functools

import pytest

from spec_harness import DRAFT_RUNGS, ExactnessHarness

SEEDS = (0, 1, 2, 3)


@functools.lru_cache(maxsize=None)
def harness(family: str, k: int = 3) -> ExactnessHarness:
    """One compiled harness per (family, k), shared across the sweep."""
    return ExactnessHarness(family, k=k)


# ---------------------------------------------------------------------------
# property 1: token exactness (2 rungs x 4 seeds)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rung", DRAFT_RUNGS)
@pytest.mark.parametrize("seed", SEEDS)
def test_token_exactness(family, rung, seed):
    rep = harness(family).run_exactness(rung, seed)
    assert rep.tokens_ok, (
        f"{family}/{rung}/seed{seed}: speculative != vanilla f32 greedy\n"
        f"  spec    {rep.speculative}\n  vanilla {rep.vanilla}"
    )
    # accounting: decoder counters == NumPy simulator replay of the trace
    assert rep.accounting_ok, (rep.accounting, rep.simulator)
    assert rep.accounting["rounds"] == rep.simulator["rounds"]
    # every committed token is f32-verified, so each round commits >= 1
    # per active lane: rounds never exceed total tokens emitted
    assert 0.0 <= rep.acceptance_rate <= 1.0


def test_acceptance_rates_vary_across_rungs_and_families(family):
    """Sanity that the sweep exercises real speculation dynamics: the
    family's measured acceptance rates are neither all-0 (drafts
    useless — machinery untested beyond the trivial path) nor all-1
    (rollback never exercised)."""
    rates = [harness(family).run_exactness(rung, seed=0).acceptance_rate
             for rung in DRAFT_RUNGS]
    assert any(r > 0.0 for r in rates), rates
    assert any(r < 1.0 for r in rates), rates


# ---------------------------------------------------------------------------
# property 2: cache rollback bit-identity after a REAL round
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", (0, 1))
def test_rollback_cache_bit_identity(family, seed):
    res = harness(family).run_rollback("q8_8", seed)
    assert res["commit_bit_identical"], (
        f"{family}/seed{seed}: committed caches != sequential-decode caches"
    )
    assert res["rejected_restored"]


def test_rollback_sweep_includes_real_rejections(family):
    """The bit-identity property is only meaningful if some round in
    the sweep actually rejected drafts; check that across seeds at the
    cheapest rung at least one rejection occurred."""
    h = harness(family)
    assert any(
        h.run_rollback("q8_8", seed)["had_rejections"] for seed in (0, 1, 2)
    ), f"{family}: no rejections in 3 seeds — sweep too easy"
