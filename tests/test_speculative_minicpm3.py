"""Ladder-speculative exactness sweep (tests/spec_sweep.py) for the
latent-attention family, minicpm3 (MLA)."""

import pytest

from spec_sweep import (  # noqa: F401  (collected here, in this order)
    test_token_exactness,
    test_acceptance_rates_vary_across_rungs_and_families,
    test_rollback_cache_bit_identity,
    test_rollback_sweep_includes_real_rejections,
)


@pytest.fixture(scope="module")
def family():
    return "minicpm3_4b"
