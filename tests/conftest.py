"""Test harness config.

The one XLA flag every test process gets is set in the checkout's root
``conftest.py``: it turns off an LLVM pass of XLA's CPU backend that
costs minutes on CORDIC kernels and changes no result.  The device
count is never overridden here — smoke tests and benches must see the
real single CPU device.  Multi-device sharding tests spawn subprocesses
that append their own device count to ``XLA_FLAGS`` (see
tests/test_dryrun.py).

Property-based tests go through ``tests/_pbt.py``, which re-exports
hypothesis when installed and a deterministic fixed-seed shim when not
— the tier-1 suite must collect and pass either way.
"""

import numpy as np
import pytest
from _pbt import settings

# Keep hypothesis deadlines off: jit compilation on first example would
# blow any wall-clock deadline and has nothing to do with correctness.
settings.register_profile("repro", deadline=None, max_examples=60, derandomize=True)
settings.load_profile("repro")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)  # the paper's seed
