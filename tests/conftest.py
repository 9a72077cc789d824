"""Shared fixtures. NOTE: no XLA_FLAGS here — smoke tests must see ONE CPU
device; multi-device tests run in subprocesses (tests/test_distributed.py)
or use the 8-device session started by tests that opt in explicitly."""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
