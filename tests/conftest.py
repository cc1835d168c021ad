"""Test config. NOTE: no XLA_FLAGS device-count override here by design —
smoke tests and benches must see the real (single) device; only the
dry-run process emulates 512 devices (see repro.launch.dryrun)."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# Tests write no persistent compilation cache into the checkout; spawned
# pool workers inherit the setting through the environment.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
