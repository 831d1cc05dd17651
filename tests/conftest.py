# NOTE: no XLA_FLAGS here on purpose — tests and benches see the real single
# CPU device; a test that needs a mesh forces host devices in a subprocess.
import os

import numpy as np
import pytest

# every optimize() in the suite runs the IR verifier after each pass unless a
# run explicitly opts out (REPRO_VERIFY_IR=0)
os.environ.setdefault("REPRO_VERIFY_IR", "1")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
