"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see the single real CPU device (the 512-device override is
exclusive to launch/dryrun.py). Sharded-path tests spawn subprocesses.
"""

import numpy as np
import pytest


@pytest.fixture(scope="session")
def small_dataset():
    from repro.data.synthetic import make_mnist_like

    return make_mnist_like(train_per_class=120, test_per_class=40, seed=0)


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
