import numpy as np
import pytest
from hypothesis import settings

# The first example of a property test includes a jit compile (seconds),
# so a per-example deadline would time the compiler, not the property.
settings.register_profile("repro", deadline=None)
settings.load_profile("repro")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
