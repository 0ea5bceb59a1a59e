import os

# Tests run single-device (the dry-run alone uses 512 host devices, in its
# own process).  Keep compilation deterministic and quiet.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np
import pytest

jax.config.update("jax_default_matmul_precision", "highest")

# hypothesis is optional (pip install -e '.[test]'): without it the
# @given property tests skip (via requires_hypothesis) and everything else
# still runs.  Test modules import the shim: from conftest import given, ...
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def given(*a, **k):
        return lambda f: f

    settings = given

    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _StrategyStub()

requires_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def chip_precision():
    """The chip's matmul precision (the program sets none), in place of
    the ``highest`` the test session sets, under which the splash kernels'
    bf16 dots do not compile and attention keeps the chunked path."""
    with jax.default_matmul_precision("default"):
        yield


def assert_trees_close(a, b, rtol=1e-5, atol=1e-5):
    import jax.numpy as jnp
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   rtol=rtol, atol=atol)
