import jax
import numpy as np
import pytest

# Smoke tests and benches run on the single real CPU device; ONLY the
# dry-run (repro.launch.dryrun, run as its own process) forces 512 devices.
jax.config.update("jax_enable_x64", False)


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)


def assert_close(a, b, rtol=2e-5, atol=2e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=rtol, atol=atol)
