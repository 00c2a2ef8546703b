import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def pallas_kernels(monkeypatch):
    """The planning path takes its Pallas kernels off the chip, in
    interpret mode (a test that compiles for a described chip sets
    ``JAX_PALLAS_INTERPRET=0`` after this).  The kernel choice is made
    at trace time and is not part of jit's cache key, so the caches are
    cleared on entry and exit: no program traced under the patch
    outlives it, and none traced before it is reused."""
    import jax
    from repro import backend
    from repro.core import device
    from repro.rebalance import planner
    jax.clear_caches()
    for mod in (backend, device, planner):
        monkeypatch.setattr(mod, "use_pallas_default", lambda: True)
    monkeypatch.setenv("JAX_PALLAS_INTERPRET", "1")
    yield
    jax.clear_caches()
