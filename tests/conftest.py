import pytest

# NOTE: no XLA_FLAGS here by design — smoke tests and benches must see the
# single real CPU device; only launch/dryrun.py forces 512 host devices.


@pytest.fixture(scope="session")
def lib_cpu():
    from repro.core import load_library

    return load_library("cpu_xla")


@pytest.fixture(scope="session")
def lib_interp():
    from repro.core import load_library

    return load_library("pallas_interpret")


@pytest.fixture
def bind_library():
    """``repro.tsl_api.use`` for one test: the process-wide library binding
    (and JAX's trace caches) are restored when the test ends."""
    import jax

    from repro import tsl_api

    before = tsl_api._lib
    yield tsl_api.use
    tsl_api._lib = before
    jax.clear_caches()


@pytest.fixture(scope="session")
def host_mesh():
    import jax

    return jax.make_mesh((1, 1), ("data", "model"))
