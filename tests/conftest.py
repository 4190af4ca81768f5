"""Test fixtures: virtual 8-device CPU mesh.

The reference tests run an N-process local cluster via LocalJobSubmission
(``DryadLinqTests/Program.cs``); our analog is a host-local virtual
device mesh (8 CPU devices), exercising the same SPMD code paths the TPU
mesh runs.  The platform is pinned before the first backend query.
"""

import jax

from dryad_tpu.parallel.mesh import force_cpu_backend
from dryad_tpu.utils.compile_cache import enable_compile_cache

force_cpu_backend(8)

# Persistent XLA compile cache: the pow2 shape palette means hundreds
# of tests lower the SAME programs into fresh contexts; deduping the
# compiles across tests (and across runs) keeps the suite inside the
# tier-1 time gate.  Keyed by HLO hash, so sharing the directory with
# the bench and the smoke is safe.
enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def clear_faults():
    """Every test starts AND ends with an empty fault registry: an
    injected fault (count-based or chaos FaultPlan) must never leak
    into an unrelated test."""
    from dryad_tpu.exec.faults import clear_faults as _clear

    _clear()
    yield
    _clear()


@pytest.fixture(scope="session")
def mesh8():
    from dryad_tpu.parallel.mesh import make_mesh

    assert len(jax.devices()) >= 8, "expected 8 virtual CPU devices"
    return make_mesh(8)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
