"""Shared test fixtures. NOTE: no XLA_FLAGS device-count override here —
smoke tests and benches must see the single real CPU device; only
launch/dryrun.py fakes 512 devices (in its own process)."""
import os

import jax
import numpy as np
import pytest

try:
    from hypothesis import HealthCheck, settings

    # Deterministic fuzzing: CI runs are derandomized (seed derived from the
    # test name, so failures reproduce across runs); local runs explore but
    # print a reproduction blob. JIT warm-up makes the default 200 ms
    # deadline flaky, so it is bounded but generous, and the example
    # database is disabled to keep runs hermetic.
    settings.register_profile(
        "repro",
        max_examples=25,
        derandomize=bool(os.environ.get("CI")),
        print_blob=True,
        database=None,
        deadline=60_000,
        suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
    )
    settings.load_profile("repro")
except ImportError:  # pragma: no cover - hypothesis is optional
    pass


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def trained_tiny_mlp():
    """A small trained MLP + dataset shared across integration tests."""
    from repro.core.digital import train_mlp, accuracy
    from repro.data.digits import train_test_split

    xtr, ytr, xte, yte = train_test_split(1200, 300, seed=0)
    params = train_mlp(
        jax.random.PRNGKey(0), [400, 48, 24, 10], xtr, ytr, steps=250
    )
    acc = accuracy(params, xte, yte)
    assert acc > 0.9, f"reference MLP failed to train: {acc}"
    return params, xte, yte


@pytest.fixture(autouse=True)
def _cold_program_cache():
    """Each test starts with no cached group program, as in a new process,
    so one test's warm programs never change another's trace counts or
    compile/run spans."""
    from repro.core.evaluate import clear_program_cache

    clear_program_cache()
    yield
