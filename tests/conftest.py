"""Shared fixtures. NOTE: no XLA_FLAGS here — unit/smoke tests run on the
single real CPU device; multi-device checks force a host device count in a
subprocess of their own (tests/sharded_driver.py)."""
import os

import jax
import numpy as np
import pytest

# Every engine.run() in the test suite ends with the invariant audit
# (DESIGN.md §12): PagePool.check() + prefix-cache refcounts == live pins.
# setdefault so REPRO_DEBUG_AUDIT=0 can still switch it off locally.
os.environ.setdefault("REPRO_DEBUG_AUDIT", "1")

# Seed discipline: the byte-identity and sampling-contract suites
# (DESIGN.md §12-13) only mean anything if every random draw is pinned.
# This used to be a runtime monkeypatch of np.random.default_rng here
# (tests-only, and blind to src/ and benchmarks/); it is now the RNG001
# rule of the static jit-safety linter (repro.analysis.jitlint, DESIGN.md
# §14), which covers src/, benchmarks/, tests/ and tools/ at CI time via
# `tools/lint_contracts.py --all`. (jax.random needs no guard — PRNGKey
# requires an explicit seed by construction — and hypothesis is
# derandomized in test_properties.py.)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
    config.addinivalue_line(
        "markers",
        "kernels: interpret-mode Pallas kernel tests (pytest -m kernels)")
    config.addinivalue_line(
        "markers",
        "serving: continuous-batching serving tests (pytest -m serving)")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / degraded-mode serving tests "
        "(pytest -m chaos)")
