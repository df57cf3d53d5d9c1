"""Shared fixtures. NOTE: no hardcoded XLA_FLAGS here — smoke tests and
benches must see 1 device by default; only launch/dryrun.py forces 512 host
devices (in its own process).  Multi-device jobs (CI's mesh tier running
tests/test_distribute.py + the golden suite) opt in per process with
``REPRO_EMULATED_DEVICES=8`` (or the legacy
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), applied below via
``repro.utils.platform`` before jax initialises.

Marker policy: ``slow`` and ``bench`` tests are deselected by default via
``addopts = -m 'not slow and not bench'`` in pyproject.toml (the tier-1
gate).  Run the full suite with ``pytest -m ""``.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.utils import platform as rplat  # noqa: E402  (pre-jax import)

rplat.apply_emulated_devices()

import jax  # noqa: E402
import pytest  # noqa: E402

# Tests never read or write the persistent compile cache, even where a
# benchmark module they import turns it on: a cache hit skips the backend
# compile that the compile-count tests count.
jax.config.update("jax_enable_compilation_cache", False)


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden", action="store_true", default=False,
        help="regenerate tests/golden/*.json from the current code instead "
             "of comparing against them (then inspect the diff!)",
    )


# ---------------------------------------------------------------------------
# XLA compilation counting (used by the sweep-engine tests to prove the
# batched path compiles strictly fewer programs than the per-scenario loop).
# The machinery lives in repro.analyze.budget so the static-analysis
# contract checker can machine-enforce the same budgets in CI; the fixture
# below is a thin re-export keeping the historical test API.
# ---------------------------------------------------------------------------

from repro.analyze.budget import (  # noqa: E402
    CompileCounter,
    warm_eager_helpers,
)


@pytest.fixture
def compile_counter():
    """Factory fixture: ``with compile_counter() as c: ...; c.count``.

    Warms the shared eager helpers first (see :func:`warm_eager_helpers`)
    so counts taken inside the context are partition/lane programs only.
    """
    warm_eager_helpers()
    return CompileCounter


@pytest.fixture(scope="session")
def key():
    return jax.random.key(0)
