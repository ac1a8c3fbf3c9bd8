"""Suite-wide setup: device-count forcing, hypothesis fallback, fixtures.

Import-order contract: pytest imports this conftest before any test module,
and nothing has imported jax yet, so the XLA host-device flag set here is
seen by jax's first initialization.  tests/test_dist_engine.py needs >= 4
CPU devices to stand up a real (data, model) mesh.
"""
from __future__ import annotations

import os
import sys

# Must precede every jax import (jax locks the device count on first init).
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# hypothesis: real package if present, deterministic fallback otherwise
# ---------------------------------------------------------------------------

HYPOTHESIS_SOURCE = "real"
try:
    import hypothesis  # noqa: F401
except ImportError:
    try:
        sys.path.insert(0, os.path.dirname(__file__))
        import _hypothesis_fallback

        sys.modules["hypothesis"] = _hypothesis_fallback
        sys.modules["hypothesis.strategies"] = \
            _hypothesis_fallback.strategies  # type: ignore[assignment]
        HYPOTHESIS_SOURCE = "fallback"
    except Exception:  # pragma: no cover - last resort: skip, never error
        HYPOTHESIS_SOURCE = "missing"


def _uses_hypothesis(path: str) -> bool:
    try:
        with open(path, "r") as f:
            src = f.read()
        return "import hypothesis" in src or "from hypothesis" in src
    except OSError:
        return False


def pytest_collection_modifyitems(config, items):
    if HYPOTHESIS_SOURCE != "missing":
        return
    skip = pytest.mark.skip(
        reason="hypothesis unavailable and fallback failed to load")
    for item in items:
        if _uses_hypothesis(str(item.fspath)):
            item.add_marker(skip)


def pytest_ignore_collect(collection_path, config):
    # property modules import hypothesis at module scope; if neither the
    # real package nor the fallback loaded, ignore them instead of erroring
    if HYPOTHESIS_SOURCE != "missing":
        return None
    p = str(collection_path)
    if p.endswith(".py") and _uses_hypothesis(p):
        return True
    return None


def pytest_report_header(config):
    return f"hypothesis backend: {HYPOTHESIS_SOURCE}"


# ---------------------------------------------------------------------------
# shared fixtures
# ---------------------------------------------------------------------------

@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def cpu_mesh():
    """A (data=N, model=1) CPU mesh over every forced host device."""
    import jax

    from repro.launch.mesh import auto_mesh

    n = jax.device_count()
    mesh = auto_mesh((n, 1), ("data", "model"))
    return mesh


@pytest.fixture(scope="session")
def sub_mesh():
    """Builder for a (data=n, model=1) mesh over the first n forced host
    devices — the elastic-restore tests shrink the machine count with it."""
    import jax

    def make(n_machines):
        devs = np.asarray(jax.devices()[:n_machines]).reshape(n_machines, 1)
        return jax.sharding.Mesh(devs, ("data", "model"))

    return make


@pytest.fixture(scope="session")
def small_power_law():
    """A ~200-vertex power-law graph shared across distributed tests."""
    from repro.graphs.generators import power_law_graph

    return power_law_graph(200, avg_degree=5, seed=7)
