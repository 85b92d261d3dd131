"""Test harness: simulate an 8-chip topology on CPU.

Reference test strategy (SURVEY.md §4): no mocks — run the real code paths
on a localhost topology. Our equivalent for the ICI stage is XLA's virtual
CPU devices (8 devices in one process); the DCN/PS leg is tested with real
localhost TCP processes in test_kv/test_server (same philosophy: real
transport, real summation, no fakes).

Must run before any jax import, hence the env mutation at module top.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tier-1 never needs a chip
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(items):
    """Every multi-process topology test is also `slow`; the fast tier is
    `pytest -m "not slow"` (docs/testing in README)."""
    for item in items:
        if ("ps" in item.keywords or "serving" in item.keywords
                or "ckpt" in item.keywords):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_byteps_state():
    """Each test gets a clean global state and a fresh env snapshot."""
    yield
    try:
        import byteps_tpu.jax as bps
        if bps.initialized():
            bps.shutdown()
    except Exception:
        pass
    import byteps_tpu.config as config
    config._config = None
    import byteps_tpu.parallel.mesh as mesh_mod
    mesh_mod._global_mesh = None


@pytest.fixture
def rng():
    return np.random.default_rng(0)
