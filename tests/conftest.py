"""Test env: force CPU with 8 virtual devices BEFORE jax initialises.

Multi-chip sharding tests run on a virtual 8-device CPU mesh (the driver
separately dry-runs the multi-chip path; real TPU is reserved for bench).
"""

import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kube_scheduler_simulator_tpu.utils.platform import force_cpu  # noqa: E402

force_cpu(n_virtual_devices=8)

# lock-witness mode (docs/static-analysis.md): KSS_TPU_LOCK_WITNESS=1
# wraps every lock created from here on in the lockdep-style witness;
# the concurrency/engine soak modules then FAIL on any acquisition-order
# cycle, even when the interleaving didn't actually deadlock.  Installed
# before any test module imports so engines/stores built inside tests
# get witnessed locks.
_WITNESS = None
if os.environ.get("KSS_TPU_LOCK_WITNESS") == "1":
    from tools.analysis import lockwitness  # noqa: E402

    _WITNESS = lockwitness.install()

_WITNESS_MODULES = {"test_concurrency_soak", "test_engine_soak"}


def pytest_runtest_teardown(item):
    if _WITNESS is None:
        return
    mod = getattr(item, "module", None)
    name = getattr(mod, "__name__", "").rpartition(".")[2]
    if name in _WITNESS_MODULES:
        _WITNESS.assert_no_cycles()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running / tooling-heavy tests (excluded from tier-1, "
        "which runs -m 'not slow'); e.g. the codec-suite-under-ASan run")


@pytest.fixture(autouse=True)
def _fresh_session_controls():
    """The per-session control registry is the process's: servers of
    successive tests all serve a session named "default", and a shed the
    autopilot opened on one test's slow first pass must not answer the
    next test's requests with 429."""
    from kube_scheduler_simulator_tpu.control import CONTROLS

    CONTROLS.reset()
    yield
