"""Test harness config.

Tests run on a virtual 8-device **CPU** mesh (no TPU pod needed — the
reference's analog is running the same suites against the nd4j-native backend
via Maven profile `test-nd4j-native`, `pom.xml:163-206`). Distributed tests
use the 8 fake devices the way `BaseSparkTest` uses `local[N]` Spark.

x64 is enabled because gradient checks require double precision
(`GradientCheckUtil.java` requirement in the reference).

IMPORTANT: env vars must be set before jax is imported anywhere.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Force the CPU backend regardless of environment: tests must never take
# the chip (it belongs to one process at a time), and config wins over env.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process spawns etc.)")
    config.addinivalue_line(
        "markers",
        "sanitize(**kwargs): run the test under "
        "analysis.sanitizer.sanitize — thread-leak watchdog + "
        "order-asserting lock shims by default; kwargs forwarded "
        "(tracer_leaks=, debug_nans=, grace_s=, ...)")


@pytest.fixture(autouse=True)
def _graftlint_sanitize(request):
    """The `sanitize` pytest marker: wraps the marked test in the
    graftlint runtime sanitizer (see analysis/sanitizer.py). Violations
    surface as test errors at teardown."""
    m = request.node.get_closest_marker("sanitize")
    if m is None:
        yield
        return
    from deeplearning4j_tpu.analysis.sanitizer import sanitize
    with sanitize(**m.kwargs):
        yield


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


def make_classification(n=256, n_features=10, n_classes=3, seed=0):
    """Synthetic linearly-separable-ish classification data (one-hot labels)."""
    r = np.random.default_rng(seed)
    centers = r.normal(0, 4.0, size=(n_classes, n_features))
    ys = r.integers(0, n_classes, size=n)
    xs = centers[ys] + r.normal(0, 1.0, size=(n, n_features))
    onehot = np.zeros((n, n_classes), np.float64)
    onehot[np.arange(n), ys] = 1.0
    return xs.astype(np.float64), onehot


@pytest.fixture
def classification_data():
    return make_classification()
