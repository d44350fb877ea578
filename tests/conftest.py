"""Test harness config: force an 8-device virtual CPU platform BEFORE jax
imports, so distributed/sharding tests run without TPU hardware (the rebuild's
analog of the reference's multi-process localhost harness,
test/legacy_test/test_dist_base.py)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from paddle_tpu.compile_cache.jax_cache import enable_jax_cache  # noqa: E402

# Persistent compilation cache: XLA programs survive across test runs, so
# repeat runs skip the multi-second compiles that dominate the suite. The
# suite dispatches thousands of tiny eager programs that compile faster than
# a cache entry round-trips, so only compiles over half a second are kept.
_cache_dir = enable_jax_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
# subprocess-based tests (graft dryrun, elastic launch) inherit the cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _cache_dir)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seeded():
    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy integration tests (large compiles / subprocesses); "
        "deselect with -m 'not slow' for the <5-minute quick loop")
    config.addinivalue_line(
        "markers",
        "serial: multi-process rendezvous tests sensitive to machine load; "
        "run isolated (pytest -m serial) when diagnosing flakes — they "
        "retry once on transient TCPStore/segfault infra failures")
