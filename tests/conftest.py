"""Test fixtures: emulate an 8-device TPU mesh on CPU.

SURVEY.md §4: the reference has zero framework tests (everything assumed a
real ssh cluster). Our strategy replaces that with in-process multi-device
tests on a virtual CPU mesh — env vars must be set before jax initializes.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8"
                           ).strip()
# the suite's thousands of sub-half-second compiles are not worth a
# cache file each (jax and the subprocess drivers read this themselves)
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402

# conftest means the CPU whatever JAX_PLATFORMS the caller exported
jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite is compile-dominated (every
# engine test pjits a training step), so repeat local runs get most of
# their wall time back. Keyed by HLO + compile env, so a stale cache can
# only miss, never corrupt. The one helper decides where it lives:
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache.
from parallax_tpu.compile.cache import ensure_persistent_cache  # noqa: E402

# export to os.environ so SUBPROCESS drivers (test_multihost.py spawns
# 2-4 jax processes per test via dict(os.environ)) share the cache too
# — without this every multihost test recompiled every engine in every
# worker on every run
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      ensure_persistent_cache())
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _devices():
    assert jax.device_count() == 8, (
        f"expected 8 virtual CPU devices, got {jax.device_count()}")
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)
