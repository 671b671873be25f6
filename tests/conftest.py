"""Test config: force an 8-device CPU mesh BEFORE jax initialises.

Mirrors the reference's strategy of testing distributed logic on small local
worlds (SURVEY.md §4): SPMD tests run against a virtual 8-device CPU mesh via
--xla_force_host_platform_device_count (no TPU needed).
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Persistent compile cache for the whole suite (rides serving/compile_cache,
# ISSUE 16). The suite builds hundreds of byte-identical tiny-llama programs
# across test files; jax's in-memory jit cache cannot dedupe them (every
# engine/fit builds fresh closures) but the persistent cache keys on the HLO
# fingerprint and serves repeats from disk. Must run before the FIRST compile
# of the process (jax latches the cache-on decision there). Where it lives:
# JAX_COMPILATION_CACHE_DIR when set from outside (enable_compile_cache then
# sets no directory itself); otherwise ONE fixed directory under the
# system's temp dir ($TMPDIR or /tmp), OUTSIDE the checkout — the suite's
# cache is hundreds of programs, and the chip tool copies the checkout whole
# on every call. No pid or time in the path: the path is part of the cache
# key. Subprocesses that tests start do not import this file and keep the
# package's default, <checkout>/.jax_cache (git-ignored).
import tempfile  # noqa: E402

from paddle_tpu.serving.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache(os.path.join(tempfile.gettempdir(),
                                  "paddle_tpu-test-compile-cache"))
# enable_compile_cache zeroes the min-compile-time floor (the engine wants
# EVERY program persisted); for the test suite that floor would serialize
# thousands of unique sub-second jits — pure write overhead. Only cache
# compiles expensive enough that a disk hit beats redoing them. Tests that
# exercise the zeroed floor (test_tuner) re-enable it through
# enable_compile_cache with their own directory.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.75)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(2024)
    np.random.seed(2024)
    yield


# The fleet suite spins real engines, serve threads, and subprocess
# workers — by far the most wall-clock-expensive file. Schedule it after
# the rest of the suite so the budgeted tier-1 run finishes the fast unit tests first; a truncation then eats the newest
# integration tests, never the long-standing ones. sort() is stable, so
# relative order inside and outside the fleet file is untouched.
_LAST_FILES = ("test_fleet.py",)


def pytest_collection_modifyitems(config, items):
    items.sort(key=lambda it: it.fspath.basename in _LAST_FILES)
