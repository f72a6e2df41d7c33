"""Test config: force an 8-device virtual CPU mesh BEFORE jax import.

Mirrors SURVEY.md §4's implication: multi-device learners are
unit-testable single-process via xla_force_host_platform_device_count.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from lightgbm_tpu.config import setup_compilation_cache  # noqa: E402

# Persistent compilation cache from the first compile on: the suite's
# cost is dominated by jitted tree-builder recompiles per config
# permutation, and a warm cache cuts the wall-clock ~40%. The directory
# follows the library's one rule (JAX_COMPILATION_CACHE_DIR, else
# <checkout>/.jax_cache). The suite compiles thousands of sub-second
# programs that are cheaper to recompile than to store.
setup_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs with `-m 'not slow'`; the slow mark carries the
    # longer acceptance rungs (make verify-fleet runs them)
    config.addinivalue_line("markers",
                            "slow: long acceptance rungs, skipped by "
                            "the tier-1 `-m 'not slow'` filter")


@pytest.fixture(autouse=True)
def _log_level():
    # training with verbose=-1 mutes the process's Log: the next test in
    # the same worker starts at the level this one found
    from lightgbm_tpu.utils.log import Log
    level = Log._level
    yield
    Log.reset_log_level(level)


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(42)
