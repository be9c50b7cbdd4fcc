"""Test harness configuration.

Mirrors the reference's SparkTestUtils strategy (photon-test-utils
.../SparkTestUtils.scala:58-76): "distributed" behavior is tested without a
cluster by running the real collective code paths on local devices. Here the
local cluster is a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``), and float64 is enabled so math
tests can compare against scipy at tight tolerances.

These env vars MUST be set before jax is imported anywhere.
"""

import os

# Force CPU: tests never use an accelerator, whatever the surrounding
# environment points JAX at.
os.environ["JAX_PLATFORMS"] = "cpu"
# Tests must not share the persistent XLA compilation cache: a cache entry
# corrupted by a killed process SEGFAULTS jax's cache read (observed:
# compilation_cache.get_executable_and_time), and test compiles would pollute
# the cache of real runs anyway. The env var (not a config update) so CLI
# subprocesses spawned by tests inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """XLA:CPU's compiler segfaults nondeterministically deep into long
    single-process sessions (observed twice: round 4 at test_scale_paths with
    device-created pjit inputs, round 5 in backend_compile after ~160 tests).
    Dropping compiled executables between test modules resets the accumulated
    compiler state that triggers it; per-module granularity keeps the
    recompile cost bounded (shared solver jits are mostly reused within one
    module)."""
    yield
    jax.clear_caches()


@pytest.fixture
def use_re_solver(monkeypatch):
    """``use_re_solver("vmapped")`` puts the tests' reference solve
    (testing/reference_solver.py) in the packed solver's place for the rest
    of the test; ``"packed"`` leaves the solver as it is."""

    def use(solver: str) -> None:
        assert solver in ("packed", "vmapped"), solver
        if solver == "vmapped":
            from photon_ml_tpu.game import coordinate
            from photon_ml_tpu.testing.reference_solver import train_blocks_vmapped

            monkeypatch.setattr(
                coordinate, "_train_blocks_packed", train_blocks_vmapped
            )

    return use


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test (multi-process smoke, scale paths)")
