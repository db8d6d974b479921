"""Test-session bootstrap.

Forces JAX onto a simulated 8-device CPU platform so multi-chip sharding
(tp/dp/ep/sp axes over a Mesh) is exercised without TPU hardware — the
strategy SURVEY.md §4 prescribes for this framework's multi-node tier.

The suite is a CPU suite wherever it runs: on a TPU host JAX would pick
the chip by default, the 8 simulated devices would not exist, and
POLYKEY_BACKEND=tpu start-up (engine/device.require_accelerator) serves
from a non-TPU platform only when JAX_PLATFORMS=cpu is explicit. So both
variables are set here, before jax is imported anywhere.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402


import gc

import pytest


def pytest_configure(config):
    # The tier-1 gate runs `-m 'not slow'`; anything heavier (e.g. the
    # full-profile graphlint self-run) opts out with this marker.
    config.addinivalue_line(
        "markers", "slow: excluded from the fast tier-1 run (-m 'not slow')"
    )


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """XLA's CPU JIT segfaults deterministically late in the FULL suite
    (inside backend_compile_and_load for the ring-attention train step;
    the same test passes in isolation and the full suite passed before
    the suite grew past ~270 tests) — compile-state accumulated across
    hundreds of in-process executables eventually corrupts a compile.
    Dropping the compiled-executable caches at module boundaries keeps
    the accumulation bounded; modules recompile their own shapes anyway,
    so the cost is small and per-module behavior is unchanged."""
    yield
    jax.clear_caches()
    gc.collect()
