"""Test harness: run the whole suite on a virtual 8-device CPU mesh.

This is the JAX-native analog of the reference's gloo-on-CPU trick
(``tests/test_cpu.py`` + ``debug_launcher`` ``launchers.py:269-302``): 8 fake
devices exercise every sharding/collective path with zero hardware (SURVEY.md §4).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Must run before any jax backend initialization: eight virtual CPU devices.
from accelerate_tpu.utils.environment import pin_cpu_platform  # noqa: E402

pin_cpu_platform(8)

# Session-scoped persistent compilation cache (dogfooding the
# ACCELERATE_COMPILE_CACHE_DIR contract): the suite launches dozens of
# subprocesses (CLI/launcher/example tests) that would each re-compile the
# same tiny programs; inheriting this env lets them load from the cache
# instead. Fresh dir per session, removed at session end — no cross-run
# state. Tests that need their own cache dir (test_compile_cache.py)
# override the var in their env.
_owned_cache_dir = None
if "ACCELERATE_COMPILE_CACHE_DIR" not in os.environ:
    import tempfile

    _owned_cache_dir = tempfile.mkdtemp(prefix="at_test_xla_cache_")
    os.environ["ACCELERATE_COMPILE_CACHE_DIR"] = _owned_cache_dir

# Flight-recorder dumps (telemetry/flight.py) default to ./flight_recorder;
# tests that trip guards / restart / hang would litter the repo — route the
# whole session's black boxes into a disposable dir instead. Tests that
# assert on dump contents override the var themselves.
_owned_flight_dir = None
if "ACCELERATE_FLIGHT_DIR" not in os.environ:
    import tempfile

    _owned_flight_dir = tempfile.mkdtemp(prefix="at_test_flight_")
    os.environ["ACCELERATE_FLIGHT_DIR"] = _owned_flight_dir

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _cleanup_session_compile_cache():
    yield
    import shutil

    if _owned_cache_dir is not None:
        shutil.rmtree(_owned_cache_dir, ignore_errors=True)
    if _owned_flight_dir is not None:
        shutil.rmtree(_owned_flight_dir, ignore_errors=True)


@pytest.fixture(autouse=True)
def _reset_singletons():
    """State hygiene between tests — reference ``AccelerateTestCase``
    (``test_utils/testing.py:618-629``) resets singletons the same way."""
    yield
    from accelerate_tpu.state import AcceleratorState, GradientState, PartialState

    AcceleratorState._reset_state()
    PartialState._reset_state()
    GradientState._reset_state()


# Pinned seeds: the resilience AND health tests (markers `resilience` /
# `health`, registered in pyproject) assert BIT-EXACT resume/rollback (params,
# optimizer moments, RNG streams), and run_resilient's backoff jitter draws
# from random.random — every test starts from the same host-RNG state so fault
# drills are reproducible run-over-run.
os.environ.setdefault("ACCELERATE_SEED", "0")


@pytest.fixture(autouse=True)
def _reset_forensics():
    """Profiler + flight recorder are process-wide by design; an armed
    capture or a populated event ring must never leak across tests."""
    yield
    from accelerate_tpu.telemetry.fleet import reset_fleet
    from accelerate_tpu.telemetry.flight import reset_flight_recorder
    from accelerate_tpu.telemetry.journal import reset_journal
    from accelerate_tpu.telemetry.profiler import reset_profile_manager
    from accelerate_tpu.telemetry.traceview import attach_collective_axes

    reset_profile_manager()
    reset_journal()  # closes the file + uninstalls the flight/metrics taps
    reset_flight_recorder()
    reset_fleet()  # endpoint registry + /fleet provider are process-wide
    attach_collective_axes(None)  # Accelerator.audit attaches a module global


@pytest.fixture(autouse=True)
def _reset_health_watchdog():
    """The hang watchdog is a process-global daemon thread by design; never
    let one test's watchdog outlive it and fire into another test."""
    yield
    from accelerate_tpu.health.hang import reset_default_watchdog

    reset_default_watchdog()


@pytest.fixture(autouse=True)
def _pin_seeds():
    import random

    import numpy as np

    random.seed(0)
    np.random.seed(0)
    yield
