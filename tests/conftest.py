"""Test harness config: force JAX onto a virtual 8-device CPU mesh, x64 on.

The suite runs on the CPU: sharding correctness is validated on
host-platform virtual devices (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip). This is NOT what
one chip runs — there the engine has no mesh, plain jit and f32 staging;
tests/test_f32_staging.py, tests/test_offload_widening.py and the
one-device engines in tests/test_mesh_scaling.py cover those paths here,
and chip_smoke.py covers them on the chip.
"""
import os

# Every process of the suite (the xdist workers, the servers some tests
# spawn) keeps its compiled programs in ONE git-ignored directory of the
# suite's own, set before jax reads its flags: the checkout's default
# cache (ops/device.py) is then written only by a program started without
# the variable. tests/test_chip_smoke.py compares that directory before
# and after a run that has to leave it alone, while the other workers
# compile; any of their compiles of a second or more used to land there
# in between and fail it (a change to a kernel makes every one a miss).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache_tests"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1 CI")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection suites "
        "(utils/failpoints.py) — seeded and reproducible, so they run in "
        "tier-1; the marker exists to select/deselect them explicitly "
        "(e.g. -m chaos / -m 'not chaos')")


_exit_status = [None]


def pytest_sessionfinish(session, exitstatus):
    _exit_status[0] = int(exitstatus)


def pytest_unconfigure(config):
    """Skip interpreter finalization after the verdict is in.

    A full-suite run occasionally dies with ``terminate called without
    an active exception`` (SIGABRT, exit 134) DURING CPython teardown,
    AFTER pytest has printed its summary — an XLA/TSL C++ worker thread
    being finalized mid-flight, not a test failure. Exiting hard with
    pytest's own status (recorded in sessionfinish; unconfigure runs
    after the terminal summary prints) preserves the real verdict and
    sidesteps the native teardown entirely (the standard JAX-suite
    workaround). Set PINOT_TPU_SOFT_EXIT=1 to restore normal
    finalization (e.g. for coverage/profiling runs that need atexit
    hooks)."""
    if os.environ.get("PINOT_TPU_SOFT_EXIT") == "1" \
            or _exit_status[0] is None:
        return
    import sys
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(_exit_status[0])
