"""Test harness configuration.

Forces JAX onto the host CPU with a virtual 8-device platform so multi-chip
sharding (Mesh/pjit/shard_map) is exercised without TPU hardware. Must run
before jax is imported anywhere.

Gives every test one time limit, around its set-up, call and tear-down, so a
wait that never ends costs that test and not the run (see ``TEST_LIMIT_S``).
"""

import faulthandler
import os
import signal
import threading

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.device_count() == 8, (
    f"test harness expected 8 virtual CPU devices, got {jax.devices()}"
)

# Seconds a test may take. Measured under the tier-1 command (PR 28, CHANGES.md):
# the slowest test takes 18 s on eight cores and 68 s when the whole run is
# pinned to two, so this leaves a machine several times slower its room and
# still costs a hung test a fifth of the run's own 1,470 s. A test that truly
# needs more carries ``@pytest.mark.time_limit(seconds)``.
TEST_LIMIT_S = 300.0
# The soft limit raises in the test's main thread. Where no handler can run (a
# native call, a lock taken in native code), this much later every thread's
# stack goes to stderr and the process ends: xdist reports `node down`, starts
# another worker and runs the rest.
HARD_GRACE_S = 30.0

_stderr = None  # the real stderr, taken before a test's capture redirects fd 2


def pytest_configure(config):
    global _stderr
    _stderr = os.fdopen(os.dup(2), "w")


def pytest_unconfigure(config):
    global _stderr
    if _stderr is not None:
        _stderr.close()
        _stderr = None


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    mark = item.get_closest_marker("time_limit")
    limit = float(mark.args[0]) if mark else TEST_LIMIT_S

    def past_limit(signum, frame):
        pytest.fail(f"{item.nodeid} ran past its time limit of {limit:g} s "
                    "(tests/conftest.py: TEST_LIMIT_S, or the test's time_limit marker)")

    soft = threading.current_thread() is threading.main_thread()
    if soft:  # signal handlers run in the main thread only
        before = signal.signal(signal.SIGALRM, past_limit)
        signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + HARD_GRACE_S, exit=True, file=_stderr)
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        if soft:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, before)
