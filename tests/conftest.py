"""Process-level hygiene for the tier-1 suite.

XLA:CPU JIT-compiles every executable into freshly mmap'd code pages, and
the full suite compiles thousands of programs in ONE pytest process.  Linux
caps a process at ``vm.max_map_count`` (65530 by default) memory mappings;
once the JIT's mmap fails, LLVM segfaults the interpreter mid-compile —
observed reproducibly near the END of the full suite (at ~65.5k maps) while
every module passes in isolation.  Dropping JAX's compilation caches
between modules unmaps retired executables and keeps the mapping count
bounded; the per-module recompiles cost a few seconds over the whole run.

Every test also runs under a time limit (``TEST_LIMIT_S``): a test that
hangs fails on its own, with every thread's stack, instead of holding its
worker until the suite's own limit cuts the whole run.
"""

import faulthandler
import signal
import threading

import jax
import pytest

# The slowest tests, two hypothesis sweeps that compile an engine per
# example, took 118-209 s under the suite's six-worker run on an 8-core
# CPU host: the limit leaves twice that.  Subprocess and service-drain
# waits inside tests are set below it.
TEST_LIMIT_S = 480


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    yield
    jax.clear_caches()


@pytest.fixture(autouse=True)
def _test_time_limit(request):
    """SIGALRM after ``TEST_LIMIT_S``: faulthandler dumps every thread's
    stack at once (even inside a native call such as an XLA compile), then
    the Python handler fails the test as soon as control returns to it."""
    if threading.current_thread() is not threading.main_thread():
        yield  # signals reach only the main thread
        return

    def expired(signum, frame):
        raise TimeoutError(
            f"{request.node.nodeid} ran past the {TEST_LIMIT_S}-s test limit"
        )

    previous = signal.signal(signal.SIGALRM, expired)
    faulthandler.register(signal.SIGALRM, all_threads=True, chain=True)
    signal.alarm(TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        faulthandler.unregister(signal.SIGALRM)
        signal.signal(signal.SIGALRM, previous)
