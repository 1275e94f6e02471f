import os

# Smoke tests and benchmarks must see the REAL device count (the dry-run
# alone forces 512 host devices, in its own process).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Deterministic jax/XLA numerics for the vcluster backend-conformance
# suite: a fixed single-threaded CPU reduction order makes kernel outputs
# reproducible across CI machines and laptops (threaded reductions may
# reassociate float sums).  setdefault only — an externally configured
# XLA_FLAGS (e.g. the dry-run's forced device count) wins.
os.environ.setdefault(
    "XLA_FLAGS",
    "--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1",
)

import signal  # noqa: E402
import threading  # noqa: E402

import pytest  # noqa: E402

#: Per-test wall-clock limit (seconds); ``@pytest.mark.timeout(n)`` sets
#: a test's own.  The fault-injection and sweep suites spawn worker
#: processes and the service suite runs live sockets: a hang should fail
#: its one test, not wedge the whole run.
TEST_LIMIT_S = 300.0


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout(seconds): this test's wall-clock limit"
    )


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    # SIGALRM interrupts the main thread only; elsewhere (no setitimer,
    # or a test running off the main thread) there is no limit.
    if (
        not hasattr(signal, "setitimer")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return
    marker = item.get_closest_marker("timeout")
    limit = float(marker.args[0]) if marker and marker.args else TEST_LIMIT_S

    def expired(signum, frame):
        pytest.fail(f"test exceeded its {limit:g} s time limit")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture(scope="session")
def rng():
    import jax

    return jax.random.PRNGKey(0)
