"""Test configuration: run JAX on a virtual 8-device CPU mesh so sharding
paths are exercised without TPU hardware. A CPU run checks correctness and
counts; times come only from the chip (``chip_smoke.py``).

The persistent XLA compile cache is the package's own
(``internals/config.py:enable_compile_cache``: $JAX_COMPILATION_CACHE_DIR,
else ``<checkout>/.jax_cache``), shared by the whole sweep and the
subprocesses it starts; ``JAX_ENABLE_COMPILATION_CACHE=false`` runs cold."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# tests always run on the virtual 8-device CPU mesh, whatever the machine
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_graph():
    """Each test gets a clean global graph and error log."""
    import pathway_tpu as pw
    from pathway_tpu.internals.errors import get_global_error_log

    pw.clear_graph()
    get_global_error_log().clear()
    yield


# ---------------------------------------------------------------- timeouts
# pytest-timeout is not installed in this image; without this hook the
# @pytest.mark.timeout guards (crash-recovery kill/restart loops) would be
# silent no-ops. SIGALRM interrupts the test in the main thread; tests that
# hang in child processes still get killed because the subprocess waits run
# there too.


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): fail the test if it runs longer than `seconds` "
        "(enforced by conftest via SIGALRM when pytest-timeout is absent)",
    )
    config.addinivalue_line(
        "markers",
        "slow: long-running perf guards, excluded from the tier-1 sweep "
        "(-m 'not slow')",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    import importlib.util
    import signal

    if importlib.util.find_spec("pytest_timeout") is not None:
        return (yield)  # real pytest-timeout installed: defer to it
    marker = item.get_closest_marker("timeout")
    if marker is None or not hasattr(signal, "SIGALRM"):
        return (yield)
    seconds = float(marker.args[0]) if marker.args else float(
        marker.kwargs.get("timeout", 300)
    )

    def on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded its {seconds:.0f}s timeout mark"
        )

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
