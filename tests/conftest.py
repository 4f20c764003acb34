"""Test env: JAX on the CPU with 8 virtual devices, set BEFORE jax imports,
so sharding/mesh tests run without TPU hardware (SURVEY.md §4 build
obligation: fake/CPU backend for multi-device simulation).

``JAX_PLATFORMS`` is the one platform switch. The suite defaults it to
``cpu``; a caller that names another platform keeps it, which is how the
compiled-kernel tests run on the chip:
``JAX_PLATFORMS=tpu python -m pytest tests/test_tpu_kernels.py``."""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

if os.environ["JAX_PLATFORMS"] == "cpu":
    assert jax.devices()[0].platform == "cpu", "tests require the CPU backend"
    assert len(jax.devices()) == 8, "tests require 8 virtual CPU devices"

import asyncio
import faulthandler
import signal
import sys

import pytest


@pytest.fixture(autouse=True)
def _per_test_timeout(request):
    """Per-test wall-clock timeout (VERDICT r1 weak #3): a wedged test must
    FAIL with a traceback pointing at the hang, not stall the whole run.
    Defaults: 120s, 420s for ``slow``-marked tests; override with
    ``@pytest.mark.timeout(seconds)``. SIGALRM only fires on the main
    thread, which is where pytest runs test bodies; every thread's stack
    goes to stderr first, so the log names the line that waited even when
    it is on another thread."""
    if not hasattr(signal, "SIGALRM"):  # pragma: no cover - non-unix
        yield
        return
    limit = 420 if request.node.get_closest_marker("slow") else 120
    m = request.node.get_closest_marker("timeout")
    if m and m.args:
        limit = int(m.args[0])

    def _on_alarm(signum, frame):
        # Past pytest's capture, straight to the run's own stderr: the log
        # has the stacks at once, also when the run is cut before its report.
        capture = request.config.pluginmanager.getplugin("capturemanager")
        with capture.global_and_fixture_disabled():
            sys.stderr.write(f"\nper-test timeout ({limit}s) in "
                             f"{request.node.nodeid}; all threads:\n")
            sys.stderr.flush()
            faulthandler.dump_traceback(all_threads=True)
        raise TimeoutError(
            f"per-test timeout: exceeded {limit}s (tests/conftest.py)")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(limit)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro, timeout=60.0):
        return asyncio.run(asyncio.wait_for(coro, timeout))

    return _run
