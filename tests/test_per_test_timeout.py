"""The per-test time limit of tests/conftest.py, run for real: a test that
hangs fails by name, the run's stderr gets every thread's stack at once
(past pytest's capture, so a run that is cut later still has it), and the
tests after it in the same process still run."""

import os
import shutil
import subprocess
import sys
import textwrap

HERE = os.path.dirname(os.path.abspath(__file__))

_SUITE = textwrap.dedent("""
    import threading
    import time

    import pytest


    def _waits_forever(ev):
        ev.wait()


    @pytest.mark.timeout(1)
    def test_hangs():
        threading.Thread(target=_waits_forever, args=(threading.Event(),),
                         daemon=True, name="the-waiter").start()
        time.sleep(60)


    def test_after_it():
        pass
""")


def test_a_hung_test_names_itself_and_the_next_one_runs(tmp_path):
    shutil.copy(os.path.join(HERE, "conftest.py"), tmp_path / "conftest.py")
    (tmp_path / "test_hang.py").write_text(_SUITE)
    (tmp_path / "pytest.ini").write_text(
        "[pytest]\nmarkers =\n    timeout: seconds\n    slow: slow\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly", str(tmp_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=100)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "1 failed, 1 passed" in proc.stdout, proc.stdout
    assert "TimeoutError: per-test timeout: exceeded 1s" in proc.stdout
    assert "per-test timeout (1s) in test_hang.py::test_hangs" in proc.stderr
    assert "in _waits_forever" in proc.stderr, \
        "the stack of the thread that waited is in the log"
    assert "in test_hangs" in proc.stderr
