"""The command itself at toy size on the CPU: the same control flow as a
chip run, a two-second window, no trace."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _command(*extra):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return [sys.executable if w == "python3" else w
            for w in bench["command"]] + list(extra)


@pytest.mark.timeout(110)
def test_command_prints_the_contracts_line_at_toy_size():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        _command("--workload", "vit_tiny.tensor_backlog", "--seed",
                 "3000000019", "--seconds", "2", "--trace", "0",
                 "--rehearse"),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=100)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(row) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert row["correct"] is True and row["failed"] == 0
    assert row["attempted"] > 0
    assert set(row["metrics"]) == {"records_per_s", "setup_s"}
    for value in row["metrics"].values():
        assert value["value"] > 0 and value["unit"]
    assert set(row["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert row["device"]["platform"] == "cpu"
    # every number compared stands beside its limit, last in the line and
    # last on standard error
    assert list(row)[-1] == "checks"
    assert all(number <= limit for number, limit in row["checks"].values())
    assert 0 < row["checks"]["farthest_output"][0]
    said = [line for line in proc.stderr.splitlines()
            if line.startswith("check ")]
    assert proc.stderr.strip().splitlines()[-len(said):] == said
    assert [line.split()[1].rstrip(":") for line in said] == \
        list(row["checks"])


BROKEN = """
import os, runpy, sys
from concurrent.futures import Future
import numpy as np
sys.path.insert(0, {root!r})
from storm_tpu.infer import engine

class Altered(Future):
    # the first answer of every batch, altered where the engine hands it on
    def set_result(self, res):
        res = np.array(res)
        res[0] = 0.0
        res[0, 0] = 1.0
        super().set_result(res)

made = engine.InflightBatch.__init__
def init(self, n, padded):
    made(self, n, padded)
    self.future = Altered()
engine.InflightBatch.__init__ = init
sys.argv = ["run.py", "--workload", "vit_tiny.tensor_backlog", "--seed",
            "3000000021", "--seconds", "2", "--trace", "0", "--rehearse"]
runpy.run_path(os.path.join({root!r}, "benchmarks", "run.py"),
               run_name="__main__")
"""


@pytest.mark.timeout(110)
def test_an_answer_altered_where_it_is_produced_is_not_correct():
    """The whole of a run but the look for a chip, with the timed path
    broken underneath: one row of every batch the engine returns is another
    answer. The run ends and says so: ``correct`` false, the altered rows
    counted as failed, the number over its limit named."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", BROKEN.format(root=ROOT)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=100)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["correct"] is False and row["failed"] > 0
    number, limit = row["checks"]["outputs_of_no_row"]
    assert number > 0 and limit == 0
    far, tol = row["checks"]["farthest_output"]
    assert far > 3 * tol
    # the request an altered row should have answered has no answer
    assert row["checks"]["unanswered"] == [number, 0]
    assert row["failed"] == 2 * number


@pytest.mark.timeout(60)
def test_without_a_tpu_there_is_no_result():
    """No CPU path: the same command without --rehearse exits non-zero and
    prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        _command("--workload", "vit_g14.tensor_backlog", "--seed", "1",
                 "--seconds", "2", "--trace", "0"),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=50)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        assert "correct" not in line
