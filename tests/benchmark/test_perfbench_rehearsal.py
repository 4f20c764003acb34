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
