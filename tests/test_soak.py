"""In-suite production soak (round-6 satellite).

The full-surface soak (`soak_harness.py`: SASL_SSL+SCRAM transport,
exactly-once offsets-in-txn, leader/coordinator churn, live rebalance,
live model swap, chaos kills, per-record sha256 audit) ran in round 5
but its artifact was never committed — which left the README/PARITY
soak claims citing a file that didn't exist. This slow-tier test makes
the claim reproducible IN the suite: a shortened CPU soak run as a
subprocess, gated on the harness's own `exactly_once` audit.

~60 s of feed + drain/audit overhead; excluded from the quick tier.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_cpu_soak_exactly_once():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "soak_harness.py",
         "--seconds", "45", "--rate", "20", "--out", "-", "--chaos"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=390)
    assert out.returncode == 0, (
        f"soak harness failed its own exactly_once gate:\n"
        f"{out.stderr[-4000:]}")
    artifact = json.loads(out.stdout)
    assert artifact["exactly_once"] is True
    audit = artifact["audit"]
    assert audit["echo_missing"] == 0
    assert audit["echo_duplicated"] == 0
    assert audit["invalid_predictions"] == 0
    assert audit["dead_letters"] == 0
    assert audit["predictions"] == audit["predictions_expected"]
    assert audit["drained"] is True
    # The churn events must actually have happened — a quiet run that
    # audited clean proves much less than a churned one.
    assert artifact["events"], "soak ran without any fault/chaos events"
    # --chaos phase: the engine-hang injection must have fired (the
    # watchdog/quarantine arc it drives is what makes the clean audit
    # above a resilience claim, not a fair-weather one).
    chaos = artifact["chaos"]
    assert chaos and chaos["enabled"]
    assert chaos["injections"] >= 1, "chaos armed but nothing injected"
    assert chaos["counts"].get("engine_hang", 0) >= 1
