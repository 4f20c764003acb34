"""``chip_smoke.py`` is the standing proof that the serving path runs on the
chip. Here, on a CPU-only host, three things about it can be held: the
rehearsal (toy presets, kernels under the Pallas interpreter) passes through
every default phase; without ``--rehearse`` there is no CPU path at all; and
the parent process stays off JAX, so each phase's child can own the chip."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*flags, timeout=110):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # the rehearsal runs on one CPU device, as the chip run does on one chip
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, SMOKE, *flags], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=timeout)
    rows = [json.loads(line) for line in p.stdout.splitlines()
            if line.startswith("{")]
    return p, rows


def test_rehearsal_passes_every_default_phase():
    p, rows = _run("--rehearse")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert rows[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    phases = {r["phase"]: r for r in rows if "phase" in r}
    for name in ("topology", "kernel", "serve", "dist"):
        assert phases[name]["ok"] is True, phases[name]
    assert phases["native_build"]["ok"] is True
    assert phases["decode"]["covered"] is False  # said, not pretended
    topo = phases["topology"]
    assert topo["records_out"] == topo["records_in"] - 1
    assert topo["dead_lettered"] == 1 and topo["native"] is True
    assert topo["trace_bytes"] > 0 and topo["match"] is True
    assert phases["kernel"]["kernel_in_program"] is True
    # the serve child found the topology child's executables
    assert phases["serve"]["cache_hits"] > 0
    dist = phases["dist"]
    assert dist["worker_backends"] == {"0": None, "1": "cpu"}
    assert dist["controller_backend"] is None
    assert dist["engines_colocated"] is True


def test_no_cpu_path_without_rehearse():
    """On a host with no TPU the script fails at the device probe: non-zero
    exit, ``"ok": false`` on the last line, and no phase started."""
    p, rows = _run(timeout=60)
    assert p.returncode != 0
    assert rows[-1]["ok"] is False
    assert rows[-1]["device"]["platform"] == "cpu"
    started = {r.get("phase") for r in rows}
    assert not started & {"native_build", "topology", "kernel", "serve",
                          "dist", "sharded"}


def test_parent_process_never_imports_jax():
    """A parent that has touched JAX holds the chip, and its children then
    fail or hang: the parent's whole code path must leave jax unimported."""
    code = (
        "import sys, runpy\n"
        f"sys.argv = [{SMOKE!r}]\n"
        "try:\n"
        f"    runpy.run_path({SMOKE!r}, run_name='__main__')\n"
        "except SystemExit:\n"
        "    pass\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n"
        "assert 'storm_tpu' not in sys.modules, 'parent imported the program'\n"
        "print('PARENT_CLEAN')\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=60)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().endswith("PARENT_CLEAN")
