"""Release gate (VERDICT r4 next-round #8): the version stamp is
consistent and the README quickstart actually works as written — parsed
out of README.md, not re-typed here, so command drift fails the suite."""

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def test_version_stamp_consistent():
    import tomllib

    import storm_tpu

    py = tomllib.loads((REPO / "pyproject.toml").read_text())
    assert py["project"]["version"] == storm_tpu.__version__


def _readme_quickstart_commands():
    """The bash block under '## Quick start', backslash continuations
    joined, comments stripped."""
    text = (REPO / "README.md").read_text()
    m = re.search(r"## Quick start\s+```bash\n(.*?)```", text, re.S)
    assert m, "README.md lost its '## Quick start' bash block"
    joined = re.sub(r"\\\n\s*", " ", m.group(1))
    return [ln.strip() for ln in joined.splitlines()
            if ln.strip() and not ln.strip().startswith("#")]


def test_readme_quickstart_block_parses():
    cmds = _readme_quickstart_commands()
    # the headline commands the README promises
    assert any("storm_tpu.main run " in c for c in cmds)
    assert any("storm_tpu.main serve" in c for c in cmds)
    assert any("storm_tpu.main dist-run" in c for c in cmds)
    assert any(c.startswith("python3 benchmarks/run.py") for c in cmds)
    assert any(c.startswith("python chip_smoke.py") for c in cmds)


@pytest.mark.slow
def test_readme_quickstart_run_daemon_smoke():
    """Run the README's first quickstart command verbatim (ephemeral UI
    port, short --duration added; CPU backend) — it must come up, print
    its running line, and exit 0 on its own."""
    cmd = next(c for c in _readme_quickstart_commands()
               if "storm_tpu.main run " in c)
    import shlex

    assert "--ui-port 8080" in cmd, (
        "README quickstart run command changed shape; update this gate")
    cmd = cmd.replace("--ui-port 8080", "--ui-port 0")
    argv = shlex.split(cmd) + ["--duration", "5"]
    assert argv[0] == "python"
    argv[0] = sys.executable
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(argv, cwd=REPO, env=env, capture_output=True,
                         text=True, timeout=360)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "running" in out.stderr, out.stderr[-3000:]
