"""The ctl CLI (storm kill/activate/rebalance command-line equivalent):
main.py's ctl subcommand driving a live UI server over HTTP."""

import asyncio
import io
import json
from contextlib import redirect_stdout

import pytest

from storm_tpu.config import Config
from storm_tpu.main import main as cli_main
from storm_tpu.runtime.cluster import AsyncLocalCluster
from storm_tpu.runtime.ui import UIServer
from tests.test_ui import EchoBolt, TrickleSpout


def _ctl(url, *argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli_main(["ctl", "--url", url, *argv])
    return rc, buf.getvalue()


def test_ctl_commands_against_live_daemon(run):
    async def go():
        from storm_tpu.runtime import TopologyBuilder

        tb = TopologyBuilder()
        tb.set_spout("spout", TrickleSpout(), parallelism=1)
        tb.set_bolt("echo", EchoBolt(), parallelism=2).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        await cluster.submit("demo", Config(), tb.build())
        ui = await UIServer(cluster, port=0).start()
        url = f"http://127.0.0.1:{ui.port}"
        loop = asyncio.get_running_loop()
        try:
            rc, out = await loop.run_in_executor(None, _ctl, url, "list")
            assert rc == 0 and json.loads(out)["topologies"][0]["name"] == "demo"

            rc, out = await loop.run_in_executor(
                None, _ctl, url, "status", "demo")
            assert rc == 0 and json.loads(out)["status"] == "ACTIVE"

            rc, out = await loop.run_in_executor(
                None, _ctl, url, "rebalance", "demo", "echo", "3")
            assert rc == 0
            assert len(cluster.runtime("demo").bolt_execs["echo"]) == 3

            rc, out = await loop.run_in_executor(
                None, _ctl, url, "deactivate", "demo")
            assert rc == 0 and json.loads(out)["status"] == "INACTIVE"
            rc, out = await loop.run_in_executor(
                None, _ctl, url, "activate", "demo")
            assert rc == 0

            rc, out = await loop.run_in_executor(
                None, _ctl, url, "graph", "demo")
            assert rc == 0 and "edges" in json.loads(out)

            rc, out = await loop.run_in_executor(
                None, _ctl, url, "status", "nope")
            assert rc == 1  # HTTP error surfaces as nonzero exit

            rc, out = await loop.run_in_executor(
                None, _ctl, url, "kill", "demo")
            assert rc == 0
            for _ in range(100):
                if "demo" not in cluster.runtimes:
                    break
                await asyncio.sleep(0.05)
            assert "demo" not in cluster.runtimes
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=120)


def test_ctl_drain_waits_for_inflight(run):
    """ctl drain hits the real drain route: deactivate + in-flight wait,
    not a bare deactivate."""

    async def go():
        from storm_tpu.runtime import TopologyBuilder

        tb = TopologyBuilder()
        tb.set_spout("spout", TrickleSpout(), parallelism=1)
        tb.set_bolt("echo", EchoBolt(), parallelism=1).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("d", Config(), tb.build())
        ui = await UIServer(cluster, port=0).start()
        url = f"http://127.0.0.1:{ui.port}"
        loop = asyncio.get_running_loop()
        try:
            await asyncio.sleep(0.2)
            rc, out = await loop.run_in_executor(None, _ctl, url, "drain", "d")
            assert rc == 0
            body = json.loads(out)
            assert body["status"] == "INACTIVE" and body["drained"] is True
            assert rt.ledger.inflight == 0
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ctl_token_flag(run):
    """`ctl --token` sends the bearer header the auth-enabled daemon
    demands; without it, mutating commands come back 401."""

    async def go():
        from storm_tpu.runtime import TopologyBuilder

        tb = TopologyBuilder()
        tb.set_spout("spout", TrickleSpout(), parallelism=1)
        tb.set_bolt("echo", EchoBolt(), parallelism=1).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        await cluster.submit("demo", Config(), tb.build())
        ui = await UIServer(cluster, port=0, auth_token="ops-tok").start()
        url = f"http://127.0.0.1:{ui.port}"
        loop = asyncio.get_running_loop()
        try:
            # read works without a token
            rc, out = await loop.run_in_executor(
                None, _ctl, url, "status", "demo")
            assert rc == 0
            # mutating without the token: nonzero rc, 401 surfaced
            rc, out = await loop.run_in_executor(
                None, _ctl, url, "deactivate", "demo")
            assert rc != 0 and "token" in out
            # with --token: accepted
            def ctl_tok():
                buf = io.StringIO()
                with redirect_stdout(buf):
                    rc = cli_main(["ctl", "--url", url, "--token", "ops-tok",
                                   "deactivate", "demo"])
                return rc, buf.getvalue()

            rc, out = await loop.run_in_executor(None, ctl_tok)
            assert rc == 0 and json.loads(out)["status"] == "INACTIVE"
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


@pytest.mark.parametrize("argv, refused", [
    (["run", "demo", "in", "out", "--topology-file", "topology.toml"],
     "unrecognized arguments: --topology-file"),
    (["ctl", "submit", "demo", "topology.toml"], "invalid choice: 'submit'"),
], ids=["run --topology-file", "ctl submit"])
def test_a_closed_door_is_an_argument_error(argv, refused, capsys):
    """PR 75: no flag and no subcommand builds a topology from a document."""
    with pytest.raises(SystemExit) as e:
        cli_main(argv)
    assert e.value.code == 2
    assert refused in capsys.readouterr().err
