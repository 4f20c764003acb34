"""Storm-UI-equivalent HTTP API (runtime/ui.py): status, metrics, errors,
and the activate/deactivate/rebalance/kill admin actions (SURVEY.md §5.1/§5.5
— the observability surface the reference got for free from Storm UI)."""

import asyncio
import json

import pytest

from storm_tpu.config import Config
from storm_tpu.runtime import Bolt, Spout, TopologyBuilder, Values
from storm_tpu.runtime.cluster import AsyncLocalCluster
from storm_tpu.runtime.ui import UIServer


class TrickleSpout(Spout):
    """Emits integers forever, slowly."""

    def open(self, context, collector):
        super().open(context, collector)
        self.n = 0

    async def next_tuple(self):
        await asyncio.sleep(0.01)
        await self.collector.emit(Values([self.n]), msg_id=self.n)
        self.n += 1
        return True

    def ack(self, msg_id):
        pass

    def fail(self, msg_id):
        pass


class EchoBolt(Bolt):
    async def execute(self, t):
        await self.collector.emit(Values([t.get("message")]), anchors=[t])
        self.collector.ack(t)


async def _http(port, method, path, body=None, headers=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode() if body is not None else b""
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    req = (
        f"{method} {path} HTTP/1.1\r\nHost: localhost\r\n{extra}"
        f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
    ).encode() + payload
    writer.write(req)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body_bytes = raw.partition(b"\r\n\r\n")
    status = int(head.split()[1])
    return status, json.loads(body_bytes)


async def _cluster_with_ui():
    tb = TopologyBuilder()
    tb.set_spout("spout", TrickleSpout(), parallelism=1)
    tb.set_bolt("echo", EchoBolt(), parallelism=2).shuffle_grouping("spout")
    cluster = AsyncLocalCluster()
    await cluster.submit("demo", Config(), tb.build())
    ui = await UIServer(cluster, port=0).start()
    return cluster, ui


def test_ui_status_routes(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            await asyncio.sleep(0.2)
            st, h = await _http(ui.port, "GET", "/healthz")
            assert st == 200 and h["status"] == "ok"

            st, summary = await _http(ui.port, "GET", "/api/v1/cluster/summary")
            assert st == 200 and summary["topologies"] == ["demo"]

            st, topo = await _http(ui.port, "GET", "/api/v1/topology/demo")
            assert st == 200
            assert topo["status"] == "ACTIVE"
            assert topo["components"]["echo"]["tasks"] == 2
            assert topo["components"]["echo"]["alive"] == 2
            assert topo["components"]["echo"]["executed"] > 0

            st, met = await _http(ui.port, "GET", "/api/v1/topology/demo/metrics")
            assert st == 200 and "echo" in met and "spout" in met

            st, errs = await _http(ui.port, "GET", "/api/v1/topology/demo/errors")
            assert st == 200 and errs["errors"] == []

            st, _ = await _http(ui.port, "GET", "/api/v1/topology/nope")
            assert st == 404
            st, _ = await _http(ui.port, "GET", "/api/v1/bogus")
            assert st == 404
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_admin_actions(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            # deactivate stops the spout; status flips
            st, r = await _http(ui.port, "POST", "/api/v1/topology/demo/deactivate")
            assert st == 200 and r["status"] == "INACTIVE"
            st, topo = await _http(ui.port, "GET", "/api/v1/topology/demo")
            assert topo["status"] == "INACTIVE"
            st, r = await _http(ui.port, "POST", "/api/v1/topology/demo/activate")
            assert st == 200 and r["status"] == "ACTIVE"

            # GET on an action is rejected
            st, _ = await _http(ui.port, "GET", "/api/v1/topology/demo/activate")
            assert st == 405

            # live rebalance via the API
            st, r = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/rebalance",
                                body={"component": "echo", "parallelism": 4})
            assert st == 200
            rt = cluster.runtime("demo")
            assert len(rt.bolt_execs["echo"]) == 4
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/rebalance",
                                body={"component": "nope", "parallelism": 2})
            assert st == 404
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/rebalance",
                                body={"component": "echo"})
            assert st == 400

            # kill removes the topology (async; poll for it)
            st, r = await _http(ui.port, "POST", "/api/v1/topology/demo/kill")
            assert st == 200 and r["status"] == "KILLED"
            for _ in range(100):
                if "demo" not in cluster.runtimes:
                    break
                await asyncio.sleep(0.05)
            assert "demo" not in cluster.runtimes
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_malformed_requests(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            # garbage request line
            reader, writer = await asyncio.open_connection("127.0.0.1", ui.port)
            writer.write(b"NONSENSE\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            assert b"400" in raw.split(b"\r\n")[0]

            # no body at all -> missing args -> 400
            st, _ = await _http(ui.port, "POST", "/api/v1/topology/demo/rebalance",
                                body=None)
            assert st == 400

            # literal non-JSON body -> the json.loads branch -> 400
            reader, writer = await asyncio.open_connection("127.0.0.1", ui.port)
            payload = b"this is { not json"
            writer.write((
                "POST /api/v1/topology/demo/rebalance HTTP/1.1\r\n"
                "Host: localhost\r\n"
                f"Content-Length: {len(payload)}\r\nConnection: close\r\n\r\n"
            ).encode() + payload)
            await writer.drain()
            raw = await reader.read()
            writer.close()
            assert b" 400 " in raw.split(b"\r\n")[0] + b" "
            assert b"not JSON" in raw

            # negative Content-Length -> 400, not a 500 stack trace
            reader, writer = await asyncio.open_connection("127.0.0.1", ui.port)
            writer.write(
                b"POST /api/v1/topology/demo/kill HTTP/1.1\r\n"
                b"Host: localhost\r\nContent-Length: -1\r\n"
                b"Connection: close\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            assert b"400" in raw.split(b"\r\n")[0]
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_double_kill_is_noop(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            for _ in range(2):
                st, r = await _http(ui.port, "POST", "/api/v1/topology/demo/kill")
                assert st in (200, 404)
            # second kill either 404s (already popped) or no-ops; daemon-style
            # explicit kill afterwards must not raise either.
            await cluster.kill("demo", wait_secs=0)
            assert "demo" not in cluster.runtimes
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_topology_graph(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            st, g = await _http(ui.port, "GET", "/api/v1/topology/demo/graph")
            assert st == 200
            assert g["components"]["spout"]["type"] == "spout"
            assert g["components"]["echo"] == {
                "type": "bolt", "parallelism": 2,
                "streams": {"default": ["message"]},
            }
            assert {"from": "spout", "stream": "default", "to": "echo",
                    "grouping": "ShuffleGrouping"} in g["edges"]
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_inbox_depth_gauge_published(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            # poll until the sweep publishes (interval is config-derived;
            # a fixed sleep races the timer on loaded machines)
            rt = cluster.runtime("demo")
            deadline = asyncio.get_event_loop().time() + 30
            while asyncio.get_event_loop().time() < deadline:
                snap = rt.metrics.snapshot()
                if "inbox_depth" in snap.get("echo", {}):
                    break
                await asyncio.sleep(0.2)
            assert "inbox_depth" in snap["echo"]
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_graph_includes_fields_and_404s_for_viewless(run):
    async def go():
        from storm_tpu.config import Config as Cfg
        from storm_tpu.runtime import TopologyBuilder as TB

        tb = TB()
        tb.set_spout("spout", TrickleSpout(), parallelism=1)
        tb.set_bolt("keyed", EchoBolt(), parallelism=2)\
            .fields_grouping("spout", "message")
        cluster = AsyncLocalCluster()
        await cluster.submit("keyed", Cfg(), tb.build())
        ui = await UIServer(cluster, port=0).start()
        try:
            st, g = await _http(ui.port, "GET", "/api/v1/topology/keyed/graph")
            assert st == 200
            (edge,) = g["edges"]
            assert edge["grouping"] == "FieldsGrouping"
            assert edge["fields"] == ["message"]
        finally:
            await ui.stop()
            await cluster.shutdown()

        # a runtime view without a .topology (dist adapter shape) 404s
        class NoTopo:
            name = "x"
            metrics = None
            errors = []

            def health(self):
                return {"components": {}, "inflight_trees": 0}

            def is_active(self):
                return True

        class FakeCluster:
            runtimes = {"x": NoTopo()}

            def runtime(self, n):
                return self.runtimes[n]

        ui2 = await UIServer(FakeCluster(), port=0).start()
        try:
            st, _ = await _http(ui2.port, "GET", "/api/v1/topology/x/graph")
            assert st == 404
        finally:
            await ui2.stop()

    run(go(), timeout=60)


def test_ui_logs_route_404s_for_local_runtime(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            st, r = await _http(ui.port, "GET", "/api/v1/topology/demo/logs")
            assert st == 404
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_logs_negative_bytes_rejected(run):
    async def go():
        class HasLogs:
            name = "x"
            metrics = None
            errors = []

            def health(self):
                return {"components": {}, "inflight_trees": 0}

            def is_active(self):
                return True

            async def worker_logs(self, index, tail_bytes=16384):
                return "ok"

        class FakeCluster:
            runtimes = {"x": HasLogs()}

            def runtime(self, n):
                return self.runtimes[n]

        ui = await UIServer(FakeCluster(), port=0).start()
        try:
            st, _ = await _http(ui.port, "GET", "/api/v1/topology/x/logs?bytes=-1")
            assert st == 400
            st, r = await _http(ui.port, "GET", "/api/v1/topology/x/logs?bytes=5")
            assert st == 200 and r["log"] == "ok"
        finally:
            await ui.stop()

    run(go(), timeout=60)


def test_ui_swap_model_action(run):
    """POST /swap_model rolls the inference component onto a new model
    config and returns it; bad requests get 4xx."""
    import numpy as np

    from storm_tpu.config import BatchConfig, ModelConfig
    from storm_tpu.infer import InferenceBolt

    class OneShotSpout(Spout):
        def open(self, context, collector):
            super().open(context, collector)
            self.sent = False

        async def next_tuple(self):
            if self.sent:
                return False
            self.sent = True
            import json as _json

            await self.collector.emit(Values([
                _json.dumps({"instances": np.zeros((1, 28, 28, 1)).tolist()})
            ]), msg_id=1)
            return True

        def ack(self, msg_id):
            pass

        def fail(self, msg_id):
            pass

    async def go():
        tb = TopologyBuilder()
        tb.set_spout("spout", OneShotSpout(), parallelism=1)
        tb.set_bolt("infer", InferenceBolt(
            ModelConfig(name="lenet5", input_shape=(28, 28, 1),
                        dtype="float32", seed=0),
            BatchConfig(max_batch=4, max_wait_ms=5, buckets=(4,))),
            parallelism=1).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit("demo", Config(), tb.build())
        ui = await UIServer(cluster, port=0).start()
        try:
            st, out = await _http(ui.port, "POST",
                                  "/api/v1/topology/demo/swap_model",
                                  {"component": "infer",
                                   "model": {"seed": 7}})
            assert st == 200 and out["model"]["seed"] == 7
            assert rt.bolt_execs["infer"][0].bolt.model_cfg.seed == 7

            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/swap_model",
                                {"component": "nope", "model": {"seed": 1}})
            assert st == 404
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/swap_model",
                                {"component": "infer", "model": {}})
            assert st == 400
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/swap_model",
                                {"component": "infer",
                                 "model": {"weights": "bogus"}})
            assert st == 400
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=120)


def test_ui_profile_capture(run, tmp_path):
    """POST /profile captures a jax trace into log_dir; concurrent
    captures are rejected with 409."""
    import os

    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            d = str(tmp_path / "trace")
            st, out = await _http(ui.port, "POST",
                                  "/api/v1/topology/demo/profile",
                                  {"log_dir": d, "seconds": 0.5})
            assert st == 200 and out["status"] == "capturing"
            st2, _ = await _http(ui.port, "POST",
                                 "/api/v1/topology/demo/profile",
                                 {"log_dir": d, "seconds": 0.5})
            assert st2 == 409
            await asyncio.wait_for(ui._profile_task, timeout=30)
            found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
            assert found, "profiler wrote no trace files"
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/profile",
                                {"log_dir": "", "seconds": 1})
            assert st == 400
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=90)


def test_ui_seek_action(run):
    """POST /seek repositions the spout; bad positions 400."""

    async def go():
        from storm_tpu.config import Config as _Config
        from storm_tpu.connectors import BrokerSpout, MemoryBroker

        broker = MemoryBroker()
        for i in range(5):
            broker.produce("t", json.dumps({"i": i}))
        tb = TopologyBuilder()
        from storm_tpu.connectors.spout import OffsetsConfig

        tb.set_spout("s", BrokerSpout(broker, "t",
                     OffsetsConfig(policy="earliest")), 1)
        tb.set_bolt("e", EchoBolt(), 1).shuffle_grouping("s")
        cluster = AsyncLocalCluster()
        await cluster.submit("sk", _Config(), tb.build())
        ui = await UIServer(cluster, port=0).start()
        try:
            st, out = await _http(ui.port, "POST",
                                  "/api/v1/topology/sk/seek",
                                  {"component": "s", "position": "earliest"})
            assert st == 200 and out["instances"] == 1
            st, out = await _http(ui.port, "POST",
                                  "/api/v1/topology/sk/seek",
                                  {"component": "s", "position": "-3"})
            assert st == 200 and out["position"] == -3
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/sk/seek",
                                {"component": "s", "position": "sideways"})
            assert st == 400
            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/sk/seek",
                                {"component": "zz", "position": "latest"})
            assert st == 404
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_component_stats(run):
    """GET /component/{cid} returns per-executor rows with task-level
    executed counts; unknown components 404."""

    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            await asyncio.sleep(0.3)
            st, out = await _http(ui.port, "GET",
                                  "/api/v1/topology/demo/component/echo")
            assert st == 200 and out["component"] == "echo"
            rows = out["executors"]
            assert [r["task"] for r in rows] == [0, 1]
            assert sum(r["executed"] for r in rows) > 0
            assert all("avg_execute_ms" in r and "inbox_depth" in r
                       for r in rows)
            st, out = await _http(ui.port, "GET",
                                  "/api/v1/topology/demo/component/spout")
            assert st == 200
            assert {"acked", "failed", "inflight"} <= set(out["executors"][0])
            st, _ = await _http(ui.port, "GET",
                                "/api/v1/topology/demo/component/zzz")
            assert st == 404
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_admin_auth(run):
    """control.auth_token (VERDICT r4 missing #4): with a token configured,
    every mutating route demands `Authorization: Bearer <token>`; reads
    stay open; rejects are 401 and have no side effect."""

    async def go():
        tb = TopologyBuilder()
        tb.set_spout("spout", TrickleSpout(), parallelism=1)
        tb.set_bolt("echo", EchoBolt(), parallelism=1).shuffle_grouping("spout")
        cluster = AsyncLocalCluster()
        await cluster.submit("demo", Config(), tb.build())
        ui = await UIServer(cluster, port=0, auth_token="s3cret-tok").start()
        try:
            # reads stay open
            st, _ = await _http(ui.port, "GET", "/healthz")
            assert st == 200
            st, topo = await _http(ui.port, "GET", "/api/v1/topology/demo")
            assert st == 200 and topo["status"] == "ACTIVE"
            # missing + wrong token: 401, and the action must NOT run
            st, err = await _http(
                ui.port, "POST", "/api/v1/topology/demo/deactivate")
            assert st == 401 and "token" in err["error"]
            st, _ = await _http(
                ui.port, "POST", "/api/v1/topology/demo/deactivate",
                headers={"Authorization": "Bearer wrong"})
            assert st == 401
            st, topo = await _http(ui.port, "GET", "/api/v1/topology/demo")
            assert topo["status"] == "ACTIVE", "rejected POST had an effect"
            # right token: accepted
            st, _ = await _http(
                ui.port, "POST", "/api/v1/topology/demo/deactivate",
                headers={"Authorization": "Bearer s3cret-tok"})
            assert st == 200
            st, topo = await _http(ui.port, "GET", "/api/v1/topology/demo")
            assert topo["status"] == "INACTIVE"
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_no_token_stays_open(run):
    """auth_token="" (the default) keeps the previous loopback posture."""

    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            st, _ = await _http(
                ui.port, "POST", "/api/v1/topology/demo/deactivate")
            assert st == 200
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


def test_ui_scorecard_route(run):
    async def go():
        cluster, ui = await _cluster_with_ui()
        try:
            # No fleet drill scoring this topology: 404, not an empty 200.
            st, r = await _http(ui.port, "GET",
                                "/api/v1/topology/demo/scorecard")
            assert st == 404

            # The fleet driver attaches its accumulated matrix to the
            # runtime mid-run; the route serves it read-only.
            rt = cluster.runtime("demo")
            rt.scorecard = {"metric": "fleet_scorecard_cells_passed",
                            "seed": 16, "in_progress": True,
                            "cells": [{"scenario": "classify",
                                       "pattern": "flash_crowd",
                                       "ok": True}]}
            st, r = await _http(ui.port, "GET",
                                "/api/v1/topology/demo/scorecard")
            assert st == 200
            assert r["topology"] == "demo" and r["seed"] == 16
            assert r["cells"][0]["pattern"] == "flash_crowd"

            st, _ = await _http(ui.port, "POST",
                                "/api/v1/topology/demo/scorecard")
            assert st == 405
        finally:
            await ui.stop()
            await cluster.shutdown()

    run(go(), timeout=60)


# ---- the doors that closed in PR 75: remote submission and DRPC --------------

_TOKEN = {"authorization": "Bearer s3cret-tok"}
_DEFINITION = {"name": "x", "definition": {"spouts": [{
    "id": "s", "class": "storm_tpu.connectors.spout.BrokerSpout"}]}}


@pytest.mark.parametrize("path, body, error", [
    # "submit" is a topology's name like any other, and nothing has it
    ("/api/v1/topology/submit", _DEFINITION, "no topology named 'submit'"),
    ("/api/v1/drpc/x", {"args": "1"}, "no route '/api/v1/drpc/x'"),
])
def test_a_closed_door_answers_404_to_the_token(run, path, body, error):
    ui = UIServer(AsyncLocalCluster(), auth_token="s3cret-tok")
    assert run(ui._route("POST", path, {}, body, _TOKEN)) == (
        404, {"error": error})


def test_no_post_goes_unguarded_the_old_data_plane_neither(run):
    """At the parent ``/api/v1/drpc/`` was let past the token check."""
    ui = UIServer(AsyncLocalCluster(), auth_token="s3cret-tok")
    status, err = run(ui._route("POST", "/api/v1/drpc/x", {}, {"args": "1"}))
    assert status == 401 and "token" in err["error"]


@pytest.mark.parametrize("argument", ["drpc", "resources"])
def test_the_ui_takes_no_argument_that_opened_a_door(argument):
    with pytest.raises(TypeError):
        UIServer(AsyncLocalCluster(), **{argument: {}})
