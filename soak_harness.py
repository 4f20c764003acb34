#!/usr/bin/env python
"""Full-surface production soak (VERDICT r4 missing #2 / next-round #2).

Every production subsystem AT ONCE, for >= 10 minutes, on the real chip:

  - transport: SASL_SSL (TLS + SCRAM-SHA-256) to a 2-node wire-protocol
    stub broker — every connection in the run is encrypted+authenticated;
  - delivery: end-to-end exactly-once (offsets.policy='txn' spout +
    whole-tree transactional sink committing consumed offsets inside the
    producer transaction, read_committed audit);
  - churn: periodic LEADER moves and COORDINATOR moves while transactions
    and group state are live;
  - elasticity: one live rebalance (prewarmed replica) mid-run;
  - ops: one live model swap mid-run (engine rebuild under traffic);
  - failure: chaos kills of the inference and echo executors (tree replay
    through the exactly-once machinery);
  - the real device path: trained LeNet-5 serving on jax.devices()[0].

Topology (product components, unmodified):

    spout(txn) ──> infer(InferenceBolt, real chip) ──┐
         │                                           ├──> txn sink ──> soak-out
         └──> echo(identity: sha256 of the record) ──┘
                                 infer dead_letter ────> dlq sink ──> soak-dlq

Each input record's tuple tree = {1 prediction + 1 echo}; the sink parks
the whole tree and commits it with the record's offset in ONE transaction.
The audit (read_committed) then proves, for EVERY consumed offset:
  - its echo hash appears EXACTLY once (identity-level exactly-once —
    catches loss+dupe pairs that count-based audits cancel out);
  - prediction count == input count, every prediction a valid softmax row
    (tree atomicity extends the echo lane's exactly-once to the
    prediction lane);
  - committed group offsets cover the whole input log;
  - zero dead-letters.
Any violation is a release blocker (exit 1). Reference analog: the
1-hour run-and-watch integration test (MainTopology.java:69-77) — this
is shorter but audited, not watched.

Run (real chip):  python soak_harness.py --seconds 660 --rate 30
CPU smoke:        JAX_PLATFORMS=cpu python soak_harness.py \
                      --seconds 60 --rate 20 --out -
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

GROUP = "soak-group"
IN, OUT, DLQ = "soak-in", "soak-out", "soak-dlq"


def log(msg: str) -> None:
    print(f"[soak] {msg}", file=sys.stderr, flush=True)


def make_certs(d: str):
    crt, key = os.path.join(d, "broker.crt"), os.path.join(d, "broker.key")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", crt, "-days", "2", "-subj",
         "/CN=127.0.0.1", "-addext", "subjectAltName=IP:127.0.0.1"],
        check=True, capture_output=True)
    return crt, key


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=660.0,
                    help="feed duration (events are scheduled across it)")
    ap.add_argument("--rate", type=float, default=30.0, help="records/sec")
    ap.add_argument("--trace", default=None,
                    help="replay a storm_tpu.loadgen trace file as the "
                         "feed source (event schedule + tenant:lane keys) "
                         "instead of fixed-interval pacing; loops until "
                         "--seconds elapse")
    ap.add_argument("--trace-speed", type=float, default=1.0,
                    help="time-compression factor for --trace replay")
    ap.add_argument("--out", default="SOAK_r05.json")
    ap.add_argument("--slo-ms", type=float, default=1000.0,
                    help="per-window sink p50 target for the SLO timeline")
    ap.add_argument("--chaos", action="store_true",
                    help="add a dist-grade chaos phase: engine-hang "
                         "injections under a live watchdog "
                         "(batch.watchdog_ms) driving a quarantine + "
                         "engine replacement mid-soak")
    ap.add_argument("--drain-drill", action="store_true",
                    help="add two graceful-drain cycles mid-soak "
                         "(deactivate -> flush inflight -> activate), the "
                         "per-worker step of a rolling restart, proving "
                         "intake pause + resume preserves exactly-once")
    args = ap.parse_args()

    import jax

    from storm_tpu.infer.engine import enable_compile_cache

    enable_compile_cache()
    device = jax.devices()[0]
    log(f"device: {device.device_kind} ({device.platform})")

    import ssl

    from tests.kafka_stub import KafkaStubBroker

    from storm_tpu.config import (BatchConfig, Config, ModelConfig,
                                  OffsetsConfig, ShardingConfig, SinkConfig)
    from storm_tpu.connectors import BrokerSink, BrokerSpout, \
        TransactionalBrokerSink
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.runtime import Bolt, TopologyBuilder, Values
    from storm_tpu.runtime.chaos import ChaosMonkey
    from storm_tpu.runtime.cluster import LocalCluster

    tmp = tempfile.mkdtemp(prefix="soak-certs-")
    crt, key = make_certs(tmp)
    P = 16  # txn policy gates ONE open tree per partition, so the
    # partition count IS the in-flight parallelism of the soak
    stub = KafkaStubBroker(partitions=P, nodes=2)
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
    ctx.load_cert_chain(crt, key)
    stub.ssl_context = ctx
    stub.sasl = ("soak-svc", "soak-pw")
    stub.sasl_mechanism = "SCRAM-SHA-256"
    security = {"protocol": "SASL_SSL", "sasl_mechanism": "SCRAM-SHA-256",
                "sasl_username": "soak-svc", "sasl_password": "soak-pw",
                "ssl_cafile": crt, "ssl_check_hostname": False}

    def wire():
        return KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                               security=security)

    class EchoBolt(Bolt):
        """Identity lane: the record's content hash, anchored to the same
        tree as its prediction, so the transactional sink commits both
        (or neither) with the offset."""

        async def execute(self, t):
            h = hashlib.sha256(t.get("message").encode()).hexdigest()[:24]
            await self.collector.emit(Values([f"h:{h}"]), anchors=[t])
            self.collector.ack(t)

    ckpt = os.path.join(REPO, "checkpoints", "lenet5_digits")
    model_cfg = ModelConfig(name="lenet5", checkpoint=ckpt,
                            input_shape=(32, 32, 1), num_classes=10)
    batch_cfg = BatchConfig(max_batch=64, max_wait_ms=20.0, buckets=(8, 64),
                            max_inflight=2,
                            # chaos phase: a 2.5s injected hang against a
                            # 500ms fetch deadline trips the watchdog; two
                            # consecutive trips quarantine the engine and
                            # the operator swaps in a fresh one mid-soak.
                            watchdog_ms=500.0 if args.chaos else 0.0,
                            watchdog_trips=2)
    run_cfg = Config()
    run_cfg.topology.message_timeout_s = 120.0
    if args.drain_drill:
        # A drain cycle lands ~2s after a chaos executor kill, and a tree
        # stranded by that kill stays in the ledger for the FULL message
        # timeout — 120s would wedge every drain. 15s bounds the stall
        # (legit trees settle in <1s)
        # without changing the replay mechanism under audit.
        run_cfg.topology.message_timeout_s = 15.0

    broker = wire()
    tb = TopologyBuilder()
    tb.set_spout(
        "spout",
        BrokerSpout(broker, IN,
                    OffsetsConfig(policy="txn", group_id=GROUP,
                                  max_behind=None)),
        parallelism=1)
    tb.set_bolt("infer",
                InferenceBolt(model_cfg, batch_cfg,
                              ShardingConfig(data_parallel=0)),
                parallelism=1).shuffle_grouping("spout")
    tb.set_bolt("echo", EchoBolt(), parallelism=1).shuffle_grouping("spout")
    tb.set_bolt(
        "sink",
        TransactionalBrokerSink(
            broker, OUT,
            SinkConfig(mode="transactional", txn_batch=64, txn_ms=250.0,
                       offsets_group=GROUP)),
        parallelism=1)\
        .shuffle_grouping("infer").shuffle_grouping("echo")
    tb.set_bolt("dlq", BrokerSink(broker, DLQ, run_cfg.sink), parallelism=1)\
        .shuffle_grouping("infer", stream="dead_letter")

    rng = np.random.RandomState(7)
    produced_hashes = []
    feeder = wire()
    stop_feed = threading.Event()
    fed = [0]

    def _produce_one(key=None):
        payload = json.dumps(
            {"instances": rng.rand(1, 32, 32, 1).round(4).tolist()})
        produced_hashes.append(
            hashlib.sha256(payload.encode()).hexdigest()[:24])
        feeder.produce(IN, payload, key=key, partition=fed[0] % P)
        fed[0] += 1

    def feed():
        if args.trace:
            # Trace-driven soak source (storm_tpu.loadgen): the recorded
            # arrival schedule paces production and each record carries
            # its tenant:lane key, so the soak sees fleet-shaped traffic
            # (bursts, tenant skew) instead of a metronome. The trace
            # loops until the run ends; the identity audit is unchanged —
            # it counts records, not pacing.
            from storm_tpu.loadgen import load_trace, replay

            tr = load_trace(args.trace)
            while not stop_feed.is_set():
                replay(tr, lambda ev: _produce_one(key=ev.key()),
                       speed=args.trace_speed,
                       stop=stop_feed.is_set)
            return
        interval = 1.0 / args.rate
        nxt = time.perf_counter()
        while not stop_feed.is_set():
            now = time.perf_counter()
            if now < nxt:
                time.sleep(min(0.01, nxt - now))
                continue
            _produce_one()
            nxt += interval

    events = []  # (t_s, name, detail)
    timeline = []  # (t_s, sink_p50_ms, windows' delivered count)

    def mark(name, detail=""):
        events.append((round(time.perf_counter() - t0, 1), name, detail))
        log(f"EVENT {name} {detail}")

    cluster = LocalCluster()
    t0 = time.perf_counter()
    wd_stats = None
    try:
        cluster.submit_topology("soak", run_cfg, tb.build())
        log("topology up; starting feed")

        rt = None

        async def _rt():
            return cluster._cluster.runtime("soak")

        rt = cluster._run(_rt())
        chaos = ChaosMonkey(rt)

        feeder_thread = threading.Thread(target=feed, daemon=True)
        feeder_thread.start()

        # events spread across the run (fractions of --seconds)
        dur = args.seconds
        plan = [
            (0.10, "move_leader", lambda: stub.move_leader(OUT, 0, 1)),
            (0.20, "move_coordinator", lambda: stub.move_coordinator(1)),
            (0.30, "chaos_kill_infer", lambda: chaos.crash_bolt("infer", 0)),
            (0.40, "rebalance_infer_2",
             lambda: cluster._run(rt.rebalance("infer", 2))),
            (0.55, "swap_model_f32",
             lambda: cluster._run(rt.swap_model(
                 "infer", {"dtype": "float32"}))),
            (0.70, "move_leader_in", lambda: stub.move_leader(IN, 1, 0)),
            (0.78, "chaos_kill_echo", lambda: chaos.crash_bolt("echo", 0)),
            (0.86, "move_coordinator_back",
             lambda: stub.move_coordinator(0)),
            (0.93, "chaos_kill_infer_2",
             lambda: chaos.crash_bolt("infer", 1)),
        ]
        if args.chaos:
            from storm_tpu.resilience import get_injector

            def arm_engine_hang():
                inj = get_injector()
                inj.bind_flight(rt.flight)
                # Two consecutive hung batches = watchdog_trips, so this
                # single injection drives the full quarantine->replace arc.
                inj.configure(engine_hang_ms=2500.0, engine_hang_next=2)

            plan.insert(4, (0.48, "chaos_engine_hang", arm_engine_hang))
        if args.drain_drill:
            # The per-worker step of a rolling restart, run against the
            # live runtime: stop intake, flush every in-flight tree, then
            # resume. Two cycles — one on each side of the rebalance/swap
            # block — so the audit proves a drain preserves exactly-once
            # both on the original mesh shape and on the reshaped one.
            def drain_cycle():
                cluster._run(rt.deactivate())
                flushed = cluster._run(rt.drain(timeout_s=60.0))
                cluster._run(rt.activate())
                if not flushed:
                    raise RuntimeError("drain did not flush within 60s")

            drill = [(0.35, "drain_drill_1", drain_cycle),
                     (0.65, "drain_drill_2", drain_cycle)]
            plan = sorted(plan + drill, key=lambda e: e[0])
        next_plan = 0
        window_s = 10.0
        next_window = time.perf_counter() + window_s
        end = time.perf_counter() + dur
        last_out = 0
        while time.perf_counter() < end:
            now = time.perf_counter()
            frac = (now - t0) / dur
            if next_plan < len(plan) and frac >= plan[next_plan][0]:
                name = plan[next_plan][1]
                try:
                    plan[next_plan][2]()
                    mark(name)
                except Exception as e:  # an event must not end the soak
                    mark(name + "_FAILED", repr(e))
                next_plan += 1
            if now >= next_window:
                next_window = now + window_s
                lat = cluster.metrics("soak")["sink"]["e2e_latency_ms"]
                p50 = lat["p50"]
                cluster.reset_histogram("soak", "sink", "e2e_latency_ms")
                out_n = stub.topic_size(OUT)
                timeline.append((round(now - t0, 1),
                                 None if p50 is None else round(p50, 1),
                                 out_n - last_out))
                last_out = out_n
                log(f"t={now - t0:6.1f}s p50="
                    f"{'stalled' if p50 is None else f'{p50:.0f}ms'} "
                    f"out+={timeline[-1][2]} fed={fed[0]}")
            time.sleep(0.2)

        stop_feed.set()
        feeder_thread.join(timeout=10)
        # A feeder still alive past the join timeout is wedged mid-produce:
        # fed[0] may keep moving under the audit below, so the exactly-once
        # accounting would compare against a moving target. Flag it and
        # fail the run rather than report a vacuous pass.
        feeder_stuck = feeder_thread.is_alive()
        if feeder_stuck:
            log("WARNING: feeder thread still alive after join timeout; "
                "exactly-once accounting is unreliable")
        n = fed[0]
        log(f"feed done: {n} records; draining")
        deadline = time.time() + 300
        while time.time() < deadline:
            if stub.topic_size(OUT) >= 2 * n:
                break
            time.sleep(0.5)
        drained = stub.topic_size(OUT) >= 2 * n
        log(f"drained={drained} out={stub.topic_size(OUT)}/{2 * n}")
        if args.chaos:
            infer_m = cluster.metrics("soak").get("infer", {})
            wd_stats = {k: infer_m.get(k)
                        for k in ("watchdog_trips", "engine_quarantined")}
            # The quarantine->replace arc as flight events: the drained
            # audit above already proves the REPLACEMENT engine served
            # (the injection lands mid-soak), these make it explicit.
            wd_stats["flight"] = [
                {k: v for k, v in ev.items() if k != "ts"}
                for ev in rt.flight.tail(400)
                if ev.get("kind") in ("engine_quarantined",
                                      "engine_replaced")]
    finally:
        try:
            cluster.shutdown()
        except Exception as e:
            log(f"shutdown: {e!r}")

    # ---- audit (read_committed) ---------------------------------------------
    n = fed[0]
    rc = KafkaWireBroker(f"127.0.0.1:{stub.port}", message_format="v2",
                         isolation="read_committed", security=security)
    out_records = []
    for p in range(P):
        off = 0
        while True:
            batch = rc.fetch(OUT, p, off, max_records=2000)
            if not batch:
                break
            out_records.extend(batch)
            off = batch[-1].offset + 1
    committed = {p: feeder.committed(GROUP, IN, p) for p in range(P)}
    produced_per_part = {p: (n - p + P - 1) // P for p in range(P)}
    dlq_n = stub.topic_size(DLQ)
    rc.close()
    feeder.close()
    broker.close()
    stub.close()

    echoes, preds, bad_preds = [], 0, 0
    for r in out_records:
        v = r.value.decode()
        if v.startswith("h:"):
            echoes.append(v[2:])
        else:
            preds += 1
            try:
                row = json.loads(v)["predictions"][0]
                if len(row) != 10 or abs(sum(row) - 1.0) > 1e-2:
                    bad_preds += 1
            except Exception:
                bad_preds += 1

    from collections import Counter

    want, got = Counter(produced_hashes), Counter(echoes)
    missing = sum((want - got).values())
    duplicated = sum((got - want).values())
    offsets_ok = committed == produced_per_part
    stalled_windows = sum(1 for w in timeline if w[1] is None and w[2] == 0)
    p50s = [w[1] for w in timeline if w[1] is not None]
    met = [p for p in p50s if p <= args.slo_ms]

    exactly_once = (missing == 0 and duplicated == 0 and preds == n
                    and bad_preds == 0 and offsets_ok and dlq_n == 0
                    and drained and not feeder_stuck)
    artifact = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "duration_s": round(args.seconds, 1),
        "offered_rate_msg_s": args.rate if not args.trace else None,
        "trace_source": (os.path.basename(args.trace) if args.trace
                         else None),
        "trace_speed": args.trace_speed if args.trace else None,
        "records_in": n,
        "records_out": len(out_records),
        "transport": "SASL_SSL + SCRAM-SHA-256 (2-node stub, "
                     "wire protocol over TLS sockets)",
        "exactly_once": exactly_once,
        "audit": {
            "echo_missing": missing,
            "echo_duplicated": duplicated,
            "predictions": preds,
            "predictions_expected": n,
            "invalid_predictions": bad_preds,
            "committed_offsets": committed,
            "committed_offsets_expected": produced_per_part,
            "dead_letters": dlq_n,
            "drained": drained,
            "feeder_stuck": feeder_stuck,
        },
        "slo": {
            "target_p50_ms": args.slo_ms,
            "windows_met": f"{len(met)}/{len(p50s)}",
            "stalled_windows": stalled_windows,
            "worst_window_p50_ms": max(p50s, default=None),
            "median_window_p50_ms": (sorted(p50s)[len(p50s) // 2]
                                     if p50s else None),
        },
        "events": events,
        "timeline": timeline,
        "chaos": None,
        "note": "echo lane = sha256 of each record, committed in the SAME "
                "transaction (same tuple tree) as its prediction and its "
                "offset; identity-level exactly-once on the echo lane + "
                "tree atomicity + count equality extends the proof to the "
                "prediction lane (the product wire contract carries no "
                "correlation id, reference parity)",
    }
    if args.chaos:
        from storm_tpu.resilience import get_injector

        snap = get_injector().snapshot()
        artifact["chaos"] = {
            "enabled": True,
            "injections": sum(snap["counts"].values()),
            "counts": snap["counts"],
            "watchdog": wd_stats,
        }
    out = json.dumps(artifact, indent=1)
    if args.out == "-":
        print(out)
    else:
        with open(os.path.join(REPO, args.out), "w") as f:
            f.write(out + "\n")
        log(f"wrote {args.out}")
    log(f"exactly_once={exactly_once} "
        f"(missing={missing} dup={duplicated} preds={preds}/{n} "
        f"bad={bad_preds} offsets_ok={offsets_ok} dlq={dlq_n} "
        f"feeder_stuck={feeder_stuck})")
    return 0 if exactly_once else 1


if __name__ == "__main__":
    sys.exit(main())
