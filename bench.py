"""End-to-end streaming benchmark.

Drives the full framework path — broker JSON in -> spout -> micro-batched
TPU inference -> sink -> broker JSON out — and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Headline config (BASELINE.md): CIFAR-10 ResNet-20, 4 inference operators.
``vs_baseline`` is measured images/sec/chip against the north-star target
of >=10k images/sec on a v5e-8 slice == 1250 images/sec/chip.

Phases:
1. warmup: compile bucket shapes;
2. throughput: preload M messages, measure drain rate;
3. latency: calibrate the latency topology's own capacity with a burst
   probe, then offer ~50% of it open-loop under a backlog guard (abort +
   halve + retry on monotonic backlog growth); report sink p50/p99 with
   the clock starting at broker APPEND time (spout._append_root_ts).

All progress goes to stderr; stdout carries only the final JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

# jax-free import (tracing pulls jax only inside device_trace): the stage
# table below derives its device-substage rows from the same constant the
# engine and operator use.
from storm_tpu.runtime.tracing import DEVICE_SUBSTAGES

BASELINE_IMGS_PER_SEC_PER_CHIP = 10_000 / 8  # north-star v5e-8 target, per chip


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def device_info() -> dict:
    """The device this process measured on (platform, device_kind,
    device_count); imported late so ``--help`` stays off jax."""
    from storm_tpu.parallel.mesh import device_info as info

    return info()


# Modes whose engines run in workers pinned to JAX_PLATFORMS=cpu, behind a
# NullEngine, or in numpy: nothing they time ran on an accelerator.
HOST_ONLY = {"platform": "cpu", "device_kind": "cpu", "device_count": 0}


CONFIGS = {
    "lenet5": dict(model="lenet5", input_shape=(28, 28, 1), num_classes=10,
                   bolts=1, max_batch=512, buckets=(64, 512), metric="mnist_lenet5"),
    "resnet20": dict(model="resnet20", input_shape=(32, 32, 3), num_classes=10,
                     bolts=4, max_batch=512, buckets=(64, 512), metric="cifar10_resnet20"),
    "resnet50": dict(model="resnet50", input_shape=(224, 224, 3), num_classes=1000,
                     bolts=4, max_batch=64, buckets=(16, 64), metric="imagenet_resnet50"),
    "vit_b16": dict(model="vit_b16", input_shape=(224, 224, 3), num_classes=1000,
                    bolts=4, max_batch=64, buckets=(16, 64), metric="imagenet_vit_b16"),
    "mobilenetv2": dict(model="mobilenetv2", input_shape=(32, 32, 3), num_classes=10,
                        bolts=4, max_batch=512, buckets=(64, 512),
                        metric="cifar10_mobilenetv2"),
    "mixer_tiny": dict(model="mixer_tiny", input_shape=(32, 32, 3), num_classes=10,
                       bolts=4, max_batch=512, buckets=(64, 512),
                       metric="cifar10_mixer_tiny"),
    # Long-context serving (S=2048 -> the Pallas flash kernel dispatches
    # in the engine path): the Kafka->Kafka datapoint the long-context
    # story was missing (VERDICT r2 weak #5).
    "longseq_encoder": dict(model="longseq_encoder", input_shape=(2048, 64),
                            num_classes=10, bolts=2, max_batch=32,
                            buckets=(8, 32), metric="longseq_encoder"),
    # BASELINE.json config 5: MNIST+CIFAR pipelines sharing one slice.
    # Dispatches to run_multi() — the dict here only carries the metric name.
    "multi": dict(metric="multi_mnist_cifar"),
}


MULTI_MODELS = {
    "mnist": dict(model="lenet5", input_shape=(28, 28, 1), num_classes=10,
                  bolts=2, max_batch=512, buckets=(64, 512)),
    "cifar": dict(model="resnet20", input_shape=(32, 32, 3), num_classes=10,
                  bolts=2, max_batch=512, buckets=(64, 512)),
}


def build_multi_topology(broker, max_wait_ms, transfer_dtype=None, max_batch=0,
                         inflight=2):
    from storm_tpu.config import (
        BatchConfig, Config, ModelConfig, OffsetsConfig, PipelineConfig, ShardingConfig,
    )
    from storm_tpu.main import build_multi_model_topology

    run_cfg = Config()
    run_cfg.topology.message_timeout_s = 300.0
    run_cfg.pipelines = [
        PipelineConfig(
            name=name,
            model=ModelConfig(
                name=mc["model"], dtype="bfloat16", input_shape=mc["input_shape"],
                num_classes=mc["num_classes"], transfer_dtype=transfer_dtype,
            ),
            batch=BatchConfig(max_batch=max_batch or mc["max_batch"],
                              max_wait_ms=max_wait_ms,
                              buckets=(max_batch,) if max_batch else mc["buckets"],
                              max_inflight=inflight),
            sharding=ShardingConfig(data_parallel=0),
            offsets=OffsetsConfig(policy="earliest", max_behind=None),
            input_topic=f"{name}-in",
            output_topic=f"{name}-out",
            dead_letter_topic=f"{name}-dlq",
            spout_parallelism=2,
            inference_parallelism=mc["bolts"],
            sink_parallelism=2,
        )
        for name, mc in MULTI_MODELS.items()
    ]
    return run_cfg, build_multi_model_topology(run_cfg, broker)


def run_multi(args) -> dict:
    """Multi-model bench: both pipelines drain concurrently from one broker
    through one TPU; reports combined images/sec/chip and the worse of the
    two per-pipeline p50s."""
    import jax

    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.runtime.cluster import LocalCluster

    n_dev = len(jax.devices())
    log(f"devices: {jax.devices()}")
    payloads = {
        name: make_payloads(mc, instances_per_msg=args.instances_per_msg)
        for name, mc in MULTI_MODELS.items()
    }
    cluster = LocalCluster()
    try:
        return _run_multi_inner(args, cluster, payloads, n_dev)
    finally:
        # Always tear down — under --all a failed config must not leave a
        # zombie topology executing on the device the next config measures.
        cluster.shutdown()


def _run_multi_inner(args, cluster, payloads, n_dev) -> dict:
    from storm_tpu.connectors import MemoryBroker

    # ---- throughput phase ----------------------------------------------------
    broker = MemoryBroker(default_partitions=4)
    run_cfg, topo = build_multi_topology(
        broker, max(args.max_wait_ms, 100.0), args.transfer_dtype, args.max_batch,
        args.inflight or 4)
    t0 = time.time()
    cluster.submit_topology("bench-multi", run_cfg, topo)
    log(f"submitted + warmed up in {time.time() - t0:.1f}s")

    per_topic = args.messages // 2
    n_msgs = per_topic * 2
    for i in range(per_topic):
        for name in MULTI_MODELS:
            broker.produce(f"{name}-in", payloads[name][i % len(payloads[name])])
    delivered, elapsed = drain_loop(
        lambda: sum(broker.topic_size(f"{n}-out") + broker.topic_size(f"{n}-dlq")
                    for n in MULTI_MODELS),
        n_msgs, args.instances_per_msg)
    imgs_done = delivered * args.instances_per_msg
    throughput = imgs_done / elapsed / n_dev
    log(f"throughput: {imgs_done} imgs in {elapsed:.2f}s -> "
        f"{throughput:.0f} img/s/chip ({n_dev} chip(s), 2 models co-resident)")
    dead = sum(broker.topic_size(f"{n}-dlq") for n in MULTI_MODELS)
    if dead:
        log(f"WARNING: {dead} dead-lettered")
    cluster.kill_topology("bench-multi", wait_secs=2)

    # ---- latency phase -------------------------------------------------------
    p50 = p99 = float("nan")
    lat_valid = True
    if not args.skip_latency:
        broker2 = MemoryBroker(default_partitions=4)
        run_cfg2, topo2 = build_multi_topology(broker2, args.max_wait_ms,
                                               args.transfer_dtype, args.max_batch,
                                               args.inflight or 2)
        cluster.submit_topology("bench-multi-lat", run_cfg2, topo2)
        log(f"latency phase: calibrate + offer (interleaved) for "
            f"{args.latency_seconds}s")
        names = list(MULTI_MODELS)

        def produce_nth(i):
            name = names[i % len(names)]
            broker2.produce(f"{name}-in", payloads[name][i % len(payloads[name])])

        def reset_hists():
            for name in names:
                cluster.reset_histogram(
                    "bench-multi-lat", f"{name}-sink", "e2e_latency_ms")

        def read_lat():
            snap = cluster.metrics("bench-multi-lat")
            p50s, p99s = [], []
            for name in names:
                lat = snap[f"{name}-sink"]["e2e_latency_ms"]
                if lat["p50"] is not None:
                    p50s.append(lat["p50"])
                    p99s.append(lat["p99"])
                    log(f"  {name}: p50={lat['p50']:.1f} p99={lat['p99']:.1f}")
            if not p50s:
                return float("nan"), float("nan")
            return max(p50s), max(p99s)

        p50, p99, rate, lat_valid = run_latency_phase(
            produce_nth,
            lambda: sum(broker2.topic_size(f"{n}-out") for n in names),
            reset_hists, read_lat, args.latency_seconds)
        log(f"e2e latency ms (append->deliver, worst pipeline): "
            f"p50={p50:.1f} p99={p99:.1f} @ {rate:.0f} msg/s offered"
            f"{'' if lat_valid else ' [INVALID: saturated]'}")
        cluster.kill_topology("bench-multi-lat", wait_secs=2)

    cluster.shutdown()
    return {
        "metric": "multi_mnist_cifar_images_per_sec_per_chip",
        "value": round(throughput, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(throughput / BASELINE_IMGS_PER_SEC_PER_CHIP, 3),
        "p50_latency_ms": round(p50, 1) if p50 == p50 else None,
        "p99_latency_ms": round(p99, 1) if p99 == p99 else None,
        "latency_valid": lat_valid,
        **device_info(),
        "config": "multi",
    }


def build_topology(cfg, broker, batch_cfg, transfer_dtype=None, chunk=0, weights="float",
                   engine=None):
    from storm_tpu.config import Config, ModelConfig, OffsetsConfig, ShardingConfig
    from storm_tpu.connectors import BrokerSink, BrokerSpout
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.runtime import TopologyBuilder

    run_cfg = Config()
    run_cfg.topology.message_timeout_s = 300.0
    model_cfg = ModelConfig(
        name=cfg["model"],
        dtype="bfloat16",
        input_shape=cfg["input_shape"],
        num_classes=cfg["num_classes"],
        transfer_dtype=transfer_dtype,
        weights=weights,
    )
    tb = TopologyBuilder()
    tb.set_spout(
        "kafka-spout",
        BrokerSpout(broker, "input", OffsetsConfig(policy="earliest", max_behind=None),
                    fetch_size=1024, chunk=chunk, scheme="raw"),
        parallelism=2,
    )
    tb.set_bolt(
        "inference-bolt",
        InferenceBolt(model_cfg, batch_cfg, ShardingConfig(data_parallel=0),
                      engine=engine),
        parallelism=cfg["bolts"],
    ).shuffle_grouping("kafka-spout")
    tb.set_bolt("kafka-bolt", BrokerSink(broker, "output", run_cfg.sink), parallelism=2)\
        .shuffle_grouping("inference-bolt")
    tb.set_bolt("dlq-bolt", BrokerSink(broker, "dead-letter", run_cfg.sink), parallelism=1)\
        .shuffle_grouping("inference-bolt", stream="dead_letter")
    return run_cfg, tb.build()


def make_payloads(cfg, n_distinct=64, instances_per_msg=1):
    rng = np.random.RandomState(0)
    shape = (instances_per_msg, *cfg["input_shape"])
    # Bound host RAM for big-instance configs (a 2048x64 longseq record is
    # ~1.2MB of JSON): fewer distinct payloads, same coverage of the
    # padding buckets.
    elems = int(np.prod(shape))
    n_distinct = max(4, min(n_distinct, (64 * 3072) // max(1, elems)))
    # Pre-encoded bytes: MemoryBroker stores bytes values by REFERENCE
    # (str values are encoded to a fresh bytes object per record), so the
    # broker log holds n_distinct payload buffers total no matter how many
    # messages — or median-of-N repeats — are produced. With str payloads
    # a longseq capture (~1.2MB JSON/record) would copy per record.
    return [
        json.dumps({"instances": rng.rand(*shape).round(4).tolist()})
        .encode("utf-8")
        for _ in range(n_distinct)
    ]


def sample_stats(samples) -> dict:
    """The min/median/max honesty protocol shared by the default headline
    (median-of-N back-to-back drains) and the --all interleaved repeats:
    one definition so the two artifacts can never diverge. True median —
    even-length lists average the middle pair (taking the upper-middle
    would make a 2-sample headline equal the MAX, biasing upward exactly
    when a repeat was dropped)."""
    s = sorted(round(x, 1) for x in samples)
    n = len(s)
    med = round(s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2, 1)
    return {"value": med, "throughput_samples": s,
            "value_min": s[0], "value_max": s[-1]}


def run_interleaved(arms, repeats, run_cell) -> dict:
    """The interleaved-A/B cell driver shared by --wire-compare and
    --cascade-compare: repeats are interleaved
    at CELL level (arm1, arm2, ..., arm1, arm2, ...) so host drift
    hits every arm equally instead of biasing whichever ran last
    (BENCH_NOTES honesty protocol). Returns {arm: [run_cell(arm, rep),
    ...]} with samples in rep order."""
    samples = {arm: [] for arm in arms}
    for rep in range(repeats):
        for arm in arms:
            samples[arm].append(run_cell(arm, rep))
    return samples


def timed_drain_window(size_fn, warm, total, deadline_s=300.0) -> tuple:
    """Ack-gated warm->last measurement window over a pre-produced
    backlog: poll ``size_fn()`` until it reaches ``total`` (or the
    deadline), timing from the moment it crossed ``warm`` — producer
    pacing, topology startup, and first-batch compile all land before
    the window. Returns ``(elapsed_s, done)``; ``elapsed_s`` is NaN when
    the warm threshold was never reached."""
    deadline = time.time() + deadline_s
    t0 = None
    while time.time() < deadline:
        n = size_fn()
        if t0 is None and n >= warm:
            t0 = time.perf_counter()
        if n >= total:
            break
        time.sleep(0.005)
    t1 = time.perf_counter()
    return (t1 - t0 if t0 is not None else float("nan")), size_fn()


def arm_stats(samples) -> dict:
    """Per-arm rate summary in the shape every interleaved artifact rows
    use: median headline + min/max + the raw samples."""
    st = sample_stats(samples)
    return {"msgs_per_sec": st.pop("value"),
            "msgs_per_sec_min": st.pop("value_min"),
            "msgs_per_sec_max": st.pop("value_max"),
            "samples": st["throughput_samples"]}


def _new_capture_session() -> str:
    """Artifact cross-reference id (VERDICT r4 weak #2): every bench
    emission carries one, and counterpart artifacts quote it, so two
    committed numbers for the same config always point at each other."""
    return "cap-" + time.strftime("%Y%m%dT%H%M%S")


def _code_version() -> str:
    """Code identity stamped into every artifact: the git commit (plus
    ``-dirty`` when the worktree has uncommitted changes), falling back to
    "unknown" outside a git checkout. Same-code pooling decisions key on
    this, not on the calendar day — two sessions hours apart on the same
    commit measured the same code; two minutes apart across a commit did
    not."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "--short=12", "HEAD"], cwd=repo,
            capture_output=True, text=True, timeout=10)
        if rev.returncode != 0:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain"], cwd=repo,
            capture_output=True, text=True, timeout=10)
        suffix = "-dirty" if dirty.returncode == 0 and dirty.stdout.strip() \
            else ""
        return rev.stdout.strip() + suffix
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"


def _latest_artifact(pattern: str):
    """(filename, parsed-artifact) for the newest committed BENCH file
    matching ``pattern`` (by round number in the name), or None. Driver
    headline files wrap the bench JSON under a "parsed" key."""
    import glob
    import re as _re

    best = None
    for path in glob.glob(os.path.join(os.path.dirname(__file__), pattern)):
        m = _re.search(r"_r(\d+)\.json$", path)
        if m:
            best = max(best or (-1, ""), (int(m.group(1)), path))
    if not best:
        return None
    try:
        with open(best[1]) as f:
            art = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(art, dict) and "parsed" in art:  # driver wrapper
        art = art["parsed"]
    return os.path.basename(best[1]), art


def _matrix_rows(artifact):
    """Rows list from either --all artifact shape (bare list pre-r5,
    {"rows": [...]} from r5 on)."""
    if isinstance(artifact, dict):
        return artifact.get("rows", [])
    return artifact if isinstance(artifact, list) else []


def cross_reference_headline(result: dict) -> None:
    """Attach the latest --all matrix's number for this config to a
    headline result, in-artifact: the r04 verdict found a 1.9x headline-
    vs-matrix gap whose reconciliation lived only in BENCH_NOTES.md."""
    ref = _latest_artifact("BENCH_ALL_r*.json")
    if not ref:
        return
    name, art = ref
    row = next((r for r in _matrix_rows(art)
                if r.get("config") == result.get("config")
                and "value" in r), None)
    if row is None:
        return
    result["see_also"] = {
        "file": name,
        "capture_session": (art.get("capture_session")
                            if isinstance(art, dict) else None),
        "matrix_value": row["value"],
        "matrix_range": [row.get("value_min", row["value"]),
                         row.get("value_max", row["value"])],
        "note": "interleaved-matrix median for this config; host load "
                "moves same-config medians across sessions — "
                "reconcile the two ranges before quoting either number",
    }


def pool_headline_into_matrix(rows: list) -> None:
    """Fold the latest committed headline's throughput samples into the
    matching --all matrix row so the artifact states ONE best-estimate
    per config (pooled median), with the source session recorded."""
    ref = _latest_artifact("BENCH_r*.json")
    if not ref:
        return
    name, art = ref
    if not isinstance(art, dict):
        return
    # Same-code-era guard: only pool headlines measured on the SAME git
    # commit as this run — pooling samples from different code would
    # present a cross-version blend as one best estimate. The commit
    # stamp replaces the earlier same-calendar-day heuristic (review r5),
    # which both over-pooled (same day, different commit) and
    # under-pooled (same commit, measured past midnight). Unstamped
    # legacy artifacts and dirty/unknown worktrees never pool.
    ours = _code_version()
    theirs = art.get("code_version") or ""
    if (not theirs or theirs != ours or "dirty" in ours
            or ours == "unknown"):
        return
    headline_samples = art.get("throughput_samples") or (
        [art["value"]] if "value" in art else [])
    if not headline_samples:
        return
    row = next((r for r in rows if r.get("config") == art.get("config")
                and "throughput_samples" in r), None)
    if row is None:
        return
    pooled = sorted(row["throughput_samples"] + list(headline_samples))
    row["pooled_from"] = {
        "file": name,
        "capture_session": art.get("capture_session"),
        "code_version": theirs,
        "headline_samples": headline_samples,
        "note": "pooled median below supersedes both artifacts' "
                "individual medians as the best estimate for this config",
    }
    row.update(sample_stats(pooled))
    row["vs_baseline"] = round(
        row["value"] / BASELINE_IMGS_PER_SEC_PER_CHIP, 3)


def drain_loop(done_fn, n_msgs, instances_per_msg, timeout_s=600.0):
    """Wait until ``done_fn()`` reaches n_msgs (or timeout). Returns
    (delivered, elapsed_s) — throughput must be computed from *delivered*,
    not offered, so a timeout never inflates the metric."""
    t0 = time.perf_counter()
    last = 0
    while True:
        done = done_fn()
        if done >= n_msgs:
            break
        now = time.perf_counter()
        if now - t0 > timeout_s:
            log(f"TIMEOUT with {done}/{n_msgs} delivered")
            break
        if done - last >= max(1, n_msgs // 8):
            log(f"  {done}/{n_msgs} @ {done * instances_per_msg / (now - t0):.0f} img/s")
            last = done
        time.sleep(0.05)
    return done_fn(), time.perf_counter() - t0


def offer_load(produce_nth, rate, seconds, backlog_fn=None,
               guard_checks=12, check_interval=0.25):
    """Paced open-loop producer: call ``produce_nth(i)`` at ``rate``/s for
    ``seconds``. Returns ``(sent, aborted)``.

    Backlog guard (VERDICT r1 weak #1): an open loop offered above the
    topology's capacity integrates queueing delay without bound (round 1
    recorded p50 = 52s this way). When ``backlog_fn(sent)`` reports a
    backlog that grows monotonically for ``guard_checks`` consecutive
    checks, the offer aborts so the caller can halve the rate and retry.
    """
    interval = 1.0 / rate
    sent = 0
    t0 = time.perf_counter()
    end = t0 + seconds
    nxt = t0
    last_check = t0
    prev_backlog = 0
    growth_streak = 0
    while time.perf_counter() < end:
        now = time.perf_counter()
        while nxt <= now:
            produce_nth(sent)
            sent += 1
            nxt += interval
        if backlog_fn is not None and now - last_check >= check_interval:
            last_check = now
            backlog = backlog_fn(sent)
            # Absolute depth guard: >2.5s of offered work queued means
            # the percentiles measure queueing, not service — saturation
            # regardless of jitter. The monotonic-growth check below
            # misses slow creep when deliveries arrive in bursts (each
            # burst resets the streak) — heavy-decode configs integrated
            # seconds of queueing while reporting valid. Healthy runs sit
            # far below this (backlog < a deadline-batch or two).
            if backlog > max(rate * 2.5, 8):
                # count floor of 8 only filters deadline-batch jitter at
                # tiny rates; anything higher would re-weaken the bound
                # exactly where per-message queueing delay is largest
                log(f"  backlog guard tripped: {backlog} msgs queued "
                    f"(>2.5s of offered work) @ {rate:.0f} msg/s")
                return sent, True
            # Only count growth beyond jitter: one deadline-batch of
            # messages can legitimately sit in flight.
            if backlog > prev_backlog and backlog > rate * check_interval * 2:
                growth_streak += 1
            else:
                growth_streak = 0
            prev_backlog = backlog
            if growth_streak >= guard_checks:
                log(f"  backlog guard tripped: {backlog} msgs behind and "
                    f"growing for {guard_checks * check_interval:.1f}s "
                    f"@ {rate:.0f} msg/s")
                return sent, True
        time.sleep(min(0.002, max(0.0, nxt - time.perf_counter())))
    return sent, False


def await_outputs(size_fn, sent, grace_s=60.0):
    end = time.perf_counter() + grace_s
    while size_fn() < sent and time.perf_counter() < end:
        time.sleep(0.05)
    return size_fn() >= sent


def run_latency_phase(produce_nth, out_size_fn, reset_hists, read_lat,
                      seconds, headroom=0.5, probe=96):
    """Measured-latency protocol (fixes VERDICT r1 weak #1 + #2):

    1. CALIBRATE against the latency topology ITSELF: burst ``probe``
       messages and measure its drain rate. The latency topology runs a
       short deadline + low inflight, so its capacity sits well below the
       throughput phase's number — offering a fraction of the *throughput*
       capacity (round 1) oversaturated it whenever the host was loaded.
    2. Offer ``headroom`` x calibrated capacity as an open loop with a
       backlog guard; on abort (or an unfinished drain), halve and retry.
    3. Reset the latency histograms after calibration and failed attempts:
       only the clean measured window is reported. The per-record clock
       starts at broker APPEND time (spout._append_root_ts), so any
       broker-side queueing the guard lets through still shows up honestly.

    Returns (p50, p99, offered_rate, valid) — ``valid`` is False when every
    attempt aborted or failed to drain, i.e. the reported percentiles come
    from a saturated window (the round-1 52s artifact) and must be marked
    untrusted in the capture, not recorded as a clean measurement.
    """
    base = out_size_fn()
    t0 = time.perf_counter()
    for i in range(probe):
        produce_nth(i)
    if not await_outputs(lambda: out_size_fn() - base, probe, grace_s=180.0):
        # No measurement without a clean start: probe stragglers delivered
        # during attempt 1 would disarm the backlog guard (negative
        # backlog), fake the drain check, and pollute the reset histogram
        # with ~minutes-old latencies — reported as valid. Bail out.
        done = out_size_fn() - base
        log(f"  calibration probe incomplete ({done}/{probe}); "
            "latency phase INVALID")
        p50, p99 = read_lat()
        return p50, p99, 0.0, False
    cap = max(out_size_fn() - base, 1) / (time.perf_counter() - t0)
    rate = max(4.0, cap * headroom)
    log(f"  calibrated latency-topology capacity ~{cap:.0f} msg/s "
        f"-> offering {rate:.0f} msg/s")
    valid = False
    for attempt in range(4):
        base = out_size_fn()
        reset_hists()
        sent, aborted = offer_load(
            produce_nth, rate, seconds,
            backlog_fn=lambda s: s - (out_size_fn() - base))
        drained = await_outputs(lambda: out_size_fn() - base, sent,
                                grace_s=60.0)
        if not aborted and drained:
            valid = True
            break
        log(f"  attempt {attempt + 1} {'aborted' if aborted else 'did not drain'}"
            f" @ {rate:.0f} msg/s")
        # The retry must start from a CLEAN system: stragglers delivered
        # during the next attempt would corrupt its drain check, disarm
        # the backlog guard (negative backlog), and pollute the reset
        # histogram with saturated-era latencies — reporting the round-1
        # 52s artifact as valid. No full drain -> no retry.
        if not await_outputs(lambda: out_size_fn() - base, sent,
                             grace_s=120.0):
            log("  backlog never cleared; not retrying into a dirty system")
            break
        if attempt < 3:
            rate = max(2.0, rate / 2)
            log(f"  retrying @ {rate:.0f} msg/s")
    if not valid:
        log("  latency phase INVALID: every attempt aborted/undrained — "
            "percentiles below are from a saturated window")
    p50, p99 = read_lat()
    return p50, p99, rate, valid


#: (component, histogram, label) — the per-stage attribution of the
#: append->deliver clock. Ordered as the record experiences them. The
#: h2d/compute/d2h rows decompose ``device`` (the engine's split-phase
#: pipeline timings), so they are excluded from the stage SUM — counting
#: them next to device_ms would double that time.
STAGES = [
    ("inference-bolt", "ingest_lag_ms", "ingest_to_bolt"),
    ("inference-bolt", "decode_ms", "decode"),
    ("inference-bolt", "batch_wait_ms", "batch_wait"),
    ("inference-bolt", "dispatch_wait_ms", "dispatch_queue"),
    ("inference-bolt", "device_ms", "device"),
    *[("inference-bolt", key, label) for key, label in DEVICE_SUBSTAGES],
    ("inference-bolt", "encode_ms", "encode"),
    ("kafka-bolt", "produce_ms", "produce"),
]

#: Labels that re-attribute time already counted by another stage row.
SUBSTAGE_LABELS = frozenset(label for _, label in DEVICE_SUBSTAGES)


def read_stage_p50s(cluster, name) -> dict:
    snap = cluster.metrics(name)
    out = {}
    for comp, hist, label in STAGES:
        h = snap.get(comp, {}).get(hist)
        if h and h.get("p50") is not None:
            out[label] = round(h["p50"], 2)
    return out


def reset_stage_hists(cluster, name) -> None:
    cluster.reset_histogram(name, "kafka-bolt", "e2e_latency_ms")
    for comp, hist, _ in STAGES:
        cluster.reset_histogram(name, comp, hist)


def run_latency_pass(cluster, args, cfg, buckets, topo_name,
                     framework_only=False, seconds=None,
                     throughput_msgs=0, pipeline_depth=None) -> dict:
    """ONE latency-protocol pass over a fresh topology: calibrate, offer
    under the backlog guard, report e2e percentiles + per-stage p50s.

    ``framework_only=True`` swaps in a :class:`NullEngine` (device time ==
    0): everything else — broker queueing, spout fetch, decode, batching,
    executor hops, encode, produce, ack ledger — is the genuine article,
    so append->deliver percentiles ARE the framework's share of the
    north-star latency. The shared implementation keeps the
    framework-only and device passes protocol-identical by construction."""
    from storm_tpu.config import BatchConfig
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.infer import NullEngine

    label = "framework-only" if framework_only else "device-path"
    broker = MemoryBroker(default_partitions=4)
    if pipeline_depth is None:
        pipeline_depth = getattr(args, "pipeline_depth", None)
    batch_kw = {}
    if pipeline_depth is not None:
        # --pipeline-compare pins the engine's split-phase depth per pass
        # (0 = the serialized pre-pipeline predict); default passes take
        # the BatchConfig default.
        batch_kw["pipeline_depth"] = pipeline_depth
    batch_cfg = BatchConfig(
        max_batch=args.max_batch or cfg["max_batch"],
        max_wait_ms=args.max_wait_ms,
        buckets=buckets,
        max_inflight=args.inflight or 2,
        eager=args.eager,
        **batch_kw,
    )
    engine = (NullEngine(cfg["input_shape"], cfg["num_classes"])
              if framework_only else None)
    run_cfg, topo = build_topology(
        cfg, broker, batch_cfg,
        None if framework_only else args.transfer_dtype, args.chunk,
        "float" if framework_only else args.weights, engine=engine)
    t0 = time.time()
    cluster.submit_topology(topo_name, run_cfg, topo)
    if not framework_only:
        log(f"submitted + warmed up in {time.time() - t0:.1f}s")
    payloads = make_payloads(cfg, instances_per_msg=args.instances_per_msg)

    result: dict = {}
    if throughput_msgs:
        for i in range(throughput_msgs):
            broker.produce("input", payloads[i % len(payloads)])
        delivered, elapsed = drain_loop(
            lambda: broker.topic_size("output"), throughput_msgs,
            args.instances_per_msg, timeout_s=180.0)
        recs = delivered * args.instances_per_msg
        result["records_per_sec"] = round(recs / elapsed, 1)
        log(f"  {label} throughput: {recs} records in {elapsed:.2f}s"
            f" -> {result['records_per_sec']:.0f} rec/s")

    def read_lat():
        lat = cluster.metrics(topo_name)["kafka-bolt"]["e2e_latency_ms"]
        return (lat["p50"] if lat["p50"] is not None else float("nan"),
                lat["p99"] if lat["p99"] is not None else float("nan"))

    p50, p99, rate, valid = run_latency_phase(
        lambda i: broker.produce("input", payloads[i % len(payloads)]),
        lambda: broker.topic_size("output"),
        lambda: reset_stage_hists(cluster, topo_name),
        read_lat, seconds or args.latency_seconds)
    stages = read_stage_p50s(cluster, topo_name)
    log(f"  {label} e2e (append->deliver): p50={p50:.1f} "
        f"p99={p99:.1f} @ {rate:.0f} msg/s"
        f"{'' if valid else ' [INVALID: saturated]'}")
    log(f"  stages (p50 ms): {stages}")
    cluster.kill_topology(topo_name, wait_secs=2)
    result.update({
        "p50_ms": round(p50, 2) if p50 == p50 else None,
        "p99_ms": round(p99, 2) if p99 == p99 else None,
        "offered_rate": round(rate, 1),
        "valid": valid,
        "stages_p50_ms": stages,
    })
    return result


def run_latency_breakdown(args) -> dict:
    """``--latency-breakdown``: the north-star latency claim as evidence
    (VERDICT r2 missing #1). Two passes over the same topology shape:

    1. framework-only (NullEngine): append->deliver percentiles with
       device time pinned to 0 — the framework's own overhead, the number
       the <50 ms claim is actually about;
    2. real engine on the chip: the same percentiles attributed per stage
       (ingest/decode/batch-wait/dispatch-queue/device/encode/produce), so
       the gap between (1) and (2) is visibly the device + its dispatch
       path, not the framework.
    """
    import jax

    from storm_tpu.runtime.cluster import LocalCluster

    cfg = CONFIGS[args.config]
    if "model" not in cfg:
        sys.exit("--latency-breakdown needs a single-model config")
    n_dev = len(jax.devices())
    log(f"devices: {jax.devices()}")
    buckets = cfg["buckets"]
    cluster = LocalCluster()
    try:
        log("== pass 1: framework-only (NullEngine, device time = 0) ==")
        fw = run_latency_pass(cluster, args, cfg, buckets, "bench-framework",
                              framework_only=True,
                              throughput_msgs=min(args.messages, 4096))
        log("== pass 2: real engine on device, per-stage attribution ==")
        dev = run_latency_pass(cluster, args, cfg, buckets,
                               "bench-device-lat")
    finally:
        cluster.shutdown()

    fw_p50 = fw.get("p50_ms")
    dev_stages = dev["stages_p50_ms"]
    # Sum of in-bolt/sink stage p50s, vs e2e p50: the unaccounted
    # remainder is inter-operator hops + ack plumbing. Device substages
    # (h2d/compute/d2h) re-attribute time device_ms already counts.
    dev["stage_sum_ex_ingest_ms"] = round(
        sum(v for k, v in dev_stages.items()
            if k != "ingest_to_bolt" and k not in SUBSTAGE_LABELS), 1)
    return {
        "metric": f"{cfg['metric']}_framework_only_p50_ms",
        "value": fw_p50,
        "unit": "ms (append->deliver, device time = 0)",
        "target_ms": 50.0,
        # >1 = beating the 50 ms framework-overhead target
        "vs_baseline": (round(50.0 / fw_p50, 2)
                        if fw_p50 else None),
        "framework_only": fw,
        "device_path": dev,
        **device_info(),
        "config": f"{args.config}+latency-breakdown",
    }


def run_pipeline_compare(args) -> dict:
    """``--pipeline-compare``: the split-phase pipeline's claim as one
    artifact. Two protocol-identical device-path passes on the same host
    in the same process (same code-version stamp, same capture session):

    1. serialized baseline — ``pipeline_depth=0``, the pre-pipeline
       engine (pad -> cast -> device_put -> fwd -> fetch under one lock,
       one batch at a time);
    2. pipelined — dispatch/fetch split with a bounded in-flight ring, so
       H2D of batch N+1 overlaps compute of batch N and D2H of batch N-1.

    The comparison metric is the device-side share the pipeline actually
    targets: dispatch_queue + device p50 (batch-formation and ingest are
    identical by construction). The pipelined pass also reports the
    h2d/compute/d2h substage decomposition (serialized predict has no
    split-phase timings to report)."""
    import jax

    from storm_tpu.runtime.cluster import LocalCluster

    cfg = CONFIGS[args.config]
    if "model" not in cfg:
        sys.exit("--pipeline-compare needs a single-model config")
    depth = args.pipeline_depth if args.pipeline_depth is not None else 2
    if depth < 1:
        sys.exit("--pipeline-depth must be >= 1 for --pipeline-compare")
    n_dev = len(jax.devices())
    log(f"devices: {jax.devices()}")
    buckets = cfg["buckets"]
    msgs = min(args.messages, 4096)
    passes = {}
    cluster = LocalCluster()
    try:
        log("== pass 1: serialized engine (pipeline_depth=0) ==")
        passes["serialized"] = run_latency_pass(
            cluster, args, cfg, buckets, "bench-pipe-serial",
            throughput_msgs=msgs, pipeline_depth=0)
        log(f"== pass 2: pipelined engine (pipeline_depth={depth}) ==")
        passes["pipelined"] = run_latency_pass(
            cluster, args, cfg, buckets, "bench-pipe-overlap",
            throughput_msgs=msgs, pipeline_depth=depth)
    finally:
        cluster.shutdown()

    def device_share(p):
        st = p["stages_p50_ms"]
        vals = [st.get("dispatch_queue"), st.get("device")]
        return round(sum(v for v in vals if v is not None), 2)

    ser, pipe = passes["serialized"], passes["pipelined"]
    ser_ms, pipe_ms = device_share(ser), device_share(pipe)
    thr_ser = ser.get("records_per_sec")
    thr_pipe = pipe.get("records_per_sec")
    return {
        "metric": f"{cfg['metric']}_pipeline_device_share_p50_ms",
        "value": pipe_ms,
        "unit": ("dispatch_queue + device p50 (ms) with the split-phase "
                 "pipeline, vs the serialized engine in the same run"),
        "serialized_device_share_p50_ms": ser_ms,
        "pipelined_device_share_p50_ms": pipe_ms,
        "speedup": (round(ser_ms / pipe_ms, 3) if pipe_ms else None),
        "pipelined_below_serialized": bool(pipe_ms < ser_ms),
        "records_per_sec_serialized": thr_ser,
        "records_per_sec_pipelined": thr_pipe,
        "device_substages_p50_ms": {
            label: pipe["stages_p50_ms"].get(label)
            for _, label in DEVICE_SUBSTAGES},
        "pipeline_depth": depth,
        "latency_valid": bool(ser["valid"] and pipe["valid"]),
        "serialized": ser,
        "pipelined": pipe,
        **device_info(),
        "config": f"{args.config}+pipeline-compare",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def run_wire_compare(args) -> dict:
    """``--wire-compare``: JSON vs binary inter-worker tuple wire, A/B'd
    on a real 3-worker CPU mesh — spout, inference, and sink pinned to
    separate worker processes so every record crosses two gRPC hops.

    Two workloads: the NullEngine framework ceiling (builder "null" — no
    device work, so the wire/routing/ledger stack IS the measurement) and
    lenet5 with the real engine (how much of the wire win survives once
    compute is in the loop). Each at two payload sizes (1 and 8
    instances/message — the binary win grows with payload bytes because
    JSON re-stringifies every value per hop).

    Protocol (r04 honesty rules): repeats are INTERLEAVED at cell level
    (json, binary, json, binary, ...) so drift hits both wires equally;
    min/median/max and the raw samples land in the artifact; the backlog
    is pre-produced and timing runs from the ``warm``-th output to the
    last, so producer pacing, topology startup, and first-batch compile
    are all outside the window. Each wire runs its best legal spout
    scheme: the JSON envelope cannot carry bytes, so it pays
    ``scheme="string"`` (decode + re-encode per hop), while the binary
    wire ships broker bytes as-is with ``scheme="raw"`` — the comparison
    is wire stack vs wire stack, not codec in isolation."""
    from storm_tpu.config import Config
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.dist import DistCluster
    from storm_tpu.dist import wire as wire_mod
    from storm_tpu.native import native_available
    from tests.kafka_stub import KafkaStubBroker

    repeats = max(1, args.repeats)
    stub = KafkaStubBroker(partitions=2)
    placement = {"kafka-spout": 0, "inference-bolt": 1,
                 "kafka-bolt": 2, "dlq-bolt": 2}

    def mk_cfg(prefix: str, wire: str, instances: int) -> Config:
        cfg = Config()
        cfg.broker.kind = "kafka"
        cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
        cfg.broker.input_topic = f"{prefix}-in"
        cfg.broker.output_topic = f"{prefix}-out"
        cfg.broker.dead_letter_topic = f"{prefix}-dlq"
        cfg.model.name = "lenet5"
        cfg.model.dtype = "float32"
        cfg.model.input_shape = (28, 28, 1)
        cfg.offsets.policy = "earliest"
        cfg.offsets.max_behind = None
        cfg.batch.max_batch = 64
        cfg.batch.max_wait_ms = 5
        cfg.batch.buckets = (64,)
        cfg.topology.spout_parallelism = 1
        cfg.topology.inference_parallelism = 2
        cfg.topology.sink_parallelism = 1
        cfg.topology.message_timeout_s = 300.0
        # Small in-flight cap: the timed window must be ack-gated steady
        # state, and `warm` outputs > this cap put the initial in-flight
        # flood (whose burst rate is not sustainable) outside the window.
        cfg.topology.max_spout_pending = 256
        cfg.tracing.sample_rate = 0.0
        cfg.topology.wire_format = wire
        cfg.topology.spout_scheme = "raw" if wire == "binary" else "string"
        return cfg

    def mk_payloads(instances: int):
        rng = np.random.RandomState(0)
        return [
            json.dumps({"instances":
                        rng.rand(instances, 28, 28, 1).round(4).tolist()})
            for _ in range(16)
        ]

    def run_once(cluster, prefix, builder, wire, instances, n_msgs, warm,
                 payloads) -> Tuple[float, int]:
        """One submit/measure/kill cycle. Returns (msgs_per_sec, replays)."""
        cfg = mk_cfg(prefix, wire, instances)
        producer = KafkaWireBroker(cfg.broker.bootstrap)
        total = warm + n_msgs
        for i in range(total):
            producer.produce(cfg.broker.input_topic, payloads[i % len(payloads)])
        out = cfg.broker.output_topic
        cluster.submit(prefix, cfg, placement, builder=builder)
        elapsed, done = timed_drain_window(
            lambda: stub.topic_size(out), warm, total)
        if not cluster.drain(timeout_s=30):
            log(f"  {prefix}: drain timed out")
        snap = cluster.metrics()
        replays = snap["kafka-spout"].get("tree_failed", 0)
        cluster.kill()
        # Free the run's backlog (the stub has no delete-topic API and a
        # 62KB x 1300-message run is ~90MB; 24 runs would not fit).
        with stub._lock:
            for t in (cfg.broker.input_topic, out,
                      cfg.broker.dead_letter_topic):
                for p in range(stub.partitions):
                    stub._logs.pop((t, p), None)
        if elapsed != elapsed or done < total:
            raise RuntimeError(
                f"{prefix}: only {done}/{total} outputs before deadline")
        return n_msgs / elapsed, replays

    # (n_msgs, warm) per payload size: warm > max_spout_pending so timing
    # starts after the in-flight flood, and n_msgs sized for multi-second
    # timed windows at this host's observed rates, so cell medians aren't
    # scheduling noise.
    workloads = [
        ("framework_null", "null", {1: (8000, 800), 8: (1600, 400)}),
        ("lenet5", "standard", {1: (4000, 800), 8: (1000, 300)}),
    ]
    rows = []
    run_id = 0
    try:
        with DistCluster(3, env={"JAX_PLATFORMS": "cpu"}) as cluster:
            for c in cluster.clients:
                assert c.control("ping").get("wire", 0) >= wire_mod.WIRE_VERSION
            for workload, builder, sizing in workloads:
                for instances in (1, 8):
                    n_msgs, warm = sizing[instances]
                    payloads = mk_payloads(instances)

                    def cell(wire, rep):
                        nonlocal run_id
                        run_id += 1
                        rate, rp = run_once(
                            cluster, f"w{run_id}", builder, wire, instances,
                            n_msgs, warm, payloads)
                        log(f"  {workload} x{instances} {wire} "
                            f"rep{rep}: {rate:.1f} msg/s"
                            + (f" ({rp} replays)" if rp else ""))
                        return rate, rp

                    cells = run_interleaved(("json", "binary"), repeats,
                                            cell)
                    samples = {w: [r for r, _ in cells[w]]
                               for w in ("json", "binary")}
                    replays = {w: [p for _, p in cells[w]]
                               for w in ("json", "binary")}
                    row = {
                        "workload": workload,
                        "builder": builder,
                        "instances_per_msg": instances,
                        "payload_bytes": len(payloads[0].encode("utf-8")),
                        "messages_timed": n_msgs,
                        "warmup_messages": warm,
                    }
                    for wire in ("json", "binary"):
                        row[wire] = dict(arm_stats(samples[wire]),
                                         replays=replays[wire])
                    row["speedup_binary_vs_json"] = round(
                        row["binary"]["msgs_per_sec"]
                        / row["json"]["msgs_per_sec"], 3)
                    rows.append(row)
    finally:
        stub.close()

    fw = [r for r in rows if r["workload"] == "framework_null"]
    return {
        "metric": "wire_compare_dist3_cpu",
        "unit": ("messages/s end-to-end across a 3-worker mesh "
                 "(records/s = msgs/s * instances_per_msg); timed from the "
                 "warm-th output to the last against a pre-produced "
                 "backlog"),
        "value": max(r["speedup_binary_vs_json"] for r in fw),
        "rows": rows,
        "binary_geq_json_framework": all(
            r["binary"]["msgs_per_sec"] >= r["json"]["msgs_per_sec"]
            for r in fw),
        "workers": 3,
        "wire_hops_per_record": 2,
        "wire_version": wire_mod.WIRE_VERSION,
        "native_crc32c": native_available(),
        "repeats": repeats,
        "protocol": ("interleaved A/B per cell; each wire at its best "
                     "legal spout scheme (json wire cannot carry bytes -> "
                     "scheme='string'; binary wire -> scheme='raw')"),
        **HOST_ONLY,
        "config": "wire-compare",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def run_chaos_recovery(args) -> dict:
    """``--chaos-recovery``: the round-14 resilience evidence run — kill a
    worker and brown out the wire UNDER STEADY LOAD on a real 3-worker CPU
    mesh, and measure the recovery the dist stack claims.

    Phase 1 (dist mesh): spout, inference, and sink pinned to separate
    worker processes; a paced producer offers a fixed msg/s rate (well
    under mesh capacity, so goodput == offered rate at steady state) and
    1 s goodput windows are read off the output topic. The timeline is
    baseline -> wire brownout (injected latency + drop on the spout
    host's senders, via the ``chaos`` control RPC) -> settle -> SIGKILL
    of the inference worker with the heartbeat monitor armed. Recovery =
    first 3-window rolling mean >= 95% of the baseline median;
    time-to-recover runs from the kill to that point, so it prices
    detection (misses x interval), respawn + topology re-ship, engine
    rebuild, ledger replay, and the replay-pacing window all together.

    Phase 2 (in-process, exactly-once): the committed soak harness under
    ``--chaos`` — engine-hang injection -> watchdog trips -> quarantine ->
    replacement engine — with its per-record sha256 read_committed audit.
    The zero-duplicate claim lives HERE by design: the dist mesh above is
    at-least-once (reference parity — a Storm worker crash replays
    trees), so phase 1's kill proves liveness + bounded replay while the
    transactional path proves no duplicate sink emits under the same
    injector."""
    import subprocess
    import threading

    from storm_tpu.config import Config
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.dist import DistCluster
    from tests.kafka_stub import KafkaStubBroker

    rate = 20.0          # offered msg/s: ~10x under lenet5 mesh capacity
    window_s = 1.0
    stub = KafkaStubBroker(partitions=2)
    placement = {"kafka-spout": 0, "inference-bolt": 1,
                 "kafka-bolt": 2, "dlq-bolt": 2}

    cfg = Config()
    cfg.broker.kind = "kafka"
    cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
    cfg.broker.input_topic = "chaos-in"
    cfg.broker.output_topic = "chaos-out"
    cfg.broker.dead_letter_topic = "chaos-dlq"
    cfg.model.name = "lenet5"
    cfg.model.dtype = "float32"
    cfg.model.input_shape = (28, 28, 1)
    cfg.offsets.policy = "earliest"
    cfg.offsets.max_behind = None
    cfg.batch.max_batch = 64
    cfg.batch.max_wait_ms = 5
    cfg.batch.buckets = (64,)
    cfg.topology.spout_parallelism = 1
    cfg.topology.inference_parallelism = 2
    cfg.topology.sink_parallelism = 1
    # Fast ledger timeout: dead-worker trees replay ~6 s after the kill
    # instead of minutes — shortens the run without changing the replay
    # MECHANISM under test.
    cfg.topology.message_timeout_s = 6.0
    cfg.topology.max_spout_pending = 256
    cfg.tracing.sample_rate = 0.0
    cfg.topology.wire_format = "binary"
    cfg.topology.spout_scheme = "raw"
    out_topic = cfg.broker.output_topic

    rng = np.random.RandomState(0)
    payloads = [
        json.dumps({"instances": rng.rand(1, 28, 28, 1).round(4).tolist()})
        for _ in range(16)
    ]
    producer = KafkaWireBroker(cfg.broker.bootstrap)
    stop_feed = threading.Event()
    fed = [0]

    def feeder() -> None:
        period = 1.0 / rate
        nxt = time.perf_counter()
        while not stop_feed.is_set():
            try:
                producer.produce(cfg.broker.input_topic,
                                 payloads[fed[0] % len(payloads)])
            except Exception:
                time.sleep(0.5)  # stub hiccup: keep offering
                continue
            fed[0] += 1
            nxt += period
            time.sleep(max(0.0, nxt - time.perf_counter()))

    timeline: list = []
    state = {"n": 0, "t": 0.0, "t0": 0.0}

    def sample(phase: str) -> float:
        """Sleep to the next window boundary, append + return its goodput."""
        time.sleep(max(0.0, state["t"] + window_s - time.perf_counter()))
        now = time.perf_counter()
        n = stub.topic_size(out_topic)
        gp = (n - state["n"]) / (now - state["t"])
        timeline.append({"t": round(now - state["t0"], 1), "phase": phase,
                         "goodput_msgs_s": round(gp, 2)})
        state["n"], state["t"] = n, now
        return gp

    interesting = ("chaos_injection", "dist_circuit_open",
                   "dist_circuit_close", "dist_peer_replaced",
                   "dist_heartbeat_miss", "dist_worker_recovered",
                   "wire_error")
    try:
        with DistCluster(3, env={"JAX_PLATFORMS": "cpu"}) as cluster:
            cluster.submit("chaos", cfg, placement, builder="standard")
            cluster.start_monitor(interval_s=0.5, misses=2)
            feeder_thread = threading.Thread(target=feeder, daemon=True)
            feeder_thread.start()
            log("chaos-recovery: warming (first outputs outside windows)")
            deadline = time.time() + 120
            while stub.topic_size(out_topic) < 3 * rate:
                if time.time() > deadline:
                    raise RuntimeError("no steady output within 120s")
                time.sleep(0.25)
            state["n"] = stub.topic_size(out_topic)
            state["t"] = state["t0"] = time.perf_counter()

            base_w = [sample("baseline") for _ in range(8)]
            baseline = sorted(base_w)[len(base_w) // 2]
            log(f"chaos-recovery: baseline {baseline:.1f} msg/s")

            # Wire brownout on the spout host: every spout->inference hop
            # eats injected latency/jitter and a 10% drop rate (ChaosDrop
            # rides the same retry/backoff path as a real outage).
            cluster.clients[0].control(
                "chaos", wire_latency_ms=40.0, wire_jitter_ms=20.0,
                wire_drop_pct=0.10)
            brown_w = [sample("brownout") for _ in range(6)]
            cluster.clients[0].control(
                "chaos", wire_latency_ms=0.0, wire_jitter_ms=0.0,
                wire_drop_pct=0.0)
            transport_brownout = dict(
                cluster.metrics().get("_transport", {}))
            chaos_counts = cluster.clients[0].control("chaos")["chaos"]["counts"]
            for _ in range(4):
                sample("settle")

            log("chaos-recovery: SIGKILL worker 1 (inference host)")
            cluster.flight.event("chaos_injection", target="worker_kill",
                                 worker=1)
            t_kill = time.perf_counter()
            cluster.procs[1].kill()
            recover_s = None
            recovered_goodput = None
            tail: list = []
            for _ in range(180):
                tail.append(sample("outage"))
                if len(tail) >= 3:
                    mean3 = sum(tail[-3:]) / 3.0
                    if mean3 >= 0.95 * baseline:
                        recover_s = round(time.perf_counter() - t_kill, 2)
                        recovered_goodput = round(mean3, 2)
                        break
            if recover_s is None:
                raise RuntimeError(
                    f"no recovery to 95% of {baseline:.1f} msg/s within "
                    f"{len(tail)} windows; timeline={timeline[-20:]}")
            log(f"chaos-recovery: recovered in {recover_s:.1f}s "
                f"({recovered_goodput:.1f} msg/s)")
            post_w = [sample("recovered") for _ in range(5)]

            stop_feed.set()
            feeder_thread.join(timeout=10)
            drained = cluster.drain(timeout_s=120)
            snap = cluster.metrics()
            transport = dict(snap.get("_transport", {}))
            replays = snap.get("kafka-spout", {}).get("tree_failed", 0)
            ctrl = cluster.ctrl_metrics.snapshot().get("controller", {})
            ctrl_flight = [ev for ev in cluster.flight.tail(200)
                           if ev.get("kind") in interesting]
            worker_flight = [ev for ev in
                             cluster.traces(80).get("flight", [])
                             if ev.get("kind") in interesting]
    finally:
        stub.close()

    # The ledger caps in-flight trees at max_spout_pending and each tree
    # replays at most once per message_timeout_s, so the replay count for
    # an outage of `recover_s` is bounded by pending * (rounds + 1).
    rounds = math.ceil(max(recover_s, 0.1) / cfg.topology.message_timeout_s)
    replay_bound = int(cfg.topology.max_spout_pending * (rounds + 1))

    # Phase 2: exactly-once + engine-hang quarantine under the same
    # injector, through the committed soak harness (its own gate exits
    # nonzero on any audit violation).
    log("chaos-recovery: phase 2 (soak --chaos, exactly-once audit)")
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    soak = subprocess.run(
        [sys.executable, "soak_harness.py",
         "--seconds", "45", "--rate", "20", "--out", "-", "--chaos"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=390)
    if soak.returncode != 0:
        raise RuntimeError(
            f"soak --chaos failed its exactly_once gate:\n"
            f"{soak.stderr[-4000:]}")
    soak_art = json.loads(soak.stdout)

    recovery_ratio = round(recovered_goodput / baseline, 3)
    return {
        "metric": "chaos_recovery_dist3_cpu",
        "unit": ("goodput msg/s in 1s windows on the output topic under a "
                 "paced offered load; time_to_recover_s from SIGKILL to "
                 "the first 3-window rolling mean >= 95% of baseline"),
        "value": recovery_ratio,
        "offered_rate_msgs_s": rate,
        "baseline_goodput_msgs_s": round(baseline, 2),
        "recovered_goodput_msgs_s": recovered_goodput,
        "recovery_ratio": recovery_ratio,
        "recovered": recovery_ratio >= 0.95,
        "time_to_recover_s": recover_s,
        "post_recovery_windows": [round(g, 2) for g in post_w],
        "brownout": {
            "wire_latency_ms": 40.0, "wire_jitter_ms": 20.0,
            "wire_drop_pct": 0.10, "windows": [round(g, 2) for g in brown_w],
            "goodput_floor_msgs_s": round(min(brown_w), 2),
            "survived": min(brown_w) > 0,
            "transport_counters_at_end": transport_brownout,
            "chaos_injection_counts": chaos_counts,
        },
        "worker_killed": 1,
        "monitor": {"interval_s": 0.5, "misses": 2,
                    "heartbeat": dict(ctrl)},
        "replays": {
            "tree_failed": replays,
            "bound": replay_bound,
            "bounded": replays <= replay_bound,
            "message_timeout_s": cfg.topology.message_timeout_s,
            "max_spout_pending": cfg.topology.max_spout_pending,
        },
        "replay_pacing": {
            "throttled": transport.get("dist_replay_throttled", 0),
            "throttle_ms": transport.get("dist_replay_throttle_ms"),
            "auto_rate_tuples_s": round(
                cfg.topology.max_spout_pending / 10.0, 1),
            "window_s": 10.0,
        },
        "transport_counters": transport,
        "flight": {"controller": ctrl_flight[-40:],
                   "workers": worker_flight[-40:]},
        "timeline": timeline,
        "drained": drained,
        "produced": fed[0],
        "exactly_once": {
            "where": ("in-process transactional path (soak harness "
                      "--chaos): offsets+outputs committed in one broker "
                      "txn per tree; the dist mesh above is at-least-once "
                      "by design, reference parity"),
            "exactly_once": soak_art["exactly_once"],
            "audit": soak_art["audit"],
            "chaos": soak_art["chaos"],
            "events": soak_art["events"],
            "capture_session": soak_art.get("capture_session"),
        },
        "quarantine": {
            "watchdog": soak_art["chaos"]["watchdog"],
            "engine_hangs_injected":
                soak_art["chaos"]["counts"].get("engine_hang", 0),
            "replacement_served": bool(soak_art["audit"]["drained"]),
        },
        "workers": 3,
        **HOST_ONLY,
        "config": "chaos-recovery",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def _failover_cfg(bootstrap: str):
    """Shared topology config for --controller-failover: built identically
    by the child controller (submit) and the parent (expectations), so the
    journaled recipe the reattach adopts is the one the parent reasons
    about. offsets.policy='resume' + a pinned group: a worker restarted by
    the rolling phase resumes its partitions from committed offsets
    instead of re-reading ('earliest') or dropping backlog ('latest')."""
    from storm_tpu.config import Config

    cfg = Config()
    cfg.broker.kind = "kafka"
    cfg.broker.bootstrap = bootstrap
    cfg.broker.input_topic = "failover-in"
    cfg.broker.output_topic = "failover-out"
    cfg.broker.dead_letter_topic = "failover-dlq"
    cfg.model.name = "lenet5"
    cfg.model.dtype = "float32"
    cfg.model.input_shape = (28, 28, 1)
    cfg.offsets.policy = "resume"
    cfg.offsets.group_id = "failover-group"
    cfg.offsets.max_behind = None
    cfg.batch.max_batch = 64
    cfg.batch.max_wait_ms = 5
    cfg.batch.buckets = (64,)
    cfg.topology.spout_parallelism = 1
    cfg.topology.inference_parallelism = 2
    cfg.topology.sink_parallelism = 1
    # Fast ledger timeout: trees stranded by a worker restart replay in
    # seconds, keeping the catch-up inside the same goodput window.
    cfg.topology.message_timeout_s = 6.0
    cfg.topology.max_spout_pending = 256
    cfg.tracing.sample_rate = 0.0
    cfg.topology.wire_format = "binary"
    cfg.topology.spout_scheme = "raw"
    return cfg


_FAILOVER_PLACEMENT = {"kafka-spout": 0, "inference-bolt": 1,
                       "kafka-bolt": 2, "dlq-bolt": 2}


def run_failover_ctl(spec_path: str) -> int:
    """Hidden child mode for --controller-failover: the FIRST controller.

    Builds the 3-worker mesh with the journal armed, submits, prints one
    ready line (peers + worker pids) and then just waits — the parent
    SIGKILLs this process mid-stream, which is the whole point: this
    controller never gets to clean up, and the mesh it orphans plus the
    journal it wrote are all the next controller has."""
    import signal as _signal

    from storm_tpu.dist import DistCluster

    with open(spec_path) as f:
        spec = json.load(f)
    cfg = _failover_cfg(spec["bootstrap"])
    cluster = DistCluster(
        3, env={"JAX_PLATFORMS": "cpu"},
        journal_dir=spec["journal_dir"], reattach=False)
    cluster.submit("failover", cfg, dict(_FAILOVER_PLACEMENT),
                   builder="standard")
    print(json.dumps({"ready": True, "peers": cluster.peers,
                      "pids": cluster._pids}), flush=True)
    while True:
        _signal.pause()


def run_controller_failover(args) -> dict:
    """``--controller-failover``: the durable-control-plane evidence run.

    A CHILD process plays the first controller: 3-worker CPU mesh (spout,
    inference, sink on separate workers), journal armed, paced offered
    load. The parent SIGKILLs the child mid-stream (controller hard
    death: no drain, no goodbyes), shows the orphaned mesh keeps serving,
    then constructs a second controller on the same journal dir and
    measures the reattach: all three survivors adopted, ZERO engine
    recompiles (worker pids unchanged, per-worker submit counts still 1 —
    engines only (re)build on submit/swap). Then the reattached
    controller rolls the whole mesh (drain -> restart -> rewire, one
    worker at a time) under load, with 10 s goodput windows gated at
    >= 50% of the baseline median at every point.

    Exactly-once lives in phase 2 (reference parity: the dist mesh is
    at-least-once): the committed soak harness under ``--drain-drill``
    runs the same drain cycle against the transactional path and its
    per-record sha256 read_committed audit."""
    import shutil
    import subprocess
    import tempfile
    import threading

    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.dist import DistCluster
    from tests.kafka_stub import KafkaStubBroker

    rate = 20.0          # offered msg/s: ~10x under lenet5 mesh capacity
    stub = KafkaStubBroker(partitions=2)
    work_dir = tempfile.mkdtemp(prefix="bench-failover-")
    journal_dir = os.path.join(work_dir, "journal")
    repo = os.path.dirname(os.path.abspath(__file__))

    cfg = _failover_cfg(f"127.0.0.1:{stub.port}")
    out_topic = cfg.broker.output_topic
    spec_path = os.path.join(work_dir, "spec.json")
    with open(spec_path, "w") as f:
        json.dump({"bootstrap": cfg.broker.bootstrap,
                   "journal_dir": journal_dir}, f)

    rng = np.random.RandomState(0)
    payloads = [
        json.dumps({"instances": rng.rand(1, 28, 28, 1).round(4).tolist()})
        for _ in range(16)
    ]
    producer = KafkaWireBroker(cfg.broker.bootstrap)
    stop_feed = threading.Event()
    fed = [0]

    def feeder() -> None:
        period = 1.0 / rate
        nxt = time.perf_counter()
        while not stop_feed.is_set():
            try:
                producer.produce(cfg.broker.input_topic,
                                 payloads[fed[0] % len(payloads)])
            except Exception:
                time.sleep(0.5)  # stub hiccup: keep offering
                continue
            fed[0] += 1
            nxt += period
            time.sleep(max(0.0, nxt - time.perf_counter()))

    timeline: list = []
    state = {"n": 0, "t": 0.0, "t0": 0.0}

    def sample(phase: str, secs: float = 1.0) -> float:
        """Sleep ``secs`` past the last mark, append + return the
        window's goodput off the output topic."""
        time.sleep(max(0.0, state["t"] + secs - time.perf_counter()))
        now = time.perf_counter()
        n = stub.topic_size(out_topic)
        gp = (n - state["n"]) / (now - state["t"])
        timeline.append({"t": round(now - state["t0"], 1), "phase": phase,
                         "goodput_msgs_s": round(gp, 2)})
        state["n"], state["t"] = n, now
        return gp

    ctl_err = open(os.path.join(work_dir, "ctl.err"), "wb")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ctl = subprocess.Popen(
        [sys.executable, os.path.join(repo, "bench.py"),
         "--_failover-ctl", spec_path],
        stdout=subprocess.PIPE, stderr=ctl_err, cwd=repo, env=env)
    cluster2 = None
    try:
        log("controller-failover: child controller building the mesh")
        line = ctl.stdout.readline().decode()
        if not line.strip():
            with open(os.path.join(work_dir, "ctl.err"), "rb") as f:
                tail = f.read()[-4000:].decode("utf-8", "replace")
            raise RuntimeError(
                f"failover child died during startup; stderr tail:\n{tail}")
        ready = json.loads(line)
        child_pids = {int(k): int(v) for k, v in ready["pids"].items()}
        log(f"controller-failover: mesh up, worker pids {child_pids}")

        feeder_thread = threading.Thread(target=feeder, daemon=True)
        feeder_thread.start()
        deadline = time.time() + 180
        while stub.topic_size(out_topic) < 3 * rate:
            if time.time() > deadline:
                raise RuntimeError("no steady output within 180s")
            time.sleep(0.25)
        state["n"] = stub.topic_size(out_topic)
        state["t"] = state["t0"] = time.perf_counter()

        base_w = [sample("baseline") for _ in range(8)]
        baseline = sorted(base_w)[len(base_w) // 2]
        log(f"controller-failover: baseline {baseline:.1f} msg/s")

        log("controller-failover: SIGKILL the controller process")
        ctl.kill()
        ctl.wait(timeout=10)
        # The orphaned mesh must keep serving: the data plane does not
        # route through the controller.
        down_w = [sample("ctl_down") for _ in range(4)]

        t0 = time.perf_counter()
        cluster2 = DistCluster(
            3, env={"JAX_PLATFORMS": "cpu"},
            journal_dir=journal_dir, reattach=True)
        reattach_s = round(time.perf_counter() - t0, 2)
        if not cluster2.reattached:
            raise RuntimeError("controller failed to reattach (cold rebuild)")
        reattach_ev = next(
            (ev for ev in cluster2.flight.tail(50)
             if ev.get("kind") == "dist_reattached"), {})
        reports = cluster2.state_reports()
        pids_after = {i: r.get("pid") for i, r in reports.items()}
        submits_after = {i: r.get("submits") for i, r in reports.items()}
        zero_recompile = (pids_after == child_pids
                          and all(s == 1 for s in submits_after.values()))
        log(f"controller-failover: reattached in {reattach_s:.2f}s "
            f"(pids {pids_after}, submits {submits_after})")
        cluster2.start_monitor(interval_s=0.5, misses=2)
        post_w = [sample("reattached") for _ in range(4)]

        log("controller-failover: rolling restart under load")
        roll: dict = {}

        def do_roll() -> None:
            # settle_s=10 between workers: with one pipeline stage per
            # worker, back-to-back restarts would keep SOME stage dark
            # for the whole roll; the settle lets the replay backlog
            # clear before the next stage goes down (the ops posture
            # the runbook prescribes).
            t = time.perf_counter()
            try:
                roll["rows"] = cluster2.rolling_restart(
                    drain_timeout_s=20.0, settle_s=10.0)
            except Exception as e:  # surfaced after the sampling loop
                roll["error"] = repr(e)
            finally:
                roll["s"] = round(time.perf_counter() - t, 2)

        roll_thread = threading.Thread(target=do_roll, daemon=True)
        roll_thread.start()
        roll_w = []
        while roll_thread.is_alive():
            roll_w.append(sample("rolling", secs=10.0))
        roll_thread.join()
        if "error" in roll:
            raise RuntimeError(f"rolling restart failed: {roll['error']}")
        roll_w.append(sample("rolling_settle", secs=10.0))  # final catch-up
        roll_s = roll["s"]
        floor = min(roll_w)
        log(f"controller-failover: rolled 3 workers in {roll_s:.1f}s, "
            f"goodput floor {floor:.1f} msg/s (baseline {baseline:.1f})")

        reports2 = cluster2.state_reports()
        rolled_pids = {i: r.get("pid") for i, r in reports2.items()}
        jstats = cluster2.journal_stats()
        stop_feed.set()
        feeder_thread.join(timeout=10)
        drained = cluster2.drain(timeout_s=120)
        interesting = ("dist_reattached", "dist_worker_draining",
                       "dist_worker_restarted", "dist_worker_recovered",
                       "dist_heartbeat_miss")
        ctrl_flight = [ev for ev in cluster2.flight.tail(200)
                       if ev.get("kind") in interesting]
    finally:
        try:
            if cluster2 is not None:
                cluster2.shutdown()
            if ctl.poll() is None:
                ctl.kill()
        finally:
            ctl_err.close()
            stub.close()
            shutil.rmtree(work_dir, ignore_errors=True)

    # Phase 2: the same drain cycle against the exactly-once transactional
    # path (soak --drain-drill gates itself: nonzero exit on any audit
    # violation).
    log("controller-failover: phase 2 (soak --drain-drill, "
        "exactly-once audit)")
    soak = subprocess.run(
        [sys.executable, "soak_harness.py",
         "--seconds", "45", "--rate", "20", "--out", "-", "--drain-drill"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=390)
    if soak.returncode != 0:
        raise RuntimeError(
            f"soak --drain-drill failed its exactly_once gate:\n"
            f"{soak.stderr[-4000:]}")
    soak_art = json.loads(soak.stdout)

    floor_ratio = round(floor / baseline, 3)
    return {
        "metric": "controller_failover_dist3_cpu",
        "unit": ("seconds from new-controller construction to adoption of "
                 "all journaled survivors (reattach_s); goodput msg/s in "
                 "windows on the output topic under a paced offered load"),
        "value": reattach_s,
        "offered_rate_msgs_s": rate,
        "baseline_goodput_msgs_s": round(baseline, 2),
        "reattach": {
            "reattach_s": reattach_s,
            "survivors": reattach_ev.get("survivors"),
            "dead": reattach_ev.get("dead"),
            "replayed_records": reattach_ev.get("replayed"),
            "reconciled": reattach_ev.get("reconciled"),
            "worker_pids_before": child_pids,
            "worker_pids_after": pids_after,
            "submits_per_worker": submits_after,
            "zero_recompile": zero_recompile,
        },
        "controller_down": {
            "windows": [round(g, 2) for g in down_w],
            "goodput_floor_msgs_s": round(min(down_w), 2),
            "served_without_controller": min(down_w) > 0,
        },
        "post_reattach_windows": [round(g, 2) for g in post_w],
        "rolling_restart": {
            "workers": roll.get("rows"),
            "total_s": roll_s,
            "window_s": 10.0,
            "windows": [round(g, 2) for g in roll_w],
            "goodput_floor_msgs_s": round(floor, 2),
            "floor_ratio": floor_ratio,
            "floor_met": floor_ratio >= 0.5,
            "worker_pids_after_roll": rolled_pids,
        },
        "journal": jstats,
        "flight": {"controller": ctrl_flight[-40:]},
        "timeline": timeline,
        "drained": drained,
        "produced": fed[0],
        "exactly_once": {
            "where": ("in-process transactional path (soak harness "
                      "--drain-drill): two deactivate -> flush -> activate "
                      "cycles mid-soak, offsets+outputs committed in one "
                      "broker txn per tree; the dist mesh above is "
                      "at-least-once by design, reference parity"),
            "exactly_once": soak_art["exactly_once"],
            "audit": soak_art["audit"],
            "events": soak_art["events"],
        },
        "workers": 3,
        **HOST_ONLY,
        "config": "controller-failover",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def run_cascade_compare(args) -> dict:
    """``--cascade-compare``: flagship-only (resnet20) vs the
    confidence-gated cascade (vit_tiny -> lenet5_rgb -> resnet20) on the
    committed digits checkpoints, through the full topology. The chain
    is ordered by MEASURED per-record cost on this host (see
    accuracy_harness.CASCADE_TIERS): on the CPU CI host conv models are
    the slow path (ms per 32-batch: vit_tiny 3.4, lenet5 17.7, resnet20
    85.0), so resnet20 — also the most accurate tier on digits — is the
    expensive flagship the cascade must beat.

    Protocol (wire-compare honesty rules): repeats are INTERLEAVED at
    cell level (flagship, cascade, flagship, ...) so drift hits both arms
    equally; the backlog is pre-produced and timing runs from the
    ``warm``-th output to the last, so producer pacing, topology startup,
    and first-batch compile are outside the ack-gated window; median-of-N
    with raw samples in the artifact. Payloads are REAL digits test
    images (cycled): synthetic noise is uniformly uncertain, escalates
    everything, and would measure a cascade that never gates — the
    accept/escalate split IS the effect under test. The operating point
    (metric, thresholds, temperature) is read from
    ACCURACY_CASCADE_r09.json so the throughput claim and the accuracy
    claim share one config, and a final sampled run captures the
    escalation evidence (metrics counter + flight event + per-tier trace
    spans) required to call the cascade observable."""
    import jax

    from storm_tpu.cascade.policy import CascadeConfig
    from storm_tpu.config import Config
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.data import load_digits_nhwc
    from storm_tpu.main import build_standard_topology
    from storm_tpu.runtime import LocalCluster

    n_dev = len(jax.devices())
    repeats = max(1, args.repeats)
    ckpt_root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "checkpoints")
    ckpts = {name: os.path.join(ckpt_root, f"{tag}_digits")
             for name, tag in (("lenet5", "lenet5_rgb"),
                               ("resnet20", "resnet20"),
                               ("vit_tiny", "vit_tiny"))}
    missing = [p for p in ckpts.values() if not os.path.exists(p)]
    if missing:
        raise SystemExit(f"cascade-compare needs the tier checkpoints "
                         f"({missing}); run accuracy_harness.py --cascade "
                         f"first")

    # One operating point for both artifacts: thresholds tuned by the
    # accuracy harness, not re-picked here.
    acc_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "ACCURACY_CASCADE_r09.json")
    if os.path.exists(acc_path):
        with open(acc_path) as f:
            acc = json.load(f)
        point = {"metric": acc["metric"],
                 "thresholds": tuple(acc["thresholds"]),
                 "temperature": acc["temperature"],
                 "source": "ACCURACY_CASCADE_r09.json"}
    else:
        point = {"metric": "max_softmax", "thresholds": (0.2, 0.2),
                 "temperature": 1.0, "source": "defaults (accuracy "
                 "artifact absent)"}

    instances = args.instances_per_msg if args.instances_per_msg > 1 else 8
    n_msgs = min(args.messages, 384)
    warm = max(64, n_msgs // 4)

    # Cover the ENTIRE test set per payload cycle: the uncertain images
    # that escalate are a handful of specific records, and a partial
    # cycle could exclude all of them — measuring a cascade that never
    # gates by accident of coverage.
    _, _, x_te, _ = load_digits_nhwc((32, 32, 3), seed=0)
    n_distinct = max(1, len(x_te) // instances)
    payloads = [
        json.dumps({"instances":
                    x_te[i * instances:(i + 1) * instances]
                    .round(4).tolist()}).encode("utf-8")
        for i in range(n_distinct)
    ]

    # Cheapest-first by measured cost; the last tier is the flagship both
    # arms must agree on, so flagship-only is "cascade with no early
    # exits" and the A/B isolates the gating itself.
    chain = ("vit_tiny", "lenet5", "resnet20")

    def mk_cfg(cascade: bool, sample_rate: float = 0.0) -> Config:
        cfg = Config()
        cfg.model.name = chain[-1]
        cfg.model.checkpoint = ckpts[chain[-1]]
        cfg.model.input_shape = (32, 32, 3)
        cfg.model.num_classes = 10
        cfg.batch.max_batch = args.max_batch or 32
        cfg.batch.max_wait_ms = 5.0
        cfg.batch.buckets = (8, 32)
        cfg.batch.max_inflight = args.inflight or 4
        cfg.topology.spout_parallelism = 1
        cfg.topology.inference_parallelism = 1
        cfg.topology.sink_parallelism = 1
        cfg.topology.message_timeout_s = 300.0
        cfg.topology.max_spout_pending = 256
        cfg.offsets.policy = "earliest"
        cfg.offsets.max_behind = None
        cfg.tracing.sample_rate = sample_rate
        if cascade:
            cfg.cascade = CascadeConfig(
                enabled=True,
                tiers=chain,
                checkpoints=tuple(ckpts[n] for n in chain),
                thresholds=point["thresholds"],
                metric=point["metric"],
                temperature=point["temperature"])
        return cfg

    def run_once(cluster, name, cfg, total) -> float:
        """One submit/measure/kill cycle against a fresh in-process
        broker. Returns timed msgs/s (outputs are sink-acked, so the
        window is ack-gated by construction)."""
        broker = MemoryBroker(default_partitions=1)
        for i in range(total):
            broker.produce(cfg.broker.input_topic,
                           payloads[i % len(payloads)], partition=0)
        topo = build_standard_topology(cfg, broker)
        cluster.submit_topology(name, cfg, topo)
        elapsed, done = timed_drain_window(
            lambda: broker.topic_size(cfg.broker.output_topic), warm, total)
        dead = broker.topic_size(cfg.broker.dead_letter_topic)
        cluster.kill_topology(name, wait_secs=2)
        if elapsed != elapsed or done < total:
            raise RuntimeError(f"{name}: only {done}/{total} outputs "
                               f"({dead} dead-lettered) before deadline")
        return (total - warm) / elapsed

    total = warm + n_msgs
    cluster = LocalCluster()
    try:
        def cell(arm, rep):
            rate = run_once(cluster, f"cc-{arm}-{rep}",
                            mk_cfg(arm == "cascade"), total)
            log(f"  {arm} rep{rep}: {rate:.1f} msg/s "
                f"({rate * instances:.0f} img/s)")
            return rate

        samples = run_interleaved(("flagship", "cascade"), repeats, cell)

        # ---- observability evidence (sampled run) ------------------------
        # One cascade run at sample_rate=1.0, small enough to read back:
        # the acceptance criterion wants the SAME escalation visible as a
        # metrics counter, a flight event, and a per-tier trace span.
        name = "cc-sampled"
        run_once(cluster, name + "-warm", mk_cfg(True), warm + 32)
        obs_cfg = mk_cfg(True, sample_rate=1.0)
        obs_msgs = 2 * len(payloads)  # two full test-set cycles
        broker = MemoryBroker(default_partitions=1)
        for i in range(obs_msgs):
            broker.produce(obs_cfg.broker.input_topic,
                           payloads[i % len(payloads)], partition=0)
        topo = build_standard_topology(obs_cfg, broker)
        cluster.submit_topology(name, obs_cfg, topo)
        deadline = time.time() + 120
        while (broker.topic_size(obs_cfg.broker.output_topic) < obs_msgs
               and time.time() < deadline):
            time.sleep(0.01)
        snap = cluster.metrics(name)
        counters = {}
        for comp, metrics_ in snap.items():
            for k, v in metrics_.items():
                if k.startswith("cascade_") and isinstance(v, (int, float)):
                    counters[k] = counters.get(k, 0) + v
            if comp == "cascade" and "escalation_rate" in metrics_:
                counters["escalation_rate"] = round(
                    float(metrics_["escalation_rate"]), 4)

        async def harvest():
            rt = cluster._cluster.runtime(name)
            flights = [e for e in rt.flight.tail(500)
                       if e.get("kind") == "cascade_escalation"]
            spans = [s for tr in rt.tracer.store.recent(200)
                     for s in tr.get("spans", [])
                     if str(s.get("name", "")).startswith("cascade_tier")]
            return flights, spans

        flights, tier_spans = cluster._run(harvest())
        cluster.kill_topology(name, wait_secs=2)
        span_counts = {}
        for s in tier_spans:
            span_counts[s["name"]] = span_counts.get(s["name"], 0) + 1
        observability = {
            "escalations_counter": counters.get("cascade_escalations", 0),
            "router_counters": counters,
            "flight_cascade_escalation_events": len(flights),
            "sample_flight_event": flights[0] if flights else None,
            "cascade_tier_spans": span_counts,
            "sample_tier_span": tier_spans[0] if tier_spans else None,
            "all_three_surfaces": bool(
                counters.get("cascade_escalations", 0) > 0
                and flights and tier_spans),
        }
    finally:
        cluster.shutdown()

    row = {"instances_per_msg": instances,
           "payload_bytes": len(payloads[0]),
           "messages_timed": n_msgs, "warmup_messages": warm}
    for arm in ("flagship", "cascade"):
        st = arm_stats(samples[arm])
        st["images_per_sec"] = round(st["msgs_per_sec"] * instances, 1)
        row[arm] = st
    speedup = round(row["cascade"]["msgs_per_sec"]
                    / row["flagship"]["msgs_per_sec"], 3)
    row["speedup_cascade_vs_flagship"] = speedup
    return {
        "metric": "cascade_compare_digits",
        "unit": ("messages/s end-to-end (records/s = msgs/s * "
                 "instances_per_msg); timed from the warm-th sink-acked "
                 "output to the last against a pre-produced backlog"),
        "value": speedup,
        "rows": [row],
        "tiers": ["vit_tiny", "lenet5 (lenet5_rgb_digits)", "resnet20"],
        "flagship": "resnet20",
        "tier_order_note": "cheapest-first by MEASURED cost on this host "
                           "(CPU: convs slow, small transformer matmuls "
                           "fast); on TPU the measured order differs and "
                           "the chain should be re-ordered accordingly",
        "operating_point": point,
        "observability": observability,
        "payload_source": "real sklearn-digits test images (cycled); "
                          "synthetic noise would escalate everything",
        "repeats": repeats,
        "protocol": "interleaved A/B per cell; median-of-N; ack-gated "
                    "warm->last window; shared operating point with the "
                    "accuracy artifact",
        **device_info(),
        "config": "cascade-compare",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def run_slo_sweep(args) -> dict:
    """``--slo-sweep``: the JOINT north star measured jointly (VERDICT r3
    missing #2). The target is throughput AND latency at once — ">=10k
    img/s on v5e-8 at p50 < 50 ms" — but every prior artifact measured one
    axis at a fixed operating point of the other. This sweeps the offered
    rate across the topology's operating range and reports, from the same
    measured curve:

    - latency vs offered rate (the reference's own thesis curve,
      README.md:13-14: "produce faster -> latency rises");
    - the SLO-constrained operating points: max measured rate whose e2e
      p50 (append->deliver) stays under 50 / 100 / 200 ms;
    - per-stage p50 attribution at every point, so the device's share
      (device + dispatch queue) is separable from the framework's share
      per point;
    - the same sweep with a NullEngine (device time = 0): the framework's
      own latency-vs-rate curve, i.e. what the identical pipeline would
      serve with an infinitely fast device.
    """
    import jax

    from storm_tpu.config import BatchConfig
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.infer import NullEngine
    from storm_tpu.runtime.cluster import LocalCluster

    cfg = CONFIGS[args.config]
    if "model" not in cfg:
        sys.exit("--slo-sweep needs a single-model config")
    n_dev = len(jax.devices())
    log(f"devices: {jax.devices()}")
    buckets = cfg["buckets"]
    ipm = args.instances_per_msg

    def sweep(framework_only: bool, topo_name: str,
              tuning: str = "throughput") -> list:
        cluster = LocalCluster()
        try:
            broker = MemoryBroker(default_partitions=4)
            if tuning == "latency":
                # The operating point a latency SLO actually deploys
                # (VERDICT r4 weak #4): tiny dispatch deadline, small
                # batch cap (short device bursts), shallow inflight. The
                # throughput-tuned sweep alone declared the 100/200 ms
                # cells unreachable while holding 75-107 ms of
                # knob-controlled batch_wait.
                batch_cfg = BatchConfig(
                    max_batch=min(64, cfg["max_batch"]),
                    max_wait_ms=3.0,
                    buckets=tuple(b for b in (8, 64) if b <= cfg["max_batch"]),
                    max_inflight=2,
                )
            else:
                batch_cfg = BatchConfig(
                    max_batch=args.max_batch or cfg["max_batch"],
                    max_wait_ms=args.max_wait_ms,
                    buckets=buckets,
                    max_inflight=args.inflight or 2,
                    eager=args.eager,
                )
            engine = (NullEngine(cfg["input_shape"], cfg["num_classes"])
                      if framework_only else None)
            run_cfg, topo = build_topology(
                cfg, broker, batch_cfg,
                None if framework_only else args.transfer_dtype, args.chunk,
                "float" if framework_only else args.weights, engine=engine)
            t0 = time.time()
            cluster.submit_topology(topo_name, run_cfg, topo)
            log(f"  submitted + warmed up in {time.time() - t0:.1f}s")
            payloads = make_payloads(cfg, instances_per_msg=ipm)

            def produce_nth(i):
                broker.produce("input", payloads[i % len(payloads)])

            def out_size():
                return broker.topic_size("output")

            def read_lat():
                lat = cluster.metrics(topo_name)["kafka-bolt"]["e2e_latency_ms"]
                return (lat["p50"] if lat["p50"] is not None else float("nan"),
                        lat["p99"] if lat["p99"] is not None else float("nan"))

            # calibrate capacity with a drain burst (the latency-protocol
            # calibration, shared rationale with run_latency_phase)
            probe = 96
            base = out_size()
            t0 = time.perf_counter()
            for i in range(probe):
                produce_nth(i)
            if not await_outputs(lambda: out_size() - base, probe,
                                 grace_s=180.0):
                log("  calibration probe incomplete; sweep aborted")
                return []
            cap = max(out_size() - base, 1) / (time.perf_counter() - t0)
            log(f"  calibrated capacity ~{cap:.0f} msg/s")

            points = []
            for frac in (0.15, 0.3, 0.45, 0.6, 0.75, 0.9, 1.0, 1.15):
                rate = max(2.0, cap * frac)
                base = out_size()
                reset_stage_hists(cluster, topo_name)
                sent, aborted = offer_load(
                    produce_nth, rate, args.sweep_seconds,
                    backlog_fn=lambda s: s - (out_size() - base))
                drained = await_outputs(lambda: out_size() - base, sent,
                                        grace_s=90.0)
                p50, p99 = read_lat()
                point = {
                    "offered_msg_s": round(rate, 1),
                    "offered_img_s": round(rate * ipm, 1),
                    "fraction_of_capacity": frac,
                    "p50_ms": round(p50, 1) if p50 == p50 else None,
                    "p99_ms": round(p99, 1) if p99 == p99 else None,
                    "valid": bool(not aborted and drained),
                    "stages_p50_ms": read_stage_p50s(cluster, topo_name),
                }
                points.append(point)
                log(f"  rate {rate:7.1f} msg/s ({frac:.2f}x cap): "
                    f"p50={point['p50_ms']} p99={point['p99_ms']} "
                    f"{'ok' if point['valid'] else 'SATURATED'}")
                if aborted:
                    # past the knee: higher rates only measure queueing
                    if not await_outputs(lambda: out_size() - base, sent,
                                         grace_s=120.0):
                        log("  backlog never cleared; stopping sweep")
                        break
            return points
        finally:
            cluster.shutdown()

    log("== device-path sweep (throughput-tuned) ==")
    device_curve = sweep(False, "slo-dev")
    log("== device-path sweep (latency-tuned) ==")
    device_lat_curve = sweep(False, "slo-dev-lat", tuning="latency")
    log("== framework-only sweep (NullEngine) ==")
    fw_curve = sweep(True, "slo-fw")

    for p in device_curve:
        p["tuning"] = "throughput"
    for p in device_lat_curve:
        p["tuning"] = "latency"

    def slo_points(curve):
        out = {}
        for slo in (50.0, 100.0, 200.0):
            ok = [p for p in curve
                  if p["valid"] and p["p50_ms"] is not None
                  and p["p50_ms"] <= slo]
            out[f"p50_le_{int(slo)}ms"] = (
                max(ok, key=lambda p: p["offered_img_s"]) if ok else None)
        return out

    # SLO cells are judged over BOTH device operating points: a cell is
    # null only after the latency-tuned configuration also failed it.
    dev_pts = slo_points(device_curve + device_lat_curve)
    fw_pts = slo_points(fw_curve)
    # The device stage's floor: the smallest device-stage p50 any device
    # point achieved (launch + compute + fetch at the lightest load).
    dev_stage_p50s = [
        p["stages_p50_ms"]["device"]
        for p in device_curve + device_lat_curve
        if p.get("stages_p50_ms") and "device" in p["stages_p50_ms"]]
    device_stage_floor = (round(min(dev_stage_p50s), 1)
                          if dev_stage_p50s else None)
    best50 = dev_pts["p50_le_50ms"]
    headline = (round(best50["offered_img_s"] / n_dev, 1)
                if best50 else None)
    out = {
        "metric": f"{cfg['metric']}_img_s_per_chip_at_p50_le_50ms",
        "value": headline,
        "unit": "images/sec/chip under measured e2e p50 <= 50 ms",
        "vs_baseline": (round(headline / BASELINE_IMGS_PER_SEC_PER_CHIP, 3)
                        if headline else None),
        **device_info(),
        "config": f"{args.config}+slo-sweep",
        "instances_per_msg": ipm,
        "device_curve": device_curve,
        "device_latency_tuned_curve": device_lat_curve,
        "device_slo_points": dev_pts,
        "framework_curve": fw_curve,
        "framework_slo_points": fw_pts,
        "device_stage_p50_floor_ms": device_stage_floor,
        "note": ("device_slo_points are judged over BOTH device operating "
                 "points (throughput- and latency-tuned; each point "
                 "carries 'tuning') — a null cell means the latency-tuned "
                 "attempt also failed it. device_stage_p50_floor_ms is "
                 "the smallest device-stage p50 any point achieved; the "
                 "framework_curve bounds what the identical pipeline "
                 "serves with device time zero"),
    }
    if best50 is None and device_curve:
        # per the done-criterion: show exactly WHERE the 50 ms budget goes
        # when it is unreachable, per stage, at the lightest load point
        lightest = device_curve[0]["stages_p50_ms"]
        if lightest:
            blame = max(lightest, key=lambda k: lightest[k])
            out["p50_le_50ms_unreachable_because"] = (
                f"stage '{blame}' alone is {lightest[blame]:.0f} ms at the "
                f"lightest offered rate (full stage p50s in "
                "device_curve[0]); the framework_slo_points show the "
                "identical pipeline meets the SLO when device time is "
                "excluded")
        else:
            # stalled lightest point: no stage histograms to attribute —
            # emit the sweep with a degraded note instead of crashing
            out["p50_le_50ms_unreachable_because"] = (
                "the lightest offered rate recorded no per-stage samples "
                "(stalled/undelivered windows); see device_curve rows")
    return out


def make_paced_bolt(service_ms: float):
    """Stand-in for a per-replica latency-bound inference endpoint (a
    remote accelerator worker / serving RPC with its own connection):
    each replica serves exactly one request at a time at a fixed service
    latency, so capacity per replica is 1000/service_ms msg/s and ADDING
    replicas adds real capacity — the regime where the reference's
    more-bolts thesis (README.md:13-14) genuinely buys throughput, and
    the complement to the single-shared-chip autoscale artifact where
    replicas only buy pipelining (BENCH_AUTOSCALE r04 note)."""
    import asyncio

    from storm_tpu.runtime import Bolt, Values

    class PacedBolt(Bolt):
        def __init__(self) -> None:
            self.service_ms = service_ms

        async def execute(self, t):
            await asyncio.sleep(self.service_ms / 1000.0)
            await self.collector.emit(Values([t.get("message")]), anchors=[t])
            self.collector.ack(t)

    return PacedBolt()


def make_engine_bolt():
    """``--capacity-backend=engine``: the real-engine variant of the
    capacity demo's backend (VERDICT r5 next #4). A lenet5 InferenceBolt
    whose replicas each own a PRIVATE engine — ``clone()`` deliberately
    does not pass the engine through and ``prepare()`` builds a fresh
    one, bypassing the ``shared_engine`` process cache — so on a
    multi-core host scale-out would own real additional compute the way
    PacedBolt replicas own serving slots. On THIS host (1 CPU core) the
    replicas time-slice one core and the artifact must say so rather
    than claim a gain; see the single-core statement emitted by
    ``run_autoscale_capacity`` when the measured gain is ~1."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.infer.engine import InferenceEngine

    model_cfg = ModelConfig(name="lenet5", dtype="bfloat16",
                            input_shape=(28, 28, 1), num_classes=10)
    batch_cfg = BatchConfig(max_batch=64, max_wait_ms=5.0, buckets=(1, 8, 64))
    sharding_cfg = ShardingConfig(data_parallel=0)

    class PrivateEngineBolt(InferenceBolt):
        def clone(self) -> "PrivateEngineBolt":
            return PrivateEngineBolt(self.model_cfg, self.batch_cfg,
                                     self.sharding_cfg, None, self._warmup,
                                     self.passthrough, self.qos)

        def prepare(self, context, collector) -> None:
            # Per-replica engine: the whole point of this backend.
            self._engine = InferenceEngine(self.model_cfg, self.sharding_cfg,
                                           self.batch_cfg)
            super().prepare(context, collector)

    return PrivateEngineBolt(model_cfg, batch_cfg, sharding_cfg)


def run_autoscale_capacity(args) -> dict:
    """``--autoscale-capacity``: the CAPACITY half of the scaling thesis
    (VERDICT r4 weak #1 / next #4). The single-chip autoscale artifact
    cannot, by construction, hold above 1.0x the parallelism-1 capacity —
    its replicas share one saturated chip (and this bench host has ONE
    CPU core, so compute-bound replicas can't add capacity either; the
    dist runtime also places components whole, one worker per component).
    This demo runs the same closed loop — ramp offered rate, latency
    breaches the SLO, the real Autoscaler rebalances live — over a bolt
    whose backend is a per-replica latency-bound endpoint (PacedBolt),
    where scale-out owns real additional serving capacity. The hold rate
    is NOT capped at 1.0x cap1; done = hold_rate_vs_cap1 > 1 within SLO.

    Deliberately a separate loop from _run_autoscale_inner, not a
    parameterization of it: that loop's probe sizes, window widths, and
    re-basing rules are the protocol BENCH_AUTOSCALE_r04 was captured
    under (frozen with its artifact); this one drops the accelerator-
    specific re-basing (no shared-chip ceiling) and keeps only the
    closed-loop skeleton."""
    from storm_tpu.config import Config, OffsetsConfig
    from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.autoscale import AutoscalePolicy, Autoscaler
    from storm_tpu.runtime.cluster import LocalCluster

    backend = getattr(args, "capacity_backend", "paced")
    service_ms = 12.0 if backend == "paced" else None
    serve_id = "paced-bolt" if backend == "paced" else "engine-bolt"
    slo_ms = min(args.slo_ms, 250.0)
    broker = MemoryBroker(default_partitions=4)
    run_cfg = Config()
    run_cfg.topology.message_timeout_s = 300.0
    tb = TopologyBuilder()
    tb.set_spout("kafka-spout",
                 BrokerSpout(broker, "input",
                             OffsetsConfig(policy="earliest", max_behind=None),
                             fetch_size=1024,
                             scheme="raw" if backend == "engine" else "string"),
                 parallelism=1)
    serve_bolt = make_paced_bolt(service_ms) if backend == "paced" \
        else make_engine_bolt()
    tb.set_bolt(serve_id, serve_bolt, parallelism=1)\
        .shuffle_grouping("kafka-spout")
    tb.set_bolt("kafka-bolt", BrokerSink(broker, "output", run_cfg.sink),
                parallelism=1).shuffle_grouping(serve_id)
    if backend == "engine":
        payload = make_payloads(CONFIGS["lenet5"], n_distinct=8)[0]
    else:
        payload = json.dumps({"instances": [[0.5]]})

    cluster = LocalCluster()
    try:
        cluster.submit_topology("cap-demo", run_cfg, tb.build())

        async def mk():
            rt = cluster._cluster.runtime("cap-demo")
            return Autoscaler(rt, AutoscalePolicy(
                component=serve_id, latency_source="kafka-bolt",
                # low_ms=1: downscale disabled for the demo — the claim
                # under test is that UP-scaling adds capacity; a scale-
                # down during the quiet post-scale hold would just
                # re-measure the ramp (first capture oscillated exactly
                # that way: up -> hold went quiet -> down -> breach).
                high_ms=slo_ms, low_ms=1.0,
                min_parallelism=1, max_parallelism=6,
                interval_s=2.0, cooldown=6,
            )).start()

        sent = 0

        def probe_capacity() -> float:
            nonlocal sent
            base = broker.topic_size("output")
            t0 = time.perf_counter()
            for _ in range(64):
                broker.produce("input", payload)
            sent += 64
            if not await_outputs(lambda: broker.topic_size("output") - base,
                                 64, grace_s=120.0):
                sys.exit("capacity probe never drained")
            return 64 / (time.perf_counter() - t0)

        def parallelism_now() -> int:
            async def f():
                return cluster._cluster.runtime("cap-demo")\
                    .parallelism_of(serve_id)

            return cluster._run(f())

        cap1 = probe_capacity()
        theory = "" if service_ms is None else \
            f" (theoretical {1000 / service_ms:.0f})"
        log(f"parallelism-1 capacity ~{cap1:.0f} msg/s{theory}; "
            f"SLO p50 <= {slo_ms:.0f} ms")
        cluster.reset_histogram("cap-demo", "kafka-bolt", "e2e_latency_ms")
        # Start the scaler only now: the probe burst's queue latencies are
        # calibration, not load — the first capture's scaler read them and
        # fired before the ramp began.
        scaler = cluster._run(mk())

        timeline = []
        window_s = 2.0
        t_start = time.perf_counter()

        def offer_stage(mult, seconds, phase, stop_fn=None):
            nonlocal sent
            rate = cap1 * mult
            interval = 1.0 / rate
            stage_end = time.perf_counter() + seconds
            nxt = time.perf_counter()
            next_window = nxt + window_s
            while time.perf_counter() < stage_end:
                now = time.perf_counter()
                while nxt <= now:
                    broker.produce("input", payload)
                    sent += 1
                    nxt += interval
                if now >= next_window:
                    next_window = now + window_s
                    lat = cluster.metrics(
                        "cap-demo")["kafka-bolt"]["e2e_latency_ms"]
                    p50 = lat["p50"]
                    par = parallelism_now()
                    cluster.reset_histogram(
                        "cap-demo", "kafka-bolt", "e2e_latency_ms")
                    timeline.append((round(now - t_start, 1), round(rate),
                                     None if p50 is None else round(p50, 1),
                                     par, phase))
                    log(f"  t={now - t_start:5.1f}s rate={rate:4.0f} "
                        f"p50={'stalled' if p50 is None else f'{p50:.1f}ms'}"
                        f" parallelism={par}")
                    if stop_fn is not None and stop_fn():
                        log("  scale-up decision landed; ending stage early")
                        return
                time.sleep(min(0.002, max(0.0, nxt - time.perf_counter())))

        def ups_so_far():
            return [d for d in scaler.decisions if d[0] == "up"]

        # ramp until the scaler fires
        mult, breach_mult = 0.8, None
        for _ in range(10):
            n_ups = len(ups_so_far())
            offer_stage(mult, args.stage_seconds, "ramp",
                        stop_fn=lambda: len(ups_so_far()) > n_ups)
            if len(ups_so_far()) > n_ups:
                breach_mult = mult
                break
            mult *= 1.3
        if breach_mult is None:
            sys.exit("autoscaler never fired within the ramp range")
        log("draining reaction backlog...")
        await_outputs(lambda: broker.topic_size("output"), sent,
                      grace_s=120.0)
        cap_scaled = probe_capacity()
        par = parallelism_now()
        log(f"scaled capacity ~{cap_scaled:.0f} msg/s (parallelism {par})")
        cluster.reset_histogram("cap-demo", "kafka-bolt", "e2e_latency_ms")
        # The capacity demo's whole point: NO 1.0x cap1 ceiling. Hold
        # clearly above parallelism-1 capacity (>= 1.2x), bounded only by
        # 80% of the scaled capacity.
        hold_mult = min(max(breach_mult, 1.2), 0.8 * cap_scaled / cap1)
        offer_stage(hold_mult, args.stage_seconds * 1.5, "hold")
        await_outputs(lambda: broker.topic_size("output"), sent,
                      grace_s=60.0)
        decisions = list(scaler.decisions)
        cluster._run(scaler.stop())
    finally:
        cluster.shutdown()

    hold = [w for w in timeline if w[4] == "hold"]
    met = [w for w in hold if w[2] is not None and w[2] <= slo_ms]
    stalled = sum(1 for w in hold if w[2] is None)
    pct = 100.0 * len(met) / len(hold) if hold else 0.0
    if backend == "engine":
        note = ("per-replica REAL lenet5 engines (private InferenceEngine "
                "per clone, shared_engine cache bypassed): on a multi-core "
                "host each replica would own real compute; capacity_gain "
                "reports what this host actually delivered")
        gain = cap_scaled / cap1
        if gain <= 1.05:
            note += (f". SINGLE-CORE STATEMENT: measured gain is "
                     f"{gain:.2f}x (<= 1) because this host has ONE CPU "
                     "core — compute-bound replicas time-slice the same "
                     "core, so scale-out cannot add capacity here by "
                     "construction, and splitting traffic across private "
                     "replicas can even LOSE capacity to smaller "
                     "per-engine batches; the paced backend in the "
                     "companion artifact is the regime where the "
                     "more-replicas thesis holds, and this engine run "
                     "documents (rather than hides) the host limit")
    else:
        note = ("per-replica latency-bound backend (each replica = its "
                "own serving endpoint): scale-out owns real capacity, so "
                "the 1.0x cap1 ceiling of the shared-chip artifact does "
                "not apply; that artifact remains the latency-headroom "
                "story for replicas sharing one chip (one chip per host: "
                "no second silicon to add)")
    return {
        "metric": "autoscale_capacity_hold_rate_vs_cap1",
        "value": round(hold_mult, 2),
        "unit": "sustained hold rate as a multiple of parallelism-1 "
                "capacity (SLO outcome in hold_windows_met / "
                "hold_slo_met)",
        # the within-SLO claim is CHECKED, not implied: every hold window
        # delivered and met the SLO, or this is false (stalled = breach)
        "hold_slo_met": bool(hold and pct == 100.0 and stalled == 0),
        "hold_windows_met_pct": round(pct, 1),
        "hold_stalled_windows": stalled,
        "slo_ms": slo_ms,
        "backend": backend,
        "service_ms_per_replica": service_ms,
        "cap1_msg_s": round(cap1, 1),
        "cap_scaled_msg_s": round(cap_scaled, 1),
        "capacity_gain": round(cap_scaled / cap1, 2),
        "final_parallelism": par,
        "hold_windows_met": f"{len(met)}/{len(hold)}",
        "worst_hold_p50_ms": max(
            (w[2] for w in hold if w[2] is not None), default=None),
        "scaled": [d[1:] for d in decisions if d[0] == "up"],
        "timeline": timeline,
        "config": f"{backend}+autoscale-capacity",
        "note": note,
    }


def run_qos_overload(args) -> dict:
    """``--qos-overload``: admission control & QoS under sustained 2x
    overload. Two phases over the same real-engine lenet5 topology and
    the same offered load — a no-QoS baseline, then QoS enabled
    (per-tenant admission at the spout edge, EDF priority lanes in the
    batcher, adaptive load shedding) — captured into ONE artifact so
    the goodput comparison can never quote numbers from different
    sessions. Offered load is two tenants on broker record keys:
    ``gold:high`` at 0.4x sustained capacity and ``free:best_effort``
    at 1.6x (2.0x total). Done criteria measured here: admitted
    high-lane p99 <= slo_ms while best_effort is shed; within-SLO
    goodput >= the baseline phase; shed decisions visible in /metrics
    counters, the flight-recorder tail, and >= 1 sampled trace.

    Protocol notes (honesty): both phases run an IDENTICAL unmeasured
    reaction window at 2x load (the QoS phase needs a few shed-
    controller intervals for hysteresis to engage; the baseline gets
    the same warmup so neither phase counts its cold start) followed by
    the same settle gap, then histograms are reset and the measured
    hold begins. ``shed_calm_steps`` is set longer than the hold so the
    level doesn't restore-oscillate mid-measurement — downward
    hysteresis is unit-tested (tests/test_qos.py), not re-measured
    here. Baseline "goodput" counts only within-SLO deliveries
    (delivered minus slo_breaches over the hold), which is the quantity
    QoS is allowed to win on while delivering FEWER records."""
    from storm_tpu.config import (BatchConfig, Config, ModelConfig,
                                  OffsetsConfig, QosConfig, ShardingConfig)
    from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.qos import LoadShedController, ShedPolicy
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import LocalCluster

    cfg = CONFIGS["lenet5"]
    slo_ms = min(args.slo_ms, 250.0)
    hold_s = float(args.stage_seconds)
    reaction_s, settle_s = 6.0, 4.0
    payloads = make_payloads(cfg, n_distinct=32)
    batch_cfg = BatchConfig(max_batch=256, max_wait_ms=10.0,
                            buckets=(64, 256))
    qos_cfg = QosConfig(
        enabled=True,
        # No edge quota here: adaptive shedding is the mechanism under
        # test. Token-bucket throttling has its own unit tests and is an
        # operator knob (docs/OPERATIONS.md), not part of this capture.
        tenant_rate=0.0,
        shed_interval_s=0.5,
        shed_hot_steps=2,
        shed_breach_rate=2.0,
        shed_inbox_frac=0.5,
        # Sticky for the hold (see docstring): 1000 calm steps ~ 500 s.
        shed_calm_steps=1000,
    )

    def build(qos):
        broker = MemoryBroker(default_partitions=4)
        run_cfg = Config()
        run_cfg.topology.message_timeout_s = 300.0
        # slo_ms arms the sink's slo_breaches counter in BOTH phases —
        # it is both the shed controller's breach signal and the
        # goodput definition, so baseline and QoS share one SLO meter.
        run_cfg.tracing.slo_ms = slo_ms
        if qos is not None:
            run_cfg.qos = qos
            # Sampled-trace evidence: big enough store that reaction-
            # window shed traces survive the hold's admitted traffic.
            run_cfg.tracing.sample_rate = 0.2
            run_cfg.tracing.store_capacity = 2048
        model_cfg = ModelConfig(name=cfg["model"], dtype="bfloat16",
                                input_shape=cfg["input_shape"],
                                num_classes=cfg["num_classes"])
        tb = TopologyBuilder()
        tb.set_spout("kafka-spout",
                     BrokerSpout(broker, "input",
                                 OffsetsConfig(policy="earliest",
                                               max_behind=None),
                                 fetch_size=1024, scheme="raw", qos=qos),
                     parallelism=2)
        tb.set_bolt("inference-bolt",
                    InferenceBolt(model_cfg, batch_cfg,
                                  ShardingConfig(data_parallel=0), qos=qos,
                                  passthrough=("qos_lane",) if qos else ()),
                    parallelism=1).shuffle_grouping("kafka-spout")
        tb.set_bolt("kafka-bolt", BrokerSink(broker, "output", run_cfg.sink),
                    parallelism=1).shuffle_grouping("inference-bolt")
        tb.set_bolt("dlq-bolt",
                    BrokerSink(broker, "dead-letter", run_cfg.sink),
                    parallelism=1).shuffle_grouping("inference-bolt",
                                                    stream="dead_letter")
        return broker, run_cfg, tb.build()

    cluster = LocalCluster()
    phases = {}
    cap1 = None
    shed_decisions = []
    flight_shed = []
    trace_shed = None
    try:
        for phase_name, qos in (("baseline", None), ("qos", qos_cfg)):
            broker, run_cfg, topo = build(qos)
            name = f"qos-{phase_name}"
            cluster.submit_topology(name, run_cfg, topo)

            def produce(key, i):
                broker.produce("input", payloads[i % len(payloads)], key=key)

            def snap():
                return cluster.metrics(name)

            def counter(component, metric, s=None):
                v = (s if s is not None else snap())\
                    .get(component, {}).get(metric, 0)
                return int(v or 0)

            shedder = None
            if qos is not None:
                async def mk():
                    rt = cluster._cluster.runtime(name)
                    return LoadShedController(
                        rt, ShedPolicy.from_qos(qos, "inference-bolt",
                                                "kafka-bolt")).start()
                shedder = cluster._run(mk())

            if cap1 is None:
                # Capacity probe on the baseline topology (the QoS phase
                # reuses the shared engine, so it starts equally warm).
                base = broker.topic_size("output")
                t0 = time.perf_counter()
                for i in range(256):
                    produce(b"gold:high", i)
                if not await_outputs(
                        lambda: broker.topic_size("output") - base, 256,
                        grace_s=180.0):
                    sys.exit("qos capacity probe never drained")
                cap1 = 256 / (time.perf_counter() - t0)
                log(f"sustained capacity ~{cap1:.0f} msg/s; overload = "
                    f"{2 * cap1:.0f} msg/s; SLO {slo_ms:.0f} ms")
            rate_hi, rate_be = 0.4 * cap1, 1.6 * cap1

            def offer_two(seconds, window_cb=None):
                iv_hi, iv_be = 1.0 / rate_hi, 1.0 / rate_be
                start = time.perf_counter()
                end = start + seconds
                nxt_hi = nxt_be = start
                next_window = start + 1.0
                n_hi = n_be = 0
                while True:
                    now = time.perf_counter()
                    if now >= end:
                        break
                    while nxt_hi <= now:
                        produce(b"gold:high", n_hi)
                        n_hi += 1
                        nxt_hi += iv_hi
                    while nxt_be <= now:
                        produce(b"free:best_effort", n_be)
                        n_be += 1
                        nxt_be += iv_be
                    if window_cb is not None and now >= next_window:
                        next_window = now + 1.0
                        window_cb(now)
                    time.sleep(min(0.002, max(
                        0.0, min(nxt_hi, nxt_be) - time.perf_counter())))
                return n_hi, n_be

            log(f"[{phase_name}] reaction window {reaction_s:.0f}s at 2x "
                "(unmeasured)...")
            offer_two(reaction_s)
            if qos is not None:
                # Harvest the sampled shed trace NOW: operator-side sheds
                # happen in the reaction window (tuples already in flight
                # when the level rises); waiting until after the hold
                # would let admitted traffic evict them from the store.
                async def harvest_trace():
                    rt = cluster._cluster.runtime(name)
                    for rec in (rt.tracer.store.recent(2048)
                                + rt.tracer.store.open_records(256)):
                        sheds = [sp for sp in rec.get("spans", ())
                                 if sp.get("name") == "qos_shed"]
                        if sheds:
                            return {"trace_id": rec["trace_id"],
                                    "qos_shed_span": sheds[0],
                                    "span_names": [sp.get("name")
                                                   for sp in rec["spans"]]}
                    return None
                trace_shed = cluster._run(harvest_trace())
            time.sleep(settle_s)  # identical settle in both phases
            for h in ("e2e_latency_ms", "e2e_latency_ms_high",
                      "e2e_latency_ms_best_effort"):
                cluster.reset_histogram(name, "kafka-bolt", h)

            s0 = snap()
            base_delivered = counter("kafka-bolt", "delivered", s0)
            base_breach = counter("kafka-bolt", "slo_breaches", s0)
            timeline = []

            t_hold = time.perf_counter()

            def window_cb(now):
                s = snap()
                timeline.append({
                    "t": round(now - t_hold, 1),
                    "shed_level": int(s.get("qos", {})
                                      .get("shed_level", 0) or 0),
                    "delivered": counter("kafka-bolt", "delivered", s)
                    - base_delivered,
                    "slo_breaches": counter("kafka-bolt", "slo_breaches", s)
                    - base_breach,
                })

            log(f"[{phase_name}] measured hold {hold_s:.0f}s at 2x...")
            n_hi, n_be = offer_two(hold_s, window_cb)
            hold_elapsed = time.perf_counter() - t_hold
            time.sleep(3.0)  # let admitted in-flight work land
            s1 = snap()
            delivered = counter("kafka-bolt", "delivered", s1) \
                - base_delivered
            breaches = counter("kafka-bolt", "slo_breaches", s1) \
                - base_breach
            goodput = max(0, delivered - breaches) / hold_elapsed

            def hist(nm):
                h = s1.get("kafka-bolt", {}).get(nm)
                if isinstance(h, dict) and h.get("count"):
                    return {k: h.get(k) for k in ("count", "p50", "p99")}
                return None

            phase_out = {
                "offered_msg_s": round(rate_hi + rate_be, 1),
                "sent_high": n_hi,
                "sent_best_effort": n_be,
                "delivered": delivered,
                "slo_breaches": breaches,
                "goodput_msg_s": round(goodput, 1),
                "e2e_latency_ms": hist("e2e_latency_ms"),
                "e2e_latency_ms_high": hist("e2e_latency_ms_high"),
                "e2e_latency_ms_best_effort":
                    hist("e2e_latency_ms_best_effort"),
                "timeline": timeline,
            }
            if qos is not None:
                phase_out["qos_counters"] = {
                    k: v for k, v in s1.get("qos", {}).items()
                    if not isinstance(v, dict)}
                phase_out["shed_rejected"] = counter(
                    "inference-bolt", "shed_rejected", s1)
                phase_out["shed_degraded"] = counter(
                    "inference-bolt", "shed_degraded", s1)
                shed_decisions = [
                    {"direction": d, "from": a, "to": b}
                    for d, a, b in shedder.decisions]

                async def harvest_flight():
                    rt = cluster._cluster.runtime(name)
                    return [e for e in rt.flight.tail(400)
                            if str(e.get("kind", "")).startswith("shed")]
                flight_shed = cluster._run(harvest_flight())
                cluster._run(shedder.stop())
            phases[phase_name] = phase_out
            log(f"[{phase_name}] delivered={delivered} breaches={breaches} "
                f"goodput={goodput:.0f} msg/s")
            cluster.kill_topology(name, wait_secs=2)
    finally:
        cluster.shutdown()

    hi = phases["qos"]["e2e_latency_ms_high"]
    hi_p99 = hi["p99"] if hi else None
    goodput_qos = phases["qos"]["goodput_msg_s"]
    goodput_base = phases["baseline"]["goodput_msg_s"]
    qc = phases["qos"].get("qos_counters", {})
    shed_count = sum(v for k, v in qc.items()
                     if k.startswith("shed_") and isinstance(v, (int, float)))
    return {
        "metric": "qos_overload_high_lane_p99_ms",
        "value": hi_p99,
        "unit": ("p99 e2e latency (ms) of admitted high-lane traffic at 2x "
                 "sustained-capacity offered load with QoS shedding active"),
        "slo_ms": slo_ms,
        "high_p99_within_slo": bool(hi_p99 is not None and hi_p99 <= slo_ms),
        "goodput_qos_msg_s": goodput_qos,
        "goodput_baseline_msg_s": goodput_base,
        "goodput_ge_baseline": bool(goodput_qos >= goodput_base),
        "offered_multiple": 2.0,
        "cap1_msg_s": round(cap1, 1),
        "rate_high_msg_s": round(0.4 * cap1, 1),
        "rate_best_effort_msg_s": round(1.6 * cap1, 1),
        "phases": phases,
        "shed_decisions": shed_decisions,
        "evidence": {
            "metrics": bool(shed_count
                            or phases["qos"].get("shed_rejected", 0)),
            "flight": bool(flight_shed),
            "trace": bool(trace_shed),
        },
        "flight_shed_tail": flight_shed[-5:],
        "sampled_shed_trace": trace_shed,
        "config": "lenet5+qos-overload",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
        "note": ("single-core CPU host: cap1 is this host's measured "
                 "sustained capacity, not an accelerator number; the claim "
                 "under test is RELATIVE (admitted-lane SLO + goodput vs "
                 "the no-QoS baseline at identical offered load), which "
                 "does not depend on the absolute rate"),
    }


def run_profile(args) -> dict:
    """``--profile``: capture the online cost profiler's per-(engine,
    bucket) stage curves into the versioned ``PROFILE_r<N>.json``
    artifact the regression sentinel (and, eventually, the ROADMAP-1
    planner) loads as its baseline.

    Protocol: two engines (lenet5 + resnet20) x three padding buckets
    each, driven through the real split-phase dispatch path (the same
    fetch-thread recording the serving path uses — NOT a synthetic
    timer). Per bucket, the first dispatch is cold (its XLA compile lands
    in the artifact's ``compiles`` table and inflates that one h2d
    sample — which is why the monotone check below reads p50, not mean),
    then ``--repeats``-scaled warm batches fill the curve. The snapshot
    is round-tripped through JSON and re-loaded as a sentinel baseline;
    ``round_trip_ok`` asserts the self-comparison reports zero
    regressions, i.e. the committed file is usable as a baseline as-is."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine
    from storm_tpu.obs.profile import ensure_installed

    store = ensure_installed()
    store.reset()
    buckets = (16, 64, 256)
    warm_batches = max(8, 4 * args.repeats)
    rng = np.random.default_rng(0)
    engine_keys = []
    for cname in ("lenet5", "resnet20"):
        cfg = CONFIGS[cname]
        eng = InferenceEngine(
            ModelConfig(name=cfg["model"], dtype="bfloat16",
                        input_shape=cfg["input_shape"],
                        num_classes=cfg["num_classes"]),
            ShardingConfig(data_parallel=0),
            BatchConfig(max_batch=max(buckets), buckets=buckets))
        engine_keys.append(eng.profile_key)
        for b in buckets:
            x = rng.standard_normal(
                (b, *cfg["input_shape"])).astype(np.float32)
            log(f"[profile] {cname} bucket {b}: 1 cold + "
                f"{warm_batches} warm batches...")
            eng.dispatch((x,)).future.result()  # cold: compile entry
            handles = [eng.dispatch((x,)) for _ in range(warm_batches)]
            for h in handles:
                h.future.result()

    snap = store.snapshot()
    # Round-trip: the artifact must reload as a sentinel baseline and
    # self-compare clean (JSON encode/decode included, so string bucket
    # keys and float rounding are part of what's verified).
    store.load_baseline(json.loads(json.dumps(snap)))
    round_trip_ok = store.regressions(factor=1.5, min_samples=1) == []

    monotone = {}
    compiles_ok = True
    for key in engine_keys:
        eng_snap = snap["engines"].get(key, {})
        p50s = [eng_snap.get("buckets", {}).get(str(b), {})
                .get("stages", {}).get("device_ms", {}).get("p50")
                for b in buckets]
        # Whole-batch device cost must not shrink as the bucket grows
        # (5% tolerance: tiny models on a shared CPU host are noisy).
        monotone[key] = bool(
            all(v is not None for v in p50s)
            and all(a <= b * 1.05 for a, b in zip(p50s, p50s[1:])))
        compiles_ok = compiles_ok and all(
            str(b) in eng_snap.get("compiles", {}) for b in buckets)

    n_curves = sum(len(e.get("buckets", {}))
                   for e in snap["engines"].values())
    return {
        "metric": "profile_curves",
        "value": n_curves,
        "unit": ("per-(engine, bucket) stage-cost curves captured by the "
                 "online profiler (h2d/compute/d2h/device ms + rows/s + "
                 "XLA compile cost per shape)"),
        "engines": engine_keys,
        "buckets": list(buckets),
        "batches_per_bucket": 1 + warm_batches,
        "profile": snap,
        "round_trip_ok": round_trip_ok,
        "monotone_device_ms": monotone,
        "monotone_ok": all(monotone.values()),
        "compiles_ok": compiles_ok,
        "config": "profile",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
        "note": ("single-core CPU host: absolute ms are this host's, not "
                 "an accelerator's; the artifact's claims are structural "
                 "(curves exist per bucket, device cost grows with bucket, "
                 "compile cost is attributed per shape, snapshot reloads "
                 "as a baseline) and those survive the host change"),
    }


def run_obs_overhead(args) -> dict:
    """``--obs-overhead``: the profiler's cost, measured honestly — the
    same warm engine hammered through the split-phase dispatch path with
    the profile sink attached vs detached (``obs.profile.set_enabled``),
    interleaved at cell level (on, off, on, off, ...) so host drift hits
    both arms equally. The acceptance bar is <= 2% throughput overhead;
    recording is one lock + a few histogram appends per BATCH, so the
    expected number is noise-level."""
    from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
    from storm_tpu.infer.engine import InferenceEngine
    from storm_tpu.obs import profile as obs_profile

    cfg = CONFIGS["lenet5"]
    eng = InferenceEngine(
        ModelConfig(name=cfg["model"], dtype="bfloat16",
                    input_shape=cfg["input_shape"],
                    num_classes=cfg["num_classes"]),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=64, buckets=(64,)))
    x = np.random.default_rng(1).standard_normal(
        (64, *cfg["input_shape"])).astype(np.float32)
    eng.predict(x)  # compile outside every measured cell
    n_batches = 200
    repeats = max(5, args.repeats)

    def run_cell(arm, rep):
        obs_profile.set_enabled(arm == "profiling_on")
        t0 = time.perf_counter()
        handles = [eng.dispatch((x,)) for _ in range(n_batches)]
        for h in handles:
            h.future.result()
        return n_batches / (time.perf_counter() - t0)

    try:
        samples = run_interleaved(("profiling_on", "profiling_off"),
                                  repeats, run_cell)
    finally:
        obs_profile.set_enabled(True)  # profiling is the default state
    on = arm_stats(samples["profiling_on"])
    off = arm_stats(samples["profiling_off"])
    overhead_pct = round(
        (off["msgs_per_sec"] - on["msgs_per_sec"])
        / off["msgs_per_sec"] * 100.0, 2) if off["msgs_per_sec"] else None
    return {
        "metric": "obs_profiling_overhead_pct",
        "value": overhead_pct,
        "unit": ("batch-throughput cost of the engine profile sink: "
                 "(off - on) / off * 100 over interleaved median-of-"
                 f"{repeats} cells of {n_batches} pipelined 64-row "
                 "lenet5 batches"),
        "batches_per_cell": n_batches,
        "repeats": repeats,
        "profiling_on": on,
        "profiling_off": off,
        "overhead_ok": bool(overhead_pct is not None
                            and overhead_pct <= 2.0),
        "config": "lenet5+obs-overhead",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
        "note": ("negative overhead = the on arm measured faster, i.e. "
                 "the true cost is below this host's run-to-run noise"),
    }


def run_copy_ledger(args) -> dict:
    """``--copy-ledger``: the round-18 evidence run for the data-plane
    copy ledger — two questions, each answered the honest way.

    **Decomposition** (3-worker dist mesh, the wire-compare topology):
    per-stage bytes/record and copies/record for the two data-plane
    arms — ``string`` spout scheme + JSON wire (every hop re-stringifies)
    vs ``raw`` scheme + binary wire (broker bytes ship as-is) — on the
    NullEngine framework-ceiling topology and on lenet5 with the real
    engine. Cells are interleaved (json, binary, json, binary, ...) per
    the BENCH_NOTES protocol. Accounting is EXACT, not windowed: a
    ledger reset lands in every worker after submit (empty input topic,
    so nothing has flowed) and one cumulative read follows the drain —
    windowed cursors can't see a hop born mid-window, so the bench
    doesn't use them.

    **Overhead** (local NullEngine pipeline): the ledger's own cost,
    measured like ``--obs-overhead`` — the same running topology
    hammered with the ledger attached vs detached
    (``copyledger.set_enabled``), interleaved at cell level. The
    pipeline is the worst case for the ledger: NullEngine does no
    device work, the string scheme exercises the per-chunk scheme hop,
    and every record pays decode/route/encode/sink hops. Acceptance
    bar: <= 2% throughput overhead."""
    from storm_tpu.config import Config
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.dist import DistCluster
    from storm_tpu.main import build_null_engine_topology
    from storm_tpu.obs import copyledger
    from storm_tpu.runtime.cluster import LocalCluster
    from tests.kafka_stub import KafkaStubBroker

    instances = 4

    def mk_payloads(n_distinct=16):
        rng = np.random.RandomState(0)
        return [
            json.dumps({"instances":
                        rng.rand(instances, 28, 28, 1).round(4).tolist()})
            for _ in range(n_distinct)
        ]

    # ---- part 1: per-stage decomposition on the 3-worker mesh ---------------
    stub = KafkaStubBroker(partitions=2)
    placement = {"kafka-spout": 0, "inference-bolt": 1,
                 "kafka-bolt": 2, "dlq-bolt": 2}
    arms = {"json_string": ("json", "string"),
            "binary_raw": ("binary", "raw")}

    def mk_cfg(prefix: str, arm: str) -> Config:
        wire, scheme = arms[arm]
        cfg = Config()
        cfg.broker.kind = "kafka"
        cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
        cfg.broker.input_topic = f"{prefix}-in"
        cfg.broker.output_topic = f"{prefix}-out"
        cfg.broker.dead_letter_topic = f"{prefix}-dlq"
        cfg.model.name = "lenet5"
        cfg.model.dtype = "float32"
        cfg.model.input_shape = (28, 28, 1)
        cfg.offsets.policy = "earliest"
        cfg.offsets.max_behind = None
        cfg.batch.max_batch = 64
        cfg.batch.max_wait_ms = 5
        cfg.batch.buckets = (64,)
        cfg.topology.spout_parallelism = 1
        cfg.topology.inference_parallelism = 2
        cfg.topology.sink_parallelism = 1
        cfg.topology.message_timeout_s = 300.0
        cfg.topology.max_spout_pending = 256
        cfg.tracing.sample_rate = 0.0
        cfg.topology.wire_format = wire
        cfg.topology.spout_scheme = scheme
        return cfg

    def cell_tree(cluster, prefix, builder, arm, n_msgs, warm, payloads):
        """One exact-accounting cell: submit -> reset ledgers (input
        topic still empty) -> produce -> drain -> cumulative read."""
        cfg = mk_cfg(prefix, arm)
        producer = KafkaWireBroker(cfg.broker.bootstrap)
        out = cfg.broker.output_topic
        total = warm + n_msgs
        cluster.submit(prefix, cfg, placement, builder=builder)
        cluster.copies(reset=True)
        for i in range(total):
            producer.produce(cfg.broker.input_topic,
                             payloads[i % len(payloads)])
        elapsed, done = timed_drain_window(
            lambda: stub.topic_size(out), warm, total)
        if not cluster.drain(timeout_s=30):
            log(f"  {prefix}: drain timed out")
        snap = cluster.copies(cumulative=True)
        cluster.kill()
        with stub._lock:
            for t in (cfg.broker.input_topic, out,
                      cfg.broker.dead_letter_topic):
                for p in range(stub.partitions):
                    stub._logs.pop((t, p), None)
        if done < total:
            raise RuntimeError(
                f"{prefix}: only {done}/{total} outputs before deadline")
        rate = (n_msgs / elapsed) if elapsed == elapsed else None
        return snap["merged"], rate, total

    repeats = max(1, args.repeats)
    workloads = [
        ("framework_null", "null", 1600, 400),
        ("lenet5", "standard", 800, 200),
    ]
    payloads = mk_payloads()
    rows = []
    run_id = 0
    try:
        with DistCluster(3, env={"JAX_PLATFORMS": "cpu"}) as cluster:
            for workload, builder, n_msgs, warm in workloads:

                def cell(arm, rep):
                    nonlocal run_id
                    run_id += 1
                    tree, rate, total = cell_tree(
                        cluster, f"cl{run_id}", builder, arm, n_msgs,
                        warm, payloads)
                    amp = tree.get("copy_amplification")
                    log(f"  {workload} {arm} rep{rep}: "
                        f"amplification={amp} "
                        f"({rate and round(rate, 1)} msg/s)")
                    return tree, rate, total

                cells = run_interleaved(tuple(arms), repeats, cell)
                row = {
                    "workload": workload,
                    "builder": builder,
                    "instances_per_msg": instances,
                    "payload_bytes": len(payloads[0].encode("utf-8")),
                    "messages": warm + n_msgs,
                }
                for arm in arms:
                    # Byte accounting is deterministic given the
                    # traffic, so the tree of the FIRST rep is the
                    # exhibit; amplification across reps lands as
                    # samples (equal across reps == determinism check).
                    tree, rate, total = cells[arm][0]
                    amps = [t.get("copy_amplification")
                            for t, _r, _n in cells[arm]]
                    stages = {
                        s: {"bytes_per_record": st["bytes_per_record"],
                            "copies_per_record": st["copies_per_record"],
                            "bytes": st["bytes"],
                            "copies": st["copies"],
                            "allocs": st["allocs"],
                            "records": st["records"]}
                        for s, st in tree["stages"].items()}
                    row[arm] = {
                        "stages": stages,
                        "totals": tree["totals"],
                        "copy_amplification": tree["copy_amplification"],
                        "amplification_samples": amps,
                        "ingest_records_expected": total,
                        "msgs_per_sec_samples": [
                            r and round(r, 1) for _t, r, _n in cells[arm]],
                    }
                row["amp_ratio_json_vs_binary"] = round(
                    row["json_string"]["copy_amplification"]
                    / row["binary_raw"]["copy_amplification"], 3)
                rows.append(row)
    finally:
        stub.close()

    # ---- part 2: ledger on/off overhead on a local NullEngine pipeline ------
    broker = MemoryBroker(default_partitions=2)
    cfg = Config()
    cfg.broker.input_topic = "cl-in"
    cfg.broker.output_topic = "cl-out"
    cfg.broker.dead_letter_topic = "cl-dlq"
    cfg.model.name = "lenet5"
    cfg.model.dtype = "float32"
    cfg.model.input_shape = (28, 28, 1)
    cfg.offsets.policy = "earliest"
    cfg.offsets.max_behind = None
    cfg.batch.max_batch = 64
    cfg.batch.max_wait_ms = 5
    cfg.batch.buckets = (64,)
    cfg.topology.message_timeout_s = 300.0
    cfg.topology.max_spout_pending = 256
    cfg.topology.spout_scheme = "string"  # exercise the scheme hop
    cfg.tracing.sample_rate = 0.0
    n_msgs, warm = 1500, 300
    o_repeats = max(5, args.repeats)
    cluster = LocalCluster()
    produced = 0

    def overhead_cell(arm, rep):
        nonlocal produced
        copyledger.set_enabled(arm == "ledger_on")
        base = broker.topic_size(cfg.broker.output_topic)
        total = warm + n_msgs
        for i in range(total):
            broker.produce(cfg.broker.input_topic,
                           payloads[i % len(payloads)])
        produced += total
        elapsed, done = timed_drain_window(
            lambda: broker.topic_size(cfg.broker.output_topic) - base,
            warm, total)
        if done < total:
            raise RuntimeError(
                f"overhead {arm} rep{rep}: {done}/{total} outputs")
        return n_msgs / elapsed

    try:
        cluster.submit_topology(
            "copy-overhead", cfg, build_null_engine_topology(cfg, broker))
        samples = run_interleaved(("ledger_on", "ledger_off"),
                                  o_repeats, overhead_cell)
    finally:
        copyledger.set_enabled(True)  # ledger is the default state
        cluster.kill_topology("copy-overhead")
        cluster.shutdown()
    on = arm_stats(samples["ledger_on"])
    off = arm_stats(samples["ledger_off"])
    overhead_pct = round(
        (off["msgs_per_sec"] - on["msgs_per_sec"])
        / off["msgs_per_sec"] * 100.0, 2) if off["msgs_per_sec"] else None

    fw = next(r for r in rows if r["workload"] == "framework_null")
    return {
        "metric": "copy_ledger_r18",
        "value": fw["amp_ratio_json_vs_binary"],
        "unit": ("copy-amplification ratio, string+json arm over "
                 "raw+binary arm, framework_null workload (bytes moved "
                 "per payload byte ingested; exact reset->cumulative "
                 "ledger accounting on a 3-worker mesh)"),
        "rows": rows,
        "amplification_gt_1_all_arms": all(
            r[a]["copy_amplification"] is not None
            and r[a]["copy_amplification"] > 1.0
            for r in rows for a in arms),
        "workers": 3,
        "wire_hops_per_record": 2,
        "overhead": {
            "metric": "copy_ledger_overhead_pct",
            "value": overhead_pct,
            "unit": ("msg-throughput cost of the attached ledger: "
                     "(off - on) / off * 100 over interleaved "
                     f"median-of-{o_repeats} cells of {n_msgs} timed "
                     "msgs through a local NullEngine pipeline "
                     "(string scheme; per-record hops are the ledger's "
                     "worst case)"),
            "ledger_on": on,
            "ledger_off": off,
            "repeats": o_repeats,
            "messages_timed": n_msgs,
            "overhead_ok": bool(overhead_pct is not None
                                and overhead_pct <= 2.0),
            "note": ("negative overhead = the on arm measured faster, "
                     "i.e. the true cost is below this host's "
                     "run-to-run noise"),
        },
        "repeats": repeats,
        "protocol": ("interleaved A/B per cell; per-cell ledger reset "
                     "after submit (input topic empty) + one cumulative "
                     "read after drain, so accounting is exact, not "
                     "windowed"),
        **HOST_ONLY,
        "config": "copy-ledger",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def run_zerocopy(args) -> dict:
    """``--zerocopy``: the round-19 evidence run for the zero-copy
    batch-native record path, interleaved A/B against the round-18
    headline data plane on the same 3-worker mesh.

    **Arms** (same logical records — 16 distinct (4, 28, 28, 1) float32
    image batches — different planes):

    - ``legacy``: the BENCH_COPY_r18 headline cell replicated verbatim —
      string spout scheme, JSON wire, per-record tuples, JSON text
      payloads (amp 3.451, ~430 msg/s on the r18 capture);
    - ``zerocopy``: the r19 dist-run DEFAULT plane — raw scheme, record
      frames (spout_chunk=32: one tuple = 32 records by reference),
      binary wire v2 with the frame slot, the shared-memory delivery
      lane, Arrow tensor payloads (view decode), batch egress (one
      predictions message per dispatched batch, bytes passthrough at
      the sink).

    **Measurements** per workload (framework_null + lenet5): exact
    reset->cumulative copy-ledger accounting (the r18 protocol: reset
    after submit while the input topic is empty, one cumulative read
    after drain), throughput over the warm->last window from the stub
    broker's own output-topic produce timestamps (poll-granularity-free
    — the zero-copy arm drains a whole backlog between two polls), and
    the receiver-side ``dist_shm_batches`` counter as positive proof
    the shm lane carried traffic. A separate PACED cell per arm (fresh submit, ~200 msg/s —
    a fraction of either arm's capacity) reads the sink's e2e p50
    without saturation queueing, which a drain-window histogram would
    bake in.

    **Gates**: framework ceiling >= 3x the interleaved legacy arm;
    zerocopy copy_amplification <= 1.5 (vs 3.451); paced framework
    p50 < 50 ms; shm engaged."""
    from storm_tpu.config import Config
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.dist import DistCluster
    from storm_tpu.serve.marshal import encode_tensor
    from tests.kafka_stub import KafkaStubBroker

    instances = 4
    rng = np.random.RandomState(0)
    # float64 rounded for compact JSON text (the r18 recipe), float32 for
    # the tensor frames — identical content at float32 precision.
    arrays = [rng.rand(instances, 28, 28, 1).round(4) for _ in range(16)]
    json_payloads = [json.dumps({"instances": a.tolist()}) for a in arrays]
    tensor_payloads = [encode_tensor(a.astype(np.float32)) for a in arrays]
    arm_payloads = {"legacy": json_payloads, "zerocopy": tensor_payloads}

    stub = KafkaStubBroker(partitions=2)
    placement = {"kafka-spout": 0, "inference-bolt": 1,
                 "kafka-bolt": 2, "dlq-bolt": 2}
    arms = ("legacy", "zerocopy")

    def mk_cfg(prefix: str, arm: str) -> Config:
        cfg = Config()
        cfg.broker.kind = "kafka"
        cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
        cfg.broker.input_topic = f"{prefix}-in"
        cfg.broker.output_topic = f"{prefix}-out"
        cfg.broker.dead_letter_topic = f"{prefix}-dlq"
        cfg.model.name = "lenet5"
        cfg.model.dtype = "float32"
        cfg.model.input_shape = (28, 28, 1)
        cfg.offsets.policy = "earliest"
        cfg.offsets.max_behind = None
        cfg.batch.max_batch = 64
        cfg.batch.max_wait_ms = 5
        cfg.batch.buckets = (64,)
        cfg.topology.spout_parallelism = 1
        cfg.topology.inference_parallelism = 2
        cfg.topology.sink_parallelism = 1
        cfg.topology.message_timeout_s = 300.0
        cfg.topology.max_spout_pending = 256
        cfg.tracing.sample_rate = 0.0
        if arm == "legacy":
            cfg.topology.wire_format = "json"
            cfg.topology.spout_scheme = "string"
        else:
            cfg.topology.wire_format = "binary"
            cfg.topology.spout_scheme = "raw"
            cfg.topology.spout_frames = True
            # one frame = one dispatch bucket (64): the dispatcher never
            # waits on a partial batch and every frame clears the shm
            # eligibility floor in one piece
            cfg.topology.spout_chunk = 64
        return cfg

    def wipe_topics(cfg):
        with stub._lock:
            for t in (cfg.broker.input_topic, cfg.broker.output_topic,
                      cfg.broker.dead_letter_topic):
                for p in range(stub.partitions):
                    stub._logs.pop((t, p), None)

    def mk_row_counter(topic):
        """Prediction ROWS at the output topic, parsed incrementally —
        batch egress emits ONE message per dispatched batch, so message
        count no longer equals record count and completion must gate on
        rows on both arms identically."""
        state = {"rows": 0, "idx": {}}

        def rows():
            with stub._lock:
                for p in range(stub.partitions):
                    recs = stub._logs.get((topic, p), [])
                    start = state["idx"].get(p, 0)
                    for rec in recs[start:]:
                        try:
                            state["rows"] += len(
                                json.loads(rec[1])["predictions"])
                        except Exception:
                            state["rows"] += 1  # non-prediction payload
                    state["idx"][p] = len(recs)
            return state["rows"]

        return rows

    def topic_rate(topic, warm_msgs, total_msgs):
        """Steady-window throughput from the stub broker's OWN produce
        timestamps at the output topic (``(key, value, ts)`` entries).
        Polling the topic can't time the zero-copy arm — it drains a
        whole backlog between two polls — but the broker stamps every
        sink produce, so the warm->last window is exact at any speed.
        Thresholds are in prediction rows (= msgs * instances); the
        returned rate is input messages/s over the post-warmup window."""
        events = []
        with stub._lock:
            for p in range(stub.partitions):
                for rec in stub._logs.get((topic, p), []):
                    if len(rec) != 3:
                        continue  # txn marker entries
                    try:
                        n = len(json.loads(rec[1])["predictions"])
                    except Exception:
                        n = 1
                    events.append((rec[2], n))
        events.sort()
        warm_rows = warm_msgs * instances
        total_rows = total_msgs * instances
        cum = 0
        t_warm = t_total = None
        for ts, n in events:
            cum += n
            if t_warm is None and cum >= warm_rows:
                t_warm = ts
            if cum >= total_rows:
                t_total = ts
                break
        if t_warm is None or t_total is None or t_total <= t_warm:
            return None
        return (total_msgs - warm_msgs) / (t_total - t_warm)

    def inject_backlog(topic, payloads, total):
        """Append the whole backlog straight into the stub log under its
        lock — the wire producer loop shares the CPU with the stub's
        serve thread and three worker processes, and under that
        contention it runs SLOWER than the zero-copy pipeline: a paced
        producer would cap the measured ceiling at its own rate (the
        spout stays caught up and frames never fill). Injection is
        instant, so the spout drains a real backlog at framework speed
        on both arms identically."""
        with stub._lock:
            stub._ensure(topic)
            now = time.time()
            for i in range(total):
                p = payloads[i % len(payloads)]
                if isinstance(p, str):
                    p = p.encode("utf-8")
                stub._logs[(topic, i % stub.partitions)].append(
                    (None, p, now))

    def cell_tree(cluster, prefix, builder, arm, n_msgs, warm):
        """One exact-accounting cell: submit -> reset ledgers (input
        topic still empty) -> inject backlog -> drain -> cumulative
        read."""
        cfg = mk_cfg(prefix, arm)
        total = warm + n_msgs
        cluster.submit(prefix, cfg, placement, builder=builder)
        cluster.copies(reset=True)
        inject_backlog(cfg.broker.input_topic, arm_payloads[arm], total)
        rows = mk_row_counter(cfg.broker.output_topic)
        deadline = time.time() + 300
        done = rows()
        while time.time() < deadline and done < total * instances:
            time.sleep(0.005)
            done = rows()
        if not cluster.drain(timeout_s=60):
            log(f"  {prefix}: drain timed out")
        snap = cluster.copies(cumulative=True)
        msnap = cluster.metrics()
        shm_batches = msnap.get("_transport", {}).get("dist_shm_batches", 0)
        rate = topic_rate(cfg.broker.output_topic, warm, total)
        cluster.kill()
        wipe_topics(cfg)
        if done < total * instances:
            raise RuntimeError(
                f"{prefix}: only {done}/{total * instances} prediction "
                f"rows before deadline")
        return snap["merged"], rate, total, shm_batches

    def cell_latency(cluster, prefix, builder, arm, n_msgs=240,
                     pace_s=0.005):
        """Paced latency cell: fresh submit (empty histograms), one
        message per ``pace_s`` — far below either arm's capacity — so
        the sink's e2e p50 is the framework's latency floor, not a
        saturation queue length."""
        cfg = mk_cfg(prefix, arm)
        payloads = arm_payloads[arm]
        producer = KafkaWireBroker(cfg.broker.bootstrap)
        cluster.submit(prefix, cfg, placement, builder=builder)
        rows = mk_row_counter(cfg.broker.output_topic)
        for i in range(n_msgs):
            producer.produce(cfg.broker.input_topic,
                             payloads[i % len(payloads)])
            time.sleep(pace_s)
        deadline = time.time() + 60
        while time.time() < deadline and rows() < n_msgs * instances:
            time.sleep(0.05)
        snap = cluster.metrics()
        lat = snap.get("kafka-bolt", {}).get("e2e_latency_ms", {})
        cluster.drain(timeout_s=30)
        cluster.kill()
        wipe_topics(cfg)
        return {"p50_ms": lat.get("p50"), "p99_ms": lat.get("p99"),
                "count": lat.get("count"),
                "paced_rate_msgs_s": round(1.0 / pace_s, 1),
                "messages": n_msgs}

    _PARSE_COPY_STAGES = ("spout_scheme", "json_decode", "wire_encode",
                          "wire_decode", "json_encode", "sink_encode")

    def parse_copy_share(tree) -> float:
        """Share of all non-ingest data-plane bytes spent in
        parse/serialize/wire stages — the critical-path fraction the
        zero-copy plane exists to collapse."""
        stages = tree["stages"]
        moved = sum(st["bytes"] for s, st in stages.items()
                    if s != "spout_ingest")
        if not moved:
            return 0.0
        pc = sum(stages[s]["bytes"] for s in _PARSE_COPY_STAGES
                 if s in stages)
        return round(pc / moved, 4)

    repeats = max(1, args.repeats)
    workloads = [
        ("framework_null", "null", 1600, 400),
        ("lenet5", "standard", 800, 200),
    ]
    rows = []
    latency = {}
    run_id = 0
    try:
        with DistCluster(3, env={"JAX_PLATFORMS": "cpu"}) as cluster:
            for workload, builder, n_msgs, warm in workloads:

                def cell(arm, rep):
                    nonlocal run_id
                    run_id += 1
                    tree, rate, total, shm_n = cell_tree(
                        cluster, f"zc{run_id}", builder, arm, n_msgs, warm)
                    amp = tree.get("copy_amplification")
                    log(f"  {workload} {arm} rep{rep}: amplification={amp} "
                        f"({rate and round(rate, 1)} msg/s, "
                        f"shm_batches={shm_n})")
                    return tree, rate, total, shm_n

                cells = run_interleaved(arms, repeats, cell)
                row = {
                    "workload": workload,
                    "builder": builder,
                    "instances_per_msg": instances,
                    "payload_bytes": {
                        "legacy": len(json_payloads[0].encode("utf-8")),
                        "zerocopy": len(tensor_payloads[0]),
                    },
                    "messages": warm + n_msgs,
                }
                for arm in arms:
                    tree, rate, total, shm_n = cells[arm][0]
                    amps = [t.get("copy_amplification")
                            for t, _r, _n, _s in cells[arm]]
                    stages = {
                        s: {"bytes_per_record": st["bytes_per_record"],
                            "copies_per_record": st["copies_per_record"],
                            "bytes": st["bytes"],
                            "copies": st["copies"],
                            "allocs": st["allocs"],
                            "records": st["records"]}
                        for s, st in tree["stages"].items()}
                    row[arm] = {
                        "stages": stages,
                        "totals": tree["totals"],
                        "copy_amplification": tree["copy_amplification"],
                        "amplification_samples": amps,
                        "parse_copy_share": parse_copy_share(tree),
                        "ingest_records_expected": total,
                        "shm_batches_samples": [s for _t, _r, _n, s
                                                in cells[arm]],
                        "msgs_per_sec_samples": [
                            r and round(r, 1)
                            for _t, r, _n, _s in cells[arm]],
                    }
                rates_l = [r for r in row["legacy"]["msgs_per_sec_samples"]
                           if r]
                rates_z = [r for r in row["zerocopy"]["msgs_per_sec_samples"]
                           if r]
                row["speedup"] = round(
                    sorted(rates_z)[len(rates_z) // 2]
                    / sorted(rates_l)[len(rates_l) // 2], 2) \
                    if rates_l and rates_z else None
                rows.append(row)

            log("latency cells (paced, fresh submits)")
            for arm in arms:
                run_id += 1
                latency[arm] = cell_latency(cluster, f"zclat{run_id}",
                                            "null", arm)
                log(f"  framework_null {arm}: "
                    f"p50={latency[arm]['p50_ms']} ms "
                    f"p99={latency[arm]['p99_ms']} ms")
    finally:
        stub.close()

    fw = next(r for r in rows if r["workload"] == "framework_null")
    zc_amp = fw["zerocopy"]["copy_amplification"]
    p50 = latency["zerocopy"]["p50_ms"]
    shm_engaged = all(s > 0 for s in fw["zerocopy"]["shm_batches_samples"])
    gates = {
        "speedup_ge_3x": bool(fw["speedup"] is not None
                              and fw["speedup"] >= 3.0),
        "zerocopy_amp_le_1_5": bool(zc_amp is not None and zc_amp <= 1.5),
        "framework_p50_lt_50ms": bool(p50 is not None and p50 < 50.0),
        "shm_engaged": shm_engaged,
    }
    return {
        "metric": "zerocopy_speedup_r19",
        "value": fw["speedup"],
        "unit": ("NullEngine framework-ceiling msg-throughput ratio, "
                 "zero-copy batch-native plane (raw+frames+binary wire "
                 "v2+shm lane+tensor payloads+batch egress) over the "
                 "r18 headline plane (string+JSON wire, per-record), "
                 "interleaved on a 3-worker mesh"),
        "rows": rows,
        "latency": latency,
        "gates": gates,
        "baseline_r18": {
            "artifact": "BENCH_COPY_r18.json",
            "framework_null_json_string_amp": 3.451,
            "framework_null_json_string_msgs_per_sec": [402.7, 453.8],
            "note": ("the interleaved legacy arm REPLICATES the r18 "
                     "headline cell on this host/commit; gate ratios "
                     "use the interleaved arm, not the stale capture"),
        },
        "workers": 3,
        "repeats": repeats,
        "protocol": ("interleaved A/B per cell; per-cell ledger reset "
                     "after submit (input topic empty) + one cumulative "
                     "read after drain (exact, not windowed); backlog "
                     "injected into the stub log in one step (a wire "
                     "producer loop under CPU contention is slower than "
                     "the zero-copy pipeline and would cap the measured "
                     "ceiling at its own rate); completion gated on "
                     "prediction ROWS at the output topic (batch egress "
                     "coalesces messages); throughput from broker-side "
                     "produce timestamps over the warm->last row window; "
                     "latency from separate paced cells on fresh "
                     "submits"),
        **HOST_ONLY,
        "config": "zerocopy",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def run_slo_burn(args) -> dict:
    """``--slo-burn``: the burn-rate tracker as an EARLY-WARNING signal,
    demonstrated on the same induced-overload machinery as
    ``--qos-overload`` (identical topology, tenants, and 2x offered
    load) with the Observatory attached. One measured hold; the
    per-second timeline samples the ``slo.burn_rate`` gauge next to
    ``qos.shed_level``, and the claim under test is ordering: the burn
    gauge rises (and trips) BEFORE the shed controller escalates,
    because burn reads the breach *ratio* against the error budget while
    the shedder waits for ``shed_hot_steps`` consecutive hot intervals
    over absolute thresholds. The same session also probes the live
    ``/api/v1/topology/{name}/profile`` route so the artifact proves the
    curves + burn state are servable while traffic flows — not just
    in-process."""
    import urllib.request

    from storm_tpu.config import (BatchConfig, Config, ModelConfig,
                                  ObsConfig, OffsetsConfig, QosConfig,
                                  ShardingConfig)
    from storm_tpu.connectors import BrokerSink, BrokerSpout, MemoryBroker
    from storm_tpu.infer import InferenceBolt
    from storm_tpu.qos import LoadShedController, ShedPolicy
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.cluster import LocalCluster
    from storm_tpu.runtime.ui import UIServer

    cfg = CONFIGS["lenet5"]
    slo_ms = min(args.slo_ms, 250.0)
    hold_s = float(args.stage_seconds)
    payloads = make_payloads(cfg, n_distinct=32)
    batch_cfg = BatchConfig(max_batch=256, max_wait_ms=10.0,
                            buckets=(64, 256))
    # Same shed knobs as --qos-overload (comparability): the shedder is
    # NOT weakened to let burn win — burn is simply a faster meter.
    qos_cfg = QosConfig(enabled=True, tenant_rate=0.0, shed_interval_s=0.5,
                        shed_hot_steps=2, shed_breach_rate=2.0,
                        shed_inbox_frac=0.5, shed_calm_steps=1000)
    obs_cfg = ObsConfig(enabled=True, interval_s=0.25,
                        burn_fast_window_s=5.0, burn_slow_window_s=15.0,
                        burn_threshold=1.0, sentinel_interval_s=5.0,
                        min_samples=10)

    broker = MemoryBroker(default_partitions=4)
    run_cfg = Config()
    run_cfg.topology.message_timeout_s = 300.0
    run_cfg.tracing.slo_ms = slo_ms
    run_cfg.qos = qos_cfg
    run_cfg.obs = obs_cfg
    model_cfg = ModelConfig(name=cfg["model"], dtype="bfloat16",
                            input_shape=cfg["input_shape"],
                            num_classes=cfg["num_classes"])
    tb = TopologyBuilder()
    tb.set_spout("kafka-spout",
                 BrokerSpout(broker, "input",
                             OffsetsConfig(policy="earliest",
                                           max_behind=None),
                             fetch_size=1024, scheme="raw", qos=qos_cfg),
                 parallelism=2)
    tb.set_bolt("inference-bolt",
                InferenceBolt(model_cfg, batch_cfg,
                              ShardingConfig(data_parallel=0), qos=qos_cfg,
                              passthrough=("qos_lane",)),
                parallelism=1).shuffle_grouping("kafka-spout")
    tb.set_bolt("kafka-bolt", BrokerSink(broker, "output", run_cfg.sink),
                parallelism=1).shuffle_grouping("inference-bolt")
    tb.set_bolt("dlq-bolt", BrokerSink(broker, "dead-letter", run_cfg.sink),
                parallelism=1).shuffle_grouping("inference-bolt",
                                                stream="dead_letter")

    cluster = LocalCluster()
    name = "slo-burn"
    ui_profile = None
    try:
        cluster.submit_topology(name, run_cfg, tb.build())

        async def mk():
            from storm_tpu.obs import Observatory

            rt = cluster._cluster.runtime(name)
            obs = Observatory(rt, obs_cfg,
                              sink_components=("kafka-bolt",)).start()
            shedder = LoadShedController(
                rt, ShedPolicy.from_qos(qos_cfg, "inference-bolt",
                                        "kafka-bolt")).start()
            # The tentpole wiring under test: burn becomes an additional
            # hot signal for the shed controller.
            shedder.burn = obs.burn
            ui = await UIServer(cluster._cluster, port=0).start()
            return obs, shedder, ui

        obs, shedder, ui = cluster._run(mk())

        def produce(key, i):
            broker.produce("input", payloads[i % len(payloads)], key=key)

        def snap():
            return cluster.metrics(name)

        def counter(component, metric, s=None):
            v = (s if s is not None else snap())\
                .get(component, {}).get(metric, 0)
            return int(v or 0)

        # Capacity probe (same as --qos-overload): overload = 2x this.
        base = broker.topic_size("output")
        t0 = time.perf_counter()
        for i in range(256):
            produce(b"gold:high", i)
        if not await_outputs(lambda: broker.topic_size("output") - base,
                             256, grace_s=180.0):
            sys.exit("slo-burn capacity probe never drained")
        cap1 = 256 / (time.perf_counter() - t0)
        log(f"sustained capacity ~{cap1:.0f} msg/s; overload = "
            f"{2 * cap1:.0f} msg/s; SLO {slo_ms:.0f} ms")
        rate_hi, rate_be = 0.4 * cap1, 1.6 * cap1

        s0 = snap()
        base_delivered = counter("kafka-bolt", "delivered", s0)
        base_breach = counter("kafka-bolt", "slo_breaches", s0)
        timeline = []
        t_hold = time.perf_counter()

        def window_cb(now):
            s = snap()
            slo = s.get("slo", {})
            timeline.append({
                "t": round(now - t_hold, 2),
                "burn_rate": round(float(slo.get("burn_rate", 0.0) or 0.0),
                                   3),
                "burn_tripped": int(slo.get("tripped", 0) or 0),
                "shed_level": int(s.get("qos", {})
                                  .get("shed_level", 0) or 0),
                "delivered": counter("kafka-bolt", "delivered", s)
                - base_delivered,
                "slo_breaches": counter("kafka-bolt", "slo_breaches", s)
                - base_breach,
            })

        # One measured hold at 2x from a cold (unshedding) start — the
        # reaction IS the evidence here, so no unmeasured warmup window.
        iv_hi, iv_be = 1.0 / rate_hi, 1.0 / rate_be
        start = time.perf_counter()
        end = start + hold_s
        nxt_hi = nxt_be = start
        next_window = start + 0.5
        n_hi = n_be = 0
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            while nxt_hi <= now:
                produce(b"gold:high", n_hi)
                n_hi += 1
                nxt_hi += iv_hi
            while nxt_be <= now:
                produce(b"free:best_effort", n_be)
                n_be += 1
                nxt_be += iv_be
            if now >= next_window:
                next_window = now + 0.5
                window_cb(now)
            time.sleep(min(0.002, max(
                0.0, min(nxt_hi, nxt_be) - time.perf_counter())))

        # Live-route probe in the SAME session, traffic still landing:
        # the route must serve the profiler's curves + the burn state.
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{ui.port}/api/v1/topology/{name}"
                    "/profile", timeout=10) as resp:
                body = json.loads(resp.read().decode())
            ui_profile = {
                "status": resp.status,
                "engines": sorted(body.get("profile", {})
                                  .get("engines", {})),
                "slo": body.get("slo", {}),
                "occupancy_rows": len(body.get("occupancy", [])),
            }
        except Exception as e:  # noqa: BLE001 - probe failure is evidence
            ui_profile = {"error": str(e)}

        time.sleep(3.0)  # let admitted in-flight work land
        s1 = snap()
        delivered = counter("kafka-bolt", "delivered", s1) - base_delivered
        breaches = counter("kafka-bolt", "slo_breaches", s1) - base_breach

        async def harvest():
            rt = cluster._cluster.runtime(name)
            return [e for e in rt.flight.tail(400)
                    if e.get("kind") == "slo_burn"
                    or str(e.get("kind", "")).startswith("shed")]

        flight = cluster._run(harvest())
        burn_snap = obs.burn.snapshot()
        cluster._run(obs.stop())
        cluster._run(shedder.stop())
        cluster._run(ui.stop())
        cluster.kill_topology(name, wait_secs=2)
    finally:
        cluster.shutdown()

    def first_t(pred):
        for w in timeline:
            if pred(w):
                return w["t"]
        return None

    burn_rise_t = first_t(lambda w: w["burn_rate"] > 0.0)
    burn_trip_t = first_t(lambda w: w["burn_tripped"])
    shed_t = first_t(lambda w: w["shed_level"] > 0)
    burn_before_shed = bool(
        burn_trip_t is not None
        and (shed_t is None or burn_trip_t <= shed_t))
    flight_burn = [e for e in flight if e.get("kind") == "slo_burn"]
    lead_s = (round(shed_t - burn_trip_t, 2)
              if burn_trip_t is not None and shed_t is not None else None)
    return {
        "metric": "slo_burn_lead_s",
        "value": lead_s,
        "unit": ("seconds between the burn-rate trip and the shed "
                 "controller's first escalation under the same 2x "
                 "overload (positive = burn warned first)"),
        "slo_ms": slo_ms,
        "burn_threshold": obs_cfg.burn_threshold,
        "burn_windows_s": [obs_cfg.burn_fast_window_s,
                           obs_cfg.burn_slow_window_s],
        "burn_rise_t": burn_rise_t,
        "burn_trip_t": burn_trip_t,
        "shed_level_t": shed_t,
        "burn_before_shed": burn_before_shed,
        "cap1_msg_s": round(cap1, 1),
        "offered_multiple": 2.0,
        "sent_high": n_hi,
        "sent_best_effort": n_be,
        "delivered": delivered,
        "slo_breaches": breaches,
        "burn_snapshot": burn_snap,
        "timeline": timeline,
        "evidence": {
            "flight_slo_burn": bool(flight_burn),
            "flight_shed": bool([e for e in flight
                                 if str(e.get("kind", ""))
                                 .startswith("shed")]),
            "ui_profile_route": bool(ui_profile
                                     and ui_profile.get("engines")),
        },
        "flight_slo_burn_events": flight_burn[-3:],
        "ui_profile": ui_profile,
        "config": "lenet5+slo-burn",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
        "note": ("single-core CPU host: cap1 is this host's sustained "
                 "capacity; the claim is ORDERING (burn trips before the "
                 "shed level moves under identical overload), which is "
                 "host-independent"),
    }


def run_fleet_matrix(args) -> dict:
    """``--fleet``: the trace-driven scenario x pattern matrix
    (storm_tpu/loadgen). Each cell replays a seeded trace — heavy-tailed
    tenants, a diurnal wave, or a flash crowd — against one serving
    scenario (classify, cascade, serve-path, decode) with the full
    protection stack live, and is scored on goodput, per-lane p99, SLO
    burn, and shed fraction against declared targets. The committed
    ``SCORECARD_r<N>.json`` is the regression surface future PRs diff
    against instead of a single paced run; traces regenerate
    byte-identically from the recorded spec+seed."""
    from storm_tpu.loadgen.fleet import run_fleet

    scenarios = None
    if args.fleet_scenarios:
        scenarios = tuple(s.strip() for s in
                          args.fleet_scenarios.split(",") if s.strip())
    kw = {}
    if scenarios:
        kw["scenarios"] = scenarios
    out = run_fleet(args, **kw)
    out["capture_session"] = _new_capture_session()
    out["code_version"] = _code_version()
    return out


def run_bottleneck(args) -> dict:
    """``--bottleneck``: the bottleneck observatory made to name a KNOWN
    limiter, induced both ways on the same DAG shape:

    - arm ``bn-infer`` (inference-bound): lenet5 behind ONE inference
      task fed 8-image records by two spouts off an in-process broker —
      the inference operator's decode + batch + dispatch path is where
      the wall time goes; the attributor must name ``inference-bolt``.
    - arm ``bn-spout`` (ingest-bound): NullEngine behind TWO inference
      tasks, the spout fetching ``fetch_size=1`` against the TCP wire
      broker — every record pays a full fetch round trip (the classic
      under-batched-consumer bottleneck), downstream idles; the
      attributor must name ``kafka-spout``.

    Verdicts are sampled mid-drain through the live
    ``/api/v1/topology/{name}/bottleneck`` route (majority over the
    sampled leaders, so one scheduler hiccup cannot flip the gate) —
    which also proves the route serves while traffic flows. The same
    capture A/Bs the observatory's cost (Observatory attached at
    interval_s=0.2 vs detached, interleaved cells over the NullEngine
    topology, bar <= 2%; the per-tuple executor clock reads are
    constitutive and present in BOTH arms — the A/B prices the sampling/
    attribution layer) and probes a 2-worker dist cluster for the
    controller-merged windowed utilization (``DistCluster.utilization``).
    """
    import urllib.request

    from storm_tpu.config import Config, ObsConfig
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.main import (build_null_engine_topology,
                                build_standard_topology)
    from storm_tpu.obs import Observatory
    from storm_tpu.runtime.cluster import LocalCluster
    from storm_tpu.runtime.ui import UIServer

    obs_cfg = ObsConfig(enabled=True, interval_s=0.2, min_samples=5)
    tiny_payload = json.dumps({"instances": [[0.5]]}).encode("utf-8")

    def null_cfg() -> Config:
        cfg = Config()
        cfg.model.input_shape = (1,)
        cfg.model.num_classes = 2
        cfg.batch.max_batch = 64
        cfg.batch.max_wait_ms = 5.0
        cfg.batch.buckets = (64,)
        cfg.topology.spout_parallelism = 1
        cfg.topology.inference_parallelism = 2
        cfg.topology.sink_parallelism = 2
        cfg.topology.message_timeout_s = 300.0
        cfg.offsets.policy = "earliest"
        cfg.offsets.max_behind = None
        cfg.tracing.sample_rate = 0.0
        cfg.obs = obs_cfg
        return cfg

    def run_arm(arm: str, cfg: Config, build_fn, backlog: int,
                window_s: float, expected: str, produce, out_size,
                broker) -> dict:
        """Hold a sustained ``backlog`` of unconsumed input for
        ``window_s`` (host-speed independent — a fixed message count
        drains before the attributor's first real window on a fast
        host), polling the live /bottleneck route throughout; then stop
        producing and drain. The named component is the majority of the
        sampled leaders."""
        produced = 0
        cluster = LocalCluster()
        leaders = []
        route = None
        mid = None

        def top_up():
            nonlocal produced
            while produced - out_size() < backlog:
                produce(produced)
                produced += 1

        try:
            top_up()
            cluster.submit_topology(arm, cfg, build_fn(cfg, broker))

            async def mk():
                rt = cluster._cluster.runtime(arm)
                obs = Observatory(rt, obs_cfg,
                                  sink_components=("kafka-bolt",)).start()
                ui = await UIServer(cluster._cluster, port=0).start()
                return obs, ui

            obs, ui = cluster._run(mk())
            url = (f"http://127.0.0.1:{ui.port}/api/v1/topology/{arm}"
                   "/bottleneck")
            # Warmup outside the verdict window: first output = topology
            # up + first batch through (incl. any XLA compile).
            warm_deadline = time.time() + 300.0
            while time.time() < warm_deadline and out_size() == 0:
                time.sleep(0.05)
            t_end = time.time() + window_s
            while time.time() < t_end:
                top_up()
                time.sleep(0.15)
                try:
                    with urllib.request.urlopen(url, timeout=10) as resp:
                        route = json.loads(resp.read().decode())
                except Exception as e:  # noqa: BLE001 - probe is evidence
                    route = {"error": str(e)}
                    continue
                mid = route  # last verdict taken UNDER load
                leader = (route.get("bottleneck") or {}).get("leader")
                if leader:
                    leaders.append(leader)
            deadline = time.time() + 300.0
            while time.time() < deadline and out_size() < produced:
                time.sleep(0.05)
            drained = out_size() >= produced
            cluster._run(obs.stop())
            cluster._run(ui.stop())
            cluster.kill_topology(arm, wait_secs=2)
        finally:
            cluster.shutdown()
        votes: dict = {}
        for ld in leaders:
            votes[ld] = votes.get(ld, 0) + 1
        named = max(votes, key=votes.get) if votes else None
        last = (mid or {}).get("bottleneck") or {}
        log(f"  {arm}: named={named} votes={votes} drained={drained} "
            f"msgs={produced}")
        return {
            "arm": arm,
            "expected": expected,
            "named": named,
            "correct": bool(named == expected and drained),
            "leader_votes": votes,
            "messages": produced,
            "window_s": window_s,
            "backlog": backlog,
            "drained": drained,
            "last_ranked": (last.get("ranked") or [])[:3],
            "last_critical_path": last.get("critical_path"),
            "last_utilization": (mid or {}).get("utilization"),
        }

    # Arm A — inference-bound: lenet5, one inference task, 8-image
    # records (decode + batch + dispatch cost lands in the operator),
    # spouts parked at a small pending cap (wait-dominated by design).
    cfg_a = Config()
    lenet = CONFIGS["lenet5"]
    cfg_a.model.name = lenet["model"]
    cfg_a.model.dtype = "bfloat16"
    cfg_a.model.input_shape = lenet["input_shape"]
    cfg_a.model.num_classes = lenet["num_classes"]
    cfg_a.batch.max_batch = 64
    cfg_a.batch.max_wait_ms = 10.0
    cfg_a.batch.buckets = (64,)
    cfg_a.topology.spout_parallelism = 2
    cfg_a.topology.inference_parallelism = 1
    cfg_a.topology.sink_parallelism = 1
    cfg_a.topology.max_spout_pending = 512
    cfg_a.topology.message_timeout_s = 300.0
    cfg_a.offsets.policy = "earliest"
    cfg_a.offsets.max_behind = None
    cfg_a.tracing.sample_rate = 0.0
    cfg_a.obs = obs_cfg
    payloads_a = make_payloads(lenet, n_distinct=16, instances_per_msg=8)
    broker_a = MemoryBroker(default_partitions=2)
    arm_a = run_arm(
        "bn-infer", cfg_a, build_standard_topology,
        backlog=1024, window_s=10.0, expected="inference-bolt",
        produce=lambda i: broker_a.produce(
            cfg_a.broker.input_topic, payloads_a[i % len(payloads_a)]),
        out_size=lambda: broker_a.topic_size(cfg_a.broker.output_topic),
        broker=broker_a)

    # Arm B — ingest-bound: NullEngine behind 2 tasks, the spout paying
    # one TCP fetch round trip PER RECORD (fetch_size=1 against the
    # wire broker) — downstream idles, the single spout task saturates.
    def build_fetch1_null(cfg: Config, broker):
        from storm_tpu.connectors import BrokerSink, BrokerSpout
        from storm_tpu.infer import InferenceBolt
        from storm_tpu.infer.engine import NullEngine
        from storm_tpu.runtime import TopologyBuilder

        engine = NullEngine(cfg.model.input_shape, cfg.model.num_classes)
        tb = TopologyBuilder()
        tb.set_spout("kafka-spout",
                     BrokerSpout(broker, cfg.broker.input_topic,
                                 cfg.offsets, fetch_size=1,
                                 scheme="string"),
                     parallelism=cfg.topology.spout_parallelism)
        tb.set_bolt("inference-bolt",
                    InferenceBolt(cfg.model, cfg.batch, cfg.sharding,
                                  engine=engine, warmup=False),
                    parallelism=cfg.topology.inference_parallelism
                    ).shuffle_grouping("kafka-spout")
        tb.set_bolt("kafka-bolt",
                    BrokerSink(broker, cfg.broker.output_topic, cfg.sink),
                    parallelism=cfg.topology.sink_parallelism
                    ).shuffle_grouping("inference-bolt")
        tb.set_bolt("dlq-bolt",
                    BrokerSink(broker, cfg.broker.dead_letter_topic,
                               cfg.sink),
                    parallelism=1
                    ).shuffle_grouping("inference-bolt",
                                       stream="dead_letter")
        return tb.build()

    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from tests.kafka_stub import KafkaStubBroker

    stub_b = KafkaStubBroker(partitions=2)
    cfg_b = null_cfg()
    cfg_b.broker.kind = "kafka"
    cfg_b.broker.bootstrap = f"127.0.0.1:{stub_b.port}"
    try:
        wire_b = KafkaWireBroker(cfg_b.broker.bootstrap)
        arm_b = run_arm(
            "bn-spout", cfg_b, build_fetch1_null,
            backlog=4000, window_s=10.0, expected="kafka-spout",
            produce=lambda i: wire_b.produce(cfg_b.broker.input_topic,
                                             tiny_payload.decode()),
            out_size=lambda: stub_b.topic_size(cfg_b.broker.output_topic),
            broker=wire_b)
    finally:
        stub_b.close()

    # Observatory-cost A/B: same NullEngine topology, Observatory
    # attached vs detached, interleaved at cell level.
    repeats = max(3, args.repeats)
    # Multi-second measured windows: this can be a 1-core host where a
    # sub-second drain window is pure scheduler noise (first capture of
    # this A/B swung +-17% with 0.3 s windows).
    n_msgs, warm = 20000, 2000
    ab_cfg = null_cfg()
    broker = MemoryBroker(default_partitions=2)
    cluster = LocalCluster()
    try:
        cluster.submit_topology("bn-ab", ab_cfg,
                                build_null_engine_topology(ab_cfg, broker))

        def cell(arm, rep):
            obs = None
            if arm == "obs_on":
                async def mk():
                    rt = cluster._cluster.runtime("bn-ab")
                    return Observatory(rt, obs_cfg,
                                       sink_components=("kafka-bolt",)
                                       ).start()

                obs = cluster._run(mk())
            base = broker.topic_size(ab_cfg.broker.output_topic)
            total = warm + n_msgs
            for _ in range(total):
                broker.produce(ab_cfg.broker.input_topic, tiny_payload)
            elapsed, done = timed_drain_window(
                lambda: broker.topic_size(ab_cfg.broker.output_topic) - base,
                warm, total)
            if obs is not None:
                cluster._run(obs.stop())
            if elapsed != elapsed or done < total:
                raise RuntimeError(
                    f"bn-ab {arm} rep{rep}: only {done}/{total} outputs")
            rate = n_msgs / elapsed
            log(f"  overhead A/B {arm} rep{rep}: {rate:.0f} msg/s")
            return rate

        samples = run_interleaved(("obs_on", "obs_off"), repeats, cell)
        cluster.kill_topology("bn-ab", wait_secs=2)
    finally:
        cluster.shutdown()
    on = arm_stats(samples["obs_on"])
    off = arm_stats(samples["obs_off"])
    overhead_pct = round(
        (off["msgs_per_sec"] - on["msgs_per_sec"])
        / off["msgs_per_sec"] * 100.0, 2) if off["msgs_per_sec"] else None

    # Dist probe: 2-worker cluster, NullEngine builder, spout on worker 0
    # and everything else on worker 1 — the controller-merged windowed
    # utilization must attribute each component to its hosting worker.
    def dist_probe() -> dict:
        from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
        from storm_tpu.dist import DistCluster
        from tests.kafka_stub import KafkaStubBroker

        stub = KafkaStubBroker(partitions=2)
        cfg = null_cfg()
        cfg.broker.kind = "kafka"
        cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
        cfg.broker.input_topic = "bn-in"
        cfg.broker.output_topic = "bn-out"
        cfg.broker.dead_letter_topic = "bn-dlq"
        placement = {"kafka-spout": 0, "inference-bolt": 1,
                     "kafka-bolt": 1, "dlq-bolt": 1}
        n = 1500
        try:
            with DistCluster(2, env={"JAX_PLATFORMS": "cpu"}) as dc:
                producer = KafkaWireBroker(cfg.broker.bootstrap)
                for _ in range(n):
                    producer.produce("bn-in", tiny_payload.decode())
                dc.submit("bn-dist", cfg, placement, builder="null")
                prime = dc.utilization("bench")
                drained = await_outputs(lambda: stub.topic_size("bn-out"),
                                        n, grace_s=180.0)
                out = dc.utilization("bench")
                dc.drain(timeout_s=30)
                dc.kill()
        finally:
            stub.close()
        comps = out["components"]
        inf = comps.get("inference-bolt", {})
        spout = comps.get("kafka-spout", {})
        ok = bool(
            drained
            and prime["components"] == {}  # first call = zero-length window
            and comps
            and inf.get("busy_s", 0.0) > 0.0
            and inf.get("capacity") is not None
            and inf.get("dt_s", 0.0) > 0.0
            and spout.get("workers") == [0]
            and inf.get("workers") == [1])
        log(f"  dist probe: ok={ok} components={sorted(comps)}")
        return {"ok": ok, "drained": drained,
                "first_call_primed_empty": prime["components"] == {},
                "merged": comps,
                "per_worker": {str(i): w for i, w in out["workers"].items()}}

    dist = dist_probe()

    attribution_ok = bool(arm_a["correct"] and arm_b["correct"])
    overhead_ok = bool(overhead_pct is not None and overhead_pct <= 2.0)
    return {
        "metric": "bottleneck_attribution_arms_correct",
        "value": int(arm_a["correct"]) + int(arm_b["correct"]),
        "unit": ("induced-limiter arms the attributor named correctly "
                 "(majority of mid-drain /bottleneck route samples), "
                 "out of 2"),
        "arms": [arm_a, arm_b],
        "overhead_pct": overhead_pct,
        "obs_on": on,
        "obs_off": off,
        "repeats": repeats,
        "attribution_ok": attribution_ok,
        "overhead_ok": overhead_ok,
        "dist_utilization": dist,
        "dist_utilization_ok": dist["ok"],
        "config": "bottleneck+lenet5/null",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
        "note": ("per-tuple executor clock reads run in BOTH overhead "
                 "arms (they are constitutive, ~2 perf_counter calls per "
                 "tuple); the A/B prices the Observatory sampling + "
                 "attribution layer at interval_s=0.2. Negative overhead "
                 "= the on arm measured faster, i.e. the true cost is "
                 "below this host's run-to-run noise"),
    }


def run_plan(args) -> dict:
    """``--plan``: the SLO-aware joint planner's claim as one artifact
    (ROADMAP item 1, the InferLine-style offline solve). Three phases:

    1. CAPTURE fresh lenet5 cost curves through the real split-phase
       dispatch path (the --profile protocol, lenet5 only): the solve
       must run on curves THIS host just measured — a committed
       baseline is another machine's milliseconds.
    2. SOLVE for the cheapest feasible config against a target derived
       from the captured curve: offered rate = 0.45 x the bucket-64
       pipelined capacity (``--plan-rate`` overrides), p99 SLO =
       ``--plan-slo-ms`` (default 250 ms).
    3. A/B/C, interleaved at cell level, every arm at the SAME paced
       offered rate under the backlog guard:

       - ``default``: what you run without a planner — stock
         ``BatchConfig()`` (5 ms idle deadline, multi-bucket padding)
         at the stock ``TopologyConfig`` inference parallelism (4);
       - ``planned``: the solver's knobs verbatim via
         ``Plan.to_overrides()`` — one pinned bucket, solved deadline,
         solved replica count;
       - ``worstcase``: the planned batching at ACCEL_MAX_PARALLELISM
         replicas — provision-for-peak, the replica cost a solver-less
         operator pays to be safe.

    Verdict per arm: sink e2e p99 over the paced window <= SLO AND the
    offer neither tripped the backlog guard nor failed to drain (an
    unbounded queue is a miss no matter what the window's percentile
    says). The planned cell's measured per-stage means land next to the
    solver's predictions with a mean absolute error, so the artifact
    prices the cost model itself, not just the outcome."""
    from storm_tpu.config import (
        BatchConfig,
        ModelConfig,
        ShardingConfig,
        TopologyConfig,
    )
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.infer.continuous import _reset_registry
    from storm_tpu.infer.engine import InferenceEngine
    from storm_tpu.obs.profile import ensure_installed
    from storm_tpu.plan import CostModel, Target, solve
    from storm_tpu.runtime.autoscale import ACCEL_MAX_PARALLELISM
    from storm_tpu.runtime.cluster import LocalCluster

    cfg = CONFIGS["lenet5"]

    # ---- phase 1: capture this host's curves -----------------------------
    store = ensure_installed()
    store.reset()
    buckets = (16, 64, 256)
    # p95 terms feed the p99 prediction directly, so the curve needs more
    # than --profile's 8 samples per bucket to settle on a noisy host.
    warm_batches = max(24, 8 * args.repeats)
    rng = np.random.default_rng(0)
    eng = InferenceEngine(
        ModelConfig(name=cfg["model"], dtype="bfloat16",
                    input_shape=cfg["input_shape"],
                    num_classes=cfg["num_classes"]),
        ShardingConfig(data_parallel=0),
        BatchConfig(max_batch=max(buckets), buckets=buckets))
    engine_key = eng.profile_key
    for b in buckets:
        x = rng.standard_normal((b, *cfg["input_shape"])).astype(np.float32)
        log(f"[plan] capture {engine_key} bucket {b}: 1 cold + "
            f"{warm_batches} warm batches...")
        eng.dispatch((x,)).future.result()  # cold: compile entry
        # Bounded inflight (contrast --profile's full flood): the live
        # topology shares this host's cores with spout/decode/sink work,
        # so fully serialized captures overestimate capacity (measured:
        # ~2x), while an unbounded flood queues every dispatch behind
        # the ring and books the wait into h2d_ms. Two outstanding = the
        # split-phase ring's own depth: the overlap the serving path
        # actually runs, with no slot-queueing on top.
        pending = []
        for _ in range(warm_batches):
            pending.append(eng.dispatch((x,)))
            if len(pending) >= 2:
                pending.pop(0).future.result()
        for h in pending:
            h.future.result()
    # JSON round-trip: the solve consumes exactly what a committed
    # PROFILE_*.json would carry (string bucket keys, float rounding).
    snap = json.loads(json.dumps(store.snapshot()))

    # ---- phase 2: derive the target and solve ----------------------------
    model = CostModel(snap)
    pipe_ms = max(model.stage_ms(engine_key, 64, st) or 0.0
                  for st in ("h2d_ms", "compute_ms", "d2h_ms"))
    cap64 = 64 * 1e3 / max(pipe_ms, 1e-6)
    # 0.55x: BENCH_PLAN_r13's operating point (the default arm pads
    # into several buckets and compiles the small ones mid-stream) while
    # the planned single-bucket config still has ~2x headroom.
    rate = float(args.plan_rate) if args.plan_rate else round(0.55 * cap64)
    # SLO derived from the same curve (absolute ms are host-relative on a
    # shared CPU box): 3x the bucket-64 device p95, floored at 250 ms and
    # rounded up to 50 — tight enough that the fragmented default arm
    # can't limbo under it, loose enough that the solve isn't chasing
    # this host's scheduler jitter.
    p95_64 = model.stage_ms(engine_key, 64, "device_ms", q="p95") or 250.0
    slo = (float(args.plan_slo_ms) if args.plan_slo_ms
           else max(250.0, math.ceil(3.0 * p95_64 / 50.0) * 50.0))
    target = Target(rate_rows_s=rate, slo_p99_ms=slo)
    res = solve(snap, target, engine=engine_key)
    if not res.feasible:
        raise RuntimeError(f"planner found no feasible config: {res.why}")
    plan = res.plan
    pred = plan.prediction
    over = plan.to_overrides()["batch"]
    log(f"[plan] target {rate:.0f} rows/s @ p99 <= {slo:.0f} ms "
        f"(bucket-64 pipelined capacity ~{cap64:.0f} rows/s); solved: "
        f"parallelism={plan.parallelism} bucket={plan.bucket} "
        f"deadline={plan.deadline_ms:g}ms "
        f"-> predicted p99 {pred['p99_ms']:.1f} ms, util {pred['util']:.2f}")

    planned_bcfg = BatchConfig(
        max_batch=over["max_batch"], buckets=tuple(over["buckets"]),
        max_wait_ms=over["max_wait_ms"],
        pipeline_depth=over["pipeline_depth"],
        max_inflight=over["max_inflight"], eager=over["eager"])
    arm_setup = {
        "default": (TopologyConfig().inference_parallelism, BatchConfig()),
        "planned": (plan.parallelism, planned_bcfg),
        "worstcase": (ACCEL_MAX_PARALLELISM, planned_bcfg),
    }

    # ---- phase 3: interleaved A/B/C at one paced rate --------------------
    paced_s = max(args.latency_seconds, 10.0)
    repeats = max(1, min(args.repeats, 3))
    payloads = make_payloads(cfg)
    warm_msgs = 96
    stage_hists = ("batch_wait_ms", "dispatch_wait_ms", "h2d_ms",
                   "compute_ms", "d2h_ms", "device_ms")
    cluster = LocalCluster()

    def run_cell(arm, rep) -> dict:
        bolts, bcfg = arm_setup[arm]
        _reset_registry()
        broker = MemoryBroker(default_partitions=4)
        run_cfg, topo = build_topology(dict(cfg, bolts=bolts), broker, bcfg)
        name = f"plan-{arm}-{rep}"
        cluster.submit_topology(name, run_cfg, topo)
        # Warm outside the window: compiles + first batches land here.
        base = broker.topic_size("output")
        for i in range(warm_msgs):
            broker.produce("input", payloads[i % len(payloads)])
        if not await_outputs(lambda: broker.topic_size("output") - base,
                             warm_msgs, grace_s=180.0):
            cluster.kill_topology(name, wait_secs=2)
            raise RuntimeError(f"{name}: warmup never drained")
        reset_stage_hists(cluster, name)
        base = broker.topic_size("output")
        sent, aborted = offer_load(
            lambda i: broker.produce("input", payloads[i % len(payloads)]),
            rate, paced_s,
            backlog_fn=lambda s: s - (broker.topic_size("output") - base))
        drained = await_outputs(lambda: broker.topic_size("output") - base,
                                sent, grace_s=90.0)
        snap_m = cluster.metrics(name)
        cluster.kill_topology(name, wait_secs=2)
        e2e = snap_m.get("kafka-bolt", {}).get("e2e_latency_ms") or {}
        stages = {}
        for hist in stage_hists:
            h = snap_m.get("inference-bolt", {}).get(hist) or {}
            if h.get("count"):
                stages[hist] = round(h["mean"], 3)
        fill = snap_m.get("inference-bolt", {}).get("batch_fill") or {}
        p99 = e2e.get("p99")
        met = bool(not aborted and drained
                   and p99 is not None and p99 <= slo)
        log(f"  {arm} rep{rep} x{bolts}: "
            f"p99={'?' if p99 is None else round(p99, 1)} ms "
            f"{'MEETS' if met else 'MISSES'} SLO {slo:.0f}"
            f"{' [abort]' if aborted else ''}"
            f"{'' if drained else ' [undrained]'}")
        return {"p50_ms": e2e.get("p50"), "p99_ms": p99,
                "delivered": e2e.get("count"), "sent": sent,
                "aborted": aborted, "drained": drained, "slo_met": met,
                "stages_mean_ms": stages,
                "batch_fill_p50": fill.get("p50")}

    try:
        samples = run_interleaved(list(arm_setup), repeats, run_cell)
    finally:
        cluster.shutdown()

    def summarize(arm) -> dict:
        reps = samples[arm]
        p99s = sorted(r["p99_ms"] for r in reps if r["p99_ms"] is not None)
        n = len(p99s)
        med = (None if not p99s else round(
            p99s[n // 2] if n % 2 else (p99s[n // 2 - 1] + p99s[n // 2]) / 2,
            2))
        clean = all(not r["aborted"] and r["drained"] for r in reps)
        return {"replicas": arm_setup[arm][0],
                "batch": ("planned" if arm != "default" else "stock"),
                "p99_ms_median": med,
                "p99_ms_samples": [None if r["p99_ms"] is None
                                   else round(r["p99_ms"], 2) for r in reps],
                "clean": clean,
                "slo_met": bool(clean and med is not None and med <= slo)}

    arms = {arm: summarize(arm) for arm in arm_setup}

    # Planned arm: predicted-vs-measured per stage, on the rep closest to
    # the arm's median p99 (the representative window).
    med = arms["planned"]["p99_ms_median"]
    prep = min(samples["planned"],
               key=lambda r: abs((r["p99_ms"] or 1e9) - (med or 1e9)))
    stages_cmp = {}
    errs = []
    werr_num = werr_den = 0.0
    for stage, pred_ms in pred["stages"].items():
        meas = prep["stages_mean_ms"].get(stage)
        row = {"predicted_ms": round(pred_ms, 3), "measured_ms": meas}
        if meas is not None and meas > 0.05:
            err = abs(pred_ms - meas) / meas * 100.0
            row["abs_error_pct"] = round(err, 1)
            errs.append(err)
            # time-weighted: a 10x relative miss on a 0.5 ms stage is
            # not a 10x miss on the record's latency — weight each
            # stage's error by its measured share of the decomposition.
            werr_num += err * meas
            werr_den += meas
        stages_cmp[stage] = row
    mean_err = round(sum(errs) / len(errs), 1) if errs else None
    weighted_err = round(werr_num / werr_den, 1) if werr_den else None
    log(f"[plan] prediction error: mean {mean_err}% / time-weighted "
        f"{weighted_err}% over {len(errs)} stages; e2e p99 predicted "
        f"{pred['p99_ms']} ms vs measured {med} ms")

    return {
        "metric": "plan_slo_ab_lenet5",
        "value": mean_err,
        "unit": ("mean abs per-stage prediction error %% (solver's cost "
                 "model vs the planned arm's measured paced window)"),
        "target": target.to_dict(),
        "offered_rows_s": rate,
        "rate_derivation": (f"--plan-rate override" if args.plan_rate else
                            f"0.45 x bucket-64 pipelined capacity "
                            f"({cap64:.0f} rows/s) from the captured curve"),
        "paced_seconds": paced_s,
        "repeats": repeats,
        "plan": plan.to_dict(),
        "solver": {"considered": res.considered,
                   "engines_ranked": res.engines_ranked},
        "coverage": res.coverage,
        "arms": arms,
        "samples": samples,
        "replica_cost": {"planned": plan.parallelism,
                         "worstcase": ACCEL_MAX_PARALLELISM,
                         "default": arm_setup["default"][0]},
        "prediction_vs_measured": {
            "stages": stages_cmp,
            "mean_abs_error_pct": mean_err,
            "time_weighted_abs_error_pct": weighted_err,
            "predicted_p99_ms": pred["p99_ms"],
            "measured_p99_ms": med,
        },
        "gates": {
            "planned_meets_slo": arms["planned"]["slo_met"],
            "default_misses_slo": not arms["default"]["slo_met"],
            "worstcase_meets_slo": arms["worstcase"]["slo_met"],
            "planned_cheaper_than_worstcase":
                plan.parallelism < ACCEL_MAX_PARALLELISM,
        },
        "config": "plan",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
        "note": ("single-core CPU host: absolute ms are this host's; the "
                 "structural claims (solver picks a config that meets the "
                 "SLO the stock config misses at this rate, at fewer "
                 "replicas than worst-case provisioning, with per-stage "
                 "predictions within the reported error) are what travel. "
                 "An aborted/undrained arm counts as an SLO miss: an "
                 "open-loop backlog integrates queueing without bound"),
    }


def run_autoscale(args) -> dict:
    """``--autoscale``: the reference's scaling thesis as a measured closed
    loop (README.md:13-14 — "input rate rises, latency grows -> scale the
    inference bolts"; there, a compile-time constant + rebuild,
    MainTopology.java:27). Here: start at inference parallelism 1 and ramp
    the offered rate adaptively (0.5x the probed parallelism-1 capacity,
    growing 1.3x per stage) until the latency-driven Autoscaler fires;
    after a drain, the scaled system must HOLD the breach rate with sink
    p50 under ``--slo-ms``. Reports the fraction of hold windows meeting
    the SLO plus the decision timeline (stalled windows count as misses)."""
    import jax

    from storm_tpu.config import BatchConfig
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.runtime.cluster import LocalCluster

    cfg = dict(CONFIGS[args.config])
    if "model" not in cfg:
        sys.exit("--autoscale needs a single-model config")
    cfg["bolts"] = 1  # start minimal; the autoscaler earns the rest
    n_dev = len(jax.devices())
    log(f"devices: {jax.devices()}")
    payloads = make_payloads(cfg, instances_per_msg=args.instances_per_msg)
    batch_cfg = BatchConfig(
        max_batch=args.max_batch or cfg["max_batch"],
        max_wait_ms=args.max_wait_ms,
        buckets=cfg["buckets"],
        max_inflight=args.inflight or 2,
    )
    broker = MemoryBroker(default_partitions=4)
    run_cfg, topo = build_topology(cfg, broker, batch_cfg, args.transfer_dtype,
                                   args.chunk, args.weights)
    cluster = LocalCluster()
    try:
        return _run_autoscale_inner(args, cfg, cluster, broker, payloads,
                                    n_dev, run_cfg, topo)
    finally:
        cluster.shutdown()


def _run_autoscale_inner(args, cfg, cluster, broker, payloads, n_dev,
                         run_cfg, topo) -> dict:
    from storm_tpu.runtime.autoscale import (
        ACCEL_MAX_PARALLELISM,
        AutoscalePolicy,
        Autoscaler,
    )

    t0 = time.time()
    cluster.submit_topology("bench-slo", run_cfg, topo)
    log(f"submitted + warmed up in {time.time() - t0:.1f}s")

    slo_ms = args.slo_ms

    def start_scaler():
        async def mk():
            rt = cluster._cluster.runtime("bench-slo")
            return Autoscaler(rt, AutoscalePolicy(
                component="inference-bolt", latency_source="kafka-bolt",
                high_ms=slo_ms, low_ms=slo_ms / 4,
                # On a batching TPU the reference's "more bolts" thesis
                # saturates fast: operator parallelism is PIPELINING
                # depth, and past ~2-3 tasks it fragments micro-batches
                # (8 tasks measured ~15% SLOWER than 1 in this
                # environment — each bolt's deadline flushes tiny
                # batches). Cap where pipelining still wins.
                min_parallelism=1,
                max_parallelism=ACCEL_MAX_PARALLELISM,
                interval_s=2.0, cooldown=6,
            )).start()

        return cluster._run(mk())

    scaler = start_scaler()

    # Every produced message (offer stages AND capacity probes) counts
    # into `sent`, and every drain awaits topic_size >= sent — otherwise
    # probe outputs not in the accounting let a "drain" return while the
    # highest-queue-latency tuples are still in flight, polluting the
    # freshly reset histograms (the contamination post_scale_windows_met
    # exists to exclude).
    probe = 96
    sent = 0

    def probe_capacity() -> float:
        nonlocal sent
        base = broker.topic_size("output")
        t0 = time.perf_counter()
        for i in range(probe):
            broker.produce("input", payloads[i % len(payloads)])
        sent += probe
        if not await_outputs(lambda: broker.topic_size("output") - base,
                             probe, grace_s=180.0):
            # A garbage capacity (partial / 180s) would re-base the demo
            # to a meaningless rate and leave stragglers contaminating
            # the next stage — same policy as the cap1 probe: bail.
            sys.exit("autoscale capacity probe never drained; "
                     "system unhealthy")
        return probe / (time.perf_counter() - t0)

    cap1 = probe_capacity()
    log(f"parallelism-1 capacity ~{cap1:.0f} msg/s; SLO p50 <= {slo_ms:.0f} ms")
    cluster.reset_histogram("bench-slo", "kafka-bolt", "e2e_latency_ms")

    def parallelism_now() -> int:
        async def f():
            return cluster._cluster.runtime("bench-slo")\
                .parallelism_of("inference-bolt")

        return cluster._run(f())

    timeline = []  # (t, offered_rate, windowed_p50, parallelism, phase)
    window_s = 2.5
    t_start = time.perf_counter()

    def offer_stage(mult: float, seconds: float, phase: str,
                    stop_fn=None) -> None:
        nonlocal sent
        rate = max(4.0, cap1 * mult)
        log(f"{phase}: offering {rate:.0f} msg/s ({mult:.1f}x cap1) "
            f"for {seconds:.0f}s")
        interval = 1.0 / rate
        stage_end = time.perf_counter() + seconds
        nxt = time.perf_counter()
        next_window = time.perf_counter() + window_s
        while time.perf_counter() < stage_end:
            now = time.perf_counter()
            while nxt <= now:
                broker.produce("input", payloads[sent % len(payloads)])
                sent += 1
                nxt += interval
            if now >= next_window:
                next_window = now + window_s
                lat = cluster.metrics(
                    "bench-slo")["kafka-bolt"]["e2e_latency_ms"]
                p50 = lat["p50"]
                par = parallelism_now()
                cluster.reset_histogram(
                    "bench-slo", "kafka-bolt", "e2e_latency_ms")
                # Record EVERY window: a stalled system (no deliveries ->
                # empty histogram -> p50 None) is the worst breach there
                # is and must count against the SLO, not vanish.
                timeline.append((round(now - t_start, 1), round(rate),
                                 None if p50 is None else round(p50, 1),
                                 par, phase))
                log(f"  t={now - t_start:5.1f}s rate={rate:4.0f} "
                    f"p50={'stalled' if p50 is None else f'{p50:.1f}ms'} "
                    f"parallelism={par}")
                if stop_fn is not None and stop_fn():
                    # Stop offering the moment the decision lands: keeping
                    # the overload flowing while the replica spins up is
                    # what integrated the round-3 multi-second windows.
                    log("  scale-up decision landed; ending stage early")
                    return
            time.sleep(min(0.002, max(0.0, nxt - time.perf_counter())))

    # Phase 1 RAMP: raise offered load until the autoscaler actually fires
    # (latency through the SLO -> scale-up; the reference's README
    # scenario). The burst-probe capacity estimate is noisy under host
    # load, so multipliers ADAPT: grow 1.3x per stage until a scale-up
    # decision lands, then run one more stage for it to take effect.
    def ups_so_far():
        return [d for d in scaler.decisions if d[0] == "up"]

    mult = 0.5
    breach_mult = None
    settle = 0
    for _ in range(12):
        n_ups = len(ups_so_far())
        offer_stage(mult, args.stage_seconds,
                    "ramp" if breach_mult is None else "settle",
                    stop_fn=lambda: len(ups_so_far()) > n_ups)
        if len(ups_so_far()) > n_ups:
            # Warm scale-up protocol: the replica was prewarmed off-loop
            # by rebalance; what remains is the REACTION backlog (tuples
            # offered above capacity while the scaler decided). Drain it
            # and reset the histograms so every post-scale window
            # measures the scaled system, not the queue it inherited.
            log("draining reaction backlog after scale-up...")
            await_outputs(lambda: broker.topic_size("output"), sent,
                          grace_s=120.0)
            if breach_mult is None:
                breach_mult = mult
            # Post-scale stages offer what the SCALED system sustains:
            # on one chip, bolt parallelism buys pipelining, not FLOPs —
            # re-hammering the breach rate past the scaled capacity just
            # measures a queue (the round-3 multi-second settle windows).
            # Burst probes overestimate SUSTAINED capacity (they drain at
            # peak pipelining), so also cap at 1.0x cap1: rates beyond
            # one chip's device throughput need more chips (dp mesh),
            # not more bolts.
            mult = min(mult, 0.8 * probe_capacity() / cap1, 1.0)
            # Reset AFTER the probe (like the cap1/cap_scaled sites): the
            # probe's burst queue latencies must not land in the first
            # settle window or trigger a spurious second scale-up.
            cluster.reset_histogram(
                "bench-slo", "kafka-bolt", "e2e_latency_ms")
            log(f"settle rate re-based to {mult:.2f}x cap1")
        if ups_so_far():
            if settle >= 2:
                break  # scaler had two settle stages after first scale-up
            settle += 1
        if breach_mult is None:
            # fine-grained growth: the breach rate should sit just past
            # parallelism-1 capacity, inside what the scaled system can
            # absorb — 1.5x jumps overshoot both
            mult *= 1.3
    # Drain the ramp backlog (its queueing belongs to the undersized
    # system, not the scaled one), then measure what the SCALED system
    # sustains: a hold at the rate that broke the parallelism-1 system.
    log("draining ramp backlog...")
    await_outputs(lambda: broker.topic_size("output"), sent, grace_s=120.0)
    # Re-probe the SCALED system's capacity: when cap1 was under-probed
    # (a loaded host), the breach rate can exceed what ANY parallelism
    # absorbs — holding there fails by construction. Hold at the lower of
    # the breach rate and 80% of the scaled capacity; as long as that is
    # above cap1, the thesis (scaling bought sustainable rate within SLO)
    # is demonstrated, and hold_rate_vs_cap1 in the JSON says by how much.
    cap_scaled = probe_capacity()
    log(f"scaled capacity ~{cap_scaled:.0f} msg/s "
        f"(parallelism {parallelism_now()})")
    cluster.reset_histogram("bench-slo", "kafka-bolt", "e2e_latency_ms")
    hold_mult = breach_mult if breach_mult is not None else mult
    # Same sustained-vs-burst honesty as the settle re-base: burst probes
    # overestimate, and one chip's sustained ceiling is ~cap1 regardless
    # of bolt count.
    hold_mult = min(hold_mult, 0.8 * cap_scaled / cap1, 1.0)
    offer_stage(hold_mult, args.stage_seconds * 1.5, "hold")
    await_outputs(lambda: broker.topic_size("output"), sent, grace_s=60.0)
    decisions = scaler.decisions if hasattr(scaler, "decisions") else []
    cluster._run(scaler.stop())
    cluster.shutdown()

    ups = [d for d in decisions if d[0] == "up"]
    # Judge the loop on its job: the scaled system must hold the rate that
    # broke the parallelism-1 system, within SLO.
    hold = [w for w in timeline if w[4] == "hold"]
    met = [w for w in hold if w[2] is not None and w[2] <= slo_ms]
    pct = 100.0 * len(met) / len(hold) if hold else 0.0
    final_par = timeline[-1][3] if timeline else 1
    # Warm scale-up criterion (VERDICT r3 weak #3): every window AFTER a
    # scale-up took effect (settle + hold) must be clean — no stalled
    # (null) windows, no multi-second p50s; the only excused breaches are
    # the ramp windows where the overload IS the scaler's trigger.
    post = [w for w in timeline if w[4] in ("settle", "hold")]
    post_p50s = [w[2] for w in post]
    post_met = [p for p in post_p50s if p is not None and p <= slo_ms]
    ramp_p50s = [w[2] for w in timeline if w[4] == "ramp"]
    log(f"decisions: {decisions}")
    log(f"hold windows ({hold_mult:.1f}x cap1) under SLO: "
        f"{len(met)}/{len(hold)}; post-scale windows under SLO: "
        f"{len(post_met)}/{len(post)}")
    return {
        "metric": f"{cfg['metric']}_autoscale_slo_windows_met",
        "value": round(pct, 1),
        "unit": "% of hold-phase windows with p50 <= SLO",
        "hold_rate_vs_cap1": round(hold_mult, 2),
        "slo_ms": slo_ms,
        "scaled": [d[1:] for d in ups],
        "final_parallelism": final_par,
        "post_scale_windows_met": f"{len(post_met)}/{len(post)}",
        "post_scale_stalled_windows": sum(
            1 for p in post_p50s if p is None),
        "worst_post_scale_p50_ms": max(
            (p for p in post_p50s if p is not None), default=None),
        "worst_ramp_p50_ms": max(
            (p for p in ramp_p50s if p is not None), default=None),
        "timeline": timeline,
        **device_info(),
        "config": f"{args.config}+autoscale",
    }


def run_decode(args) -> dict:
    """``--decode``: the round-20 stateful decode serving evidence.

    Three measured phases on the in-process runtime (the decode tier is
    pure-numpy, so there is no wire/broker confound to control for):

    1. **Throughput** — N sessions with ragged budgets (8/24/48 tokens)
       drive the DecodeBolt through ``ring_fields_grouping`` sticky
       routing; the headline is delivered tokens/s over the
       first-submit -> last-ack window, median of ``--repeats``
       back-to-back cells (each on a fresh engine + arena). TTFT and
       per-token p50/p99 come from the bolt's own histograms.
    2. **Exactly-once audit** — an injected mid-stream failure
       (``fail_after_tokens``) at a commit boundary; the spout replays
       the request and the captured per-session token streams must be
       gapless and duplicate-free.
    3. **Rolling-restart probe** — long-budget sessions, a graceful kill
       whose drain window is too short for them to finish (so the
       executor's flush path migrates them), then a resubmit: >= 95% of
       the sessions live at the kill must come back ``restored == "kv"``
       with ZERO cold starts, and the cross-restart token streams must
       stay gapless/duplicate-free.

    The artifact also embeds the observatory's view of the run (decode
    session rows, KV arena occupancy, the decode engine in the
    occupancy/profile sweeps) — the "sessions are first-class in the
    observatories" claim as captured JSON.
    """
    import asyncio
    import tempfile

    from storm_tpu.config import Config
    from storm_tpu.decode import DecodeBolt, DecodeConfig, SessionSpout
    from storm_tpu.decode import decode_stats
    from storm_tpu.decode.engine import _reset_engines
    from storm_tpu.obs import Observatory
    from storm_tpu.runtime import TopologyBuilder
    from storm_tpu.runtime.base import Bolt
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    repeats = max(1, args.repeats)
    n_sessions = args.decode_sessions
    shapes = (8, 24, 48)

    class Cap(Bolt):
        seen = []

        async def execute(self, t):
            Cap.seen.append((t.get("session_id"), t.get("token_index")))
            self.collector.ack(t)

    def mk_reqs(n, tag, budget=None):
        return [{"session_id": f"{tag}-{i:04d}",
                 "prompt": f"decode bench {tag} session {i}",
                 "max_new_tokens": budget or shapes[i % len(shapes)]}
                for i in range(n)]

    def build(reqs, dcfg, parallelism=2):
        b = TopologyBuilder()
        b.set_spout("requests", SessionSpout(reqs), 1)
        b.set_bolt("decode-bolt", DecodeBolt(dcfg), parallelism) \
            .ring_fields_grouping("requests", "session_id")
        b.set_bolt("capture", Cap(), 1).shuffle_grouping("decode-bolt")
        return b.build()

    def topo_cfg(state_dir=None):
        cfg = Config()
        cfg.topology.message_timeout_s = 60.0
        cfg.topology.checkpoint_interval_s = 5.0
        if state_dir:
            cfg.topology.state_dir = state_dir
        return cfg

    def audit(seen):
        by = {}
        for sid, idx in seen:
            by.setdefault(sid, []).append(idx)
        dups = sum(len(v) - len(set(v)) for v in by.values())
        gapped = sum(1 for v in by.values()
                     if sorted(set(v)) != list(range(len(set(v)))))
        return {"sessions": len(by), "tokens": len(seen),
                "duplicates": dups, "gapped_sessions": gapped,
                "clean": dups == 0 and gapped == 0}

    async def wait_acked(rt, n, deadline_s=120.0):
        sp = rt.spout_execs["requests"][0].spout
        t_end = time.perf_counter() + deadline_s
        while len(sp.acked) < n and time.perf_counter() < t_end:
            await asyncio.sleep(0.01)
        return sp

    async def throughput_cell(rep):
        _reset_engines()
        Cap.seen = []
        reqs = mk_reqs(n_sessions, f"tp{rep}")
        cluster = AsyncLocalCluster()
        rt = await cluster.submit(
            f"decode-bench-{rep}", topo_cfg(),
            build(reqs, DecodeConfig(seed=args.seed, arena_blocks=64)))
        obs = Observatory(rt)  # enables the profile sink for this cell
        t0 = time.perf_counter()
        sp = await wait_acked(rt, len(reqs))
        elapsed = time.perf_counter() - t0
        assert len(sp.acked) == len(reqs), "throughput cell did not drain"
        ttft = rt.metrics.histogram("decode-bolt", "decode_ttft_ms")
        tok = rt.metrics.histogram("decode-bolt", "decode_token_ms")
        cell = {
            "tokens": len(Cap.seen),
            "sessions": len(reqs),
            "elapsed_s": round(elapsed, 3),
            "tokens_per_s": round(len(Cap.seen) / elapsed, 1),
            "ttft_p50_ms": round(ttft.percentile(50), 3),
            "ttft_p99_ms": round(ttft.percentile(99), 3),
            "token_p50_ms": round(tok.percentile(50), 3),
            "token_p99_ms": round(tok.percentile(99), 3),
            "audit": audit(Cap.seen),
        }
        snap = obs.snapshot()
        cell["observatory"] = {
            "decode": {k: snap["decode"][k]
                       for k in ("sessions_live", "tokens_emitted")},
            "store_rows": len(snap["decode"]["stores"]),
            "engine_rows": [e for e in snap["decode"]["engines"]],
            "occupancy": [r for r in snap["occupancy"]
                          if "decode" in r["engine"]],
            "profile_keys": sorted(obs.profile.snapshot()["engines"]),
        }
        await cluster.shutdown()
        return cell

    async def audit_cell():
        """Injected mid-stream failure at a commit boundary; the replay
        must resume above the watermark."""
        _reset_engines()
        Cap.seen = []
        reqs = mk_reqs(4, "audit", budget=24)
        cluster = AsyncLocalCluster()
        rt = await cluster.submit(
            "decode-audit", topo_cfg(),
            build(reqs, DecodeConfig(seed=args.seed, arena_blocks=16),
                  parallelism=1))
        rt.bolt_execs["decode-bolt"][0].bolt.fail_after_tokens = 5
        sp = await wait_acked(rt, len(reqs))
        out = audit(Cap.seen)
        out["injected_failures"] = 1
        out["request_replays"] = len(sp.failed)
        out["all_acked"] = len(sp.acked) == len(reqs)
        await cluster.shutdown()
        return out

    async def migration_probe():
        _reset_engines()
        Cap.seen = []
        reqs = mk_reqs(12, "mig", budget=150)
        state_dir = tempfile.mkdtemp(prefix="storm-decode-bench-")
        cfg = topo_cfg(state_dir)
        dcfg = DecodeConfig(seed=args.seed, arena_blocks=16,
                            drain_mode="migrate")

        cluster = AsyncLocalCluster()
        rt = await cluster.submit("decode-migrate", cfg,
                                  build(reqs, dcfg))
        t_end = time.perf_counter() + 60.0
        while time.perf_counter() < t_end:
            if len({s for s, _ in Cap.seen}) == len(reqs) \
                    and len(Cap.seen) >= 4 * len(reqs):
                break
            await asyncio.sleep(0.01)
        bolts = [e.bolt for e in rt.bolt_execs["decode-bolt"]]
        live_before = sum(
            1 for b in bolts for s in b.sessions.all() if not s.done)
        # Graceful kill with a drain window the 150-token budgets cannot
        # finish inside: flush() suspends the sessions at their commit
        # boundaries and the final checkpoint carries KV.
        await cluster.kill("decode-migrate", wait_secs=0.2)
        tokens_before = len(Cap.seen)

        rt2 = await cluster.submit("decode-migrate", cfg,
                                   build(reqs, dcfg))
        sp2 = await wait_acked(rt2, len(reqs))
        bolts2 = [e.bolt for e in rt2.bolt_execs["decode-bolt"]]
        kv_restored = sum(1 for b in bolts2 for s in b.sessions.all()
                          if s.restored == "kv")
        cold = sum(b.sessions.sessions_cold for b in bolts2)
        out = {
            "sessions": len(reqs),
            "live_at_kill": live_before,
            "tokens_before_kill": tokens_before,
            "kv_restored": kv_restored,
            "cold_started": cold,
            "survived_frac": round(kv_restored / max(1, live_before), 3),
            "all_acked_after_restart": len(sp2.acked) == len(reqs),
            "audit_across_restart": audit(Cap.seen),
        }
        await cluster.shutdown()
        return out

    log(f"decode: throughput x{repeats} "
        f"({n_sessions} sessions, budgets {shapes})")
    cells = [asyncio.run(throughput_cell(r)) for r in range(repeats)]
    log("decode: exactly-once audit (injected failure)")
    audit_out = asyncio.run(audit_cell())
    log("decode: rolling-restart migration probe")
    probe = asyncio.run(migration_probe())

    rates = sorted(c["tokens_per_s"] for c in cells)
    headline = rates[len(rates) // 2]
    gates = {
        "tokens_per_s_positive": headline > 0,
        "exactly_once_audit_clean": bool(audit_out["clean"]
                                         and audit_out["all_acked"]),
        "migration_survived_ge_95pct": probe["survived_frac"] >= 0.95,
        "migration_zero_cold_started": probe["cold_started"] == 0,
        "migration_audit_clean": bool(
            probe["audit_across_restart"]["clean"]),
        "observatory_decode_rows": bool(
            cells[-1]["observatory"]["engine_rows"]
            and cells[-1]["observatory"]["occupancy"]),
    }
    log(f"decode: headline {headline} tokens/s; gates "
        + ", ".join(f"{k}={'OK' if v else 'FAIL'}"
                    for k, v in gates.items()))
    return {
        "metric": "decode_tokens_per_s_r20",
        "value": headline,
        "unit": ("delivered decode tokens/s, e2e spout->capture on the "
                 "in-process runtime (numpy on the host CPU, no device "
                 "path yet), median of "
                 f"{repeats} back-to-back cells on fresh arenas"),
        "tokens_per_s_samples": rates,
        "cells": cells,
        "exactly_once_audit": audit_out,
        "migration_probe": probe,
        "gates": gates,
        "sessions_per_cell": n_sessions,
        "token_budgets": list(shapes),
        "protocol": ("closed-loop SessionSpout drive; per-cell fresh "
                     "shared engine + arena (_reset_engines) so no cell "
                     "inherits warm KV; throughput window is first "
                     "submit -> last request ack; TTFT/per-token "
                     "percentiles from the bolt's own histograms over "
                     "the whole cell; audit = per-session token_index "
                     "streams gapless + duplicate-free at the capture "
                     "bolt; migration probe kills gracefully with a "
                     "drain window shorter than the sessions' budgets "
                     "so flush() must migrate, then resubmits against "
                     "the same durable state dir"),
        **HOST_ONLY,
        "config": "decode",
        "capture_session": _new_capture_session(),
        "code_version": _code_version(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="resnet20", choices=sorted(CONFIGS))
    ap.add_argument("--all", action="store_true",
                    help="run EVERY baseline config in one process and "
                         "print a single JSON array (one driver-verifiable "
                         "capture of the whole matrix)")
    ap.add_argument("--messages", type=int, default=4096,
                    help="messages for the throughput phase")
    ap.add_argument("--instances-per-msg", type=int, default=1)
    ap.add_argument("--latency-seconds", type=float, default=8.0)
    ap.add_argument("--max-wait-ms", type=float, default=25.0)
    ap.add_argument("--max-batch", type=int, default=0, help="override config max_batch")
    ap.add_argument("--buckets", default="",
                    help="comma-separated padding buckets override, e.g. 64,1024")
    ap.add_argument("--eager", action="store_true",
                    help="work-conserving dispatch in the latency phase: "
                         "flush when a device slot frees instead of aging "
                         "to max_wait_ms")
    ap.add_argument("--inflight", type=int, default=0,
                    help="batches in flight per operator (BatchConfig."
                         "max_inflight); 0 = auto (4 for the throughput "
                         "phase to amortize launch RTT, 2 for latency)")
    ap.add_argument("--weights", default="float",
                    choices=["float", "int8", "int8_fused"],
                    help="weight precision: int8 = w8a16 (XLA-fused dequant), "
                         "int8_fused = Pallas fused dequant-matmul for dense")
    ap.add_argument("--transfer-dtype", default=None, choices=["uint8"],
                    help="quantize the host->device wire to uint8 (4x fewer "
                         "bytes than f32 over the link; lossy, opt-in)")
    ap.add_argument("--chunk", type=int, default=4,
                    help="spout chunking: records per emitted tuple (1 = "
                         "per-record tuples, the reference's granularity; "
                         "N>1 cuts ledger/executor overhead for small "
                         "payloads at chunk-replay granularity). Default 4: "
                         "interleaved A/B beat chunk=1 in every pairing "
                         "(BENCH_NOTES.md)")
    ap.add_argument("--skip-latency", action="store_true")
    ap.add_argument("--latency-breakdown", action="store_true",
                    help="two-pass latency evidence: framework-only "
                         "(NullEngine, device time = 0) percentiles + "
                         "per-stage attribution of the device path")
    ap.add_argument("--pipeline-compare", action="store_true",
                    help="split-phase pipeline evidence: serialized engine "
                         "(pipeline_depth=0) vs pipelined dispatch/fetch in "
                         "one artifact — dispatch_queue+device p50 and "
                         "h2d/compute/d2h substages, same code version")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="engine split-phase pipeline depth override "
                         "(default: BatchConfig default; 0 disables)")
    ap.add_argument("--autoscale", action="store_true",
                    help="closed-loop SLO demo: ramp offered load and let "
                         "the latency-driven autoscaler hold p50 under "
                         "--slo-ms by rebalancing inference parallelism")
    ap.add_argument("--autoscale-capacity", action="store_true",
                    help="the capacity half of the scaling thesis: the "
                         "same closed loop over per-replica latency-bound "
                         "backends, holding ABOVE parallelism-1 capacity "
                         "within SLO (no 1.0x cap)")
    ap.add_argument("--capacity-backend", choices=("paced", "engine"),
                    default="paced",
                    help="--autoscale-capacity backend: 'paced' = per-"
                         "replica latency-bound endpoints (scale-out owns "
                         "real capacity); 'engine' = per-replica PRIVATE "
                         "lenet5 engines (real compute; on a single-core "
                         "host the artifact documents why no gain is "
                         "possible instead of claiming one)")
    ap.add_argument("--qos-overload", action="store_true",
                    help="admission control & QoS demo: 2x sustained-"
                         "capacity offered load, no-QoS baseline vs QoS "
                         "(admission + EDF lanes + adaptive shedding) in "
                         "one artifact — high-lane p99 vs --slo-ms and "
                         "within-SLO goodput vs baseline")
    ap.add_argument("--slo-ms", type=float, default=600.0,
                    help="p50 target for --autoscale (default 600ms)")
    ap.add_argument("--stage-seconds", type=float, default=20.0,
                    help="seconds per offered-load stage in --autoscale")
    ap.add_argument("--cascade-compare", action="store_true",
                    help="flagship-only vs confidence-gated cascade on the "
                         "digits checkpoints (interleaved median-of-N, "
                         "ack-gated windows, operating point from "
                         "ACCURACY_CASCADE_r09.json) + a sampled run "
                         "capturing the escalation evidence")
    ap.add_argument("--chaos-recovery", action="store_true",
                    help="resilience evidence run (BENCH_CHAOS): worker "
                         "SIGKILL + wire brownout under steady load on a "
                         "3-worker CPU mesh with measured time-to-recover "
                         "and bounded replays, plus the exactly-once soak "
                         "under engine-hang chaos")
    ap.add_argument("--controller-failover", action="store_true",
                    help="durable control plane evidence run "
                         "(BENCH_FAILOVER): SIGKILL the controller of a "
                         "3-worker CPU mesh mid-stream, reattach a new one "
                         "from the journal with zero survivor recompiles, "
                         "then rolling-restart every worker under load with "
                         "a goodput floor, plus the exactly-once soak under "
                         "--drain-drill")
    ap.add_argument("--_failover-ctl", dest="failover_ctl", default="",
                    help=argparse.SUPPRESS)
    ap.add_argument("--wire-compare", action="store_true",
                    help="A/B the JSON vs binary inter-worker tuple wire "
                         "on a 3-worker CPU mesh (NullEngine framework "
                         "ceiling + lenet5 row, two payload sizes, "
                         "interleaved repeats) -> BENCH_WIRE artifact")
    ap.add_argument("--plan", action="store_true",
                    help="SLO-aware planner A/B/C: capture lenet5 curves, "
                         "solve for the cheapest config meeting a derived "
                         "(rate, p99 SLO) target, then default vs planned "
                         "vs worst-case-provisioned arms at one paced rate "
                         "-> BENCH_PLAN artifact (per-stage predicted vs "
                         "measured + mean prediction error)")
    ap.add_argument("--plan-rate", type=float, default=0.0,
                    help="--plan offered rate in rows/s (0 = derive 0.45x "
                         "the captured bucket-64 pipelined capacity)")
    ap.add_argument("--plan-slo-ms", type=float, default=0.0,
                    help="--plan p99 SLO target in ms (0 = 250)")
    ap.add_argument("--profile", action="store_true",
                    help="capture the online cost profiler's per-(engine, "
                         "bucket) stage curves (lenet5 + resnet20 x 3 "
                         "buckets, real dispatch path) -> PROFILE "
                         "artifact; round-trips as the regression "
                         "sentinel's baseline")
    ap.add_argument("--copy-ledger", action="store_true",
                    help="copy-ledger evidence run: per-stage bytes/record "
                         "decomposition (string+json vs raw+binary arms, "
                         "NullEngine + lenet5 on a 3-worker mesh) plus the "
                         "ledger's own on/off throughput overhead")
    ap.add_argument("--zerocopy", action="store_true",
                    help="zero-copy batch-native plane evidence run: "
                         "r19 default dist data plane (raw+frames+wire "
                         "v2+shm+tensor payloads) vs the r18 headline "
                         "plane, interleaved on a 3-worker mesh -> "
                         "BENCH_ZEROCOPY_r19 artifact (gates: >=3x "
                         "framework ceiling, amp <=1.5, paced p50 "
                         "<50ms, shm engaged)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="profiling-on vs profiling-off interleaved A/B "
                         "on the warm engine dispatch path -> "
                         "BENCH_OBS_OVERHEAD artifact (bar: <= 2%%)")
    ap.add_argument("--slo-burn", action="store_true",
                    help="induced 2x overload with the Observatory "
                         "attached: burn-rate gauge vs shed_level "
                         "timeline + live /profile route probe -> "
                         "BENCH_SLO_BURN artifact")
    ap.add_argument("--decode", action="store_true",
                    help="stateful decode serving evidence: tokens/s "
                         "headline + TTFT/per-token percentiles, "
                         "injected-failure exactly-once audit, and the "
                         "rolling-restart KV-migration probe -> "
                         "BENCH_DECODE artifact")
    ap.add_argument("--decode-sessions", type=int, default=48,
                    help="sessions per decode throughput cell "
                         "(ragged 8/24/48-token budgets)")
    ap.add_argument("--fleet", action="store_true",
                    help="trace-driven fleet matrix: every scenario "
                         "(classify/cascade/serve-path/decode) x every "
                         "traffic pattern (heavy-tail/diurnal/flash-crowd) "
                         "scored on goodput, per-lane p99, SLO burn, and "
                         "shed fraction -> SCORECARD artifact")
    ap.add_argument("--fleet-scenarios", default=None,
                    help="comma list restricting --fleet scenarios "
                         "(default: all four)")
    ap.add_argument("--seed", type=int, default=16,
                    help="base RNG seed for --fleet trace generation "
                         "(recorded per cell; same seed -> byte-identical "
                         "traces)")
    ap.add_argument("--bottleneck", action="store_true",
                    help="bottleneck attributor vs two induced limiters "
                         "(inference-bound lenet5 vs spout-bound null "
                         "engine, verdicts via live /bottleneck route) + "
                         "Observatory on/off interleaved A/B + dist "
                         "merged-utilization probe -> BENCH_BOTTLENECK "
                         "artifact (bars: both arms named, <= 2%%)")
    ap.add_argument("--slo-sweep", action="store_true",
                    help="sweep offered rate; report latency-vs-rate curve "
                         "+ max img/s/chip under measured p50 <= 50/100/"
                         "200 ms (the joint north star, VERDICT r3 #2)")
    ap.add_argument("--sweep-seconds", type=float, default=8.0,
                    help="seconds per rate point in --slo-sweep")
    ap.add_argument("--repeats", type=int, default=3,
                    help="throughput drains per capture for single-model "
                         "configs: the default run reports the median of N "
                         "back-to-back drains (samples in the JSON); under "
                         "--all the N measurements are interleaved at "
                         "matrix level instead (min/median/max recorded, "
                         "median is the headline; 1 = old single-capture). "
                         "The multi/autoscale/latency-breakdown demo rows "
                         "stay single-capture")
    args = ap.parse_args()
    from storm_tpu.infer.engine import enable_compile_cache

    enable_compile_cache()
    if args.failover_ctl:
        sys.exit(run_failover_ctl(args.failover_ctl))
    if args.controller_failover:
        print(json.dumps(run_controller_failover(args)))
        return
    if args.plan:
        print(json.dumps(run_plan(args)))
        return
    if args.profile:
        print(json.dumps(run_profile(args)))
        return
    if args.copy_ledger:
        print(json.dumps(run_copy_ledger(args)))
        return
    if args.zerocopy:
        print(json.dumps(run_zerocopy(args)))
        return
    if args.obs_overhead:
        print(json.dumps(run_obs_overhead(args)))
        return
    if args.slo_burn:
        print(json.dumps(run_slo_burn(args)))
        return
    if args.decode:
        print(json.dumps(run_decode(args)))
        return
    if args.fleet:
        print(json.dumps(run_fleet_matrix(args)))
        return
    if args.bottleneck:
        print(json.dumps(run_bottleneck(args)))
        return
    if args.cascade_compare:
        print(json.dumps(run_cascade_compare(args)))
        return
    if args.wire_compare:
        print(json.dumps(run_wire_compare(args)))
        return
    if args.chaos_recovery:
        print(json.dumps(run_chaos_recovery(args)))
        return
    if args.slo_sweep:
        print(json.dumps(run_slo_sweep(args)))
        return
    if args.qos_overload:
        print(json.dumps(run_qos_overload(args)))
        return
    if args.autoscale_capacity:
        print(json.dumps(run_autoscale_capacity(args)))
        return
    if args.autoscale:
        print(json.dumps(run_autoscale(args)))
        return
    if args.latency_breakdown:
        print(json.dumps(run_latency_breakdown(args)))
        return
    if args.pipeline_compare:
        print(json.dumps(run_pipeline_compare(args)))
        return
    if args.all:
        results = []
        matrix = [
            ("lenet5", {}),
            ("resnet20", {}),
            # wire + weight quantization variants on the headline config
            ("resnet20", {"transfer_dtype": "uint8"}),
            ("resnet20", {"weights": "int8"}),
            ("mobilenetv2", {}),
            ("mixer_tiny", {}),
            ("longseq_encoder", {}),
            ("resnet50", {}),
            # the byte-heavy 224x224 configs with the repo's own
            # mitigations applied (uint8 wire = 4x fewer host->device
            # bytes, multi-instance messages)
            ("resnet50", {"transfer_dtype": "uint8", "instances_per_msg": 4}),
            ("vit_b16", {}),
            ("vit_b16", {"transfer_dtype": "uint8", "instances_per_msg": 4}),
            ("multi", {}),
            # the reference's scaling thesis as a captured closed loop
            # (VERDICT r2 next #5)
            ("autoscale", {}),
            # north-star latency evidence (VERDICT r2 next #1)
            ("latency_breakdown", {}),
        ]
        def entry_args(name, overrides):
            a = argparse.Namespace(**vars(args))
            for k, v in overrides.items():
                setattr(a, k, v)
            if name in ("resnet50", "vit_b16"):
                # ~600 KB of JSON per 224x224 record: keep the wall time
                # bounded.
                a.messages = min(args.messages, 512)
            if name == "longseq_encoder":
                # ~1.2MB JSON per record: bound the host-side work
                a.messages = min(args.messages, 256)
            a.config = name
            # --all variance honesty lives at matrix level (interleaved
            # repeats below); run_single's own median-of-N would compound
            # it into repeats^2 drains.
            a.repeats = 1
            return a

        for name, overrides in matrix:
            label = name + "".join(f"+{v}" for v in overrides.values())
            log(f"===== --all: {label} =====")
            a = entry_args(name, overrides)
            try:
                if name == "autoscale":
                    a.config = "resnet20"
                    a.stage_seconds = min(args.stage_seconds, 15.0)
                    r = run_autoscale(a)
                elif name == "latency_breakdown":
                    a.config = "resnet20"
                    r = run_latency_breakdown(a)
                else:
                    r = run_multi(a) if name == "multi" else run_single(a)
                if overrides:
                    r["config"] = label
                results.append(r)
            except Exception as e:  # keep the matrix going; record the hole
                log(f"--all config {label} FAILED: {e!r}")
                results.append({"config": label, "error": repr(e)})

        # Variance honesty: single captures carried +-40% swings and rank
        # flips into committed artifacts. Re-measure every single-model row's
        # throughput (args.repeats - 1) more times, INTERLEAVED at matrix
        # level so weather drift spreads across configs instead of biasing
        # one, and report min/median/max with the median as the headline.
        singles = _repeatable_rows(matrix, results)
        if args.repeats > 1 and singles:
            # (value, tainted) pairs: a timed-out drain's sample is
            # deflated (timeout in the denominator) — same protocol as the
            # default run: exclude it unless it is all we have, flag the row.
            samples = {i: [(results[i]["value"],
                            bool(results[i].get("drain_incomplete")))]
                       for i, *_ in singles}
            for rep in range(1, args.repeats):
                log(f"===== --all: interleaved repeat {rep + 1}/"
                    f"{args.repeats} (throughput only) =====")
                for i, name, overrides in singles:
                    a = entry_args(name, overrides)
                    a.skip_latency = True
                    try:
                        r = run_single(a)
                        samples[i].append(
                            (r["value"], bool(r.get("drain_incomplete"))))
                    except Exception as e:
                        log(f"repeat for {results[i]['config']} "
                            f"FAILED: {e!r}")
            for i, *_ in singles:
                row = results[i]
                clean = [v for v, t in samples[i] if not t]
                if len(clean) < len(samples[i]):
                    row["drain_incomplete"] = True
                row.update(sample_stats(clean or [v for v, _ in samples[i]]))
                row["vs_baseline"] = round(
                    row["value"] / BASELINE_IMGS_PER_SEC_PER_CHIP, 3)
            # Reconcile with the committed headline BEFORE rank flags so
            # the flags describe the pooled best-estimate numbers.
            pool_headline_into_matrix(results)
            # Rank stability: could two rows swap order within their
            # observed ranges? Flag both so no reader quotes a coin flip.
            for i, *_ in singles:
                unstable = [
                    results[j]["config"] for j, *_ in singles if j != i
                    and ((results[i]["value"] > results[j]["value"]
                          and results[i]["value_min"]
                          < results[j]["value_max"])
                         or (results[i]["value"] < results[j]["value"]
                             and results[i]["value_max"]
                             > results[j]["value_min"]))
                ]
                if unstable:
                    results[i]["rank_unstable_with"] = unstable
        headline_ref = _latest_artifact("BENCH_r*.json")
        print(json.dumps({
            "capture_session": _new_capture_session(),
            "code_version": _code_version(),
            "see_also": headline_ref[0] if headline_ref else None,
            "rows": results,
        }))
        return
    result = run_multi(args) if args.config == "multi" else run_single(args)
    result["capture_session"] = _new_capture_session()
    result["code_version"] = _code_version()
    cross_reference_headline(result)
    print(json.dumps(result))


def _repeatable_rows(matrix, results):
    """--all rows eligible for interleaved throughput repeats: the
    single-model configs run_single can re-measure. Excludes 'multi'
    (a run_multi aggregate — run_single(config='multi') raises), the
    autoscale / latency-breakdown demo rows (not in CONFIGS), and rows
    whose first pass already failed."""
    return [(i, name, overrides)
            for i, (name, overrides) in enumerate(matrix)
            if name in CONFIGS and name != "multi"
            and "error" not in results[i]]


def run_single(args) -> dict:
    cfg = CONFIGS[args.config]

    import jax

    from storm_tpu.config import BatchConfig
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.runtime.cluster import LocalCluster

    n_dev = len(jax.devices())
    log(f"devices: {jax.devices()}")
    payloads = make_payloads(cfg, instances_per_msg=args.instances_per_msg)
    cluster = LocalCluster()
    try:
        return _run_single_inner(args, cfg, cluster, payloads, n_dev)
    finally:
        cluster.shutdown()  # see run_multi: no zombie topologies under --all


def _run_single_inner(args, cfg, cluster, payloads, n_dev) -> dict:
    from storm_tpu.config import BatchConfig
    from storm_tpu.connectors import MemoryBroker

    # ---- throughput phase: long deadline -> full MXU-sized batches -----------
    if args.buckets:
        buckets = tuple(int(b) for b in args.buckets.split(",") if b.strip())
        if not buckets:
            sys.exit(f"--buckets {args.buckets!r} contains no bucket sizes")
        top = args.max_batch or cfg["max_batch"]
        if max(buckets) > top:
            sys.exit(f"--buckets max {max(buckets)} exceeds max_batch {top}; "
                     f"pass --max-batch {max(buckets)}")
    else:
        buckets = cfg["buckets"]
    batch_cfg = BatchConfig(
        max_batch=args.max_batch or cfg["max_batch"],
        max_wait_ms=max(args.max_wait_ms, 100.0),
        buckets=buckets,
        max_inflight=args.inflight or 4,
    )
    broker = MemoryBroker(default_partitions=4)
    run_cfg, topo = build_topology(cfg, broker, batch_cfg, args.transfer_dtype, args.chunk,
                                 args.weights)
    t0 = time.time()
    cluster.submit_topology("bench-throughput", run_cfg, topo)
    log(f"submitted + warmed up in {time.time() - t0:.1f}s")

    # Median-of-N drains: single captures of the SAME config ranged more
    # than 2x same-day — one drain is a coin flip, and the headline value
    # is what the driver records. Same honesty protocol as --all rows.
    n_msgs = args.messages
    n_reps = max(1, args.repeats)
    samples = []
    for rep in range(n_reps):
        base = broker.topic_size("output") + broker.topic_size("dead-letter")
        for i in range(n_msgs):
            broker.produce("input", payloads[i % len(payloads)])
        delivered, elapsed = drain_loop(
            lambda: broker.topic_size("output")
            + broker.topic_size("dead-letter") - base,
            n_msgs, args.instances_per_msg)
        imgs_done = delivered * args.instances_per_msg
        samples.append(imgs_done / elapsed / n_dev)
        log(f"throughput[{rep + 1}/{n_reps}]: {imgs_done} imgs "
            f"in {elapsed:.2f}s -> {samples[-1]:.0f} img/s/chip "
            f"({n_dev} chip(s))")
        if delivered < n_msgs:
            # Timed-out drain: its stragglers would deliver past the next
            # rep's base snapshot and inflate that sample. No clean system,
            # no more samples.
            log("  drain incomplete; skipping remaining repeats")
            break
    # A timed-out rep's sample is deflated (timeout seconds in the
    # denominator) — keep it OUT of the published stats unless it is all
    # we have, and flag the row either way so no reader mistakes a
    # truncated capture for real variance.
    drain_incomplete = delivered < n_msgs
    complete = samples[:-1] if drain_incomplete and len(samples) > 1 \
        else samples
    stats = sample_stats(complete)
    throughput = stats["value"]
    log(f"throughput: median {throughput:.0f} img/s/chip of "
        f"{stats['throughput_samples']}"
        + (" [DRAIN INCOMPLETE]" if drain_incomplete else ""))
    dead = broker.topic_size("dead-letter")
    if dead:
        log(f"WARNING: {dead} dead-lettered")
    snap = cluster.metrics("bench-throughput")
    bs = snap["inference-bolt"]["batch_size"]["mean"]
    dev = snap["inference-bolt"]["device_ms"]["p50"]
    log(f"batch size mean={bs if bs is None else round(bs)}; "
        f"device ms p50={dev if dev is None else round(dev, 1)}")
    cluster.kill_topology("bench-throughput", wait_secs=2)

    # ---- latency phase: short deadline, offered load below saturation --------
    # Fresh topology + metrics registry; the jit cache is shared via
    # shared_engine, so no recompilation happens here.
    lat = fw = None
    if not args.skip_latency:
        log(f"latency phase: calibrate + offer for {args.latency_seconds}s")
        lat = run_latency_pass(cluster, args, cfg, buckets, "bench-latency")
        # Framework-only phase, same protocol, NullEngine: the north-star
        # claim (<50 ms framework overhead) measured directly on every run.
        log("framework-only phase (NullEngine, device time = 0)")
        fw = run_latency_pass(cluster, args, cfg, buckets, "bench-framework",
                              framework_only=True,
                              seconds=min(args.latency_seconds, 6.0))

    cluster.shutdown()

    result = {
        "metric": f"{cfg['metric']}_images_per_sec_per_chip",
        "value": round(throughput, 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(throughput / BASELINE_IMGS_PER_SEC_PER_CHIP, 3),
        "p50_latency_ms": lat["p50_ms"] if lat else None,
        "p99_latency_ms": lat["p99_ms"] if lat else None,
        "latency_valid": lat["valid"] if lat else True,
        **device_info(),
        "config": args.config,
    }
    if len(stats["throughput_samples"]) > 1:
        # sample_stats rounds uniformly; no re-rounding here
        result["throughput_samples"] = stats["throughput_samples"]
        result["value_min"] = stats["value_min"]
        result["value_max"] = stats["value_max"]
    if drain_incomplete:
        result["drain_incomplete"] = True
    if lat is not None:
        result["stages_p50_ms"] = lat["stages_p50_ms"]
    if fw is not None:
        result["framework_p50_ms"] = fw["p50_ms"]
        result["framework_p99_ms"] = fw["p99_ms"]
        result["framework_latency_valid"] = fw["valid"]
        result["framework_stages_p50_ms"] = fw["stages_p50_ms"]
    return result


if __name__ == "__main__":
    main()
