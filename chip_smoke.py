#!/usr/bin/env python3
"""Standing proof that the serving path runs on the local TPU.

    python chip_smoke.py              one chip: topology, kernel, serve, dist
    python chip_smoke.py --chips 4    four chips: the sharded phase, nothing else
    python chip_smoke.py --rehearse   toy presets on the CPU, kernels under the
                                      Pallas interpreter (sandbox, tier-1 test)

A TPU belongs to one process at a time, so this parent process never imports
JAX. It asks a child for ``jax.devices()`` and fails at once unless that is a
TPU (there is no automatic CPU path), builds the native library from the
committed sources, then runs each phase as a child process, one after another:
each child opens the chip and releases it on exit. Every phase prints one JSON
line; a phase that fails makes the script print ``"ok": false`` and exit
non-zero. The last line of stdout is the result the driver reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases (model widths are the published ones; weights are random, from a seed):

- ``topology``  vit_b16 through ``build_standard_topology`` on a LocalCluster
  over a MemoryBroker: JSON records plus one ragged poison record in,
  predictions out, poison dead-lettered, every tree acked, device substage
  histograms filled, a profiler trace captured, predictions compared with a
  plain float32 ``jit(model.apply)`` at ``highest`` precision.
- ``kernel``    longseq_encoder (S=2048) through the same topology: the Pallas
  flash kernel must be in the engine's program, and its predictions are
  compared with the same records served with ``attention_reference``.
- ``serve``     ``InferenceWorker`` + ``InferenceClient``: Arrow requests
  against vit_b16, answers equal to the topology phase's engine, executables
  found in the compile cache the topology child filled.
- ``dist``      ``DistCluster``, two workers over ``tests/kafka_stub``,
  resnet20: spout and sink on worker 0, the engine on worker 1. Worker 1 holds
  the chip; the controller and worker 0 never open it; a second process that
  tries gets an error that names the cause; a placement that would put engines
  on two workers of the host is refused at submit.
- ``sharded``   (``--chips 4`` only) vit_b16 under dp=2 x tp=2 and
  longseq_encoder under sequence_parallel=4 (ring attention), each compared
  with the same records on a one-device mesh in the same process, with
  parameters and batches asserted to occupy four distinct devices.

Decode (storm_tpu/decode/engine.py) is numpy and has no device path yet; the
script says so on an earlier line and does not pretend to cover it. Expert
parallelism exists only at toy width (moe_vit_tiny) and is left out.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# The contract allows 1200 s, compilation included.
TIME_LIMIT_S = 1150.0

ONE_CHIP_PHASES = ("topology", "kernel", "serve", "dist")
FOUR_CHIP_PHASES = ("sharded",)

# bf16 keeps 8 mantissa bits (2^-8 = 0.4% per rounding); through a dozen
# layers the logits move by a few percent, so served probabilities are held
# to this share of the largest reference probability.
BF16_REL_TOL = 0.05
# Same executable, same rows: serve must reproduce the topology's engine.
SAME_ENGINE_TOL = 1e-6

SEED = 0


def out_dir(args) -> str:
    """Where a run keeps its trace and the arrays one phase hands to the
    next: inside the checkout, git-ignored, and copied back by the chip tool
    so it can be looked at afterwards. A rehearsal keeps its own, and leaves
    what a chip run brought back alone."""
    return os.path.join(ROOT, "chiprun_out",
                        "chip_smoke_rehearsal" if args.rehearse
                        else "chip_smoke")


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# parent: no JAX in this process
# ---------------------------------------------------------------------------


def _run_child(phase: str, flags: list, env: dict, deadline: float):
    """Run one phase in its own process group, echo its stdout lines and
    return ``(exit_code, last_json_row)``. The whole group is killed at the
    deadline and on the way out, so no worker outlives its phase."""
    cmd = [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
           "--child", phase, *flags]
    proc = subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            text=True, start_new_session=True)

    def kill_group() -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timer = threading.Timer(max(1.0, deadline - time.monotonic()), kill_group)
    timer.start()
    last = None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            print(line, flush=True)
            try:
                row = json.loads(line)
            except ValueError:
                continue
            if isinstance(row, dict):
                last = row
        return proc.wait(), last
    finally:
        timer.cancel()
        kill_group()
        proc.wait()


def parent(args) -> int:
    t_start = time.monotonic()
    deadline = t_start + TIME_LIMIT_S
    device = None

    def finish(ok: bool, **why) -> int:
        if why:
            say(phase="result", **why)
        say(ok=ok, device=device)
        return 0 if ok else 1

    if not os.path.isdir(os.path.join(ROOT, "storm_tpu")):
        return finish(False, error="no storm_tpu package beside "
                                   "chip_smoke.py: run it from a checkout")

    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    flags = ["--chips", str(args.chips)]
    if args.rehearse:
        flags.append("--rehearse")
        env["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}"
            ).strip()

    rc, probe = _run_child("probe", flags, env, deadline)
    if rc != 0 or not probe or "platform" not in probe:
        return finish(False, error="jax.devices() failed in the probe child "
                                   "(no accelerator, or the chip is held by "
                                   "another process)")
    device = {k: probe[k] for k in ("platform", "kind", "count")}
    if not args.rehearse and device["platform"] != "tpu":
        return finish(False, error=f"platform is {device['platform']!r}, not "
                                   "'tpu'; --rehearse is the only CPU path")
    if device["count"] < args.chips:
        return finish(False, error=f"--chips {args.chips} but JAX reports "
                                   f"{device['count']} device(s)")

    t0 = time.monotonic()
    make = subprocess.run(
        ["make", "-C", os.path.join(ROOT, "storm_tpu", "native"),
         "clean", "all"], capture_output=True, text=True)
    say(phase="native_build", ok=make.returncode == 0,
        seconds=round(time.monotonic() - t0, 2))
    if make.returncode != 0:
        log(make.stdout + make.stderr)
        return finish(False, error="make -C storm_tpu/native clean all failed")

    shutil.rmtree(out_dir(args), ignore_errors=True)
    os.makedirs(out_dir(args))
    if args.chips == 1:
        say(phase="decode", covered=False,
            note="storm_tpu/decode/engine.py is numpy: no device path yet")
    ok = True
    for phase in (FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES):
        rc, row = _run_child(phase, flags, env, deadline)
        if rc != 0 or not row or row.get("phase") != phase \
                or not row.get("ok"):
            ok = False
            say(phase=phase, ok=False, exit_code=rc,
                error="phase failed (its own line and stderr say where)")
    say(phase="total", seconds=round(time.monotonic() - t_start, 1))
    return finish(ok)


# ---------------------------------------------------------------------------
# children: each is the one process that holds the chip while it runs
# ---------------------------------------------------------------------------


class CompileMeter:
    """Compile seconds and persistent-cache traffic of this process, from
    JAX's own monitoring events (backend compile time covers the cache
    lookup, so a warm cache shows as hits and a small number)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def row(self) -> dict:
        state = ("warm" if self.hits and not self.misses
                 else "cold" if self.misses and not self.hits
                 else "mixed" if self.hits else "unused")
        return {"compile_s": round(self.compile_s, 2), "cache": state,
                "cache_hits": self.hits, "cache_misses": self.misses}


def child_setup() -> "CompileMeter":
    import logging

    logging.basicConfig(level=logging.WARNING)
    from storm_tpu.infer.engine import enable_compile_cache

    cache_dir = enable_compile_cache()
    log(f"compile cache: {cache_dir}")
    return CompileMeter()


def device_row() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def presets(rehearse: bool) -> dict:
    """One model per phase: published widths on the chip, the zoo's toy
    twins of the same families (same code path) in rehearsal."""
    # the paper's CIFAR workload is small enough to be its own toy twin
    cifar = dict(name="resnet20", input_shape=(32, 32, 3), num_classes=10)
    if rehearse:
        return {
            "classifier": dict(name="vit_tiny", input_shape=(32, 32, 3),
                               num_classes=10),
            "longseq": dict(name="longseq_tiny", input_shape=(64, 16),
                            num_classes=10),
            "cifar": cifar,
        }
    return {
        # the configuration __graft_entry__.entry() builds
        "classifier": dict(name="vit_b16", input_shape=(224, 224, 3),
                           num_classes=1000),
        "longseq": dict(name="longseq_encoder", input_shape=(2048, 64),
                        num_classes=10),
        "cifar": cifar,
    }


def make_inputs(n: int, shape: tuple, seed: int = SEED):
    """``n`` zero-mean unit-variance instances, rounded so their JSON text
    parses back to exactly these float32 values."""
    import numpy as np

    rng = np.random.RandomState(seed)
    return np.round(rng.randn(n, *shape), 3).astype(np.float32)


def json_record(x) -> str:
    """One ``{"instances": [...]}`` record holding one instance."""
    import numpy as np

    return json.dumps(
        {"instances": [np.round(x.astype(np.float64), 3).tolist()]})


def smoke_config(model: dict, parallelism=(2, 4, 2), buckets=(8,),
                 sharding=None):
    """A ``Config`` for the standard topology: the reference's 2/4/2 operator
    shape by default; (1, 1, 1) plus one partition, one batch in flight and
    a synchronous sink where output order has to equal input order. Buckets
    are restricted through the normal config so the cold compile stays
    short."""
    from storm_tpu.config import BatchConfig, Config, ModelConfig

    cfg = Config()
    cfg.model = ModelConfig(dtype="bfloat16", seed=SEED, **model)
    ordered = tuple(parallelism) == (1, 1, 1)
    cfg.batch = BatchConfig(max_batch=buckets[-1], buckets=tuple(buckets),
                            max_wait_ms=20.0,
                            max_inflight=1 if ordered else 2)
    if sharding is not None:
        cfg.sharding = sharding
    cfg.broker.partitions = 1 if ordered else 2
    if ordered:
        cfg.sink.mode = "sync"  # async sends to a wire broker may overtake
    cfg.offsets.policy = "earliest"
    cfg.offsets.max_behind = None
    (cfg.topology.spout_parallelism, cfg.topology.inference_parallelism,
     cfg.topology.sink_parallelism) = parallelism
    cfg.topology.message_timeout_s = 300.0
    return cfg


def _wait(cond, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout_s:.0f}s: {what}")
        time.sleep(0.05)


def serve_through_topology(cfg, records: list, n_poison: int = 0,
                           trace_dir: str = "", name: str = "smoke"):
    """``records`` (JSON strings; ``n_poison`` of them malformed) through
    ``build_standard_topology`` on a LocalCluster over a MemoryBroker.
    With ``trace_dir`` the second half of the records is served under
    ``device_trace``. Returns the decoded prediction rows in output-topic
    order and a dict of what the run counted."""
    import numpy as np

    from storm_tpu.api.schema import decode_predictions
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.main import build_standard_topology
    from storm_tpu.runtime.cluster import LocalCluster
    from storm_tpu.runtime.tracing import DEVICE_SUBSTAGES, device_trace

    broker = MemoryBroker(default_partitions=cfg.broker.partitions)
    topo = build_standard_topology(cfg, broker)
    n_good = len(records) - n_poison
    out_t, dlq_t = cfg.broker.output_topic, cfg.broker.dead_letter_topic
    partition = 0 if cfg.broker.partitions == 1 else None

    def produce(batch) -> None:
        for rec in batch:
            broker.produce(cfg.broker.input_topic, rec, partition=partition)

    def landed() -> int:
        return broker.topic_size(out_t) + broker.topic_size(dlq_t)

    with LocalCluster() as cluster:
        t0 = time.monotonic()
        cluster.submit_topology(name, cfg, topo)  # builds + warms the engine
        submit_s = time.monotonic() - t0
        half = len(records) // 2 if trace_dir else len(records)
        produce(records[:half])
        _wait(lambda: landed() >= half, 600, f"{name}: first {half} records")
        if trace_dir:
            with device_trace(trace_dir):
                produce(records[half:])
                _wait(lambda: landed() >= len(records), 600,
                      f"{name}: traced records")
        if not cluster.drain(name, timeout_s=60):
            raise RuntimeError(f"{name}: drain did not complete")
        metrics = cluster.metrics(name)
        errors = cluster.errors(name)
    outs = broker.drain_topic(out_t)
    dead = broker.drain_topic(dlq_t)
    rows = (np.concatenate([decode_predictions(r.value).data for r in outs])
            if outs else np.zeros((0, cfg.model.num_classes), np.float32))
    infer, spout = metrics["inference-bolt"], metrics["kafka-spout"]
    counted = {
        "records_in": len(records), "records_out": len(outs),
        "dead_lettered": len(dead),
        "tree_acked": spout.get("tree_acked", 0),
        "tree_failed": spout.get("tree_failed", 0),
        "errors": [repr(e) for e in errors],
        "substage_counts": {key: infer.get(key, {}).get("count", 0)
                            for key, _ in DEVICE_SUBSTAGES},
        "submit_s": round(submit_s, 2),
    }
    problems = []
    if len(outs) != n_good or rows.shape != (n_good, cfg.model.num_classes):
        problems.append(f"{len(outs)} outputs {rows.shape} for {n_good} "
                        "good records")
    if not np.isfinite(rows).all():
        problems.append("non-finite predictions")
    if len(dead) != n_poison or infer.get("dead_lettered", 0) != n_poison:
        problems.append(f"{len(dead)} dead letters for {n_poison} poison "
                        "records")
    if counted["tree_acked"] != len(records) or counted["tree_failed"]:
        problems.append(f"ledger not closed: acked {counted['tree_acked']}/"
                        f"{len(records)}, failed {counted['tree_failed']}")
    if errors:
        problems.append(f"cluster.errors(): {counted['errors']}")
    if not all(counted["substage_counts"].values()):
        problems.append(f"empty device substage histogram: "
                        f"{counted['substage_counts']}")
    counted["problems"] = problems
    return rows, counted


def float32_reference(model_cfg, x):
    """Plain float32 ``jit(model.apply)`` at ``highest`` matmul precision on
    the parameters the engine was built from, outside the topology."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from storm_tpu.models.registry import build_model, load_or_init

    model = build_model(model_cfg.name, num_classes=model_cfg.num_classes,
                        input_shape=tuple(model_cfg.input_shape))
    params, state = load_or_init(model, None, model_cfg.seed)

    @jax.jit
    def ref(p, s, xx):
        logits, _ = model.apply(p, s, xx, train=False)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    with jax.default_matmul_precision("highest"):
        return np.asarray(ref(params, state, jnp.asarray(x)))


def compare(got, ref, rel_tol: float, ordered: bool) -> dict:
    """Max abs difference between served rows and reference rows, held to
    ``rel_tol`` of the largest reference probability. Where several bolt
    tasks finish out of order (the wire contract carries no record id) each
    served row is paired with its nearest reference row; the pairing must
    be a bijection, and the reference rows must lie at least four times
    further apart than any served row lies from its partner."""
    import numpy as np

    tol = rel_tol * float(ref.max())
    out = {"tolerance": tol}
    if got.shape != ref.shape:
        return {**out, "match": False,
                "why": f"shape {got.shape} != reference {ref.shape}"}
    if ordered:
        diff = float(np.abs(got - ref).max())
        return {**out, "max_abs_diff": diff, "match": diff <= tol}
    d = np.abs(got[:, None, :] - ref[None, :, :]).max(-1)
    apart = np.abs(ref[:, None, :] - ref[None, :, :]).max(-1)
    apart = float((apart + np.eye(len(ref)) * 1e9).min())
    diff = float(d.min(1).max())
    bijection = sorted(d.argmin(1).tolist()) == list(range(len(ref)))
    return {**out, "max_abs_diff": diff, "min_row_separation": apart,
            "match": bool(bijection and diff <= tol and apart >= 4 * diff)}


def served_engine(cfg):
    """The engine the topology's bolts built (the process-wide cache hands
    the same object back for the same three configs)."""
    from storm_tpu.infer.engine import shared_engine

    eng = shared_engine(cfg.model, cfg.sharding, cfg.batch)
    if not eng.compiled_batches:
        raise RuntimeError("engine cache returned an engine that never ran")
    return eng


def lowered_text(eng, batch: int) -> str:
    import jax

    x = jax.ShapeDtypeStruct((batch, *eng.input_shape), eng.dtype,
                             sharding=eng._x_sharding)
    return eng._fwd.lower(eng.params, eng.state, x).as_text()


def trace_row(trace_dir: str, platform: str) -> dict:
    """Size of the captured ``.xplane.pb`` and, on the chip, the number of
    device-plane events in it — the next PR's busy/idle reduction reads
    exactly these."""
    import glob

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    size = sum(os.path.getsize(f) for f in files)
    row = {"trace_bytes": size, "trace_ok": size > 0}
    if files and platform == "tpu":
        from jax.profiler import ProfileData

        events = 0
        for plane in ProfileData.from_file(files[0]).planes:
            if plane.name.startswith("/device:TPU"):
                events += sum(len(list(ln.events)) for ln in plane.lines)
        row["device_events"] = events
        row["trace_ok"] = size > 0 and events > 0
    return row


def native_loaded() -> bool:
    from storm_tpu.native import native_available

    return native_available()


def phase_topology(args) -> dict:
    import numpy as np

    meter = child_setup()
    model = presets(args.rehearse)["classifier"]
    cfg = smoke_config(model)
    n = 24
    x = make_inputs(n, model["input_shape"])
    records = [json_record(row) for row in x]
    # ragged: the second instance is shorter than the first
    records.insert(n // 3, '{"instances": [[1.0, 2.0], [3.0]]}')
    trace_dir = os.path.join(out_dir(args), "trace")
    rows, counted = serve_through_topology(
        cfg, records, n_poison=1, trace_dir=trace_dir, name="smoke-topology")
    ref = float32_reference(cfg.model, x)
    cmp = compare(rows, ref, BF16_REL_TOL, ordered=False)
    trace = trace_row(trace_dir, device_row()["platform"])
    # What the serve phase must reproduce, from the engine that just served.
    eng = served_engine(cfg)
    np.savez(os.path.join(out_dir(args), "topology_engine.npz"), x=x[:8],
             pred=eng.predict(x[:8]), compile_s=meter.row()["compile_s"])
    problems = counted.pop("problems")
    if not cmp["match"]:
        problems.append("predictions do not match the float32 reference")
    if not trace["trace_ok"]:
        problems.append("profiler trace empty")
    if not native_loaded():
        problems.append("native parser not loaded")
    return {"phase": "topology", "ok": not problems, "model": model["name"],
            "dtype": "bfloat16", **counted, **meter.row(), **cmp, **trace,
            "native": native_loaded(), "problems": problems}


def rehearse_kernels() -> dict:
    """Rehearsal 1 of the on-chip-measurement guide: on the CPU the flash
    kernel runs under the Pallas interpreter. The dispatch predicate asks
    the platform, so the steering happens here, in the smoke, and not
    through an option of the program. Returns the kernel's trace count."""
    import storm_tpu.ops.attention as attention
    import storm_tpu.ops.flash_attention as fa

    calls = {"n": 0}
    compiled = fa.flash_attention

    def interpreted(q, k, v, scale=None):
        calls["n"] += 1
        return compiled(q, k, v, scale=scale, interpret=True)

    fa.flash_attention = interpreted
    attention._use_pallas = \
        lambda: not os.environ.get("STORM_TPU_NO_PALLAS")
    os.environ["STORM_TPU_FLASH_MIN_SEQ"] = "64"
    return calls


def phase_kernel(args) -> dict:
    from storm_tpu.infer.engine import unload_engine

    meter = child_setup()
    calls = rehearse_kernels() if args.rehearse else None
    model = presets(args.rehearse)["longseq"]
    cfg = smoke_config(model, parallelism=(1, 1, 1))
    n = 12
    records = [json_record(row)
               for row in make_inputs(n, model["input_shape"])]

    def kernel_in_program(eng, traces_before: int) -> bool:
        if calls is not None:  # interpret mode lowers to plain HLO
            return calls["n"] > traces_before
        return "tpu_custom_call" in lowered_text(eng, cfg.batch.buckets[-1])

    flash_rows, counted = serve_through_topology(
        cfg, records, name="smoke-kernel")
    eng = served_engine(cfg)
    with_kernel = kernel_in_program(eng, 0)
    traces = calls["n"] if calls is not None else 0
    # The same records served with attention_reference: drop the engine so
    # the next topology traces a fresh one with the kernels forced off.
    unload_engine(eng)
    del eng
    os.environ["STORM_TPU_NO_PALLAS"] = "1"
    try:
        ref_rows, ref_counted = serve_through_topology(
            cfg, records, name="smoke-kernel-reference")
        without_kernel = not kernel_in_program(served_engine(cfg), traces)
    finally:
        del os.environ["STORM_TPU_NO_PALLAS"]
    cmp = compare(flash_rows, ref_rows, BF16_REL_TOL, ordered=True)
    problems = counted.pop("problems") + ref_counted["problems"]
    if not with_kernel:
        problems.append("flash kernel missing from the engine's program "
                        "(silently replaced)")
    if not without_kernel:
        problems.append("reference run still contains the kernel")
    if not cmp["match"]:
        problems.append("flash predictions differ from attention_reference")
    return {"phase": "kernel", "ok": not problems, "model": model["name"],
            "seq": model["input_shape"][0], **counted, **meter.row(), **cmp,
            "kernel_in_program": with_kernel,
            "kernel_check": ("interpreter trace count" if calls is not None
                             else "tpu_custom_call in lowered text"),
            "problems": problems}


def phase_serve(args) -> dict:
    import numpy as np

    from storm_tpu.serve import InferenceClient, InferenceWorker

    meter = child_setup()
    model = presets(args.rehearse)["classifier"]
    cfg = smoke_config(model)  # the topology phase's configs: same programs
    want = np.load(os.path.join(out_dir(args), "topology_engine.npz"))
    x, pred = want["x"], want["pred"]
    topology_compile_s = float(want["compile_s"])
    t0 = time.monotonic()
    worker = InferenceWorker(cfg.model, cfg.sharding, cfg.batch,
                             port=0).start()
    worker.engine.warmup()
    startup_s = time.monotonic() - t0
    client = InferenceClient(f"localhost:{worker.port}")
    try:
        info = client.info()
        got = np.concatenate([client.predict(x[a:b])
                              for a, b in ((0, 3), (3, 6), (6, 8))])
    finally:
        client.close()
        worker.stop(grace=1.0)
    diff = float(np.abs(got - pred).max())
    row = meter.row()
    problems = []
    if info["model"] != model["name"] or \
            tuple(info["input_shape"]) != tuple(model["input_shape"]):
        problems.append(f"Info() describes another model: {info}")
    if diff > SAME_ENGINE_TOL:
        problems.append("answers differ from the topology phase's engine")
    if not row["cache_hits"]:
        problems.append("no compile-cache hit: the topology child's "
                        "executables were not found")
    return {"phase": "serve", "ok": not problems, "model": model["name"],
            "requests": 3, "rows": int(got.shape[0]), "max_abs_diff": diff,
            "tolerance": SAME_ENGINE_TOL, "startup_s": round(startup_s, 2),
            **row, "topology_compile_s": topology_compile_s,
            "problems": problems}


def second_process_error() -> dict:
    """While worker 1 holds the chip, a second process that opens it must
    fail with a message that names the cause, and must not hang."""
    code = "from storm_tpu.parallel.mesh import make_mesh; make_mesh()"
    t0 = time.monotonic()
    try:
        p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                           capture_output=True, text=True, timeout=120)
    except subprocess.TimeoutExpired:
        return {"second_process": "hung for 120 s", "second_process_ok": False}
    named = "a chip belongs to one process at a time" in p.stderr
    return {"second_process": ("refused, cause named" if named else
                               p.stderr.strip().splitlines()[-1:]),
            "second_process_s": round(time.monotonic() - t0, 1),
            "second_process_ok": p.returncode != 0 and named}


def phase_dist(args) -> dict:
    import numpy as np

    from storm_tpu.api.schema import decode_predictions
    from storm_tpu.config import PipelineConfig
    from storm_tpu.connectors.kafka_protocol import KafkaWireBroker
    from storm_tpu.dist import DistCluster
    from storm_tpu.dist.worker import _opened_backend
    from storm_tpu.infer.engine import InferenceEngine
    from tests.kafka_stub import KafkaStubBroker

    meter = child_setup()
    model = presets(args.rehearse)["cifar"]
    cfg = smoke_config(model, parallelism=(1, 1, 1))
    n = 24
    x = make_inputs(n, model["input_shape"])
    stub = KafkaStubBroker(partitions=1)
    cfg.broker.kind = "kafka"
    cfg.broker.bootstrap = f"127.0.0.1:{stub.port}"
    in_t, out_t = cfg.broker.input_topic, cfg.broker.output_topic
    placement = {"kafka-spout": 0, "kafka-bolt": 0, "dlq-bolt": 0,
                 "inference-bolt": 1}
    # Two engines under dist-run: auto-placement puts both on one worker;
    # spreading them over the host's workers is refused at submit.
    multi = smoke_config(model, parallelism=(1, 1, 1))
    multi.broker = cfg.broker
    multi.pipelines = [
        PipelineConfig(name=name, model=multi.model, batch=multi.batch,
                       input_topic=f"{name}-in", output_topic=f"{name}-out",
                       dead_letter_topic=f"{name}-dlq")
        for name in ("a", "b")]
    problems = []
    extra = {}
    try:
        # Workers inherit this process's environment: JAX_PLATFORMS as the
        # machine set it, and one compile cache for the run.
        with DistCluster(2) as cluster:
            auto = cluster._auto_place(multi, "multi")
            colocated = auto["a-inference"] == auto["b-inference"]
            split = dict(auto)
            split["b-inference"] = 1 - auto["a-inference"]
            try:
                cluster._check_one_process_per_chip(multi, "multi", split)
                refused = False
            except ValueError as e:
                refused = "one process per chip" in str(e)
            cluster.submit("smoke-dist", cfg, placement)
            producer = KafkaWireBroker(cfg.broker.bootstrap)
            try:
                for row in x:
                    producer.produce(in_t, json_record(row), partition=0)
                _wait(lambda: stub.topic_size(out_t) >= n, 300,
                      "dist: outputs at the broker")
                if not cluster.drain(timeout_s=60):
                    problems.append("dist drain did not complete")
                snap = cluster.metrics()
                reports = cluster.state_reports()
                recs = producer.fetch(out_t, 0, 0, max_records=n + 8)
            finally:
                producer.close()
            backends = {i: r.get("backend") for i, r in reports.items()}
            platform = backends.get(1)
            extra = ({"second_process": "not applicable on the cpu",
                      "second_process_ok": True}
                     if platform != "tpu" else second_process_error())
            cluster.kill()
    finally:
        stub.close()
    # The cluster is down and the chip is free: the controller never opened
    # a backend while it ran one, and may now open it for the comparison —
    # the same engine program, fed the same rows without the cluster. (The
    # model's numerics against float32 are the topology phase's business.)
    controller_backend = _opened_backend()
    rows = np.concatenate([decode_predictions(r.value).data for r in recs])
    eng = InferenceEngine(cfg.model, cfg.sharding, cfg.batch)
    ref = np.concatenate([eng.predict(x[i:i + 8]) for i in range(0, n, 8)])
    diff = float(np.abs(rows - ref).max()) if rows.shape == ref.shape \
        else float("inf")
    spout = snap["kafka-spout"]
    if len(recs) != n:
        problems.append(f"{len(recs)} outputs for {n} records")
    if spout.get("tree_acked", 0) != n or spout.get("tree_failed", 0):
        problems.append(f"ledger not closed: {spout}")
    if backends.get(0) is not None or controller_backend is not None:
        problems.append(f"a process that hosts no engine opened a backend: "
                        f"worker 0 {backends.get(0)!r}, controller "
                        f"{controller_backend!r}")
    if platform != device_row()["platform"]:
        problems.append(f"worker 1 served on {platform!r}")
    if not colocated:
        problems.append(f"auto-placement split the engines: {auto}")
    # Workers pinned to JAX_PLATFORMS=cpu cannot take a chip and are exempt.
    if not refused and reports[0].get("jax_platforms") != "cpu":
        problems.append("a placement with engines on two workers of one "
                        "host was not refused")
    if not extra["second_process_ok"]:
        problems.append("second process: " + str(extra["second_process"]))
    if diff > SAME_ENGINE_TOL:
        problems.append("predictions differ from the same engine fed "
                        "directly")
    return {"phase": "dist", "ok": not problems, "model": model["name"],
            "workers": 2, "records_in": n, "records_out": len(recs),
            "tree_acked": spout.get("tree_acked", 0),
            "worker_backends": backends,
            "controller_backend": controller_backend,
            "engines_colocated": colocated, "split_refused": refused,
            **extra, **meter.row(), "max_abs_diff": diff,
            "tolerance": SAME_ENGINE_TOL, "problems": problems}


def phase_sharded(args) -> dict:
    import jax
    import numpy as np

    from storm_tpu.config import ShardingConfig
    from storm_tpu.infer.engine import unload_engine

    meter = child_setup()
    cells = [("classifier", "dp2_tp2",
              ShardingConfig(data_parallel=2, tensor_parallel=2)),
             ("longseq", "dp1_sp4",
              ShardingConfig(data_parallel=1, sequence_parallel=4))]
    out = {}
    problems = []
    for family, label, sharding in cells:
        model = presets(args.rehearse)[family]
        n = 16
        records = [json_record(row)
                   for row in make_inputs(n, model["input_shape"])]
        cfg = smoke_config(model, parallelism=(1, 1, 1), sharding=sharding)
        rows, counted = serve_through_topology(
            cfg, records, name=f"smoke-{label}")
        eng = served_engine(cfg)
        # Where the arrays really live: parameters over the whole mesh, a
        # placed batch and the program's output likewise, and (tp) at least
        # one kernel split so a chip holds less than the full model.
        param_devs = set().union(*(leaf.sharding.device_set for leaf in
                                   jax.tree.leaves(eng.params)))
        xd = jax.device_put(
            np.zeros((cfg.batch.buckets[-1], *eng.input_shape), eng.dtype),
            eng._x_sharding)
        batch_devs = {s.device for s in xd.addressable_shards}
        out_devs = eng._fwd(eng.params, eng.state, xd).sharding.device_set
        split = eng.param_bytes_per_device() < eng.param_bytes()
        unload_engine(eng)
        del eng, xd
        # The same records on a one-device mesh, in this process.
        one = smoke_config(model, parallelism=(1, 1, 1),
                           sharding=ShardingConfig(data_parallel=1))
        one_rows, one_counted = serve_through_topology(
            one, records, name=f"smoke-{label}-one-device")
        one_eng = served_engine(one)
        one_devs = len(one_eng.mesh.devices.flat)
        unload_engine(one_eng)
        del one_eng
        cmp = compare(rows, one_rows, BF16_REL_TOL, ordered=True)
        cell_problems = counted.pop("problems") + one_counted["problems"]
        if not (len(param_devs) == len(batch_devs) == len(out_devs) == 4):
            cell_problems.append(
                f"arrays on {len(param_devs)}/{len(batch_devs)}/"
                f"{len(out_devs)} devices (params/batch/output), not 4")
        if sharding.tensor_parallel > 1 and not split:
            cell_problems.append("tp parameters are not split")
        if one_devs != 1:
            cell_problems.append(f"comparison ran on {one_devs} devices")
        if not cmp["match"]:
            cell_problems.append("sharded predictions differ from one device")
        out[label] = {"model": model["name"], **counted, **cmp,
                      "param_devices": len(param_devs),
                      "batch_devices": len(batch_devs),
                      "output_devices": len(out_devs),
                      "params_split": split, "problems": cell_problems}
        problems += [f"{label}: {p}" for p in cell_problems]
    return {"phase": "sharded", "ok": not problems, "cells": out,
            **meter.row(), "problems": problems}


PHASES = {"topology": phase_topology, "kernel": phase_kernel,
          "serve": phase_serve, "dist": phase_dist, "sharded": phase_sharded}


def child(args) -> int:
    if args.child == "probe":
        say(**device_row())
        return 0
    try:
        row = PHASES[args.child](args)
    except Exception as e:  # the phase's line must still say what broke
        import traceback

        traceback.print_exc()
        row = {"phase": args.child, "ok": False,
               "problems": [f"{type(e).__name__}: {e}"]}
    say(**row)
    return 0 if row["ok"] else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the sharded phase and its one-device "
                         "comparison, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy presets on the CPU backend, kernels under the "
                         "Pallas interpreter; the only CPU path")
    ap.add_argument("--child", choices=("probe", *PHASES),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    return child(args) if args.child else parent(args)


if __name__ == "__main__":
    sys.exit(main())
