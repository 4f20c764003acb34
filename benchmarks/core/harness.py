"""One run of one cell: set up, warm up, measure a window, drain, check.

    process start ... set-up ... | warm-up traffic | window | drain | check
                                 g0                t0       t1

Everything before ``t0`` is ``setup_s``. The generator is a thread of this
process (the chip belongs to one process, the broker is in-process); it does
nothing per record but append an already encoded payload.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

from benchmarks.core import pairing, spec, xplane
from benchmarks.core.meter import CompileMeter

SPAN_MIN_S = 0.25  # a host-clock time shorter than this is mostly error


def say(**row) -> None:
    print(json.dumps(row), flush=True)


def farthest_rows(rows: np.ndarray, k: int) -> np.ndarray:
    """``k`` row indices chosen greedily so that each is as far (Euclidean
    distance) from those already chosen as any row can be."""
    def distance(i):
        return np.sqrt(((rows - rows[i]) ** 2).sum(1))

    chosen = [int(np.argmax(rows.max(1)))]
    nearest = distance(chosen[0])
    for _ in range(k - 1):
        chosen.append(int(np.argmax(nearest)))
        nearest = np.minimum(nearest, distance(chosen[-1]))
    return np.asarray(chosen)


def choose_pool(config: dict, pool: int, seed: int, reference_of):
    """The pool's instances and their reference predictions.

    ``inputs.kind`` draws ``candidates`` instances from the seed (the pool's
    size where none is given), rounded to ``decimals`` so that JSON text
    carries them exactly. From more candidates than the pool holds, the rows
    whose reference predictions lie farthest apart are kept, so that an
    output is known by its value. Returns ``(x, reference)``."""
    inputs = config["inputs"]
    shape = tuple(config["model"]["input_shape"])
    n = max(pool, int(inputs.get("candidates", 0)))
    raw = spec.plugin("inputs", inputs["kind"]).make(n, shape, seed)
    x = np.round(raw, int(inputs["decimals"])).astype(np.float32)
    ref = reference_of(x)
    pick = farthest_rows(ref, pool) if n > pool else np.arange(pool)
    return x[pick], ref[pick]


class Generator:
    """Appends pool payloads in turn and keeps the log of what it appended:
    for each request its pool row, its due time and its append time (one
    clock throughout: ``time.time()``, which the broker stamps with too)."""

    def __init__(self, served, traffic: dict, payloads: list, schedule,
                 arrivals) -> None:
        self.served, self.traffic = served, traffic
        self.payloads, self.schedule, self.arrivals = \
            payloads, schedule, arrivals
        self.stop = threading.Event()
        self.stop_at = float("inf")  # the window's end, once it is known
        self.appended = 0
        self.rows, self.due, self.at = [], [], []
        self.started = 0.0
        self.error = None
        self._thread = threading.Thread(target=self._main, name="generator",
                                        daemon=True)

    def landed(self) -> int:
        return self.served.landed()

    def done(self) -> bool:
        return self.stop.is_set() or time.time() >= self.stop_at

    def append(self, due=None) -> None:
        row = self.appended % len(self.payloads)
        self.served.append(self.payloads[row])
        now = time.time()
        self.rows.append(row)
        self.at.append(now)
        self.due.append(now if due is None else due)
        self.appended += 1

    def _main(self) -> None:
        try:
            self.arrivals.run(self)
        except BaseException as e:  # read by the main thread after join
            self.error = e

    def start(self) -> float:
        self.started = time.time()
        self._thread.start()
        return self.started

    def finish(self) -> None:
        self.stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("the generator thread did not stop")
        if self.error is not None:
            raise self.error


class Run:
    """What one run established; every metric is a reader over this."""

    def __init__(self, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = seed, seconds
        self.setup_s = None
        self.delivered_in_window = 0
        self.delivery_times = ()  # sorted broker stamps inside the window
        self.latencies_ms = None
        self.late_ms = None
        self.registry_before, self.registry_after = {}, {}
        self.compile_at_window_start = {}
        self.trace = None
        self.roofline_bound = None
        self.notes = {}  # what a reader found beside its number; logged
        self.device = {}
        self.bytes_per_value = {"bfloat16": 2, "float16": 2,
                                "float32": 4}[config["model"]["dtype"]]

    def peaks(self) -> dict:
        table = spec.load_json(os.path.join(spec.BENCH_DIR, "peaks.json"))
        kind = self.device.get("kind")
        if kind not in table["devices"]:
            raise spec.SpecError(
                f"peaks.json has no device_kind {kind!r}: a share of another "
                "device's peak is not a measurement")
        return table["devices"][kind]


def read_metrics(run: Run, entries: list) -> dict:
    out = {}
    for entry in entries:
        doc = spec.metric(entry["name"])
        value = spec.plugin("readers", doc["reader"]).read(
            run, **doc.get("args", {}))
        if value is None or value != value:
            continue  # nothing to read: the metric is left out of the line
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def device_row() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for dev in jax.devices():
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def _sleep_until(when: float) -> None:
    while True:
        left = when - time.time()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def run_cell(args, t_start: float) -> int:
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload, rehearse=args.rehearse)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    for item in args.traffic_set:
        key, _, number = item.partition("=")
        traffic[key] = float(number)
    runner = spec.plugin("runners", config["runner"])
    arrivals = spec.plugin("arrivals", traffic["arrivals"])
    encode = spec.plugin("payloads", traffic["payload"]).encode
    reference = spec.plugin("references", config["reference"])
    run = Run(cell, config, traffic, args.seed, float(args.seconds))
    trace_dir = os.path.join(spec.ROOT, "bench_out", "trace",
                             cell["name"]) if args.trace else ""

    import jax

    run.device = device_row()
    if run.device["platform"] != "tpu" and not args.rehearse:
        print(f"no TPU: JAX reports platform {run.device['platform']!r}; "
              "the benchmark has no CPU path (--rehearse is for the tests)",
              file=sys.stderr)
        return 2
    if run.device["count"] < int(cell["chips"]):
        print(f"cell {cell['name']} needs {cell['chips']} chip(s); JAX "
              f"reports {run.device['count']}", file=sys.stderr)
        return 2
    prepared = runner.prepare(spec.ROOT, args.rehearse)
    meter = CompileMeter()
    say(phase="start", cell=cell["name"], seed=args.seed,
        seconds=run.seconds, trace=args.trace, **prepared, **run.device)

    # the reference: the benchmark's own plain forward, float32 at
    # `highest`, on the parameters the engine will be built from, at one
    # fixed batch so that it is one cached program
    params, state = runner.parameters(config, args.seed)
    forward = jax.jit(lambda p, s, xx: reference.forward(
        config["published"], p, s, xx))

    pool = int(traffic["pool"])

    def reference_of(xx):
        # a pool's worth of rows at a time: candidates beyond the pool cost
        # time and not device memory, and the program is the same one
        with jax.default_matmul_precision("highest"):
            return np.concatenate([
                np.asarray(forward(params, state, xx[a:a + pool]), np.float64)
                for a in range(0, len(xx), pool)])

    x, ref = choose_pool(config, pool, args.seed, reference_of)
    del params, state
    separation = pairing.row_separation(ref)
    # an output may lie this far from its reference row, as a share of the
    # row's length, and never more than half the distance between two pool
    # rows: another row's answer must fail
    tol = min(float(config["tolerance"]["relative_distance"]), separation / 2)
    # the pool, encoded once; appended by reference from then on
    payloads = [encode(row, int(config["inputs"]["decimals"])) for row in x]
    t_pool = time.time()

    served = runner.Served(config, traffic, args.seed)
    t_submit = time.time()
    try:
        warmup_s = float(traffic["warmup_seconds"])
        schedule = arrivals.schedule(traffic, args.seed,
                                     warmup_s + run.seconds)
        gen = Generator(served, traffic, payloads, schedule, arrivals)
        g0 = gen.start()
        t0 = g0 + warmup_s
        t1 = t0 + run.seconds
        gen.stop_at = t1  # it stops by itself, whatever this thread is at
        _sleep_until(t0)
        run.registry_before = served.registry()
        run.compile_at_window_start = meter.row()
        run.setup_s = t0 - t_start
        traced = None
        counted_to = t1
        if args.trace:
            # The window's last seconds run under the profiler: the
            # registry's metrics are read over the part before it, the
            # trace's over the part under it.
            span = min(float(traffic.get("trace_seconds", 3.0)),
                       run.seconds / 2)
            counted_to = t1 - span
            _sleep_until(counted_to)
            run.registry_after = served.registry()
            options = jax.profiler.ProfileOptions()
            # The device's planes only. Host tracing, even at level 1, wrote
            # 2.9 M events of the transfer threads in 3 s and slowed the
            # system 5.5-fold (PERF.md, PR 23); the Python tracer floods on a
            # busy event loop. Without them the traced part runs at the
            # untraced rate.
            options.python_tracer_level = 0
            options.host_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            tr0 = time.time()
            _sleep_until(t1)
            traced = (tr0, time.time())
            jax.profiler.stop_trace()
        else:
            _sleep_until(t1)
            run.registry_after = served.registry()
        compiles_in_window = meter.compiles \
            - run.compile_at_window_start["compiles"]
        gen.finish()

        # a bounded drain: everything appended must land
        deadline = time.time() + float(traffic["drain_seconds"])
        while served.landed() < gen.appended and time.time() < deadline:
            time.sleep(0.02)
        drained_at = time.time()
        settled = served.settle(timeout_s=max(1.0, deadline - drained_at))
        registry_end = served.registry()
        errors = served.errors()
        out_ts, out_rows, dead = served.outputs()
    finally:
        served.close()
    t_closed = time.time()

    # which request each output answers, and whether it is right
    matched, err = pairing.match_rows(out_rows, ref, tol)
    wrong = int((matched < 0).sum())
    req_due = np.asarray(gen.due)
    req_at = np.asarray(gen.at)
    delivered_at, duplicates = pairing.pair_latencies(
        req_due, gen.rows, out_ts, matched)
    unanswered = int(np.isnan(delivered_at).sum())
    in_window = (req_due >= t0) & (req_due < t1)
    # a traced run counts up to where the profiler started
    run.seconds = counted_to - t0
    run.delivery_times = np.sort(
        out_ts[(out_ts >= t0) & (out_ts < counted_to)])
    run.delivered_in_window = len(run.delivery_times)
    if schedule is not None:
        waited = np.where(np.isnan(delivered_at), drained_at, delivered_at)
        run.latencies_ms = (waited - req_due)[in_window] * 1e3
        run.late_ms = (req_at - req_due)[in_window] * 1e3
        # does the queue grow through the window? (read by tools/sweep.py)
        quarter = max(1, len(run.latencies_ms) // 4)
        first, last = run.latencies_ms[:quarter], run.latencies_ms[-quarter:]
        say(phase="drift", rate=traffic.get("rate"),
            first_quarter_p50_ms=pairing.quantile(first, 0.5),
            last_quarter_p50_ms=pairing.quantile(last, 0.5),
            first_quarter_iqr_ms=pairing.quantile(first, 0.75)
            - pairing.quantile(first, 0.25),
            **{f"p{round(q * 100)}_ms": pairing.quantile(run.latencies_ms, q)
               for q in (0.5, 0.75, 0.9, 0.95, 0.99)},
            unanswered=unanswered)
    if traced:
        path = xplane.find_trace(trace_dir)
        run.trace = xplane.reduce(xplane.load(path)) if path else {}
    worst = float(err[matched >= 0].max()) if (matched >= 0).any() else 0.0
    spout = registry_end.get("kafka-spout", {})
    # Every number compared, beside its limit, as [number, limit]; a run is
    # correct where none is over its limit.
    checks = {
        "farthest_output": [float(err.max()) if len(err) else 0.0, tol],
        "outputs_of_no_row": [wrong, 0],
        "unanswered": [unanswered, 0],
        "dead_lettered": [int(dead), 0],
        "trees_failed": [int(spout.get("tree_failed", 0)), 0],
        "cluster_errors": [len(errors), 0],
        "unsettled": [int(not settled), 0],
        "compiles_in_window": [int(compiles_in_window), 0],
        # a window shorter than a host clock can time
        "window_short_by_s": [max(0.0, SPAN_MIN_S - run.seconds), 0],
    }
    problems = [f"{name}: {number} over its limit {limit}"
                for name, (number, limit) in checks.items() if number > limit]
    failed = unanswered + wrong + dead
    say(phase="counts", appended=gen.appended, outputs=len(out_ts),
        dead_lettered=dead, wrong=wrong, unanswered=unanswered,
        duplicates=duplicates, tree_acked=spout.get("tree_acked", 0),
        tree_failed=spout.get("tree_failed", 0),
        delivered_in_window=run.delivered_in_window,
        farthest_matched=worst, tolerance=tol, min_row_separation=separation,
        farthest_wrong=float(err[matched < 0].max()) if wrong else None,
        largest_reference_probability=float(ref.max()),
        compiles_in_window=compiles_in_window,
        rate_while_traced=(int(((out_ts >= counted_to) & (out_ts < t1)).sum())
                           / (t1 - counted_to)) if traced else None,
        trace_lines=(run.trace or {}).get("lines"),
        trace_executions={
            "whole": {n: len(ds) for n, ds in
                      (run.trace or {}).get("modules", {}).items()},
            "left_out": (run.trace or {}).get("cut_modules")}
        if traced else None,
        delivered_each_second=np.histogram(
            out_ts, bins=np.arange(t0, t1 + 0.5, 1.0))[0].tolist(),
        **meter.row(), problems=problems, errors=[str(e) for e in errors[:3]])
    say(phase="times", pool_and_reference_s=t_pool - t_start,
        submit_s=t_submit - t_pool, warmup_s=warmup_s,
        drain_s=drained_at - t1, close_s=t_closed - drained_at,
        check_s=time.time() - t_closed)

    every = {g: read_metrics(run, spec.metrics_for(bench, g, cell))
             for g in ("end_to_end", "per_layer")}
    say(phase="all_metrics", roofline_bound=run.roofline_bound,
        notes=run.notes,
        **{g: {k: v["value"] for k, v in ms.items()}
           for g, ms in every.items()})
    device = dict(run.device, memory_peak_bytes=memory_peak_bytes())
    result = {"correct": not problems,
              "attempted": int(in_window.sum()), "failed": failed,
              "metrics": every["per_layer" if args.trace else "end_to_end"],
              "device": device}
    if args.trace:
        trace = run.trace or {}
        device["busy_s"] = trace.get("busy_s", 0.0)
        device["window_s"] = trace.get("window_s", traced[1] - traced[0])
        result["breakdown"] = {"device_ops": trace.get("device_ops", []),
                               "idle_gaps": trace.get("idle_gaps", [])}
    result["checks"] = checks  # last in the line, and last on stderr
    say(**result)
    for name, (number, limit) in checks.items():
        print(f"check {name}: {number} limit {limit}", file=sys.stderr)
    return 0
