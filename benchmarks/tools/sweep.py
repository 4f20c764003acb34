#!/usr/bin/env python3
"""Find, once, the highest rate an open-loop cell sustains.

    python3 benchmarks/tools/sweep.py --workload vit_g14.json_paced \
        --seconds 20 --start 250 --out chiprun_out/sweep.jsonl

Doubles the rate from ``--start`` until a run is not sustained, then bisects
twice between the last sustained rate and the first that was not. A run is
sustained when every record was answered, the median latency of the window's
last quarter exceeds that of its first quarter by no more than the first
quarter's own spread (the distance between its quartiles) or 1 ms, whichever
is more, and the first quarter's median is under the length of the warm-up
before it: above capacity the queue grows all through the run, and a record
that waits longer than the system has run met a queue that grew from the
first record on (at three times this cell's rate the first quarter's own
spread was 5 s, and hid a growth of 1 s; PERF.md, PR 31). The
cell's traffic file then gets half the knee, rounded to two figures, as a
number; the sweep is recorded in PERF.md. Each probe is a new process (this
parent never imports JAX)."""

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def probe(args, rate: float, out) -> bool:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cmd = bench["command"] + [
        "--workload", args.workload, "--seed", str(args.seed), "--seconds",
        str(args.seconds), "--trace", "0", "--traffic-set", f"rate={rate}"]
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    rows = []
    for line in proc.stdout.splitlines():
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass
    drift = next((r for r in rows if r.get("phase") == "drift"), None)
    times = next((r for r in rows if r.get("phase") == "times"), None)
    last = rows[-1] if rows else {}
    ok = bool(
        proc.returncode == 0 and drift and times and last.get("failed") == 0
        and drift["last_quarter_p50_ms"] - drift["first_quarter_p50_ms"]
        <= max(drift["first_quarter_iqr_ms"], 1.0)
        and drift["first_quarter_p50_ms"] < times["warmup_s"] * 1e3)
    row = {"rate": rate, "sustained": ok, "rc": proc.returncode,
           "drift": drift, "correct": last.get("correct"),
           "attempted": last.get("attempted"), "failed": last.get("failed")}
    if proc.returncode != 0:
        row["stderr"] = proc.stderr[-1500:]
    out.write(json.dumps(row) + "\n")
    out.flush()
    print(json.dumps(row), flush=True)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--start", type=float, default=250.0)
    ap.add_argument("--ceiling", type=float, default=64000.0)
    ap.add_argument("--seed", type=int, default=3000000019)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as out:
        good, rate = None, args.start
        while rate <= args.ceiling and probe(args, rate, out):
            good, rate = rate, rate * 2
        if good is None:
            print(json.dumps({"knee": None, "why": "the start rate is not "
                              "sustained"}))
            return 1
        bad = rate
        for _ in range(2):
            mid = (good + bad) / 2
            if probe(args, mid, out):
                good = mid
            else:
                bad = mid
        summary = {"knee": good, "first_unsustained": bad,
                   "half_of_knee": good / 2}
        out.write(json.dumps(summary) + "\n")
        print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
