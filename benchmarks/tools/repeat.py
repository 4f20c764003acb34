#!/usr/bin/env python3
"""Run the benchmark command several times in one call and keep every line.

    python3 benchmarks/tools/repeat.py --out chiprun_out/<name>.jsonl \
        --seconds 20 --runs vit_g14.tensor_backlog:0:101,102,103 ...

Each ``--runs`` item is ``<cell>:<trace>:<seed>,<seed>,...``. Every run is a
new process (this parent never imports JAX, so it never holds the chip). The
last line of each run goes to ``--out`` with the cell, the seed and the
run's wall time; everything a run printed goes to ``<out>.log``."""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--runs", nargs="+", required=True)
    ap.add_argument("--extra", default="",
                    help="further arguments for every run, as one string")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bad = 0
    with open(args.out, "a") as out, open(args.out + ".log", "a") as log:
        for item in args.runs:
            cell, trace, seeds = item.split(":")
            for seed in seeds.split(","):
                cmd = bench["command"] + [
                    "--workload", cell, "--seed", seed, "--seconds",
                    str(args.seconds), "--trace", trace, *args.extra.split()]
                t0 = time.time()
                proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                      text=True)
                wall = time.time() - t0
                log.write(f"### {' '.join(cmd)} rc={proc.returncode} "
                          f"wall={wall:.1f}\n{proc.stdout}\n"
                          f"{proc.stderr[-6000:]}\n")
                log.flush()
                lines = proc.stdout.strip().splitlines()
                try:
                    row = json.loads(lines[-1])
                except (IndexError, ValueError):
                    row = {"error": proc.stderr[-800:]}
                row.update(cell=cell, seed=int(seed), trace=int(trace),
                           rc=proc.returncode, wall_s=wall)
                bad += proc.returncode != 0 or not row.get("correct")
                out.write(json.dumps(row) + "\n")
                out.flush()
                brief = {k: v["value"] for k, v in
                         row.get("metrics", {}).items()}
                print(cell, seed, f"rc={proc.returncode}",
                      f"wall={wall:.0f}s", row.get("correct"),
                      row.get("attempted"), row.get("failed"),
                      json.dumps(brief), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
