#!/usr/bin/env python3
"""Spreads and bounds from the result lines ``repeat.py`` kept.

    python3 benchmarks/tools/spread.py chiprun_out/proof_*.jsonl

Runs of one cell are split into sets in the order they were made (``--set``
runs to a set, 6 by default). A spread is the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median; a metric's bound is about five times its widest spread over the
cells and sets, never under 1%. ``setup_s`` leaves out each set's first run
when there are more than two (it compiles on a cold cache)."""

import argparse
import json
import statistics
import sys


def spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("files", nargs="+")
    ap.add_argument("--set", type=int, default=6)
    args = ap.parse_args()
    runs: dict = {}
    for path in args.files:
        for line in open(path):
            row = json.loads(line)
            if row.get("trace") or "metrics" not in row:
                continue
            runs.setdefault(row["cell"], []).append(row)
    widest: dict = {}
    for cell, rows in runs.items():
        bad = [r["seed"] for r in rows if not r.get("correct") or r["rc"]]
        print(f"{cell}: {len(rows)} runs, incorrect or failed: {bad}")
        sets = [rows[i:i + args.set] for i in range(0, len(rows), args.set)]
        for name in rows[0]["metrics"]:
            medians = []
            for k, one in enumerate(sets):
                values = [r["metrics"][name]["value"] for r in one
                          if name in r["metrics"]]
                if name == "setup_s" and len(values) > 2 and k == 0:
                    values = values[1:]
                if len(values) < 2:
                    continue
                s = spread(values) if len(values) >= 3 else float("nan")
                medians.append(statistics.median(values))
                widest[name] = max(widest.get(name, 0.0), 0.0 if s != s else s)
                print(f"  {name:18s} set {k}: median "
                      f"{statistics.median(values):.6g} spread {s:.4%} "
                      f"min {min(values):.6g} max {max(values):.6g} "
                      f"n={len(values)}")
            if len(medians) == 2:
                print(f"  {name:18s} second median over first: "
                      f"{medians[1] / medians[0] - 1:+.4%}")
    for name, s in widest.items():
        print(f"widest spread of {name}: {s:.4%}; five times: "
              f"{max(5 * s, 0.01):.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
