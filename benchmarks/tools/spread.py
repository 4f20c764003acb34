#!/usr/bin/env python3
"""Spreads and bounds from the result lines ``repeat.py`` kept.

    python3 benchmarks/tools/spread.py chiprun_out/proof_*.jsonl

Runs of one cell are split into sets in the order they were made (``--set``
runs to a set, 6 by default). A spread is the distance between the first and
the third quartile (``statistics.quantiles(values, n=4)``) as a share of the
median. A check reads a set three ways, and so does this: the spread of all
its runs (a bound over eight times the widest of these is too loose), the
spread once the run farthest from the median is left out (a bound under twice
the mean of these over a cell's sets is too tight), and the trimmed range,
the distance between the extremes of the runs so kept, by which a later
check says whether a metric moved at all.

The rule for a bound: five times the mean, over a cell's sets, of the spread
with the farthest run left out, in the cell where that is most; or twice the
widest trimmed range where that is more (a set that spreads by over half its
bound leaves a check unable to say "unchanged"); rounded up to two figures,
never under 0.01 and never over 0.1. One far-off run in a set therefore
widens nothing, and the bound lies inside the check's window by at least
two and a half times on the tight side. ``setup_s`` leaves out the first
set's first run when there are more than two (it compiles on a cold cache);
its bound is 0.1 whatever its spread."""

import argparse
import json
import math
import statistics
import sys

FLOOR, CEILING = 0.01, 0.1


def quartile_spread(values: list) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def without_farthest(values: list) -> list:
    """The runs but the one farthest from their median (of more than three:
    quartiles want three)."""
    mid = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - mid))
    return kept[:-1] if len(kept) > 3 else kept


def trimmed_range(values: list) -> float:
    kept = without_farthest(values)
    return (max(kept) - min(kept)) / statistics.median(values)


def round_up(x: float, figures: int = 2) -> float:
    if x <= 0:
        return 0.0
    step = 10.0 ** (math.floor(math.log10(x)) - figures + 1)
    return round(math.ceil(x / step - 1e-9) * step, 12)


def bound(sets: list) -> float:
    """The bound that one cell's sets of one metric's readings give."""
    steady = statistics.mean(quartile_spread(without_farthest(s))
                             for s in sets)
    room = max(5 * steady, 2 * max(trimmed_range(s) for s in sets))
    return min(CEILING, max(FLOOR, round_up(room)))


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
    bounds: dict = {}
    for cell, rows in runs.items():
        bad = [r["seed"] for r in rows if not r.get("correct") or r["rc"]]
        print(f"{cell}: {len(rows)} runs, incorrect or failed: {bad}")
        sets = [rows[i:i + args.set] for i in range(0, len(rows), args.set)]
        for name in dict.fromkeys(n for r in rows for n in r["metrics"]):
            kept = []
            for k, one in enumerate(sets):
                values = [r["metrics"][name]["value"] for r in one
                          if name in r["metrics"]]
                if name == "setup_s" and len(values) > 2 and k == 0:
                    values = values[1:]
                if len(values) < 3:
                    continue
                kept.append(values)
                print(f"  {name:18s} set {k}: median "
                      f"{statistics.median(values):.6g} spread "
                      f"{quartile_spread(values):.4%}, farthest run left "
                      f"out {quartile_spread(without_farthest(values)):.4%}"
                      f", trimmed range {trimmed_range(values):.4%} "
                      f"min {min(values):.6g} max {max(values):.6g} "
                      f"n={len(values)}")
            if len(kept) >= 2:
                first, second = (statistics.median(v) for v in kept[:2])
                print(f"  {name:18s} second median over first: "
                      f"{second / first - 1:+.4%}")
            if kept:
                tight = 2 * statistics.mean(
                    quartile_spread(without_farthest(v)) for v in kept)
                loose = 8 * max(quartile_spread(v) for v in kept)
                by_rule = bound(kept)
                print(f"  {name:18s} bound by the rule {by_rule:.4g}; a "
                      f"check's window on these runs: {tight:.4f} to "
                      f"{loose:.4f}")
                bounds[name] = max(bounds.get(name, 0.0), by_rule)
    for name, b in bounds.items():
        print(f"{name}: bound {b:.4g}"
              + (" (setup_s stands at 0.1)" if name == "setup_s" else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
