#!/usr/bin/env python3
"""Is a run's result line complete? The check a driver makes before it
compares anything, for a builder to make first.

    python3 benchmarks/run.py --workload <cell> ... --trace 1 > out.txt
    python3 benchmarks/tools/check_line.py --workload <cell> --trace 1 out.txt

    python3 benchmarks/tools/check_line.py --jsonl chiprun_out/<name>.jsonl

The last line of the output must be a JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device``, and ``metrics`` must
hold **every** metric that ``BENCHMARK.json`` gives the cell for the run's
group (``per_layer`` for ``--trace 1``, ``end_to_end`` for ``--trace 0``),
each a finite number with the unit it is listed with. A reader that returned
nothing leaves its metric out of the line (``core/harness.py read_metrics``),
and a line that lacks a listed metric is refused as malformed whatever the
run gained. ``--jsonl`` checks every row of a file ``tools/repeat.py`` wrote
(each row names its cell and whether it was traced). Prints a line a run and
exits 1 if any is incomplete. Needs no chip and never imports JAX."""

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.core import spec  # noqa: E402

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def problems(row, cell_name: str, traced: bool) -> list:
    """What keeps ``row`` (a result line, parsed) from being the complete
    line of a run of ``cell_name``; empty where nothing does."""
    if not isinstance(row, dict):
        return ["the last line is no JSON object"]
    out = [f"lacks {k!r}" for k in KEYS if k not in row]
    metrics = row.get("metrics")
    if not isinstance(metrics, dict):
        return out + ["metrics is no object"]
    bench = spec.benchmark()
    group = "per_layer" if traced else "end_to_end"
    for entry in spec.metrics_for(bench, group, spec.cell(bench, cell_name)):
        got = metrics.get(entry["name"])
        if not isinstance(got, dict):
            out.append(f"metrics lacks {entry['name']}")
            continue
        value = got.get("value")
        if isinstance(value, bool) or not isinstance(value, (int, float)) \
                or not math.isfinite(value):
            out.append(f"{entry['name']} is {value!r}, no finite number")
        if got.get("unit") != entry["unit"]:
            out.append(f"{entry['name']} has unit {got.get('unit')!r}, "
                       f"listed with {entry['unit']!r}")
    return out


def last_line(text: str):
    lines = text.strip().splitlines()
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--trace", type=int, choices=(0, 1))
    ap.add_argument("--jsonl", help="a file tools/repeat.py wrote")
    ap.add_argument("output", nargs="?",
                    help="a run's output (standard input where left out)")
    args = ap.parse_args(argv)
    runs = []  # (label, row, cell, traced)
    if args.jsonl:
        with open(args.jsonl) as f:
            for n, line in enumerate(filter(str.strip, f), 1):
                row = json.loads(line)
                runs.append((f"{args.jsonl}:{n} seed {row.get('seed')}", row,
                             row["cell"], bool(row["trace"])))
    else:
        if args.workload is None or args.trace is None:
            ap.error("--workload and --trace, or --jsonl")
        text = open(args.output).read() if args.output else sys.stdin.read()
        runs.append((args.output or "stdin", last_line(text), args.workload,
                     bool(args.trace)))
    bad = 0
    for label, row, cell_name, traced in runs:
        found = problems(row, cell_name, traced)
        bad += bool(found)
        n = len((row or {}).get("metrics") or {}) if isinstance(row, dict) \
            else 0
        print(f"{'INCOMPLETE' if found else 'complete'} {cell_name} "
              f"--trace {int(traced)} ({label}): {n} metrics"
              + "".join(f"\n  {p}" for p in found))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
