#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <config>.<mix> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

A new process each time: it opens the chip, builds what the cell needs, warms
up, measures for ``--seconds``, checks every output against the plain
reference and prints one JSON object as its last line. There is no CPU path:
without a TPU it exits non-zero and prints no result (``--rehearse`` lets the
benchmark's own tests run a toy cell on the CPU). See ``README.md`` here.
"""

import time

T_START = time.time()  # set-up is counted from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow the CPU and cells BENCHMARK.json does not "
                         "list; for the tests, never for a number")
    ap.add_argument("--traffic-set", action="append", default=[],
                    metavar="KEY=NUMBER",
                    help="override a number of the traffic file; for the "
                         "rate sweep of tools/sweep.py, never for a result")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "storm_tpu")):
        print("no storm_tpu package beside benchmarks/: run from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from benchmarks.core.harness import run_cell

    return run_cell(args, T_START)


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program's runtime must not hold the exit
    os._exit(code)
