#!/usr/bin/env python
"""Run every Pallas kernel's compiled-on-TPU parity check and record the
artifact (VERDICT r4 missing #1 / next-round item 1).

The interpret-mode tests (tests/test_ops.py) prove the kernel math on CPU;
this runner proves the *Mosaic-compiled* kernels on the real chip — the
configuration that actually serves — against the same jnp references, and
writes KERNEL_TPU_r{N}.json with per-case max-abs error vs tolerance.

Run on a machine that holds the chip (JAX then picks the TPU by itself):
  python tpu_kernel_parity.py --out KERNEL_TPU_r05.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="KERNEL_TPU_r05.json")
    ap.add_argument("--interpret", action="store_true",
                    help="run under the Pallas interpreter instead "
                         "(smoke-testing this runner off-TPU)")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        print(f"ERROR: compiled parity needs a TPU; jax.devices()[0] is "
              f"{dev.platform!r}. Use --interpret to smoke-test off-TPU.",
              file=sys.stderr)
        return 2

    from storm_tpu.ops.parity_checks import run_all

    t0 = time.time()
    rows = run_all(interpret=args.interpret)
    artifact = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "compiled": not args.interpret,
        "note": "max_abs_err is measured in f32 against the jnp reference "
                "on identical (dtype-rounded) inputs, so it isolates the "
                "kernel's own accumulation/rounding from input casts; "
                "interpret-mode math coverage lives in tests/test_ops.py",
        "all_pass": all(r["pass"] for r in rows),
        "wall_s": round(time.time() - t0, 1),
        "results": rows,
    }
    out = json.dumps(artifact, indent=1)
    if args.out == "-":
        print(out)
    else:
        with open(os.path.join(REPO, args.out), "w") as f:
            f.write(out + "\n")
        print(f"wrote {args.out}: all_pass={artifact['all_pass']} "
              f"({len(rows)} cases, {artifact['wall_s']}s)")
    for r in rows:
        err = r["max_rel_err"] if r["metric"] == "rel" else r["max_abs_err"]
        print(f"  {'PASS' if r['pass'] else 'FAIL'} {r['kernel']:20s} "
              f"{r['case']:26s} {r['dtype']:8s} "
              f"{r['metric']}_err={err:.2e} tol={r['tol']:.0e}")
    return 0 if artifact["all_pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
