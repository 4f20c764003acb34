#!/usr/bin/env python3
"""The chip checks beside a cell whose answer is several distributions a
record: the EVA mixer alone against the configuration's plain reference at the
published widths, and the two readings that set the configuration's
``tolerance``, one JSON line each:

    python3 benchmarks/tools/evabyte_check.py --config evabyte \
        mixer:4900000021:2048,16384 4900000031 4900000033:f8

- ``mixer:<seed>:<length>,...`` (``tools/sparse_mixer_check.py``'s manner):
  layer 0's mixer in the served type (the form its shape rule picks) against
  ``references/<model>.py``'s mixer in float32 at ``highest`` from the same
  leaves and the same input, one window at a time, as the largest and the
  root-mean-square distance over the result's root mean square; and the
  summaries alone the same way. A length of one attention window reads no
  summary; a longer one does.
- ``<seed>`` and ``<seed>:f8`` (``tools/tolerance.py``'s two readings, which
  that tool cannot take here: it applies one softmax over a row's logits and
  compares them as a matrix, and this model's logits are ``(rows, heads,
  vocabulary)``): the model's own forward in the served type, ``max_rows``
  windows a step, a softmax a head and the heads laid end to end as the
  engine lays them, against the reference from the same parameters over the
  ``inputs.candidates`` windows the harness would draw from the seed; with
  ``:f8`` also the control, the same program with every matrix among the
  parameters and every projection's input rounded to float8 e4m3, each scaled
  to its tensor's largest value: the nearest precision below bfloat16. Each
  is judged as the harness judges a run's outputs (``core/pairing.py
  match_rows`` under ``min(tolerance.relative_distance, row separation /
  2)``). The program has to read ``correct: true`` and the control ``correct:
  false``.

Exit code 1 where a reading reads otherwise or a mixer's distance is over
``--limit``. Every item is a process of its own (this parent never imports
JAX, so it never holds the chip)."""

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--limit", type=float, default=0.05)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    ap.add_argument("--stage", choices=["mixer", "program", "float8"],
                    help=argparse.SUPPRESS)  # a child: one item, one reading
    ap.add_argument("items", nargs="+")
    args = ap.parse_args()
    if args.stage == "mixer":
        _, seed, lengths = args.items[0].split(":")
        return mixer(args, int(seed), [int(n) for n in lengths.split(",")])
    if args.stage:
        return reading(args, int(args.items[0]))
    bad = 0
    for item in args.items:
        if item.startswith("mixer:"):
            stages, child = ("mixer",), item
        else:
            child, _, control = item.partition(":")
            stages = ("program", "float8") if control else ("program",)
        row = {}
        for stage in stages:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--config",
                 args.config, "--limit", str(args.limit), "--stage", stage,
                 child] + ["--rehearse"] * args.rehearse,
                stdout=subprocess.PIPE, text=True)
            if proc.returncode == 2:
                return 2
            lines = proc.stdout.strip().splitlines()
            if stage == "mixer":
                print("\n".join(lines), flush=True)
            else:
                try:
                    row.update(json.loads(lines[-1]))
                except (IndexError, ValueError):
                    row[stage] = {"error": f"rc {proc.returncode}"}
            bad += proc.returncode != 0
        if row:
            print(json.dumps(row), flush=True)
    return 1 if bad else 0


def _open(args):
    import jax

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the readings are the chip's", file=sys.stderr)
        return None
    enable_compile_cache()
    config = spec.config(args.config)
    return (config, spec.plugin("runners", config["runner"]),
            spec.plugin("references", config["reference"]))


def mixer(args, seed: int, lengths: list) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from storm_tpu.models import evabyte as program
    from storm_tpu.ops import eva_attention, rope
    from storm_tpu.ops.platform import dispatch_notes

    opened = _open(args)
    if opened is None:
        return 2
    config, runner, reference = opened
    sizes = config["published"]
    params, _ = runner.parameters(config, seed)
    leaves = params["layers"][0]["mixer"]
    del params
    heads = sizes["num_attention_heads"]
    d = sizes["hidden_size"] // heads
    window, chunk = sizes["window_size"], sizes["chunk_size"]
    dtype = jnp.dtype(config["model"]["dtype"])
    inv_freq = float(sizes["rope_theta"]) ** (-2.0 * np.arange(d // 2) / d)

    def distances(got, want):
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        rms = np.sqrt((want ** 2).mean())
        return (float(np.abs(got - want).max() / rms),
                float(np.sqrt(((got - want) ** 2).mean()) / rms))

    bad = 0
    for n in lengths:
        u = jax.random.normal(jax.random.PRNGKey(seed % 2 ** 31 + n),
                              (1, n, sizes["hidden_size"]), jnp.float32
                              ).astype(dtype)
        with dispatch_notes() as forms:
            got = jax.jit(lambda p, x: program.eva_mixer(
                p, x, heads, d, window, chunk,
                rope.rotary_tables(n, inv_freq))[0])(leaves, u)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda p, x: reference.mixer(p, x, sizes))(
                leaves, u[0].astype(jnp.float32))
        # the summaries alone, from the same keys and values (the heads
        # merged, as the program's ops take them)
        k, v = (jax.random.normal(jax.random.PRNGKey(seed % 2 ** 31 + n + i),
                                  (1, n, heads * d), jnp.float32
                                  ).astype(dtype) for i in (1, 2))
        pooled = jax.jit(lambda p, k, v: eva_attention.chunk_summaries(
            k, v, p["mu"], p["phi"], chunk))(leaves, k, v)
        with jax.default_matmul_precision("highest"):
            plain = jax.jit(lambda p, k, v: tuple(
                a.reshape(n // chunk, heads * d)
                for a in reference.summaries(
                    *(a[0].astype(jnp.float32).reshape(n, heads, d)
                      for a in (k, v)),
                    p["mu"].astype(jnp.float32), p["phi"].astype(jnp.float32),
                    chunk)))(leaves, k, v)
        row = {"config": args.config, "seed": seed, "length": n,
               "forms": forms, "device": jax.devices()[0].device_kind}
        row["max_over_rms"], row["rms_over_rms"] = distances(got[0], want)
        for name, a, b in (("kbar", pooled[0][0], plain[0]),
                           ("vbar", pooled[1][0], plain[1])):
            row[f"{name}_max_over_rms"], row[f"{name}_rms_over_rms"] = \
                distances(a, b)
        row["pass"] = bool(max(row["rms_over_rms"], row["kbar_rms_over_rms"],
                               row["vbar_rms_over_rms"]) <= args.limit)
        bad += not row["pass"]
        print(json.dumps(row), flush=True)
    return 1 if bad else 0


def reading(args, seed: int) -> int:
    """One seed's reference and one reading against it, as one JSON line."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import pairing, spec
    from storm_tpu.models.registry import build_model
    from storm_tpu.ops import layers

    opened = _open(args)
    if opened is None:
        return 2
    config, runner, reference = opened
    shape = tuple(config["model"]["input_shape"])
    model = build_model(config["model"]["name"],
                        num_classes=int(config["model"]["num_classes"]),
                        input_shape=shape)
    step = int(model.max_rows or 8)
    limit = float(config["tolerance"]["relative_distance"])

    def served(p, s, xx):  # a softmax a head, the heads end to end
        logits = model.apply(p, s, xx)[0].astype(jnp.float32)
        return jax.nn.softmax(logits, -1).reshape(len(xx), -1)

    served = jax.jit(served)
    plain = jax.jit(lambda p, s, xx: reference.forward(
        config["published"], p, s, xx))

    def round8(a):
        top = jnp.max(jnp.abs(a.astype(jnp.float32)))
        scale = jnp.where(top > 0, 448.0 / top, 1.0)
        return ((a.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
                .astype(jnp.float32) / scale).astype(a.dtype)

    inputs = config["inputs"]
    n = int(inputs.get("candidates", 8))
    x = np.round(spec.plugin("inputs", inputs["kind"]).make(
        n, shape, seed), int(inputs["decimals"])).astype(np.float32)
    params, state = runner.parameters(config, seed)
    t0 = time.time()
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(plain(params, state, x), np.float64)
    reference_s = time.time() - t0
    separation = pairing.row_separation(ref)
    tol = min(limit, separation / 2)
    if args.stage == "float8":
        # in place of the original, leaf by leaf: two trees may not fit
        leaves, tree = jax.tree.flatten(params)
        for i, leaf in enumerate(leaves):
            if leaf.ndim >= 2:
                leaves[i] = round8(leaf)
                leaf.delete()
        params = jax.tree.unflatten(tree, leaves)
        matmul = layers.matmul
        layers.matmul = lambda a, w: matmul(round8(a), w)
    got = np.concatenate([np.asarray(served(params, state, x[a:a + step]))
                          for a in range(0, n, step)])
    idx, _ = pairing.match_rows(got, ref, tol)
    # a row's distance from its own reference row, whichever lies nearest
    own = np.sqrt(((got - ref) ** 2).sum(1) / (ref ** 2).sum(1))
    wrong = idx != np.arange(n)
    print(json.dumps({
        "config": args.config, "seed": seed, "tolerance": tol,
        "row_separation": separation, "reference_s": reference_s,
        "device": jax.devices()[0].device_kind,
        args.stage: {
            "correct": bool(not wrong.any()), "rows_failed": int(wrong.sum()),
            "rows": n, "min": float(own.min()),
            "median": float(np.median(own)), "max": float(own.max()),
            "sorted": [float(f"{e:.4g}") for e in np.sort(own)]}}),
        flush=True)
    # the program has to answer every row, the control not to
    return int(bool(wrong.any()) == (args.stage == "program"))


if __name__ == "__main__":
    sys.exit(main())
