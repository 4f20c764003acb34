#!/usr/bin/env python3
"""One chip check beside a cell of the ``ouro`` family: a block's two
operators against the configuration's plain reference at the published
widths and the cell's step, the causal kernel at one query head a key head
beside XLA's form, what the loop over passes costs, and what the looped
program holds:

    python3 benchmarks/tools/ouro_check.py --config ouro_2_6b --seed 7300000021

One JSON line a check, all on the model's own parameters from ``--seed``
(``runners/standard.py parameters``: the tree the engine is built from) and a
step's rows (``held.rows_per_step`` windows):

- ``attention_mixer``: ``models/falcon_h1.py rotary_gqa`` as ``models/ouro.py``
  calls it (four projections, the turn of q and of k, the causal kernel) on
  block 0's leaves in the served type, against ``references/ouro.py
  _attention`` in float32 at ``highest`` from the same leaves and input: the
  largest and the root-mean-square distance over the reference's root mean
  square.
- ``feed_forward``: ``models/falcon_h1.py gated_ffn`` at a multiplier of 1 on
  block 0's leaves against ``references/ouro.py _feed_forward``, the same
  way.
- ``attention``: ``ops/attention.py causal_attention_merged`` alone at the
  configuration's heads, in the form the rule gives and in XLA's blocked
  form: the median of ``--repeats`` timed calls on the host's clock around
  ``block_until_ready``, in milliseconds, and the largest distance between
  the two results. ``shipped`` marks the rule's.
- ``passes``: the whole model's forward with ``total_ut_steps`` passes as one
  loop beside the same plan at one pass (the same leaves), timed the same
  way: ``loop_over_passes`` is the looped step over ``total_ut_steps`` times
  the single pass, what the loop itself costs; and both programs' lowered
  texts' lengths.
- ``program``: the looped program compiled for this device: the compiler's
  temporaries, arguments and code in bytes, and the ``while`` loops its text
  holds.

Each line carries the forms the program noted. Exit code 1 where a distance
reads over ``--limit`` or a form on the chip is XLA's (``blocked``,
``halves``)."""

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--limit", type=float, default=0.02)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models import ouro as OU
    from storm_tpu.models.falcon_h1 import gated_ffn, rotary_gqa
    from storm_tpu.models.registry import build_model
    from storm_tpu.ops import attention as A
    from storm_tpu.ops import rope as R
    from storm_tpu.ops.platform import dispatch_notes

    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    held = sizes["held"]
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    dtype = jnp.dtype(config["model"]["dtype"])
    f32 = jnp.float32
    dim, heads, hd = (sizes["hidden_size"], sizes["num_attention_heads"],
                      sizes["head_dim"])
    s, rows = held["sequence_length"], held["rows_per_step"]
    model = build_model(config["model"]["name"],
                        num_classes=int(config["model"]["num_classes"]),
                        input_shape=(s,))
    params, state = runner.parameters(config, args.seed)
    block = params["layers"][0]
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 5)
    inv_freq = float(sizes["rope_theta"]) ** (
        -2.0 * np.arange(hd // 2) / hd)
    tile = 512 if s >= 512 else 16  # the presets' attention_block
    # a normed input: unit root mean square a token
    x = jax.random.normal(ks[0], (rows, s, dim), f32).astype(dtype)
    row = {"config": args.config, "seed": args.seed, "length": s,
           "rows": rows, "device": jax.devices()[0].device_kind}
    bad = 0

    def against(check, fast, plain, p):
        with jax.default_matmul_precision("highest"):
            # the reference reads what the program reads: the served input
            want = np.asarray(jax.jit(lambda p, x: jax.lax.map(
                lambda u: plain(p, u), x.astype(f32)))(p, x), np.float64)
        rms = np.sqrt((want ** 2).mean())
        with dispatch_notes() as forms:
            got = np.asarray(jax.jit(fast)(p, x), np.float64)
        line = {**row, "check": check, "forms": forms,
                "reference_rms": float(rms),
                "max_over_rms": float(np.abs(got - want).max() / rms),
                "rms_over_rms": float(
                    np.sqrt(((got - want) ** 2).mean()) / rms)}
        xla = [f for f in forms if f.endswith("=halves") or "blocked" in f]
        line["pass"] = bool(np.isfinite(got).all()
                            and line["rms_over_rms"] <= args.limit
                            and not (on_chip and xla))
        print(json.dumps(line), flush=True)
        return not line["pass"]

    bad += against(
        "attention_mixer",
        lambda p, x: rotary_gqa(p, x, heads, heads,
                                R.rotary_tables(s, inv_freq), hd ** -0.5,
                                tile),
        lambda p, u: reference._attention(p, u, sizes), block["mixer"])
    bad += against(
        "feed_forward", lambda p, x: gated_ffn(p, x, 1.0),
        lambda p, u: reference._feed_forward(p, u), block["ffn"])

    def timed(fn, *operands):
        out = fn(*operands)
        jax.block_until_ready(out)  # compiled
        times = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*operands))
            times.append(1e3 * (time.perf_counter() - t0))
        return out, {"repeats": args.repeats,
                     "ms_median": statistics.median(times),
                     "ms_min": min(times)}

    # the causal loop alone: the rule's form, then XLA's blocked form
    q, k, v = (jax.random.normal(key, (rows, s, heads * hd), f32
                                 ).astype(dtype) for key in ks[1:4])
    rule, first = A.merged_form, None
    for form in (None, "blocked"):
        if form:
            A.merged_form = lambda *a, form=form: form
        try:
            with dispatch_notes() as forms:
                out, took = timed(jax.jit(
                    lambda q, k, v: A.causal_attention_merged(
                        q, k, v, heads, heads, scale=hd ** -0.5,
                        block=tile)), q, k, v)
        finally:
            A.merged_form = rule
        out = np.asarray(out, np.float64)
        first = out if first is None else first
        print(json.dumps({
            **row, "check": "attention", "heads": heads, "shipped": not form,
            "forms": forms, **took,
            "max_from_shipped": float(np.abs(out - first).max()),
            "pass": True}), flush=True)
    del q, k, v, out, first

    # the loop over passes: T passes as one loop beside T times one pass
    passes = int(sizes["total_ut_steps"])
    once = OU.build_ouro(
        "once", int(config["model"]["num_classes"]), (s,),
        **{key: model.hyper[key] for key in (
            "layers", "dim", "ffn_width", "heads", "head_dim", "rope_theta")},
        passes=1, attention_block=tile, param_dtype=dtype)
    ids = jnp.asarray(np.round(spec.plugin(
        "inputs", config["inputs"]["kind"]).make(rows, (s,), args.seed)),
        f32)
    took, texts = {}, {}
    for name, m, st in (("looped", model, state), ("once", once, {})):
        fwd = jax.jit(lambda p, st, xx, m=m: m.apply(p, st, xx)[0])
        with dispatch_notes() as forms:
            texts[name] = len(fwd.lower(params, st, ids).as_text())
        _, took[name] = timed(fwd, params, st, ids)
    print(json.dumps({
        **row, "check": "passes", "passes": passes, "forms": forms,
        "looped": took["looped"], "once": took["once"],
        "loop_over_passes": took["looped"]["ms_median"]
        / (passes * took["once"]["ms_median"]),
        "lowered_chars": texts, "pass": True}), flush=True)

    compiled = jax.jit(model.apply).lower(params, state, ids).compile()
    memory = compiled.memory_analysis()
    text = compiled.as_text()
    print(json.dumps({
        **row, "check": "program", "passes": passes,
        "temporaries_bytes": int(memory.temp_size_in_bytes),
        "arguments_bytes": int(memory.argument_size_in_bytes),
        "code_bytes": int(memory.generated_code_size_in_bytes),
        "compiled_chars": len(text), "whiles": text.count(" while("),
        "kernels": text.count("tpu_custom_call"), "pass": True}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
