#!/usr/bin/env python3
"""One chip check beside a cell of the ``solar_open2`` family: the program's
two mixers alone against the configuration's plain reference, at the
published widths, one window, each in both of its forms:

    python3 benchmarks/tools/solar_mixer_check.py --config solar_open2_250b \
        --seed 5200000021

One JSON line a mixer, form and case: the program's mixer in the served type
against ``references/solar_open2.py`` in float32 at ``highest`` from the same
leaves and the same input, as the largest and the root-mean-square distance
over the reference's root mean square.

- ``kda``: ``models/kimi_linear.py kda_mixer`` with steps in (0, 2), with the
  tables by the Pallas kernel (what ``ops/kda.py tables_form`` picks on one
  chip) and by XLA's form (this tool answers the rule's question about the
  platform in the kernel's place: no option of the program does). Two cases:
  ``random`` (a window of N(0, 1) rows) and ``near_parallel`` (every row the
  same vector plus a twentieth of noise, so a chunk's keys are near-parallel,
  and the step's projection times four, so a quarter of the heads step past
  1.9: where ``(I + tril(beta A))`` is farthest from the identity). Each
  line says how the steps lay: their least, their largest, the share past 1
  and past 1.9.
- ``gqa``: ``models/nemotron_h.py gqa_mixer`` with the gate, by the causal
  kernel (``ops/attention.py causal_form``) and by XLA's blocked form.

Exit code 1 where a line reads over ``--limit``."""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--limit", type=float, default=0.02)
    ap.add_argument("--rehearse", action="store_true",
                    help="any platform (the tests' toy configurations)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.core import spec
    from storm_tpu.infer.engine import enable_compile_cache
    from storm_tpu.models.kimi_linear import kda_mixer
    from storm_tpu.models.nemotron_h import gqa_mixer
    from storm_tpu.ops import attention, kda
    from storm_tpu.ops.platform import dispatch_notes

    if jax.devices()[0].platform != "tpu" and not args.rehearse:
        print("no TPU: the check is the chip's", file=sys.stderr)
        return 2
    enable_compile_cache()
    config = spec.config(args.config)
    sizes = config["published"]
    held = sizes["held"]
    runner = spec.plugin("runners", config["runner"])
    reference = spec.plugin("references", config["reference"])
    params, _ = runner.parameters(config, args.seed)
    layers = range(held["num_hidden_layers"])
    gqa_leaves = params["layers"][next(
        i for i in layers if i in sizes["gqa_layers"])]["mixer"]
    kda_leaves = params["layers"][next(
        i for i in layers if i not in sizes["gqa_layers"])]["mixer"]
    del params
    la = sizes["linear_attn_config"]
    eps, s = sizes["rms_norm_eps"], held["sequence_length"]
    dtype = jnp.dtype(config["model"]["dtype"])
    ks = jax.random.split(jax.random.PRNGKey(args.seed % 2 ** 31), 3)
    noise = jax.random.normal(ks[0], (1, s, sizes["hidden_size"]), jnp.float32)
    inputs = {"random": noise, "near_parallel": jax.random.normal(
        ks[1], (1, 1, sizes["hidden_size"]), jnp.float32) + 0.05 * noise}
    # the harder case's leaves: the step's logits four times as wide
    leaves = {"random": kda_leaves, "near_parallel": {
        **kda_leaves, "beta": (kda_leaves["beta"].astype(jnp.float32) * 4
                               ).astype(kda_leaves["beta"].dtype)}}

    def kda_program(p, x):
        return kda_mixer(p, x, la["num_heads"], la["head_dim"],
                         held["kda_chunk"], eps, step_range=2.0)

    def gqa_program(p, x):
        return gqa_mixer(p, x, sizes["num_attention_heads"],
                         sizes["num_key_value_heads"], sizes["head_dim"])

    def steps(p, x):
        beta = 2 * jax.nn.sigmoid(x[0] @ p["beta"].astype(jnp.float32))
        return {"step_min": float(beta.min()), "step_max": float(beta.max()),
                "steps_past_1": float((beta > 1).mean()),
                "steps_past_1.9": float((beta > 1.9).mean())}

    checks = [("kda", case, module, kda_program, leaves[case],
               lambda p, x: reference._kda(p, x, sizes, eps))
              for case in inputs for module in (None, kda)]
    checks += [("gqa", "random", module, gqa_program, gqa_leaves,
                lambda p, x: reference._gqa(p, x, sizes))
               for module in (None, attention)]
    bad, wanted = 0, {}  # a case's reference serves both of its forms
    for mixer, case, xla_in, program, p, plain in checks:
        u = inputs[case].astype(dtype)
        kept = xla_in and xla_in._use_pallas
        if xla_in:  # XLA's form: the rule's platform question answered no
            xla_in._use_pallas = lambda: False
        try:
            with dispatch_notes() as forms:  # a new function: traced anew
                got = jax.jit(lambda p, x: program(p, x))(p, u)
        finally:
            if xla_in:
                xla_in._use_pallas = kept
        if (mixer, case) not in wanted:
            with jax.default_matmul_precision("highest"):
                wanted[mixer, case] = np.asarray(jax.jit(plain)(
                    p, u[0].astype(jnp.float32)), np.float64)
        got, want = np.asarray(got[0], np.float64), wanted[mixer, case]
        rms = np.sqrt((want ** 2).mean())
        row = {"config": args.config, "seed": args.seed, "mixer": mixer,
               "case": case, "length": s, "forms": forms,
               "device": jax.devices()[0].device_kind,
               "max_over_rms": float(np.abs(got - want).max() / rms),
               "rms_over_rms": float(np.sqrt(((got - want) ** 2).mean())
                                     / rms)}
        if mixer == "kda":
            row.update(steps(p, u.astype(jnp.float32)))
        row["pass"] = bool(np.isfinite(got).all()
                           and row["rms_over_rms"] <= args.limit)
        bad += not row["pass"]
        print(json.dumps(row), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
