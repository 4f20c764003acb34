"""A part's device time a step under the least time of the kernel it runs,
in percent: a roofline share for a loop that only a *name* tells apart.

``trace_ops_time`` finds a kernel by the shapes its loop carries. Trinity's
two attention loops (a sliding layer's and a full layer's) carry identical
shapes, so no pattern can part them; their parts' names do
(``mix.window_attention``, ``mix.attention``: ``storm_tpu/ops/parts.py``).
The time is ``trace_part_time``'s own sum for ``part`` (the mean over the
whole executions of the ``prefix`` programs, every layer of the part
together); the least time is ``kernels()[kernel]`` of the configuration's
``ops`` file (summed over the same layers) by ``peaks.json``, the larger of
operations over peak operations and bytes over peak bytes, for the rows a
step the program was built for.

None where there is no trace, no operation under that part (a program from
before the name: the metric is then left out of the line), or no shape to
read the rows off.
"""

from benchmarks.core import spec


def read(run, prefix, part, kernel, **_):
    ms = spec.plugin("readers", "trace_part_time").read(run, prefix, part=part)
    if not ms:
        return None
    ops = spec.plugin("ops", run.config["ops"])
    names = [op for module, ns in (run.trace or {}).get(
        "module_ops", {}).items() if module.startswith(prefix) for op in ns]
    rows = ops.rows_per_step(names, run.config["published"])
    if rows is None:
        return None
    work = ops.kernels(run.config["published"], rows,
                       run.bytes_per_value)[kernel]
    peaks = run.peaks()
    least_ms = 1e3 * max(work["flops"] / peaks["bf16_flops_per_s"],
                         work["bytes"] / peaks["hbm_bytes_per_s"])
    run.notes.setdefault("kernels", {})[kernel] = {
        "ms": ms, "least_ms": least_ms, "rows": rows}
    return 100.0 * least_ms / ms
