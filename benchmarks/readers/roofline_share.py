"""Least time the chip could take for the steps executed in the traced
window, over the device time the program took for them, in percent.

A step's least time is the larger of its operations over peak operations per
second and its bytes over peak bytes per second; both counts come from
shapes, by the functions of ``ops/<model>.py`` that the configuration names,
and the peaks from ``peaks.json`` by ``device_kind`` (an unlisted device is
an error). Each bucket is a program of its own in the trace, and the batch it
was compiled for is read off the shapes in its operations' names: the share
is of the work the program was built to do, padding included, so it says how
well the kernels use the chip; how full the batches were is the batcher's
metric. ``run.roofline_bound`` says which bound applied to most of the time."""

from benchmarks.core import spec


def read(run, prefix, **_):
    trace = run.trace or {}
    if "ops" not in run.config:
        return None
    ops = spec.plugin("ops", run.config["ops"])
    least = {"compute": 0.0, "memory": 0.0}
    taken = 0.0
    for name, times in trace.get("modules", {}).items():
        if not name.startswith(prefix):
            continue
        rows = ops.rows_per_step(trace["module_ops"].get(name, []),
                                 run.config["published"])
        if rows is None:
            return None  # a program whose batch cannot be read: no share
        peaks = run.peaks()
        counts = ops.counts(run.config["published"], rows=rows, steps=1,
                            bytes_per_value=run.bytes_per_value)
        by_compute = counts["flops"] / peaks["bf16_flops_per_s"]
        by_memory = counts["bytes"] / peaks["hbm_bytes_per_s"]
        bound = "compute" if by_compute >= by_memory else "memory"
        least[bound] += len(times) * max(by_compute, by_memory)
        taken += sum(times)
    if not taken:
        return None
    run.roofline_bound = max(least, key=least.get)
    return 100.0 * sum(least.values()) / taken
