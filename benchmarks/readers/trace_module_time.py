"""Mean device duration in milliseconds of the executions of a program, from
the ``XLA Modules`` line of the device plane of the profiler's trace."""

from benchmarks.core.xplane import module_times


def read(run, prefix, **_):
    times = module_times(run.trace or {}, prefix)
    return sum(times) / len(times) * 1e3 if times else None
