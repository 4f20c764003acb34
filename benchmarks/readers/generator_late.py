"""How late the benchmark's own generator appended: a quantile of append
time minus due time over the records due inside the window, so that a
starved generator is not read as a fast or a slow system."""

from benchmarks.core.pairing import quantile


def read(run, q, **_):
    if run.late_ms is None or not len(run.late_ms):
        return None
    return quantile(run.late_ms, float(q))
