"""A quantile, over the records due inside the window, of the time from a
record's due time on the schedule to its prediction's append at the output
topic. A record with no answer at the end of the drain stands at the time it
had then been waiting, which is more than any answered record's, and counts
in ``failed``. Only an open-loop cell has due times."""

from benchmarks.core.pairing import quantile


def read(run, q, **_):
    if run.latencies_ms is None or not len(run.latencies_ms):
        return None
    return quantile(run.latencies_ms, float(q))
