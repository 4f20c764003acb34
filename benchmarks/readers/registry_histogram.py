"""A histogram of the program's always-on metrics registry
(``runtime/metrics.py``), read through ``cluster.metrics(name)`` at the
window's start and end.

``terms`` is a list of ``[component, name]``; ``stat`` is one of

- ``mean``            (sum at the end - sum at the start) / (count likewise),
                      over all terms together
- ``sum_per_record``  the same sums, over the predictions delivered in the
                      window: host milliseconds of that layer per record
- ``p50``             the median of the first term's reservoir at the
                      window's end (the registry keeps the most recent
                      samples, so this is the window's later part)
"""


def _delta(run, component, name):
    after = run.registry_after.get(component, {}).get(name)
    if not isinstance(after, dict):
        return None
    before = run.registry_before.get(component, {}).get(name) or {}
    return (after["count"] - before.get("count", 0),
            (after["sum"] or 0.0) - (before.get("sum") or 0.0), after)


def read(run, terms, stat, **_):
    deltas = [_delta(run, c, n) for c, n in terms]
    if any(d is None for d in deltas):
        return None
    count = sum(d[0] for d in deltas)
    total = sum(d[1] for d in deltas)
    if stat == "mean":
        return total / count if count else None
    if stat == "sum_per_record":
        return total / run.delivered_in_window \
            if run.delivered_in_window else None
    if stat == "p50":
        return deltas[0][2].get("p50") if deltas[0][0] else None
    raise ValueError(f"registry_histogram: unknown stat {stat!r}")
