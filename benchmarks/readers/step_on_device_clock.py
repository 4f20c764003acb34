"""A median, over the traced whole executions, of the time between a moment
of the step log and an edge of the step's execution on the device, in
milliseconds: ``cut_to_device_start`` (device start - ``t_cut``: formation,
the ring, staging, the transfer, the launch, and what the device still ran)
or ``device_end_to_host`` (``t_fetched`` - device end: seeing the result
ready and copying it out).

Log and trace are matched and brought onto one clock by ``core/steplog.py
match`` (``run.notes["clock"]`` says how well they fit). None where there is
no trace, no step log or no whole execution with its step."""

from benchmarks.core import pairing, steplog

EDGES = {"cut_to_device_start": ("t_cut", 1, +1),
         "device_end_to_host": ("t_fetched", 2, -1)}


def read(run, prefix, stat, **_):
    found = steplog.on_trace_clock(run, prefix)
    if not found:
        return None
    moment, edge, sign = EDGES[stat]
    spans = [sign * (e[edge] + found["offset_s"] - row[moment]) * 1e3
             for e, row in found["pairs"]
             if e[3] and row.get(moment) is not None]
    return pairing.quantile(spans, 0.5) if spans else None
