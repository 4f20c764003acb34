"""The program's record log (``core/recordlog.py``), over the records whose
``t_produced`` lies between the window's first and last delivery.

- ``stat`` ``p50``: the median, in milliseconds, of moment ``b`` less moment
  ``a`` (each a record's own, or its step's through the key: ``t_resolved``).
- ``stat`` ``logged_share``: of the window's deliveries as the benchmark sees
  them from outside (``run.delivery_times``: the output records' broker
  timestamps), the share in percent that has a row of the log whose
  ``t_produced`` lies within a millisecond, whose moments are all set and
  whose step is in the step log: the log's append to produced is then the
  time the cell's latency is made of, record by record.

Beside the number, ``run.notes["record"]`` (``core/recordlog.py note``). None
where the program keeps no record log, or no record passed both moments."""

from benchmarks.core import pairing, recordlog


def read(run, stat, a=None, b=None, **_):
    if recordlog.rows(run) is None:
        return None
    recordlog.note(run)
    if stat == "logged_share":
        return recordlog.logged_share(run.delivery_times,
                                      recordlog.paths(run))
    if stat == "p50":
        spans = recordlog.spans_ms(recordlog.in_window(run), a, b)
        return pairing.quantile(spans, 0.5) if spans else None
    raise ValueError(f"record_interval: unknown stat {stat!r}")
