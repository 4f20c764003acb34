"""Of the traced span, the share in percent in which the device ran no
operation, the step log says ``no rows`` (no step between its oldest row's
entry to the queue and its device start), and some record of the record log
was between its broker append and its entry to the queue: the host still held
what the device idled for.

It splits ``readers/idle_with_rows_share.py``'s ``no rows`` into "nothing was
appended" and "the host still held it", on the offset ``core/steplog.py
on_trace_clock`` fits. Beside the number, in ``run.notes["idle_before_queue"]``,
the seconds of each. 0.0 where no step matched the trace (nothing then says a
record waited). None where there is no trace, or the program keeps no record
log or no step log."""

from benchmarks.core import recordlog, steplog, xplane_meta


def read(run, prefix, **_):
    if not run.trace or recordlog.rows(run) is None \
            or steplog.rows(run) is None:
        return None
    found = steplog.on_trace_clock(run, prefix)
    if not found:
        run.notes["idle_before_queue"] = {"why": "no step matched"}
        return 0.0
    span, seconds = recordlog.host_held(
        xplane_meta.device_planes(run), steplog.rows(run), found,
        recordlog.rows(run))
    run.notes["idle_before_queue"] = dict(seconds, span_s=span)
    return 100.0 * seconds["host_held"] / span if span else 0.0
