"""Of the traced span, the share in percent in which the device ran no
operation while the host had work for it: some step of the log was between
its oldest row's entry to the queue and its start on the device.

``core/steplog.py idle_classes`` cuts every idle stretch by what the host was
at; everything but ``no rows`` counts here. Beside the number, in
``run.notes["idle"]``, the idle seconds by class and the ten longest gaps,
each with the class of its longest piece. 0.0 where the device never idled,
every gap is ``no rows``, or the log has no step to set against the trace
(then nothing says a row waited). None where there is no trace or the
program keeps no step log."""

from benchmarks.core import steplog, xplane_meta


def read(run, prefix, **_):
    if not run.trace or steplog.rows(run) is None:
        return None
    found = steplog.on_trace_clock(run, prefix)
    if not found:
        run.notes["idle"] = {"classes": None, "why": "no step matched"}
        return 0.0
    span, classes, gaps = steplog.idle_classes(
        xplane_meta.device_planes(run), steplog.rows(run), found)
    run.notes["idle"] = {"span_s": span, "classes": classes, "gaps": gaps}
    if not span:
        return 0.0
    held = sum(s for name, s in classes.items() if name != "no rows")
    return 100.0 * held / span
