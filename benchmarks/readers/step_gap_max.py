"""The longest time between two consecutive steps' results becoming ready,
in milliseconds, from the program's step log alone (no trace needed).

Over the steps of the busiest engine whose ``t_ready`` lies between the
window's first and last delivery (``run.delivery_times``: in a traced run
that is the part before the profiler started). In a backlog a step ends as
the next begins, so this is the step's time, and a stall of the device or of
the host shows as one gap many steps long. Beside the number, in
``run.notes["step_gap"]``, the gap's two rows and the interval of the later
one (``obs/profile.py STEP_INTERVALS``) that exceeds its median over the window
by most: where the time went. With fewer than two such steps the window
itself is the gap. None where the program keeps no step log.

In a traced run of any cell it also has the log and the trace fitted onto one
clock (``prefix``: the engine's programs), so that ``run.notes["clock"]``
says in every cell how the host's stamps lie against the device's."""

from benchmarks.core import steplog


def read(run, prefix=None, **_):
    log = steplog.rows(run)
    if log is None:
        return None
    if prefix and run.trace:
        steplog.on_trace_clock(run, prefix)
    found = None
    if len(run.delivery_times):
        found = steplog.longest_gap(log, float(run.delivery_times[0]),
                                    float(run.delivery_times[-1]))
    if found is None:
        run.notes["step_gap"] = {"steps": 0}
        return run.seconds * 1e3
    gap, note = found
    run.notes["step_gap"] = note
    return gap * 1e3
