"""Share of the traced window in which no operation ran on the device:
1 - union of the device's busy intervals / traced window, in percent."""


def read(run, **_):
    trace = run.trace or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
