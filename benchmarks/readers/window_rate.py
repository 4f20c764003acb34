"""Predictions appended to the output topic inside the window, over the
window's length: all the work and all the time of the window."""


def read(run, **_):
    if run.seconds <= 0:
        return None
    return run.delivered_in_window / run.seconds
