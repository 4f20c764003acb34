"""Predictions appended to the output topic over the window, read between
whole landings where results land a batch at a time.

Count over seconds moves in steps of one batch over the window (256 rows in
20 s: 4.5 %), whichever side of the window's edges a landing falls. So where
the deliveries come in bursts, the rate is read over the sub-window from the
first delivery of the window's first whole landing to the first delivery of
its last: the records delivered in between, over the time between. That is
still all the work over all the time of all but the window's two ends, a
stall between those two deliveries is in the denominator, and it moves
continuously. What it does not see: the part of a landing interval before
the first whole landing begins and everything from the start of the last
landing to the window's end, up to two landing intervals together (1.3-1.8 s
of this cell's 20): a stall there is in neither the records nor the time.

A landing begins at a delivery that follows a gap longer than half the
typical gap *between* landings: the gap in which the window's time passes its
half when the gaps are laid end to end in order of length. (Nearly all of a
bursty window's time lies in the gaps between landings, so that gap is one
of them, stall or no stall; half the *longest* gap would leave a window with
one stall in it a single landing.) No constant of the program: the batch
size stays unpinned. Fewer than three landings, or no burst structure (that
gap under ten median gaps): count over seconds."""

import numpy as np

BURST = 10.0  # typical gap between landings over the median gap


def landing_starts(times):
    """Indices into the sorted ``times`` at which a landing begins after a
    gap inside the window; none where deliveries do not come in bursts. The
    window's first delivery never counts: the landing it belongs to may have
    begun before the window."""
    gaps = np.diff(np.asarray(times, np.float64))
    none = np.zeros(0, int)
    if len(gaps) < 2 or gaps.max() <= 0:
        return none
    ordered = np.sort(gaps)
    passed = np.cumsum(ordered)
    between = ordered[np.searchsorted(passed, passed[-1] / 2)]
    if between < BURST * np.median(gaps):
        return none
    return np.flatnonzero(gaps > between / 2) + 1


def read(run, **_):
    if run.seconds <= 0:
        return None
    times = np.asarray(run.delivery_times, np.float64)
    starts = landing_starts(times)
    over_seconds = run.delivered_in_window / run.seconds
    if len(starts) < 3:
        run.notes["window_rate"] = {"read": "count over seconds",
                                    "landings": len(starts)}
        return over_seconds
    first, last = starts[0], starts[-1]
    run.notes["window_rate"] = {
        "read": "between landings", "landings": len(starts),
        "seconds_between": float(times[last] - times[first])}
    return (last - first) / (times[last] - times[first])
