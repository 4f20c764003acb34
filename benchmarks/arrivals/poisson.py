"""An open loop at a fixed rate: exponential gaps, each record timed from
when it was due, whether or not the system or this thread kept up.

Every seed gets the same multiset of gaps in another order (the gaps are
drawn once from ``gaps_seed`` in the traffic file and permuted by the run's
seed), so the seed changes which record waits behind which and not how much
work a window holds."""

import time

import numpy as np


def gaps(traffic: dict, seed: int, seconds: float):
    """The gaps between records: one multiset for every seed, a fifth more
    than ``seconds`` need, in the seed's order."""
    rate = float(traffic["rate"])
    n = int(rate * seconds * 1.2) + 16
    drawn = np.random.RandomState(int(traffic.get("gaps_seed", 0))) \
        .exponential(1.0 / rate, n)
    return drawn[np.random.RandomState(seed % 2 ** 32).permutation(n)]


def schedule(traffic: dict, seed: int, seconds: float):
    """Due times in seconds from the generator's start, up to ``seconds``."""
    due = np.cumsum(gaps(traffic, seed, seconds))
    return due[due < seconds]


def run(gen) -> None:
    due = gen.schedule
    i, n = 0, len(due)
    while i < n and not gen.done():
        now = time.time() - gen.started
        while i < n and due[i] <= now:
            gen.append(due=gen.started + due[i])
            i += 1
        if i < n:
            time.sleep(min(max(due[i] - (time.time() - gen.started), 0.0),
                           0.002))
