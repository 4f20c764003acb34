"""A backlog: ``outstanding`` records are kept between the input topic and
the output topic. Whenever answers land, as many new records are appended.
A slow system thereby receives less load, which is what working off a topic
is: the rate completed is the result."""

import time


def schedule(traffic: dict, seed: int, seconds: float):
    """A closed loop has no schedule: the system's own pace sets it."""
    return None


def run(gen) -> None:
    outstanding = int(gen.traffic["outstanding"])
    while not gen.done():
        room = outstanding - (gen.appended - gen.landed())
        for _ in range(max(room, 0)):
            gen.append()
        time.sleep(0.001)
