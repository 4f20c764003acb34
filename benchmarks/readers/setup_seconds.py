"""Process start to the start of the window: opening the chip, the native
build check, parameters, compilation or cache load, the warm-up of every
bucket, the pool and its reference, and the warm-up traffic."""


def read(run, **_):
    return run.setup_s
