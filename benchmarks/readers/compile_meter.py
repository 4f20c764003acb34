"""JAX's own monitoring events during set-up: ``compile_s`` (backend compile
seconds, cache look-ups included) or ``cache_misses`` (programs compiled
because the persistent cache did not hold them)."""


def read(run, field, **_):
    return run.compile_at_window_start.get(field)
