"""One counter's increase over the window as a share, in percent, of the
increase of several together: ``100 * of / sum(among)``. None where a
counter is absent or nothing was counted."""


def read(run, component, of, among, **_):
    def delta(name):
        after = run.registry_after.get(component, {}).get(name)
        if not isinstance(after, (int, float)):
            return None
        return after - (run.registry_before.get(component, {}).get(name) or 0)

    parts = [delta(name) for name in among]
    part = delta(of)
    if part is None or any(p is None for p in parts) or not sum(parts):
        return None
    return 100.0 * part / sum(parts)
