"""A counter of the program's metrics registry: its increase over the
window, or with ``per_record`` that increase over the predictions delivered
in the window."""


def read(run, component, name, per_record=False, **_):
    after = run.registry_after.get(component, {}).get(name)
    if not isinstance(after, (int, float)):
        return None
    delta = after - (run.registry_before.get(component, {}).get(name) or 0)
    if per_record:
        return delta / run.delivered_in_window \
            if run.delivered_in_window else None
    return delta
