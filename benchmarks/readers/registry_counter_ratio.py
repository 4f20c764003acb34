"""One counter's increase over the window over the increase of several
together: ``of / sum(over)``, a plain ratio: ``registry_counter_share``'s
number, which is that in percent (``registry_counter`` divides by the records
*delivered* in the window, which a step that straddles the window's edge
moves). None where a counter is absent (a program from before it) or nothing
was counted."""

from benchmarks.core import spec


def read(run, component, of, over, **_):
    share = spec.plugin("readers", "registry_counter_share").read(
        run, component, of=of, among=over)
    return None if share is None else share / 100.0
