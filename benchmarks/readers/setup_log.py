"""A start by span, from the program's set-up log alone
(``storm_tpu/obs/profile.py ProfileStore.setup()``: one row a span of a
start, ``span``, ``parent``, ``name``, ``t_start``, ``t_end`` on
``time.time()``, ``thread`` and ``attrs``; the program's own spans where its
work happens, and JAX's traces, lowerings and backend compiles with what the
persistent cache did, each under the span that caused it).

``what`` names the number (seconds, each the union of its rows' intervals so
that rows which overlap on two threads count once):

``topology_ready_s``  the ``topology.submit`` roots: what a restart costs a
                      user, less the chip's opening where the harness opened
                      it first
``parameters_s``      ``parameters`` and ``parameters.serve`` under an
                      ``engine.build``: initialising or restoring them, and
                      casting, arranging and placing them
``programs_load_s``   the ``program`` spans under a ``warmup.bucket``: a cold
                      bucket's stage, put, trace, lowering, cache look-up or
                      compile, and launch
``spanned_share``     every row's interval over ``run.setup_s``, in percent:
                      how much of the metric the program accounts for; the
                      rest is the yardstick's own (its reference's execution
                      above all) and what runs before the log listens

Beside the number, once a run, ``run.notes["setup"]``: the ``tree`` (the
program's spans, and of JAX's rows those of a ``program``, those of
``NOTE_S`` seconds or more and those that wrote a cache entry, each ``[name, seconds from the first row, seconds,
parent's name, attrs]`` in order of start; the rest of JAX's rows a line a
parent) and the totals no metric holds: ``first_runs_s`` (the warm-up's
buckets less their programs: first execution and fetch),
``compiles_outside_s`` (JAX's rows under no ``engine.build``: in the
benchmark the reference's program and the harness's eager operations, in a
daemon nothing), ``backend_compile_s`` (the sum ``compile_s`` counts),
``programs_written`` and ``outside_written`` (backend compiles that wrote a
cache entry, under an ``engine.build`` and not) and ``written`` (each such
row's ``fun_name`` with its parent's name).

None where the program keeps no such log (a commit before it) or the log is
empty (the switch off); never raises on a CPU."""

from benchmarks.core import xplane

NOTE_S = 0.05
JAX = "jax."


def rows(run):
    """The set-up log at the run's end, once a run. None where the program
    keeps none."""
    if not hasattr(run, "_setup_rows"):
        try:
            from storm_tpu.obs.profile import profile_store

            run._setup_rows = profile_store().setup()
        except (ImportError, AttributeError):
            run._setup_rows = None
    return run._setup_rows


def union(spans: list) -> float:
    """Seconds that some row of ``spans`` covers."""
    return sum(b - a for a, b in xplane.union(
        [[r["t_start"], r["t_end"]] for r in spans]))


def reading(log: list, setup_s=None) -> dict:
    """Every number of this reader over ``log`` (plain rows, so a test hands
    it a log made by hand), and the note."""
    by_span = {r["span"]: r for r in log}

    def above(r):
        seen = by_span.get(r["parent"])
        while seen is not None:
            yield seen["name"]
            seen = by_span.get(seen["parent"])

    def under(r, name):
        return name in above(r)

    def named(*names):
        return [r for r in log if r["name"] in names]

    def parent_name(r):
        return (by_span.get(r["parent"]) or {}).get("name")

    jax_rows = [r for r in log if r["name"].startswith(JAX)]
    compiles = named("jax.backend_compile")
    written = [r for r in compiles if r["attrs"].get("cache") == "written"]
    written_spans = {r["span"] for r in written}
    programs = [r for r in named("program") if under(r, "warmup.bucket")]
    zero = min(r["t_start"] for r in log)

    def line(r):
        return [r["name"], r["t_start"] - zero, r["t_end"] - r["t_start"],
                parent_name(r), r["attrs"]]

    small: dict = {}
    tree = []
    for r in sorted(log, key=lambda r: r["t_start"]):
        if not r["name"].startswith(JAX) or r["span"] in written_spans \
                or r["t_end"] - r["t_start"] >= NOTE_S \
                or parent_name(r) == "program":
            tree.append(line(r))
        else:
            at = small.setdefault(r["parent"], [parent_name(r), 0, 0.0])
            at[1] += 1
            at[2] += r["t_end"] - r["t_start"]
    spanned = union(log)
    return {
        "topology_ready_s": union([r for r in named("topology.submit")
                                   if r["parent"] is None]),
        "parameters_s": union([
            r for r in named("parameters", "parameters.serve")
            if under(r, "engine.build")]),
        "programs_load_s": union(programs),
        "spanned_share": None if not setup_s else 100.0 * spanned / setup_s,
        "note": {
            "rows": len(log), "jax_rows": len(jax_rows),
            "spanned_s": spanned,
            "first_runs_s": union(named("warmup.bucket")) - union(programs),
            "compiles_outside_s": union([
                r for r in jax_rows if not under(r, "engine.build")]),
            "backend_compile_s": sum(r["t_end"] - r["t_start"]
                                     for r in compiles),
            "programs_written": sum(under(r, "engine.build")
                                    for r in written),
            "outside_written": sum(not under(r, "engine.build")
                                   for r in written),
            "written": [[r["attrs"].get("fun_name"), parent_name(r)]
                        for r in written],
            "tree": tree,
            "jax_rows_not_in_the_tree": [
                [name, n, seconds] for name, n, seconds in small.values()],
        },
    }


def read(run, what, **_):
    log = rows(run)
    if not log:
        return None
    if not hasattr(run, "_setup_reading"):
        # what began once the window's first output was out is no set-up
        # (a bucket that traffic met cold: the run is then not correct)
        times = getattr(run, "delivery_times", ())
        edge = float(times[0]) if len(times) else float("inf")
        before = [r for r in log if r["t_start"] < edge]
        found = reading(before, run.setup_s) if before else None
        if found:
            run.notes["setup"] = dict(found.pop("note"),
                                      rows_after=len(log) - len(before))
        run._setup_reading = found
    return (run._setup_reading or {}).get(what)
