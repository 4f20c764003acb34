"""The set-up log (PR 70): one row a span of a start, stamped where the work
happens (``obs/profile.py setup_span``) on the step log's clock, with every
trace, lowering and backend compile JAX reports, and what its persistent
cache did, under the span that caused it (``ProfileStore.setup()``;
docs/OPERATIONS.md, "Reading the set-up log")."""

import asyncio
import logging
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from storm_tpu.config import BatchConfig, ModelConfig, ShardingConfig
from storm_tpu.infer import engine as engine_mod
from storm_tpu.infer.engine import shared_engine
from storm_tpu.obs import profile
from storm_tpu.obs.profile import (
    SETUP_FIELDS,
    SETUP_LOG,
    setup_span,
    setup_summary,
    setup_tree,
    union_seconds,
)

JAX_NAMES = ("jax.trace", "jax.lower", "jax.backend_compile")


@pytest.fixture(autouse=True)
def _fresh():
    profile.set_enabled(True)
    profile.profile_store().reset()
    yield
    profile.set_enabled(True)


def _log():
    return profile.profile_store().setup()


def _named(rows, name):
    return [r for r in rows if r["name"] == name]


def _toy(tag):
    """A jitted function no other test has compiled."""
    def toy(x):
        return jnp.tanh(x) * 3.0 + len(tag)

    toy.__name__ = f"toy_{tag}"
    return jax.jit(toy)


def test_a_row_has_the_fields_and_a_child_names_its_parent():
    before = time.time()
    with setup_span("outer", topology="t") as outer:
        with setup_span("inner", bucket=8) as inner:
            inner.attrs["padded"] = 8
    rows = _log()
    assert [r["name"] for r in rows] == ["inner", "outer"]  # as they ended
    for r in rows:
        assert tuple(r) == SETUP_FIELDS
        assert before <= r["t_start"] <= r["t_end"] <= time.time()
        assert r["thread"] == threading.current_thread().name
    child, root = rows
    assert root["parent"] is None and child["parent"] == root["span"]
    assert child["span"] > root["span"]  # a process-wide count
    assert child["attrs"] == {"bucket": 8, "padded": 8}
    assert root["attrs"] == {"topology": "t"}
    assert outer.ms >= inner.ms > 0
    # a reader's copy: changing it changes no row
    rows[0]["attrs"]["x"] = 1
    assert "x" not in _log()[0]["attrs"]


def test_the_parent_follows_to_thread_and_tasks(run):
    async def main():
        with setup_span("topology.submit"):
            def prepare():
                with setup_span("component.prepare"):
                    return threading.current_thread().name

            async def task():
                with setup_span("in.task"):
                    await asyncio.sleep(0)

            worker = await asyncio.to_thread(prepare)
            await asyncio.create_task(task())
            return worker

    worker = run(main())
    rows = {r["name"]: r for r in _log()}
    root = rows["topology.submit"]
    assert rows["component.prepare"]["parent"] == root["span"]
    assert rows["component.prepare"]["thread"] == worker != root["thread"]
    assert rows["in.task"]["parent"] == root["span"]


def test_a_context_copied_under_a_span_that_ended_writes_roots(run):
    """A task made in ``prepare`` keeps its copy of the context: what it
    begins after the span has ended has no cause among the spans."""
    async def main():
        go = asyncio.Event()

        async def later():
            await go.wait()
            with setup_span("program"):
                pass

        with setup_span("component.prepare"):
            task = asyncio.create_task(later())
            await asyncio.sleep(0)
        go.set()
        await task

    run(main())
    rows = {r["name"]: r for r in _log()}
    assert rows["program"]["parent"] is None


def test_a_compile_inside_a_span_lands_as_its_children_by_name():
    profile.ensure_installed()
    f = _toy("inside")
    with setup_span("program") as span:
        f(np.ones((3, 5), np.float32)).block_until_ready()
    rows = _log()
    mine = [r for r in rows if r["parent"] == span.span]
    assert [r["name"] for r in mine] == list(JAX_NAMES)
    trace, lower, compiled = mine
    assert trace["attrs"] == {"fun_name": "toy_inside"}
    assert lower["attrs"] == {"fun_name": "jit(toy_inside)"}
    assert compiled["attrs"]["fun_name"] == "jit(toy_inside)"
    assert compiled["attrs"]["cache"] in ("none", "written")
    # JAX's own stamps, in order, inside the span that caused them
    own = _named(rows, "program")[0]
    assert own["t_start"] <= trace["t_start"] <= trace["t_end"] \
        <= lower["t_start"] <= lower["t_end"] <= compiled["t_start"] \
        <= compiled["t_end"] <= own["t_end"]
    # a second call compiles nothing and writes nothing
    f(np.ones((3, 5), np.float32)).block_until_ready()
    assert len(_log()) == len(rows)


def test_a_compile_outside_any_span_lands_as_roots():
    profile.ensure_installed()
    _toy("outside")(np.ones((2, 7), np.float32)).block_until_ready()
    mine = [r for r in _log()
            if "toy_outside" in str(r["attrs"].get("fun_name"))]
    assert [r["name"] for r in mine] == list(JAX_NAMES)
    assert all(r["parent"] is None for r in mine)


def test_only_a_programs_outermost_trace_is_a_row():
    """A program's trace holds a trace of every jitted function it calls,
    and an eager operation whose executable is in memory is traced again
    at every call: neither writes a row."""
    profile.ensure_installed()
    inner = _toy("inner")

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 2.0)

    outer(np.ones((4, 3), np.float32)).block_until_ready()
    x = jnp.ones((4, 3)) + 1.0
    count = len(_log())
    for _ in range(5):
        x = x + 1.0  # in memory since the line above
    rows = _log()
    assert len(rows) == count
    traces = [r["attrs"]["fun_name"] for r in _named(rows, "jax.trace")]
    assert "outer" in traces and "toy_inner" not in traces
    assert len(traces) == len(_named(rows, "jax.lower"))


def test_what_a_lowering_traces_does_not_take_its_programs_trace():
    """The random bits' lowering traces a score of jitted functions after
    the program's own trace and before its lowering ends (on the chip: an
    ``add`` stood where Kimi K2's ``fwd`` should have): the program's trace
    is a row once its lowering begins, and what the lowering traces is
    nested in it."""
    profile.ensure_installed()

    def drawn(x):
        return jax.random.normal(jax.random.PRNGKey(7), x.shape) + x

    drawn.__name__ = "toy_drawn"
    with setup_span("program") as span:
        jax.jit(drawn)(np.ones((5, 3), np.float32)).block_until_ready()
    mine = [r for r in _log() if r["parent"] == span.span]
    assert [(r["name"], r["attrs"]["fun_name"]) for r in mine] == [
        ("jax.trace", "toy_drawn"), ("jax.lower", "jit(toy_drawn)"),
        ("jax.backend_compile", "jit(toy_drawn)")]


@pytest.fixture
def cache_dir(tmp_path):
    """JAX's persistent cache in a directory of the test's own."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        yield tmp_path
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_the_cache_writes_first_and_hits_after_the_memory_is_cleared(
        cache_dir):
    profile.ensure_installed()
    x = np.ones((6, 2), np.float32)
    with setup_span("first"):
        _toy("cached")(x).block_until_ready()
    jax.clear_caches()
    with setup_span("second"):
        _toy("cached")(x).block_until_ready()
    rows = _log()
    spans = {r["name"]: r["span"] for r in rows}
    first, second = (
        next(r for r in _named(rows, "jax.backend_compile")
             if r["parent"] == spans[name]) for name in ("first", "second"))
    assert first["attrs"]["cache"] == "written"
    assert "retrieval_s" not in first["attrs"]
    assert second["attrs"]["cache"] == "hit"
    assert second["attrs"]["retrieval_s"] > 0
    assert "saved_s" in second["attrs"]
    assert any(cache_dir.iterdir())


def _shared(name="lenet5", buckets=(2, 8)):
    cfg = ModelConfig(name=name, seed=70)
    batch = BatchConfig(buckets=buckets, max_batch=max(buckets),
                        max_wait_ms=2.0)
    return shared_engine(cfg, ShardingConfig(), batch)


def _under(rows, r, name):
    by_span = {x["span"]: x for x in rows}
    r = by_span.get(r["parent"])
    while r is not None:
        if r["name"] == name:
            return True
        r = by_span.get(r["parent"])
    return False


def test_a_build_and_its_warm_up_by_span_and_one_timer():
    profile.ensure_installed()
    eng = _shared()
    try:
        seen = []
        eng.on_compile = lambda padded, ms: seen.append((padded, ms))
        eng.warmup()
        rows = _log()
        build, = _named(rows, "engine.build")
        assert build["attrs"] == {"engine": eng.profile_key}
        for name in ("devices", "model.build", "parameters",
                     "parameters.serve"):
            row, = _named(rows, name)
            assert row["parent"] == build["span"], name
        made, = _named(rows, "parameters")
        assert made["attrs"]["source"] == "seed"
        assert made["attrs"]["leaves"] == 10
        assert made["attrs"]["bytes"] > 0
        served, = _named(rows, "parameters.serve")
        assert served["attrs"]["bytes"] == eng.param_bytes()
        buckets = _named(rows, "warmup.bucket")
        assert [(b["attrs"]["bucket"], b["attrs"]["padded"])
                for b in buckets] == [(2, 2), (8, 8)]
        programs = _named(rows, "program")
        assert len(programs) == 2
        compiles = profile.profile_store().snapshot()[
            "engines"][eng.profile_key]["compiles"]
        for bucket, program in zip(buckets, programs):
            assert bucket["parent"] == build["span"]
            assert program["parent"] == bucket["span"]
            assert program["attrs"]["padded"] == bucket["attrs"]["padded"]
            assert program["attrs"]["engine"] == eng.profile_key
            assert "form" in program["attrs"]
            # JAX's rows of the program are its children, by name
            mine = [r for r in rows if r["parent"] == program["span"]]
            assert [r["name"] for r in mine] == list(JAX_NAMES)
            assert mine[0]["attrs"]["fun_name"] == "fwd"
            # the one timer: the span's milliseconds, wherever reported
            ms = (program["t_end"] - program["t_start"]) * 1e3
            padded = program["attrs"]["padded"]
            assert compiles[str(padded)]["last_ms"] == pytest.approx(ms)
            assert (padded, pytest.approx(ms)) in seen
        assert profile.profile_store().coverage()[eng.profile_key][
            "compile_known"] == ["2", "8"]
        # every JAX row of the build lies under it
        for r in rows:
            if r["name"] in JAX_NAMES and r["t_start"] >= build["t_start"]:
                assert _under(rows, r, "engine.build"), r
        # a second warm-up, and a warm step, write nothing
        eng.warmup()
        eng.predict(np.zeros((2, *eng.input_shape), np.float32))
        assert len(_log()) == len(rows)
    finally:
        engine_mod.unload_engine(eng)


def test_a_bucket_met_cold_by_traffic_writes_a_root_program():
    profile.ensure_installed()
    eng = _shared(buckets=(4, 16))
    try:
        eng.warmup((4,))
        before = len(_named(_log(), "program"))
        out = eng.predict(np.zeros((9, *eng.input_shape), np.float32))
        assert out.shape[0] == 9
        programs = _named(_log(), "program")
        assert len(programs) == before + 1
        cold = programs[-1]
        assert cold["parent"] is None
        assert cold["attrs"]["padded"] == 16
        assert cold["attrs"]["engine"] == eng.profile_key
        rows = _log()
        assert [r["name"] for r in rows if r["parent"] == cold["span"]] \
            == list(JAX_NAMES)
        assert setup_summary(rows)["cold_in_traffic"] == 1
    finally:
        engine_mod.unload_engine(eng)


def test_the_serial_path_writes_the_same_program_row():
    profile.ensure_installed()
    cfg = ModelConfig(name="lenet5", seed=71)
    batch = BatchConfig(buckets=(4,), max_batch=4, pipeline_depth=0)
    eng = shared_engine(cfg, ShardingConfig(), batch)
    try:
        seen = []
        eng.on_compile = lambda padded, ms: seen.append((padded, ms))
        eng.warmup()
        program, = _named(_log(), "program")
        assert program["attrs"]["step"] is None
        bucket, = _named(_log(), "warmup.bucket")
        assert program["parent"] == bucket["span"]
        ms = (program["t_end"] - program["t_start"]) * 1e3
        assert seen == [(4, pytest.approx(ms))]
    finally:
        engine_mod.unload_engine(eng)


def test_off_writes_nothing_and_registers_nothing_twice():
    from jax._src import monitoring as mon

    profile.ensure_installed()
    profile.ensure_installed()
    profile.set_enabled(False)
    profile.set_enabled(True)
    for listeners, mine in (
            (mon.get_event_time_span_listeners(), profile._jax_span),
            (mon.get_event_listeners(), profile._jax_cache_event),
            (mon.get_event_duration_listeners(),
             profile._jax_cache_seconds),
            (mon.get_scalar_listeners(), profile._jax_enter)):
        assert listeners.count(mine) == 1
    profile.profile_store().reset()
    profile.set_enabled(False)
    with setup_span("outer") as outer:
        with setup_span("inner"):
            _toy("off")(np.ones((2, 2), np.float32)).block_until_ready()
    assert outer.span is None and outer.ms >= 0  # still a timer
    assert _log() == []
    profile.set_enabled(True)
    # and what the off arm compiled leaves nothing behind on the thread
    with setup_span("after"):
        pass
    assert [r["name"] for r in _log()] == ["after"]


def test_reset_clears_and_the_log_keeps_its_last_rows():
    for i in range(SETUP_LOG + 10):
        with setup_span("span", i=i):
            pass
    rows = _log()
    assert len(rows) == SETUP_LOG == 1024
    assert [r["attrs"]["i"] for r in rows] == list(range(10, SETUP_LOG + 10))
    profile.profile_store().reset()
    assert _log() == []
    assert profile.profile_store().snapshot()["setup"]["count"] == 0


def _row(span, parent, name, t0, t1, **attrs):
    return dict(zip(SETUP_FIELDS,
                    (span, parent, name, t0, t1, "MainThread", attrs)))


def test_the_snapshots_totals_are_unions_not_sums():
    store = profile.profile_store()
    # two builds on two threads that overlap for two of their four seconds
    for row in (_row(1, None, "engine.build", 10.0, 14.0, engine="a"),
                _row(2, None, "engine.build", 12.0, 16.0, engine="b"),
                _row(3, 1, "parameters", 10.0, 13.0),
                _row(4, 2, "parameters", 12.0, 15.0),
                _row(5, 1, "parameters", 10.5, 11.0)):  # nested
        store.log_setup(tuple(row.values()))
    setup = store.snapshot()["setup"]
    assert setup["count"] == 5 and len(setup["rows"]) == 5
    assert setup["by_name"]["engine.build"] == {"count": 2, "seconds": 6.0}
    assert setup["by_name"]["parameters"] == {"count": 3, "seconds": 5.0}
    assert setup["summary"]["parameters_s"] == 5.0
    assert setup["summary"]["total_s"] == 6.0
    assert union_seconds([]) == 0.0


def test_a_summary_by_what_a_start_went_to_and_its_line():
    rows = [
        _row(1, None, "topology.submit", 0.0, 14.2, topology="t"),
        _row(2, 1, "component.prepare", 0.1, 14.1),
        _row(3, 2, "engine.build", 0.1, 4.6, engine="m"),
        _row(4, 3, "parameters", 0.5, 3.6),
        _row(5, 3, "parameters.serve", 3.6, 4.6),
        _row(6, 3, "warmup.bucket", 4.6, 9.6, bucket=8),
        _row(7, 6, "program", 4.6, 9.0, padded=8),
        _row(8, 7, "jax.backend_compile", 5.0, 8.9, cache="hit"),
        _row(9, 3, "warmup.bucket", 9.6, 14.1, bucket=32),
        _row(10, 9, "program", 9.6, 13.1, padded=32),
        _row(11, 10, "jax.backend_compile", 10.0, 11.0, cache="hit"),
        _row(12, 10, "jax.backend_compile", 11.0, 13.0, cache="written"),
        # another topology's, and a bucket that traffic met cold
        _row(20, None, "topology.submit", 20.0, 21.0, topology="u"),
        _row(21, 20, "parameters", 20.0, 21.0),
        _row(30, None, "program", 30.0, 31.0, padded=128),
    ]
    got = setup_summary(rows, root=1)
    assert got["total_s"] == pytest.approx(14.2)
    assert got["parameters_s"] == pytest.approx(3.1)
    assert got["serve_s"] == pytest.approx(1.0)
    assert got["programs_s"] == pytest.approx(7.9)
    assert (got["programs_loaded"], got["programs_compiled"]) == (1, 1)
    assert got["first_runs_s"] == pytest.approx(1.6)
    assert got["other_s"] == pytest.approx(0.6)
    assert got["cold_in_traffic"] == 0
    assert profile.setup_line(got) == (
        "set-up 14.2 s: parameters 3.1, serve 1.0, programs 7.9 (1 loaded, "
        "1 compiled), first runs 1.6, other 0.6")
    assert setup_summary(rows)["cold_in_traffic"] == 1
    # the tree: a root, then what it caused, each level by its start
    tree = setup_tree(rows)
    assert [(d, r["span"]) for d, r in tree][:6] == [
        (0, 1), (1, 2), (2, 3), (3, 4), (3, 5), (3, 6)]
    assert [r["span"] for d, r in tree if d == 0] == [1, 20, 30]


def test_submit_stamps_the_topology_and_logs_the_line(run, caplog):
    from storm_tpu.connectors import MemoryBroker
    from storm_tpu.config import Config
    from storm_tpu.main import build_standard_topology
    from storm_tpu.runtime.cluster import AsyncLocalCluster

    cfg = Config()
    cfg.model = ModelConfig(name="lenet5", seed=72)
    cfg.batch = BatchConfig(buckets=(4,), max_batch=4)
    broker = MemoryBroker(default_partitions=cfg.broker.partitions)

    async def main():
        cluster = AsyncLocalCluster()
        try:
            await cluster.submit("t70", cfg,
                                 build_standard_topology(cfg, broker))
        finally:
            await cluster.shutdown()

    with caplog.at_level(logging.INFO, logger="storm_tpu.cluster"):
        run(main(), timeout=110)
    rows = _log()
    root, = _named(rows, "topology.submit")
    assert root["parent"] is None and root["attrs"] == {"topology": "t70"}
    prepared = _named(rows, "component.prepare")
    assert all(r["parent"] == root["span"] for r in prepared)
    assert {r["attrs"]["component"] for r in prepared} >= {
        "inference-bolt", "kafka-spout", "kafka-bolt"}
    build, = _named(rows, "engine.build")
    first = min((r for r in prepared
                 if r["attrs"]["component"] == "inference-bolt"),
                key=lambda r: r["t_start"])
    assert build["parent"] == first["span"]
    program, = _named(rows, "program")
    assert _under(rows, program, "topology.submit")
    said = [r.getMessage() for r in caplog.records
            if "set-up" in r.getMessage()]
    assert len(said) == 1 and said[0].startswith("t70: set-up ")
    assert "(0 loaded, 1 compiled)" in said[0] \
        or "(1 loaded, 0 compiled)" in said[0]


def test_the_profile_command_prints_the_tree():
    from storm_tpu.main import _setup_lines

    profile.ensure_installed()
    eng = _shared(buckets=(2,))
    try:
        eng.warmup()
        lines = _setup_lines(profile.profile_store().snapshot()["setup"])
    finally:
        engine_mod.unload_engine(eng)
    assert lines[0].startswith("set-up log, ") and "set-up " in lines[0]
    text = "\n".join(lines)
    for name in ("engine.build", "parameters", "parameters.serve",
                 "warmup.bucket", "program"):
        assert f" {name} " in text
    # a child stands deeper than its parent
    depth = {line.split()[0]: len(line) - len(line.lstrip())
             for line in lines[1:]}
    assert depth["engine.build"] < depth["warmup.bucket"] < depth["program"]
    assert any(line.strip().startswith("jax: 1 trace") for line in lines)
    assert _setup_lines({}) == []


def test_nothing_of_it_is_on_the_path_of_a_step_or_a_record():
    """``setup_span`` stands where a start's work happens and nowhere that
    runs a step or a record."""
    import inspect

    from storm_tpu import connectors
    from storm_tpu.infer import continuous

    assert "setup_span" not in inspect.getsource(continuous)
    import os
    for root, _dirs, files in os.walk(os.path.dirname(connectors.__file__)):
        for name in files:
            if name.endswith(".py"):
                assert "setup_span" not in open(
                    os.path.join(root, name)).read(), name
    assert "setup_span" not in inspect.getsource(engine_mod._fetch_loop)
    # the warm path of a dispatch: the span opens under the cold test
    source = inspect.getsource(engine_mod.InferenceEngine._dispatch_phase)
    assert source.index("if cold:") < source.index("_program_span")
    assert "setup_span" not in inspect.getsource(
        engine_mod.InferenceEngine._stage_and_launch)
