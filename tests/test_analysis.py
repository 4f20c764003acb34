"""Unit tests for the invariant analyzer (storm_tpu/analysis/).

Each rule gets a positive fixture (a minimal snippet that MUST trip it)
and a negative fixture (the sanctioned idiom that must NOT) — the negative
fixtures are the idioms the real tree relies on (condition-wait under its
own lock, finally-based deferral, static_argnames branching), so a checker
regression shows up here before it floods the clean-tree gate."""

import json
import os
import textwrap

import pytest

from storm_tpu.analysis import (
    LintConfig,
    filter_new,
    lint_source,
    load_baseline,
    load_config,
    write_baseline,
)
from storm_tpu.analysis.callgraph import CallGraph
from storm_tpu.analysis.core import cross_file_findings, parse_source
from storm_tpu.analysis.locks import check_cycles, check_ordering, \
    check_transitive
from storm_tpu.analysis.observability import check_kinds, generate_registry
from storm_tpu.analysis.protocol import check_protocols
from storm_tpu.analysis.threads import check_lifecycles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src, **cfg):
    return lint_source(textwrap.dedent(src), "fixture.py",
                       LintConfig(**cfg) if cfg else None)


def rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# LCK001: blocking call under a lock
# ---------------------------------------------------------------------------


def test_lck001_sleep_under_with_lock():
    fs = lint("""
        import threading, time
        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def f(self):
                with self._lock:
                    time.sleep(1)
    """)
    assert rules_of(fs) == {"LCK001"}
    (f,) = fs
    assert f.detail == "time.sleep"
    assert "hint" in f.to_dict() and f.line == 8


def test_lck001_sleep_outside_lock_ok():
    fs = lint("""
        import threading, time
        class C:
            def f(self):
                with self._lock:
                    x = 1
                time.sleep(1)
    """)
    assert fs == []


def test_lck001_acquire_release_region():
    fs = lint("""
        import time
        def f(lock):
            lock.acquire()
            time.sleep(1)
            lock.release()
            time.sleep(2)
    """)
    assert [f.rule for f in fs] == ["LCK001"]
    assert fs[0].line == 5  # only the sleep inside the region


def test_lck001_condition_wait_on_held_lock_exempt():
    # Condition.wait releases the lock — the sanctioned sleep-under-lock
    # (continuous batcher's dispatcher loop).
    fs = lint("""
        class C:
            def f(self):
                with self._cond:
                    while not self._ready:
                        self._cond.wait(timeout=0.1)
    """)
    assert fs == []


def test_lck001_foreign_wait_under_lock_flagged():
    fs = lint("""
        class C:
            def f(self):
                with self._lock:
                    self._event.wait()
    """)
    assert rules_of(fs) == {"LCK001"}


def test_lck001_queue_get_vs_dict_get():
    fs = lint("""
        class C:
            def f(self):
                with self._lock:
                    item = self.queue.get()
                    val = self._cache.get("key")
    """)
    assert len(fs) == 1 and fs[0].detail == "self.queue.get"


def test_lck001_future_result_and_zero_arg_join():
    fs = lint("""
        class C:
            def f(self):
                with self._lock:
                    v = fut.result()
                    self._thread.join()
                    s = ",".join(parts)
    """)
    assert sorted(f.detail for f in fs) == ["fut.result", "self._thread.join"]


def test_lck001_configured_blocking_method():
    src = """
        class C:
            def f(self):
                with self._lock:
                    self.client.control("drain")
    """
    assert lint(src) == []  # not blocking by default
    fs = lint(src, blocking_methods=["control"])
    assert rules_of(fs) == {"LCK001"}


# ---------------------------------------------------------------------------
# LCK002: lock-order inversion
# ---------------------------------------------------------------------------


def _files(*srcs):
    return [parse_source(textwrap.dedent(s), f"mod{i}.py")
            for i, s in enumerate(srcs)]


def test_lck002_inversion_flagged():
    fs = check_ordering(_files("""
        class A:
            def f(self):
                with self._lock_a:
                    with self._lock_b:
                        pass
            def g(self):
                with self._lock_b:
                    with self._lock_a:
                        pass
    """), LintConfig())
    assert [f.rule for f in fs] == ["LCK002"]
    assert "opposite order" in fs[0].message


def test_lck002_consistent_order_ok():
    fs = check_ordering(_files("""
        class A:
            def f(self):
                with self._lock_a:
                    with self._lock_b:
                        pass
            def g(self):
                with self._lock_a:
                    with self._lock_b:
                        pass
    """), LintConfig())
    assert fs == []


def test_lck002_cross_file_inversion():
    fs = check_ordering(_files(
        """
        import m
        def f():
            with GLOBAL_LOCK:
                with m.OTHER_LOCK:
                    pass
        """,
        """
        import m
        def g():
            with m.OTHER_LOCK:
                with GLOBAL_LOCK:
                    pass
        """), LintConfig())
    # different modules -> different global-lock identities; only the
    # m.OTHER_LOCK pair unifies, and the GLOBAL_LOCK halves are
    # per-module — no shared 2-cycle unless identities match
    assert all(f.rule == "LCK002" for f in fs)


# ---------------------------------------------------------------------------
# XO001: exactly-once discipline
# ---------------------------------------------------------------------------


def test_xo001_unhandled_else_path():
    fs = lint("""
        class FooBolt:
            def execute(self, t):
                if t.values[0] > 0:
                    self.collector.ack(t)
    """)
    assert rules_of(fs) == {"XO001"}


def test_xo001_all_paths_acked_ok():
    fs = lint("""
        class FooBolt:
            def execute(self, t):
                if t.values[0] > 0:
                    self.collector.ack(t)
                else:
                    self.collector.fail(t)
    """)
    assert fs == []


def test_xo001_finally_deferral_rescues_all_paths():
    fs = lint("""
        class BarBolt:
            def execute(self, t):
                try:
                    risky(t.values)
                    if maybe():
                        return
                finally:
                    self._pending.append(t)
    """)
    assert fs == []


def test_xo001_exception_edge_swallowed_unhandled():
    # the except arm swallows the error without failing the tuple: the
    # ledger waits forever — the exact silent-drop class
    fs = lint("""
        class QuxBolt:
            def execute(self, t):
                try:
                    self.collector.ack(t)
                except Exception:
                    pass
    """)
    assert rules_of(fs) == {"XO001"}


def test_xo001_raise_through_is_handled():
    # BoltExecutor._run catches and fails the tuple
    fs = lint("""
        class BazBolt:
            def execute(self, t):
                if not valid(t.values):
                    raise ValueError("bad")
                self.collector.ack(t)
    """)
    assert fs == []


def test_xo001_test_position_call_not_ownership():
    fs = lint("""
        class TickBolt:
            def execute(self, t):
                if is_tick(t):
                    return
                self.collector.ack(t)
    """)
    # `if is_tick(t)` reads the tuple; the True arm returns it unhandled
    assert rules_of(fs) == {"XO001"}


def test_xo001_deferral_and_store_count():
    fs = lint("""
        class DeferBolt:
            def execute(self, t):
                if fast(t.values):
                    self.registry.defer(t)
                else:
                    self._by_key[t.values[0]] = t
    """)
    assert fs == []


def test_xo001_non_tuple_classes_skipped():
    fs = lint("""
        class Helper:
            def execute(self, t):
                return 1
    """)
    assert fs == []


def test_xo001_abstract_body_skipped():
    fs = lint("""
        class BaseBolt:
            def execute(self, t):
                raise NotImplementedError
        class PassBolt:
            def execute(self, t):
                ...
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# JIT001-004: tracer hygiene
# ---------------------------------------------------------------------------


def test_jit001_numpy_on_traced_arg():
    fs = lint("""
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return np.sum(x)
    """)
    assert rules_of(fs) == {"JIT001"}


def test_jit001_jnp_ok():
    fs = lint("""
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            return jnp.sum(x)
    """)
    assert fs == []


def test_jit002_branch_on_tracer():
    fs = lint("""
        import jax
        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)
    assert rules_of(fs) == {"JIT002"}


def test_jit002_static_argname_branch_ok():
    fs = lint("""
        import functools, jax
        @functools.partial(jax.jit, static_argnames=("flag",))
        def f(x, flag):
            if flag:
                return x
            return -x
    """)
    assert fs == []


def test_jit002_shape_branch_ok():
    # x.shape is concrete at trace time — the kernels' row-block math
    fs = lint("""
        import jax
        @jax.jit
        def f(x):
            rows = x.shape[0]
            r8 = rows if rows > 8 else 8
            assert x.ndim == 2
            return x * r8
    """)
    assert fs == []


def test_jit003_clock_read():
    fs = lint("""
        import jax, time
        @jax.jit
        def f(x):
            t0 = time.time()
            return x * t0
    """)
    assert rules_of(fs) == {"JIT003"}


def test_jit004_host_sync():
    fs = lint("""
        import jax
        @jax.jit
        def f(x):
            y = x * 2
            y.block_until_ready()
            return float(y)
    """)
    assert rules_of(fs) == {"JIT004"} and len(fs) == 2


def test_jit_call_form_target_resolved():
    # the engine builds fwd as a closure, then self._fwd = jax.jit(fwd)
    fs = lint("""
        import jax
        import numpy as np
        def build():
            def fwd(params, batch):
                return np.dot(params, batch)
            return jax.jit(fwd)
    """)
    assert rules_of(fs) == {"JIT001"}


def test_unjitted_function_ignored():
    fs = lint("""
        import numpy as np, time
        def f(x):
            if x > 0:
                time.sleep(0)
            return np.sum(x)
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# OBS001-003: observability hygiene
# ---------------------------------------------------------------------------


def test_obs001_unknown_metric_name():
    fs = lint("""
        def f(m):
            m.counter("bolt", "bogus_metric_typo").inc()
    """)
    assert rules_of(fs) == {"OBS001"}
    assert "registry" in fs[0].message


def test_obs001_registered_name_ok():
    fs = lint("""
        def f(m):
            m.counter("bolt", "emitted").inc()
            m.histogram("bolt", "execute_ms").observe(1.0)
    """)
    assert fs == []


def test_obs001_fstring_pattern_matches_registry():
    # tracing's span() records f"{name}_ms" -> pattern "*_ms"
    fs = lint("""
        def f(m, name):
            m.histogram("bolt", f"{name}_ms").observe(1.0)
    """)
    assert fs == []


def test_obs002_unbalanced_trace():
    fs = lint("""
        import jax
        def f(d):
            jax.profiler.start_trace(d)
            work()
    """)
    assert rules_of(fs) == {"OBS002"}


def test_obs002_balanced_trace_ok():
    fs = lint("""
        import jax
        def f(d):
            jax.profiler.start_trace(d)
            try:
                work()
            finally:
                jax.profiler.stop_trace()
    """)
    assert fs == []


def test_obs003_conflicting_kinds():
    fs = check_kinds(_files(
        'def f(m):\n    m.counter("a", "dual_series").inc()\n',
        'def g(m):\n    m.histogram("b", "dual_series").observe(1)\n',
    ), LintConfig())
    assert [f.rule for f in fs] == ["OBS003"]


def test_registry_generation_roundtrip():
    src = generate_registry(_files(
        'def f(m):\n'
        '    m.counter("a", "gen_fixture_total").inc()\n'
        '    m.histogram("a", f"lane_{k}_ms").observe(1)\n'))
    ns = {}
    exec(compile(src, "metric_names.py", "exec"), ns)
    assert "gen_fixture_total" in ns["METRIC_NAMES"]
    assert "lane_*_ms" in ns["METRIC_PATTERNS"]
    assert ns["is_known"]("lane_7_ms") and not ns["is_known"]("nope")


# ---------------------------------------------------------------------------
# baseline, config, CLI
# ---------------------------------------------------------------------------

_POSITIVE = """
    import threading, time
    class C:
        def f(self):
            with self._lock:
                time.sleep(1)
"""


def test_baseline_suppression_roundtrip(tmp_path):
    fs = lint(_POSITIVE)
    assert fs
    path = str(tmp_path / "baseline.json")
    write_baseline(path, fs)
    baseline = load_baseline(path)
    assert filter_new(fs, baseline) == []
    # an unrelated edit moving the line must NOT invalidate the entry
    moved = lint("\n\n# comment\n" + textwrap.dedent(_POSITIVE))
    assert moved[0].line != fs[0].line
    assert filter_new(moved, baseline) == []
    # preserving prior justifications across rewrites
    data = json.loads(open(path).read())
    data["findings"][0]["why"] = "reviewed: intentional"
    open(path, "w").write(json.dumps(data))
    write_baseline(path, fs, prior=load_baseline(path))
    assert "intentional" in open(path).read()


def test_config_from_pyproject(tmp_path):
    (tmp_path / "pyproject.toml").write_text(textwrap.dedent("""
        [tool.storm-tpu.lint]
        disable = ["LCK002"]
        exclude = ["generated/*"]
        blocking_methods = ["rpc_call"]
        exclude_XO001 = ["storm_tpu/legacy/*"]
    """))
    cfg = load_config(str(tmp_path))
    assert "LCK002" not in cfg.enable and "LCK001" in cfg.enable
    assert cfg.blocking_methods == ["rpc_call"]
    assert cfg.excluded("LCK001", "generated/x.py")
    assert cfg.excluded("XO001", "storm_tpu/legacy/old.py")
    assert not cfg.excluded("LCK001", "storm_tpu/legacy/old.py")


def test_repo_config_has_grpc_blocking_methods():
    cfg = load_config(ROOT)
    assert "control" in cfg.blocking_methods
    # Round-14 retry/backoff wrappers: a deadline-budgeted retry loop can
    # sleep for SECONDS — under a lock that is a pipeline-wide stall, so
    # the repo config must keep them in the blocking-call table.
    for m in ("call_sync", "throttle_sync", "wait_ready"):
        assert m in cfg.blocking_methods, m


def test_lck001_retry_loop_under_lock():
    """A retry wrapper invoked while holding a lock is an LCK001 finding
    with the repo's configured blocking-method table."""
    src = """
        class C:
            def f(self):
                with self._lock:
                    self._retry.call_sync(self._send, b"x")
    """
    assert lint(src) == []  # unknown method without the table
    fs = lint(src, blocking_methods=load_config(ROOT).blocking_methods)
    assert rules_of(fs) == {"LCK001"}


def test_cli_json_schema(capsys):
    from storm_tpu.main import main
    rc = main(["lint", "--root", ROOT, "--json",
               "storm_tpu/analysis/core.py"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert set(out) == {"findings", "total", "baselined", "new"}
    for f in out["findings"]:
        assert {"rule", "description", "path", "line", "scope", "message",
                "hint", "key", "chain"} <= set(f)


def test_cli_json_chain_bearing_finding(capsys):
    """--json includes the offending call chain on interprocedural
    findings (LCK003's witness path down to the concrete blocking call)."""
    from storm_tpu.main import main
    rc = main(["lint", "--root", ROOT, "--json", "--no-baseline",
               "storm_tpu/dist/controller.py"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1  # the baselined intentional holds resurface
    chains = [f for f in out["findings"] if f["chain"]]
    assert chains, "expected at least one chain-bearing LCK003 finding"
    for f in chains:
        assert isinstance(f["chain"], list)
        assert all(isinstance(s, str) for s in f["chain"])


@pytest.mark.parametrize(
    "rule", ["LCK001", "LCK002", "XO001", "JIT001", "OBS001"])
def test_cli_rules_listing(capsys, rule):
    from storm_tpu.main import main
    assert main(["lint", "--rules"]) == 0
    assert rule in capsys.readouterr().out


def test_cli_bad_path(capsys):
    from storm_tpu.main import main
    assert main(["lint", "--root", ROOT, "no/such/dir"]) == 2


def test_cli_nonzero_on_new_finding(tmp_path, capsys):
    from storm_tpu.main import main
    pkg = tmp_path / "storm_tpu" / "analysis"
    pkg.mkdir(parents=True)
    bad = tmp_path / "mod.py"
    bad.write_text(textwrap.dedent(_POSITIVE))
    assert main(["lint", "--root", str(tmp_path), "mod.py"]) == 1
    err = capsys.readouterr()
    assert "LCK001" in err.out


# ---------------------------------------------------------------------------
# LCK003: transitively-blocking call under a lock
# ---------------------------------------------------------------------------


def _cross(*srcs, **cfg):
    files = _files(*srcs)
    config = LintConfig(**cfg) if cfg else LintConfig()
    return CallGraph(files, config), files, config


_DEEP_BLOCK = """
    import threading, time
    class C:
        def __init__(self):
            self._lock = threading.Lock()
        def top(self):
            with self._lock:
                self.mid()
        def mid(self):
            self.deep()
        def deep(self):
            time.sleep(1)
"""


def test_lck003_catches_blocking_two_frames_below_lock():
    """The acceptance fixture: the blocking call sits TWO frames below the
    lock, so depth-1 LCK001 is blind to it and LCK003 must catch it."""
    assert lint(_DEEP_BLOCK) == []  # LCK001 sees nothing
    graph, _files_, config = _cross(_DEEP_BLOCK)
    fs = check_transitive(graph, config)
    assert [f.rule for f in fs] == ["LCK003"]
    (f,) = fs
    assert f.chain == ["mod0.C.mid", "mod0.C.deep", "time.sleep"]
    assert f.detail == "self.mid->time.sleep"
    assert "_lock" in f.message and "time.sleep" in f.message


def test_lck003_direct_block_stays_lck001():
    src = """
        import threading, time
        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def f(self):
                with self._lock:
                    time.sleep(1)
    """
    assert rules_of(lint(src)) == {"LCK001"}
    graph, _fs, config = _cross(src)
    assert check_transitive(graph, config) == []  # no double report


def test_lck003_nonblocking_callee_ok():
    graph, _fs, config = _cross("""
        class C:
            def top(self):
                with self._lock:
                    self.mid()
            def mid(self):
                return 1
    """)
    assert check_transitive(graph, config) == []


def test_lck003_cross_file_chain():
    graph, _fs, config = _cross("""
        from mod1 import slow
        class C:
            def f(self):
                with self._lock:
                    slow()
    """, """
        import time
        def slow():
            time.sleep(1)
    """)
    fs = check_transitive(graph, config)
    assert [f.rule for f in fs] == ["LCK003"]
    assert fs[0].chain == ["mod1.slow", "time.sleep"]


# ---------------------------------------------------------------------------
# LCK004: lock-order cycles beyond LCK002's 2-cycle special case
# ---------------------------------------------------------------------------


def test_lck004_three_cycle_flagged():
    graph, _fs, config = _cross("""
        class C:
            def f(self):
                with self._lock_a:
                    with self._lock_b:
                        pass
            def g(self):
                with self._lock_b:
                    with self._lock_c:
                        pass
            def h(self):
                with self._lock_c:
                    with self._lock_a:
                        pass
    """)
    assert check_ordering([], config, edges_in=graph.lock_edges) == []
    fs = check_cycles(graph, config)
    assert [f.rule for f in fs] == ["LCK004"]
    assert len(fs[0].chain) == 3
    assert "lock-order cycle" in fs[0].message


def test_lck004_interprocedural_edge_closes_cycle():
    """No single function nests a->b; the edge comes from f holding A while
    calling a function whose lock summary says it takes B."""
    graph, _fs, config = _cross("""
        class C:
            def f(self):
                with self._lock_a:
                    self.takes_b()
            def takes_b(self):
                with self._lock_b:
                    pass
            def g(self):
                with self._lock_b:
                    with self._lock_a:
                        pass
    """)
    fs = check_cycles(graph, config)
    assert [f.rule for f in fs] == ["LCK004"]
    assert "via self.takes_b()" in fs[0].message


def test_lck004_leaves_syntactic_two_cycles_to_lck002():
    graph, files, config = _cross("""
        class A:
            def f(self):
                with self._lock_a:
                    with self._lock_b:
                        pass
            def g(self):
                with self._lock_b:
                    with self._lock_a:
                        pass
    """)
    assert check_cycles(graph, config) == []  # LCK002's report, not ours
    fs = check_ordering(files, config, edges_in=graph.lock_edges)
    assert [f.rule for f in fs] == ["LCK002"]


def test_lck004_consistent_order_ok():
    graph, _fs, config = _cross("""
        class C:
            def f(self):
                with self._lock_a:
                    with self._lock_b:
                        pass
            def g(self):
                with self._lock_a:
                    self.h()
            def h(self):
                with self._lock_b:
                    pass
    """)
    assert check_cycles(graph, config) == []


# ---------------------------------------------------------------------------
# THR001/THR002: thread and executor lifecycle
# ---------------------------------------------------------------------------


def _thr(*srcs, **cfg):
    graph, files, config = _cross(*srcs, **cfg)
    return check_lifecycles(files, config, graph)


def test_thr001_unjoined_attr_thread():
    fs = _thr("""
        import threading
        class C:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()
    """)
    assert [f.rule for f in fs] == ["THR001"]
    assert fs[0].detail == "thread:self._t"


def test_thr001_daemon_ok():
    assert _thr("""
        import threading
        class C:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()
    """) == []


def test_thr001_joined_in_close_ok():
    assert _thr("""
        import threading
        class C:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()
            def close(self):
                self._t.join()
    """) == []


def test_thr001_join_alias_through_for_loop_ok():
    assert _thr("""
        import threading
        def scale_demo():
            pool = [threading.Thread(target=work) for _ in range(8)]
            for t in pool:
                t.start()
            for t in pool:
                t.join()
    """) == []


def test_thr001_join_site_must_be_lifecycle_reachable():
    fs = _thr("""
        import threading
        class C:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()
            def _helper_nobody_invokes(self):
                self._t.join()
    """)
    assert [f.rule for f in fs] == ["THR001"]
    assert "no close/shutdown/stop path reaches" in fs[0].message


def test_thr001_finalizer_ok():
    assert _thr("""
        import threading, weakref
        class C:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()
                weakref.finalize(self, _noop, self._t)
    """) == []


def test_thr002_executor_without_shutdown():
    fs = _thr("""
        from concurrent.futures import ThreadPoolExecutor
        class C:
            def start(self):
                self._pool = ThreadPoolExecutor(max_workers=2)
    """)
    assert [f.rule for f in fs] == ["THR002"]
    assert fs[0].detail == "executor:self._pool"


def test_thr002_context_managed_or_handed_off_ok():
    assert _thr("""
        from concurrent import futures
        def a():
            with futures.ThreadPoolExecutor(max_workers=2) as pool:
                pool.submit(print)
        def b(grpc):
            server = grpc.server(futures.ThreadPoolExecutor(max_workers=16))
            return server
    """) == []


def test_thr002_shutdown_in_close_ok():
    assert _thr("""
        from concurrent.futures import ThreadPoolExecutor
        class C:
            def start(self):
                self._pool = ThreadPoolExecutor(max_workers=2)
            def close(self):
                self._pool.shutdown(wait=True)
    """) == []


# ---------------------------------------------------------------------------
# PRT001-003: protocol conformance
# ---------------------------------------------------------------------------


def _prt(*srcs):
    return check_protocols(_files(*srcs), LintConfig())


def test_prt001_sent_without_handler():
    fs = _prt("""
        class Ctl:
            def kick(self):
                self.client.control("ping")
                self.client.control("frobnicate")
    """, """
        class Worker:
            def _control(self, cmd, body):
                if cmd == "ping":
                    return {}
    """)
    assert [f.detail for f in fs] == ["unhandled:frobnicate"]


def test_prt001_handler_without_sender():
    fs = _prt("""
        class Ctl:
            def kick(self):
                self.client.control("ping")
    """, """
        class Worker:
            def _control(self, cmd, body):
                if cmd in ("ping", "zap"):
                    return {}
    """)
    assert [f.detail for f in fs] == ["unsent:zap"]


def test_prt001_balanced_ok():
    assert _prt("""
        class Ctl:
            def kick(self):
                self.client.control("ping")
        class Worker:
            def _control(self, cmd, body):
                if cmd == "ping":
                    return {}
    """) == []


def test_prt002_emitted_kind_without_fold_arm():
    fs = _prt("""
        class J:
            def record(self):
                self._jappend("rebalance", x=1)
                self._jappend("mystery", x=2)
        class S:
            def apply(self, kind, rec):
                if kind == "rebalance":
                    return
    """)
    assert [f.detail for f in fs] == ["unfolded:mystery"]


def test_prt002_unknown_kind_replay_stays_legal():
    """Fold arms MAY exceed emitted kinds: an old journal replayed by a new
    binary hits arms nothing emits any more — that is the forward-compat
    contract and must not flag."""
    assert _prt("""
        class J:
            def record(self):
                self._jappend("rebalance", x=1)
        class S:
            def apply(self, kind, rec):
                if kind == "rebalance":
                    return
                if kind == "retired_kind":
                    return
    """) == []


def test_prt003_unregistered_event_name():
    fs = _prt("""
        class C:
            def f(self):
                self.flight.event("definitely_not_a_registered_event", x=1)
    """)
    assert [f.rule for f in fs] == ["PRT003"]
    assert fs[0].detail == "event:definitely_not_a_registered_event"


def test_prt003_registered_event_ok():
    # dist_worker_draining is a real registered event; **kw leaves the
    # field set unknowable, so only the name is checked.
    assert _prt("""
        class C:
            def f(self, kw):
                self.flight.event("dist_worker_draining", **kw)
    """) == []


def test_prt003_missing_required_field():
    from storm_tpu.analysis import protocol_names
    required = protocol_names.FLIGHT_EVENTS["dist_worker_draining"]
    assert "worker" in required  # the contract this fixture violates
    fs = _prt("""
        class C:
            def f(self):
                self.flight.event("dist_worker_draining")
    """)
    assert [f.rule for f in fs] == ["PRT003"]
    assert fs[0].detail.startswith("fields:dist_worker_draining:")


# ---------------------------------------------------------------------------
# regression: the PR 9 rules are unchanged under the interprocedural engine
# ---------------------------------------------------------------------------


def test_lck001_fixtures_unchanged_under_interprocedural():
    src = """
        import threading, time
        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def f(self):
                with self._lock:
                    time.sleep(1)
    """
    fs = lint(src)
    assert rules_of(fs) == {"LCK001"} and len(fs) == 1
    extra = cross_file_findings(_files(src), LintConfig())
    assert [f.rule for f in extra] == []  # nothing doubled, nothing added
