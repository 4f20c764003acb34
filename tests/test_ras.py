"""Resource-aware placement (Storm's RAS equivalent): worst-fit-decreasing
bin-packing of component demands onto worker capacities, refusing
oversubscription; wired into DistCluster auto-placement via
topology.component_resources."""

import pytest

from storm_tpu.dist.controller import DistCluster

plan = DistCluster.plan_placement


def _caps(n, memory_mb=1000.0, cpu=400.0):
    return [{"memory_mb": memory_mb, "cpu": cpu} for _ in range(n)]


def test_wfd_packs_biggest_first():
    demands = {
        "small": {"memory_mb": 100, "cpu": 10},
        "big": {"memory_mb": 900, "cpu": 50},
        "mid": {"memory_mb": 500, "cpu": 20},
    }
    p = plan(demands, _caps(2))
    # big (900) takes one worker; mid (500) the other; small fits beside mid
    assert p["big"] != p["mid"]
    assert p["small"] == p["mid"]


def test_refuses_oversubscription():
    with pytest.raises(ValueError, match="fits no worker"):
        plan({"huge": {"memory_mb": 5000, "cpu": 10}}, _caps(3))
    # cpu constrains independently of memory
    with pytest.raises(ValueError, match="fits no worker"):
        plan({"spin": {"memory_mb": 1, "cpu": 800}}, _caps(2))


def test_spout_prefers_worker0_when_it_fits():
    demands = {
        "spout": {"memory_mb": 100, "cpu": 10, "is_spout": True},
        "bolt": {"memory_mb": 800, "cpu": 10},
    }
    p = plan(demands, _caps(2))
    assert p["spout"] == 0
    # spouts place FIRST: a big bolt must not evict the spout from 0
    demands = {
        "hog": {"memory_mb": 950, "cpu": 10},
        "spout": {"memory_mb": 100, "cpu": 10, "is_spout": True},
    }
    p = plan(demands, _caps(2))
    assert p["spout"] == 0 and p["hog"] == 1


def test_zero_demand_components_always_place():
    demands = {"a": {}, "b": {}, "c": {"memory_mb": 1000}}
    p = plan(demands, _caps(1))
    assert set(p) == {"a", "b", "c"}


def test_dist_auto_place_uses_hints():
    """component_resources drives placement through the real controller
    (no worker processes needed: attach to fake addrs, plan only)."""
    from storm_tpu.config import Config

    class FakeClient:
        def __init__(self, target):
            self.target = target

    cluster = DistCluster.__new__(DistCluster)
    cluster.clients = [FakeClient("a:1"), FakeClient("b:2")]
    cluster._worker_resources = {"memory_mb": 2048.0, "cpu": 400.0}

    cfg = Config()
    cfg.model.name = "lenet5"
    cfg.topology.component_resources = {
        "inference-bolt": {"memory_mb": 400, "cpu": 50},  # x4 tasks = 1600
        "kafka-bolt": {"memory_mb": 300},  # x2 = 600
    }
    placement = cluster._auto_place(cfg, "standard")
    # inference (1600) and kafka-bolt (600) cannot share a 2048 worker
    assert placement["inference-bolt"] != placement["kafka-bolt"]
    assert set(placement.values()) <= {0, 1}


def test_dist_auto_place_refuses_when_too_big():
    from storm_tpu.config import Config

    class FakeClient:
        def __init__(self, target):
            self.target = target

    cluster = DistCluster.__new__(DistCluster)
    cluster.clients = [FakeClient("a:1")]
    cluster._worker_resources = {"memory_mb": 1024.0, "cpu": 400.0}
    cfg = Config()
    cfg.topology.component_resources = {
        "inference-bolt": {"memory_mb": 400},  # x4 = 1600 > 1024
    }
    with pytest.raises(ValueError, match="fits no worker"):
        cluster._auto_place(cfg, "standard")


def test_declarer_resource_hints():
    from storm_tpu.runtime import Bolt, Spout, TopologyBuilder

    class S(Spout):
        async def next_tuple(self):
            return False

    class B(Bolt):
        async def execute(self, t):
            pass

    tb = TopologyBuilder()
    tb.set_spout("s", S(), 1).set_memory_load(64)
    tb.set_bolt("b", B(), 2).shuffle_grouping("s")\
        .set_memory_load(512).set_cpu_load(150)
    topo = tb.build()
    assert topo.specs["s"].resources == {"memory_mb": 64.0}
    assert topo.specs["b"].resources == {"memory_mb": 512.0, "cpu": 150.0}


def test_capacity_missing_key_means_unconstrained():
    p = plan({"a": {"memory_mb": 10, "cpu": 10}}, [{"memory_mb": 100}])
    assert p == {"a": 0}
    p = plan({"a": {"memory_mb": 10}}, [{"cpu": 100}])
    assert p == {"a": 0}


def test_zero_demand_components_spread():
    demands = {"a": {}, "b": {}, "c": {}, "d": {"memory_mb": 100}}
    p = plan(demands, _caps(3))
    # one hint must not collapse the unhinted components onto one worker
    assert len({p["a"], p["b"], p["c"]}) == 3


def test_unknown_hint_key_rejected():
    from storm_tpu.config import Config

    class FakeClient:
        def __init__(self, target):
            self.target = target

    cluster = DistCluster.__new__(DistCluster)
    cluster.clients = [FakeClient("a:1")]
    cluster._worker_resources = {"memory_mb": 4096.0, "cpu": 400.0}
    cfg = Config()
    cfg.topology.component_resources = {"inference_bolt": {"memory_mb": 10}}
    with pytest.raises(ValueError, match="unknown components"):
        cluster._auto_place(cfg, "standard")


def test_cpu_only_hints_spread():
    demands = {f"b{i}": {"cpu": 100} for i in range(4)}
    p = plan(demands, _caps(2, memory_mb=4096, cpu=400))
    # memory never changes; the cpu/count tie-break must still spread
    from collections import Counter

    assert sorted(Counter(p.values()).values()) == [2, 2]


def test_unknown_resource_key_rejected():
    from storm_tpu.config import Config

    class FakeClient:
        def __init__(self, target):
            self.target = target

    cluster = DistCluster.__new__(DistCluster)
    cluster.clients = [FakeClient("a:1")]
    cluster._worker_resources = {"memory_mb": 4096.0, "cpu": 400.0}
    cfg = Config()
    cfg.topology.component_resources = {"inference-bolt": {"mem_mb": 400}}
    with pytest.raises(ValueError, match="unknown keys"):
        cluster._auto_place(cfg, "standard")


# ---- one process per chip (a TPU belongs to one process at a time) ----------


def _two_engine_cluster(n_workers, reports):
    """A controller with fake clients (plan only, no worker processes) and
    a two-pipeline config: two inference bolts, each building an engine."""
    from storm_tpu.config import Config, PipelineConfig

    class FakeClient:
        def __init__(self, target):
            self.target = target

    cluster = DistCluster.__new__(DistCluster)
    cluster.clients = [FakeClient(f"w:{i}") for i in range(n_workers)]
    cluster._worker_resources = {"memory_mb": 4096.0, "cpu": 400.0}
    cluster.state_reports = lambda: reports
    cfg = Config()
    cfg.pipelines = [
        PipelineConfig(name=name, input_topic=f"{name}-in",
                       output_topic=f"{name}-out",
                       dead_letter_topic=f"{name}-dlq")
        for name in ("mnist", "cifar")]
    return cluster, cfg


def test_dist_auto_place_colocates_engines():
    """build_multi_model_topology under dist-run: round-robin used to put
    the two inference bolts on two workers, and the second one to build
    its engine found the chip taken. Every engine now shares one worker;
    the other bolts still spread."""
    cluster, cfg = _two_engine_cluster(3, {})
    placement = cluster._auto_place(cfg, "multi")
    assert placement["mnist-inference"] == placement["cifar-inference"] != 0
    assert placement["mnist-spout"] == placement["cifar-spout"] == 0
    assert len(set(placement.values())) == 3


@pytest.mark.parametrize("platforms,hosts,refused", [
    (("tpu,cpu", "tpu,cpu"), ("h", "h"), True),   # both could open the chip
    (("", ""), ("h", "h"), True),                 # unset: jax picks the TPU
    (("tpu", "cpu"), ("h", "h"), False),          # the other is pinned off it
    (("tpu", "tpu"), ("h1", "h2"), False),        # a chip each
], ids=["same_host", "platform_unset", "one_pinned_to_cpu", "two_hosts"])
def test_dist_submit_refuses_engines_on_two_workers_of_a_host(
        platforms, hosts, refused):
    reports = {i: {"host": hosts[i], "jax_platforms": platforms[i]}
               for i in range(2)}
    cluster, cfg = _two_engine_cluster(2, reports)
    split = {"mnist-spout": 0, "cifar-spout": 0,
             "mnist-inference": 0, "cifar-inference": 1,
             "mnist-sink": 0, "cifar-sink": 1, "mnist-dlq": 0, "cifar-dlq": 1}
    if refused:
        with pytest.raises(ValueError, match="one process per chip"):
            cluster._check_one_process_per_chip(cfg, "multi", split)
    else:
        cluster._check_one_process_per_chip(cfg, "multi", split)
    # engines on one worker are always fine, whatever the platform
    together = dict(split, **{"cifar-inference": 0})
    cluster._check_one_process_per_chip(cfg, "multi", together)
