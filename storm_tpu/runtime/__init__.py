from storm_tpu.runtime.tuples import Tuple, TickTuple, Values
from storm_tpu.runtime.topology import TopologyBuilder, Topology
from storm_tpu.runtime.base import Spout, Bolt, OutputCollector, TopologyContext
from storm_tpu.runtime.cluster import LocalCluster
from storm_tpu.runtime.state import (
    FileStateBackend,
    KeyValueState,
    MemoryStateBackend,
    StatefulBolt,
)

__all__ = [
    "StatefulBolt",
    "KeyValueState",
    "MemoryStateBackend",
    "FileStateBackend",
    "Tuple",
    "TickTuple",
    "Values",
    "TopologyBuilder",
    "Topology",
    "Spout",
    "Bolt",
    "OutputCollector",
    "TopologyContext",
    "LocalCluster",
]
