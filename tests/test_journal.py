"""Controller WAL tests (storm_tpu/dist/journal.py): CRC-stamped
append-only log + snapshot compaction, the durability layer behind
controller crash-reattach. Torn tails (a crash mid-append) are tolerated;
mid-log damage is NOT (silent truncation there would roll the control
plane back in time) and raises the named JournalCorrupt.
"""

import json
import os

import pytest

from storm_tpu.dist.journal import (
    JOURNAL_FILE,
    SNAPSHOT_FILE,
    ControllerJournal,
    ControlPlaneState,
    JournalCorrupt,
)


def _seed(d, snapshot_every=64):
    j = ControllerJournal(str(d), snapshot_every=snapshot_every)
    j.append("workers", peers={0: "127.0.0.1:1", 1: "127.0.0.1:2"},
             pids={0: 11, 1: 22})
    j.append("submit", name="topo", config={"k": 1},
             builder="standard", placement={"spout": 0, "sink": 1})
    j.append("rebalance", component="infer", parallelism=4)
    j.append("activation", activated=False)
    return j


def test_roundtrip_fold(tmp_path):
    j = _seed(tmp_path)
    j.close()
    st = ControllerJournal(str(tmp_path)).load()
    assert st.peers == {0: "127.0.0.1:1", 1: "127.0.0.1:2"}
    assert st.pids == {0: 11, 1: 22}
    assert st.recipe["name"] == "topo"
    assert st.placement == {"spout": 0, "sink": 1}
    assert st.rebalances == {"infer": 4}
    assert st.activated is False
    assert st.replayed == 4


def test_kill_resets_fold(tmp_path):
    j = _seed(tmp_path)
    j.append("kill")
    j.close()
    st = ControllerJournal(str(tmp_path)).load()
    assert st.recipe is None and st.rebalances == {}


def test_torn_tail_tolerated(tmp_path):
    """A crash mid-append leaves a partial final line: replay stops there
    and the next append drops the torn bytes instead of corrupting."""
    j = _seed(tmp_path)
    j.close()
    path = os.path.join(str(tmp_path), JOURNAL_FILE)
    with open(path, "ab") as f:
        f.write(b'{"seq": 5, "kind": "rebalance", "da')  # torn record
    j2 = ControllerJournal(str(tmp_path))
    st = j2.load()
    assert st.replayed == 4  # torn tail ignored, good prefix kept
    seq = j2.append("activation", activated=True)
    assert seq == 5  # resumes after the good prefix, not the torn bytes
    j2.close()
    st2 = ControllerJournal(str(tmp_path)).load()
    assert st2.activated is True and st2.replayed == 5


def test_corrupt_mid_log_raises(tmp_path):
    """Damage BEFORE the final record is not a torn write — replaying
    around it would silently drop an applied transition."""
    j = _seed(tmp_path)
    j.close()
    path = os.path.join(str(tmp_path), JOURNAL_FILE)
    lines = open(path, "rb").read().splitlines(keepends=True)
    assert len(lines) == 4
    lines[1] = lines[1][:10] + b"X" + lines[1][11:]  # flip a mid-log byte
    with open(path, "wb") as f:
        f.writelines(lines)
    with pytest.raises(JournalCorrupt):
        ControllerJournal(str(tmp_path)).load()


def test_crc_rejects_tamper(tmp_path):
    """A VALID-JSON record whose content was altered fails its CRC —
    mid-log it's corruption, as the final record it's a torn tail."""
    j = _seed(tmp_path)
    j.close()
    path = os.path.join(str(tmp_path), JOURNAL_FILE)
    lines = open(path, "rb").read().splitlines(keepends=True)
    rec = json.loads(lines[-1])
    rec["data"]["activated"] = True  # flip the payload, keep the old crc
    lines[-1] = json.dumps(rec).encode() + b"\n"
    with open(path, "wb") as f:
        f.writelines(lines)
    st = ControllerJournal(str(tmp_path)).load()
    assert st.replayed == 3 and st.activated is True  # tail dropped


@pytest.mark.parametrize("last, snapshots, since_snapshot", [
    (2, 0, 3),  # three appends: the WAL alone
    (3, 1, 0),  # the fourth compacts: the snapshot alone
    (4, 1, 1), (5, 1, 2), (6, 1, 3),  # the snapshot and a WAL after it
])
def test_snapshot_compaction_roundtrip(tmp_path, last, snapshots,
                                       since_snapshot):
    j = ControllerJournal(str(tmp_path), snapshot_every=4)
    j.append("workers", peers={0: "127.0.0.1:1"}, pids={0: 9})
    j.append("submit", name="t", config={}, builder="standard",
             placement={})
    for n in range(2, last + 1):
        j.append("rebalance", component="infer", parallelism=n)
        j.maybe_snapshot()
    assert j.stats()["snapshots"] == snapshots
    assert os.path.exists(
        os.path.join(str(tmp_path), SNAPSHOT_FILE)) == bool(snapshots)
    # WAL shrank: compaction truncated the folded prefix
    assert j.stats()["since_snapshot"] == since_snapshot
    j.close()
    st = ControllerJournal(str(tmp_path)).load()
    assert st.rebalances == {"infer": last}
    assert st.peers == {0: "127.0.0.1:1"}


def test_unknown_kind_ignored(tmp_path):
    """Forward compat: a newer controller's record kinds replay as
    no-ops instead of wedging an older one."""
    st = ControlPlaneState()
    st.apply("hologram", {"x": 1})
    assert st.recipe is None


def test_reconcile_parallelism():
    """Reattach reconciliation: journal intent wins; only components
    whose hosting worker disagrees need a re-issued rebalance."""
    from storm_tpu.dist.controller import DistCluster

    rebalances = {"infer": 4, "sink": 2}
    placement = {"infer": 1, "sink": 2}
    reports = {1: {"parallelism": {"infer": 2}},
               2: {"parallelism": {"sink": 2}}}
    assert DistCluster.reconcile_parallelism(
        rebalances, placement, reports) == {"infer": 4}
    # unreachable host -> nothing to compare, nothing to fix
    assert DistCluster.reconcile_parallelism(
        {"infer": 4}, {"infer": 1}, {}) == {}
