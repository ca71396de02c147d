"""Hibernate (checkpoint + sleep on disk) and local restore — the
``PaxosManager.hibernate``/``restore`` analog (``PaxosManager.java:
2209-2252``) — plus the linwrites example (linearizable writes, local
reads: ``examples/linwrites/LinWritesLocReadsApp.java``)."""

import numpy as np

from gigapaxos_tpu.models.apps import HashChainApp, LinWritesLocReadsApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.reconfiguration.coordinator import PaxosReplicaCoordinator
from gigapaxos_tpu.testing.cluster import ManagerCluster


def _converged(c, name):
    states = {m.app.state.get(name) for m in c.managers}
    return states.pop() if len(states) == 1 else None


def test_hibernate_restore(tmp_path):
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    dirs = [str(tmp_path / f"n{r}") for r in range(3)]
    c = ManagerCluster(cfg, HashChainApp, log_dirs=dirs)
    try:
        c.create("svc", members=[0, 1, 2])
        for i in range(5):
            c.submit("svc", f"v{i}")
            c.run(4)
        for _ in range(40):
            c.run(1)
            h0 = _converged(c, "svc")
            if h0 is not None and all(
                m.app.n_executed.get("svc") == 5 for m in c.managers
            ):
                break
        assert h0 is not None

        # hibernate everywhere: rows freed, records journaled AND paged
        # out of RAM (demote), instance gone from the live tables
        for m in c.managers:
            assert m.hibernate("svc")
            assert m.names.get("svc") is None
            assert ("svc", 0) in m.paused
            assert m.paused.n_in_memory == 0  # sleeping on disk
        c.republish()
        c.run(3)

        # a second hibernate (unknown name now) reports failure
        assert not c.managers[0].hibernate("svc")

        # local wake-up: full rollback to the snapshot, deterministic row
        for m in c.managers:
            assert m.restore("svc")
            assert m.names.get("svc") is not None
        c.republish()
        c.run(5)
        assert _converged(c, "svc") == h0
        rows = {m.names["svc"] for m in c.managers}
        assert len(rows) == 1  # default_row_for realigned everyone

        # traffic resumes, exactly-once preserved
        c.submit("svc", "after")
        got = None
        for _ in range(60):
            c.run(1)
            got = _converged(c, "svc")
            if got is not None and got != h0 and all(
                m.app.n_executed.get("svc") == 6 for m in c.managers
            ):
                break
        assert got is not None and got != h0
        assert all(m.app.n_executed.get("svc") == 6 for m in c.managers)
        # restore of an already-awake name is a no-op success
        assert c.managers[0].restore("svc")
        # restore of an unknown name fails
        assert not c.managers[0].restore("nope")
    finally:
        c.close()


def test_linwrites_local_reads():
    cfg = EngineConfig(n_groups=4, window=8, req_lanes=4, n_replicas=3)
    c = ManagerCluster(cfg, LinWritesLocReadsApp)
    try:
        c.create("k", members=[0, 1, 2])
        coords = [
            PaxosReplicaCoordinator(m.app, m) for m in c.managers
        ]
        answers = []
        # coordinated write: goes through consensus, lands on every replica
        assert coords[0].coordinate_request(
            "k", "7", callback=lambda rid, resp: answers.append(resp)
        )
        for _ in range(40):
            c.run(1)
            if all(m.app.totals.get("k") == 7 for m in c.managers):
                break
        assert all(m.app.totals.get("k") == 7 for m in c.managers)
        assert answers == ["7"]

        # local read: answered immediately from THIS replica, no consensus
        # traffic (frontiers unchanged), re-sends just re-read
        row = c.managers[1].names["k"]
        fr_before = int(np.asarray(c.managers[1].state.exec_slot)[row])
        reads = []
        for _ in range(3):
            assert coords[1].coordinate_request(
                "k", LinWritesLocReadsApp.READ,
                callback=lambda rid, resp: reads.append(resp),
            )
        assert reads == ["7", "7", "7"]
        c.run(3)
        assert int(
            np.asarray(c.managers[1].state.exec_slot)[row]
        ) == fr_before  # reads never entered consensus
        # reads against an unknown name report failure
        assert not coords[1].coordinate_request(
            "nope", LinWritesLocReadsApp.READ
        )
    finally:
        c.close()
