"""A4: a request id decided in two slots of one name executes once on
every replica, whatever the replicas' clocks say — what a replica
remembers of a name's executed ids is a function of the name's decided
sequence (``gigapaxos_tpu/dedup.py``)."""

import time

import pytest

from gigapaxos_tpu.dedup import DEDUP_SLOTS, ExecutedIds
from gigapaxos_tpu.models.apps import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import ManagerCluster

CFG = EngineConfig(n_groups=6, window=8, req_lanes=4, n_replicas=3)


def _executed(c):
    return [m.metrics.snapshot()["counters"].get("decisions_executed", 0)
            for m in c.managers]


@pytest.mark.timeout(300)
def test_same_id_in_two_slots_executes_once_whatever_the_clocks_say(
        monkeypatch, tmp_path):
    """Seed 103's shape: a retransmission that finds its proposal older
    than ``repropose_after_s`` and no longer queued is proposed anew, and
    both copies decide, in two slots.  Between the two executions every
    node's clock runs on — by a different amount on each — and the
    checkpoint's housekeeping runs.  The parent forgot the id by
    wall-clock age there (TTL 60 s, swept per node) and executed the
    write twice on all three."""
    c = ManagerCluster(CFG, StatefulAdderApp,
                       log_dirs=[str(tmp_path / f"n{i}") for i in range(3)])
    c.create("acct")
    row = c.managers[0].names["acct"]
    coord = c.managers[c.managers[0].coordinator_of_row(row)]
    got = []
    cb = lambda rid, resp: got.append(resp)
    coord.propose("acct", "7", callback=cb, request_id=77)
    c.run(2)  # admitted, not decided
    coord._inflight_since[77] -= coord.repropose_after_s + 1
    assert coord.propose("acct", "7", callback=cb, request_id=77) is not None
    assert coord.metrics.snapshot()["counters"]["requests_reproposed"] == 1
    for _ in range(8):
        c.step_all()
        if _executed(c) == [1, 1, 1]:
            break
    # the first copy has executed everywhere, the second nowhere yet
    assert _executed(c) == [1, 1, 1] and got == ["7"]
    assert [m.app.totals.get("acct") for m in c.managers] == [7, 7, 7]

    real = time.time
    for m, skew in zip(c.managers, (3600.0, 7200.0, 86400.0)):
        monkeypatch.setattr(time, "time", lambda s=skew: real() + s)
        m.checkpoint_now()  # where the parent swept its cache by age
    monkeypatch.setattr(time, "time", lambda: real() + 86400.0)

    c.run(8)  # the second copy decides, in the next slot
    assert _executed(c) == [2, 2, 2]
    assert [m.app.totals.get("acct") for m in c.managers] == [7, 7, 7]
    skipped = [m.metrics.snapshot()["counters"].get(
        "executions_skipped_duplicate", 0) for m in c.managers]
    assert skipped == [1, 1, 1], skipped
    c.close()


@pytest.mark.timeout(300)
def test_a_client_that_moved_is_answered_by_the_replica_it_moved_to():
    """The same id proposed at two entries: one execution, and the
    second entry answers its client with the first execution's response
    when it executes — it does not wait for the duplicate's slot."""
    c = ManagerCluster(CFG, StatefulAdderApp)
    c.create("acct")
    row = c.managers[0].names["acct"]
    coord = c.managers[0].coordinator_of_row(row)
    e1, e2 = [r for r in range(3) if r != coord]
    got1, got2 = [], []
    c.managers[e1].propose("acct", "5", request_id=9,
                           callback=lambda r, v: got1.append(v))
    c.step_all()
    c.managers[e2].propose("acct", "5", request_id=9,
                           callback=lambda r, v: got2.append(v))
    c.run(12)
    assert got1 == ["5"] and got2 == ["5"]
    assert [m.app.totals.get("acct") for m in c.managers] == [5, 5, 5]
    # asked again anywhere: the first execution's response, no execution
    got3 = []
    c.managers[coord].propose("acct", "5", request_id=9,
                              callback=lambda r, v: got3.append(v))
    assert got3 == ["5"]
    c.close()


def test_window_is_a_function_of_the_names_decided_slots():
    a, b = ExecutedIds(slots=4), ExecutedIds(slots=4)
    for slot in range(1, 8):
        a.add("x", [(slot, str(slot))], now=0.0)
        b.add("x", [(slot, str(slot))], now=1e9)  # another clock
        a.add("y", [(100 + slot, "y")])           # another name's load
    assert sorted(r for r in a.entries if r < 100) == [4, 5, 6, 7]
    assert sorted(b.entries) == [4, 5, 6, 7]
    # a batch shares its slot: all of it stays or goes together
    a.add("x", [(20, "a"), (21, "b"), (22, "c")])
    assert {5, 6, 7, 20, 21, 22} <= set(a.entries) and 4 not in a.entries
    # a hand-over carries the numbering: the receiver prunes as the donor
    r = ExecutedIds(slots=4)
    r.add("x", [(1, "1")])
    r.install(a.of_name("x"))
    assert set(r.of_name("x")) == set(a.of_name("x"))
    r.add("x", [(30, "z")])
    a.add("x", [(30, "z")])
    assert set(r.of_name("x")) == set(a.of_name("x"))
    r.forget("x")
    assert not r.of_name("x") and 30 not in r.entries
    assert DEDUP_SLOTS >= 64
