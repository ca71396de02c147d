"""End-to-end manager tests: app execution, callbacks, forwarding,
exactly-once, checkpoint + crash recovery — the minimum end-to-end slice
(SURVEY.md §7 stage 6, ``tests/loopback_1_group`` parity in-process)."""

import numpy as np
import pytest

from gigapaxos_tpu.models import HashChainApp, NoopPaxosApp, StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster

CFG = EngineConfig(n_groups=6, window=8, req_lanes=4, n_replicas=3)


def test_end_to_end_commit_with_callback():
    c = ManagerCluster(CFG, NoopPaxosApp)
    c.create("svc")
    got = {}
    c.submit("svc", "hello", entry=0, callback=lambda rid, resp: got.update(
        {"rid": rid, "resp": resp}
    ))
    c.run(8)  # covers the forward-to-coordinator hop if entry != coord
    assert got.get("resp") == "noop-ack"
    assert (c.app_exec()[:, c.managers[0].names["svc"]] == 1).all()
    c.close()


def test_adder_consistency_across_replicas():
    c = ManagerCluster(CFG, StatefulAdderApp)
    c.create("acct")
    for i in range(10):
        c.submit("acct", str(i + 1), entry=i % 3)
        c.step_all()
    c.run(10)
    totals = [m.app.totals.get("acct", 0) for m in c.managers]
    assert totals == [55, 55, 55], totals
    c.close()


def test_hash_chain_rsm_invariant_under_drops():
    rng = np.random.default_rng(7)
    c = ManagerCluster(CFG, HashChainApp)
    c.create("chain")
    for i in range(20):
        delivery = np.where(rng.random((3, 3)) < 0.25, DROP, DELIVER)
        c.submit("chain", f"v{i}", entry=int(rng.integers(0, 3)))
        c.step_all(delivery=delivery)
    c.run(15)
    n = [m.app.n_executed.get("chain", 0) for m in c.managers]
    s = [m.app.state.get("chain") for m in c.managers]
    assert n[0] > 0 and n == [n[0]] * 3, n
    assert s == [s[0]] * 3, s
    c.close()


def test_exactly_once_response_cache():
    c = ManagerCluster(CFG, StatefulAdderApp)
    c.create("acct")
    responses = []
    cb = lambda rid, resp: responses.append(resp)
    vid = c.managers[0].propose("acct", "5", callback=cb, request_id=777)
    assert vid is not None
    c.run(8)
    assert responses == ["5"]
    # retransmission: same request_id must answer from cache, not re-add
    again = c.managers[0].propose("acct", "5", callback=cb, request_id=777)
    assert again is None
    assert responses == ["5", "5"]
    c.run(4)
    assert c.managers[0].app.totals["acct"] == 5  # executed exactly once
    c.close()


def test_checkpoint_and_crash_recovery(tmp_path):
    dirs = [str(tmp_path / f"n{i}") for i in range(3)]
    c = ManagerCluster(
        CFG, StatefulAdderApp, log_dirs=dirs, checkpoint_every=5
    )
    c.create("acct")
    for i in range(8):
        c.submit("acct", "10", entry=0)
        c.step_all()
    c.run(6)
    total_before = c.managers[1].app.totals["acct"]
    assert total_before == 80
    c.close()

    # restart all three from disk; totals and names must be restored
    c2 = ManagerCluster(
        CFG, StatefulAdderApp, log_dirs=dirs, checkpoint_every=5
    )
    assert "acct" in c2.managers[1].names
    c2.run(6)  # replay any post-checkpoint decisions through the engine
    totals = [m.app.totals.get("acct", 0) for m in c2.managers]
    assert totals == [80, 80, 80], totals
    # the recovered cluster keeps committing
    c2.submit("acct", "1", entry=1)
    c2.run(8)
    totals = [m.app.totals.get("acct", 0) for m in c2.managers]
    assert totals == [81, 81, 81], totals
    c2.close()


def test_stop_request_via_manager():
    c = ManagerCluster(CFG, NoopPaxosApp)
    c.create("ephemeral")
    c.submit("ephemeral", "a", entry=0)
    c.step_all()
    c.submit("ephemeral", "bye", entry=0, stop=True)
    c.run(8)
    row = c.managers[0].names["ephemeral"]
    for m in c.managers:
        assert int(np.asarray(m.state.stopped)[row]) == 1
    # post-stop proposals never commit
    before = c.frontiers()[:, row].copy()
    c.submit("ephemeral", "late", entry=0)
    c.run(5)
    assert (c.frontiers()[:, row] == before).all()
    c.close()


def test_pending_row_gates_admission_until_commit():
    """A start-epoch create is PENDING: proposals queue but nothing may
    commit until the reconfigurator's epoch_commit confirms the row
    (advisor r2: a pre-COMPLETE row move must never discard an
    acknowledged write)."""
    c = ManagerCluster(CFG, NoopPaxosApp)
    row = c.managers[0].default_row_for("pend")
    for m in c.managers:
        m.create_paxos_instance("pend", [0, 1, 2], row=row, pending=True)
    c.republish()
    got = {}
    c.submit("pend", "v0", entry=0, callback=lambda rid, resp: got.update(r=resp))
    c.run(8)
    assert not got, "pending row executed a request before epoch_commit"
    assert (np.asarray([m.state.n_execd for m in c.managers])[:, row] == 0).all()
    for m in c.managers:
        m.commit_row("pend", 0)
    c.run(8)
    assert got.get("r") == "noop-ack"
    c.close()


def test_pending_row_move_carries_held_queue():
    """The probe moving a pending row recreates it at the new row; held
    requests follow the name and execute after the commit."""
    c = ManagerCluster(CFG, NoopPaxosApp)
    for m in c.managers:
        m.create_paxos_instance("mv", [0, 1, 2], row=1, pending=True)
    got = {}
    c.managers[0].propose("mv", "x", callback=lambda rid, resp: got.update(r=resp))
    for m in c.managers:
        assert m.create_paxos_instance("mv", [0, 1, 2], row=3, pending=True)
        assert m.names["mv"] == 3
        m.commit_row("mv", 0)
    c.republish()
    c.run(10)
    assert got.get("r") == "noop-ack"
    c.close()


def test_executed_row_refuses_same_epoch_move():
    """A row that already executed decisions must refuse the move (raises,
    surfacing as a collision NACK so the RC's probe converges back here)."""
    c = ManagerCluster(CFG, NoopPaxosApp)
    c.create("ex")  # non-pending; commits flow
    row = c.managers[0].names["ex"]
    c.submit("ex", "w", entry=0)
    c.run(8)
    assert int(np.asarray(c.managers[0].state.n_execd)[row]) > 0
    with pytest.raises(RuntimeError, match="already executed"):
        c.managers[0].create_paxos_instance(
            "ex", [0, 1, 2], row=(row + 1) % CFG.n_groups, pending=True
        )
    c.close()


def test_pending_gate_survives_restart(tmp_path):
    """The propose-refusal gate is durable: a pending row recovers pending;
    an unpended row recovers live (UNPEND journal block)."""
    from gigapaxos_tpu.manager import PaxosManager

    d = str(tmp_path / "n0")
    cfg = EngineConfig(n_groups=6, window=8, req_lanes=4, n_replicas=3)
    m = PaxosManager(0, NoopPaxosApp(), cfg, log_dir=d)
    m.create_paxos_instance("a", [0, 1, 2], row=2, pending=True)
    m.create_paxos_instance("b", [0, 1, 2], row=4, pending=True)
    m.commit_row("b", 0, row=4)
    m.close()
    m2 = PaxosManager(0, NoopPaxosApp(), cfg, log_dir=d)
    assert m2.pending_rows == {2}
    m2.close()


def test_retransmit_reproposes_after_row_killed():
    """A queued-but-undecided request whose row is killed must not leave a
    dead inflight entry behind: the client's retransmit (same request id)
    has to RE-propose into the name's next incarnation and complete, not
    be deduped against the dead proposal forever (review find on the
    queue-drop sites)."""
    c = ManagerCluster(CFG, StatefulAdderApp)
    c.create("acct")
    rid = 987654321
    got = {}
    # queue on a NON-coordinator entry but don't tick: the vid sits in the
    # row's queue when the kill lands
    m = c.managers[0]
    row = m.names["acct"]
    m.propose("acct", "5", request_id=rid,
              callback=lambda r, resp: got.update({"first": resp}))
    assert m.queues.get(row), "setup: vid must be queued"
    for mm in c.managers:
        mm.kill("acct")
    assert rid not in m.inflight, "kill must release the inflight slot"
    # the name is re-created (fresh incarnation) and the client retransmits
    c.create("acct")
    m.propose("acct", "7", request_id=rid,
              callback=lambda r, resp: got.update({"second": resp}))
    c.run(10)
    assert got.get("second") == "7", got
    assert all(mm.app.totals.get("acct", 0) == 7 for mm in c.managers)
    c.close()


def test_retransmission_revives_a_request_stranded_by_an_election():
    """A coordinator deposed while its proposal was accepted by itself
    alone keeps that proposal in its ring until another value decides
    the slot; with no other traffic for the name, the entry replica's
    in-flight dedup used to swallow every retransmission and the request
    hung for good (36 of 1,000 single writes on the chip, PR 22).  A
    retransmission that finds its proposal older than the failure
    detector's timeout, and no longer queued here, is proposed anew —
    and executed exactly once."""
    c = ManagerCluster(CFG, StatefulAdderApp)
    c.create("acct")
    row = c.managers[0].names["acct"]
    old = c.managers[0].coordinator_of_row(row)
    new = (old + 1) % 3
    got = []
    cb = lambda rid, resp: got.append(resp)
    # the coordinator proposes and accepts alone: nobody hears it
    cut = np.full((3, 3), DELIVER)
    for r in range(3):
        if r != old:
            cut[r, old] = cut[old, r] = DROP
    c.managers[old].propose("acct", "7", callback=cb, request_id=77)
    c.step_all(delivery=cut)
    c.step_all(delivery=cut)
    # the others elect `new` without hearing the old coordinator
    want = np.zeros(CFG.n_groups, bool)
    want[row] = True
    c.step_all(delivery=cut, want_coord={new: want})
    for _ in range(4):
        c.step_all(delivery=cut)
    c.run(12)  # healed: everyone hears everyone, and still nothing decides
    assert all(m.coordinator_of_row(row) == new for m in c.managers)
    assert not got and all(
        m.app.totals.get("acct", 0) == 0 for m in c.managers
    ), "the stranded request was expected to hang"

    entry = c.managers[old]
    # a young proposal is still waited for: the retransmission is deduped
    entry.propose("acct", "7", callback=cb, request_id=77)
    c.run(8)
    assert not got
    # aged past the failure detector's timeout: proposed anew
    entry._inflight_since[77] -= entry.repropose_after_s + 1
    entry.propose("acct", "7", callback=cb, request_id=77)
    c.run(16)
    assert got == ["7"], got
    # the revived original decides too (a later slot) and is skipped
    assert [m.app.totals.get("acct") for m in c.managers] == [7, 7, 7]
    assert entry.metrics.snapshot()["counters"]["requests_reproposed"] == 1
    c.close()
