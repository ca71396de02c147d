"""The epoch-commit round's truthful-ack + heal matrix, unit-level: an
active must ack ok only when it truly runs the current epoch at the
winning row; every other shape NACKs 'missing' and is healed by a
committed RESUME start (re-home / restore / empty join)."""

from typing import Dict, List, Tuple

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models.apps import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.reconfiguration.active_replica import ActiveReplica
from gigapaxos_tpu.reconfiguration.coordinator import PaxosReplicaCoordinator

CFG = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)


def make_ar() -> Tuple[ActiveReplica, PaxosManager, List]:
    mgr = PaxosManager(0, StatefulAdderApp(), CFG)
    coord = PaxosReplicaCoordinator(mgr.app, mgr)
    sent = []
    ar = ActiveReplica(0, coord, lambda dst, kind, body: sent.append(
        (dst, kind, body)
    ))
    return ar, mgr, sent


def commit(ar, name, epoch, row) -> None:
    ar.handle_message("epoch_commit", {
        "name": name, "epoch": epoch, "row": row, "rc": ["RC", 0],
    })


def last_ack(sent) -> Dict:
    kind_bodies = [(k, b) for (_d, k, b) in sent if k == "ack_epoch_commit"]
    assert kind_bodies, "no ack sent"
    return kind_bodies[-1][1]


def test_ack_matrix():
    ar, mgr, sent = make_ar()

    # live at the winning row, pending -> ok + unpended
    mgr.create_paxos_instance("a", [0, 1, 2], row=3, pending=True)
    commit(ar, "a", 0, 3)
    assert last_ack(sent)["ok"] and 3 not in mgr.pending_rows

    # losing pending row (commit names row 5, we hold row 3) -> missing
    mgr.create_paxos_instance("b", [0, 1, 2], row=4, pending=True)
    commit(ar, "b", 0, 6)
    ack = last_ack(sent)
    assert not ack["ok"] and ack["reason"] == "missing"
    assert 4 in mgr.pending_rows  # the losing row must stay gated

    # not hosting at all -> missing
    commit(ar, "ghost", 0, 7)
    ack = last_ack(sent)
    assert not ack["ok"] and ack["reason"] == "missing"

    # paused -> missing (the member needs a resume, not a silent ok)
    mgr.create_paxos_instance("c", [0, 1, 2], row=5)
    assert mgr.pause_group("c", 0) == "ok"
    commit(ar, "c", 0, 5)
    ack = last_ack(sent)
    assert not ack["ok"] and ack["reason"] == "missing"

    # historic round for a superseded epoch -> ok (nothing to confirm)
    mgr.create_paxos_instance("d", [0, 1, 2], row=6)
    mgr.propose_stop("d")
    # simulate the stop having executed so the epoch can move on
    st = mgr.state
    mgr.state = st._replace(stopped=st.stopped.at[6].set(1))
    mgr.create_paxos_instance("d", [0, 1, 2], row=7, version=1)
    commit(ar, "d", 0, 6)
    assert last_ack(sent)["ok"]


def test_resume_heal_shapes():
    """The committed resume start heals each missing shape."""
    ar, mgr, sent = make_ar()

    def heal(name, epoch, row, initial=None):
        ar.handle_message("start_epoch", {
            "name": name, "epoch": epoch, "actives": [0, 1, 2], "row": row,
            "initial_state": initial, "prev_actives": [], "prev_epoch": -1,
            "resume": True, "committed": True, "rc": ["RC", 0],
        })
        ar.drain_residency()  # since PR 31 a resume waits for the next
        #   message or the layer's tick (a burst restores in one batch)

    # losing pending row -> re-homed to the winning row, unpended, queue kept
    mgr.create_paxos_instance("x", [0, 1, 2], row=1, pending=True)
    mgr.propose("x", "5")
    heal("x", 0, 2)
    assert mgr.names["x"] == 2 and 2 not in mgr.pending_rows
    assert mgr.queues.get(2), "held queue lost in the re-home"

    # paused -> restored at the new row with its state
    mgr.create_paxos_instance("y", [0, 1, 2], row=3)
    assert mgr.pause_group("y", 0) == "ok"
    heal("y", 0, 4)
    assert mgr.names["y"] == 4 and ("y", 0) not in mgr.paused

    # nothing at all -> empty join with the birth state
    heal("z", 0, 5, initial="7")
    assert mgr.names["z"] == 5
    assert mgr.app.totals.get("z") == 7  # StatefulAdder restore("7")

    # after healing, the commit retransmit acks ok
    for nm, row in (("x", 2), ("y", 4), ("z", 5)):
        commit(ar, nm, 0, row)
        assert last_ack(sent)["ok"], nm


def test_ready_audit_heals_post_commit_row_loss():
    """Chaos-sweep find: a member can lose its row AFTER the epoch's
    commit round completed (failed re-home / aborted pause) — it holds
    no pause record and no pending row, so no probe fires, and the old
    one-shot commit round never re-runs: the READY record keeps a
    member hosting NOTHING forever.  The slow READY audit re-runs the
    idempotent commit round; its missing-NACK drives the committed
    resume that re-joins the member."""
    import time as _t

    from gigapaxos_tpu.models.apps import HashChainApp
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster

    ar_cfg = EngineConfig(n_groups=16, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    c = ReconfigurableCluster(ar_cfg, rc_cfg, HashChainApp)
    try:
        for rc in c.reconfigurators:
            rc.REDRIVE_EVERY = 4
            rc.ready_audit_period_s = 0.3  # fast audit for the test
        for ar in c.active_replicas:
            ar.pause_option = False
        c.client_request("create_service", {"name": "pl", "actives": [0, 1, 2]})
        ack = c.wait_for("create_ack", max_steps=200)
        assert ack and ack["ok"], ack
        done = {}
        c.ars.managers[0].propose(
            "pl", "w", callback=lambda rid, r: done.setdefault(rid, r)
        )
        for _ in range(80):
            if done:
                break
            c.step()
        assert done

        # post-commit row loss on member 2: no pause record, no pending
        # row — only the audit can see it
        m2 = c.ars.managers[2]
        assert m2.kill("pl")
        assert m2.names.get("pl") is None

        deadline = _t.time() + 60
        while _t.time() < deadline and m2.names.get("pl") is None:
            c.step()
        assert m2.names.get("pl") is not None, "audit never re-healed"
        # and the healed member converges to the group state
        deadline = _t.time() + 60
        while _t.time() < deadline:
            states = {m.app.state.get("pl") for m in c.ars.managers}
            if len(states) == 1:
                break
            c.step()
        assert len(states) == 1, states
    finally:
        c.close()
