"""A member stranded a SMALL distance behind the majority must still
heal when the decisions it needs no longer exist in any peer's window
(chaos-soak find: after the live majority pause+resume at frontier f,
their below-f decision lanes are gone — a member at f-1 could neither
learn the decision through the rings nor qualify for a checkpoint jump,
and diverged forever)."""

import numpy as np

from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster


def _isolate(R, dead):
    d = np.full((R, R), DELIVER)
    d[dead, :] = DROP
    d[:, dead] = DROP
    return d


def test_small_gap_straggler_heals_after_majority_resume():
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    c = ManagerCluster(cfg, HashChainApp)
    c.create("svc", members=[0, 1, 2])
    row = c.managers[0].names["svc"]

    # commit TWO slots on the majority while member 2 is isolated
    dead = _isolate(3, 2)
    done = {}
    for v in ("x1", "x2"):
        c.managers[0].propose(
            "svc", v, callback=lambda r, resp: done.setdefault(r, resp)
        )
    for _ in range(40):
        if len(done) == 2:
            break
        c.step_all(delivery=dead)
    assert len(done) == 2

    # the live majority pause + resume in place: their window remnants
    # (>= frontier) survive, but the decided slots BELOW the frontier
    # leave every ring — nothing can serve them lane-wise anymore
    epoch = c.managers[0].current_epoch("svc")
    for m in (c.managers[0], c.managers[1]):
        assert m.pause_group("svc", epoch, force=True) == "ok"
        assert m.resume_group("svc", epoch, [0, 1, 2], row, pending=False)
    c.republish()

    # reconnect member 2: it sits 2 slots behind (< W=8, < jump horizon);
    # the frontier-stall heal must pull it up to the majority frontier
    for i in range(400):
        c.step_all()
        if int(np.asarray(c.managers[2].state.exec_slot)[row]) >= 2 and \
                c.managers[2].app.state.get("svc") == \
                c.managers[0].app.state.get("svc"):
            break
    h2 = c.managers[2].app.state.get("svc")
    h0 = c.managers[0].app.state.get("svc")
    assert h0 is not None and h2 == h0, (
        "small-gap straggler never healed",
        int(np.asarray(c.managers[2].state.exec_slot)[row]), h2, h0,
    )
    # and new traffic keeps all three in agreement
    done2 = {}
    c.managers[0].propose(
        "svc", "x3", callback=lambda r, resp: done2.setdefault(r, resp)
    )
    for _ in range(40):
        if done2:
            break
        c.step_all()
    assert done2
    for _ in range(40):
        states = {m.app.state.get("svc") for m in c.managers}
        if len(states) == 1:
            break
        c.step_all()
    assert len(states) == 1, states
    c.close()


def test_majority_behind_single_ahead_member_heals():
    """The inverted shape (also chaos-found): TWO members blank-rejoin at
    frontier 0 while ONE resumed member sits at frontier 2 with no
    below-frontier lanes.  maj_exec equals the stragglers' own frontier,
    so a majority-based stall detector never fires — the detector must
    measure against the MAX known frontier (peer app-cursor gossip)."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    c = ManagerCluster(cfg, HashChainApp)
    c.create("svc", members=[0, 1, 2])
    row = c.managers[0].names["svc"]

    done = {}
    for v in ("x1", "x2"):
        c.managers[0].propose(
            "svc", v, callback=lambda r, resp: done.setdefault(r, resp)
        )
    for _ in range(40):
        if len(done) == 2:
            break
        c.step_all()
    assert len(done) == 2
    epoch = c.managers[0].current_epoch("svc")

    # member 2: pause+resume in place (frontier 2, below-frontier lanes
    # gone).  members 0 and 1: blank re-join at frontier 0 (the commit-
    # heal shape) — now the MAJORITY is behind the lone resumed member.
    assert c.managers[2].pause_group("svc", epoch, force=True) == "ok"
    assert c.managers[2].resume_group("svc", epoch, [0, 1, 2], row,
                                      pending=False)
    for r in (0, 1):
        m = c.managers[r]
        assert m.kill("svc")
        assert m.create_paxos_instance("svc", [0, 1, 2], row=row,
                                       version=epoch)
    c.republish()

    import numpy as np

    for _ in range(400):
        c.step_all()
        if all(
            int(np.asarray(m.state.exec_slot)[row]) >= 2 for m in c.managers
        ) and len({m.app.state.get("svc") for m in c.managers}) == 1:
            break
    states = {m.app.state.get("svc") for m in c.managers}
    assert len(states) == 1 and None not in states, (
        "majority-behind stragglers never healed",
        [int(np.asarray(m.state.exec_slot)[row]) for m in c.managers],
        states,
    )
    c.close()
