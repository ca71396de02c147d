"""The demand plane's wire form: one ``demand_report`` frame a
reconfigurator a flush, ``{"from", "load", "reports": [[name, epoch,
count], ...]}``, against the single-name handler it replaced (kept here
as the reference)."""

import pytest

from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.reconfiguration import RCState
from gigapaxos_tpu.reconfiguration.chash import ConsistentHashing
from gigapaxos_tpu.reconfiguration.demand import (
    AbstractDemandProfile,
    AggregateDemandProfiler,
)
from gigapaxos_tpu.reconfiguration.rc_config import RC
from gigapaxos_tpu.reconfiguration.reconfigurator import (
    RECONFIGURE_INTENT,
    row_for,
)
from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster
from gigapaxos_tpu.utils.config import Config

NAMES = [f"svc{i:02d}" for i in range(12)]


class TellingProfile(AbstractDemandProfile):
    """Keeps every report it is told, and asks for a move once a name's
    count reaches THRESHOLD (so that a frame holds names that move and
    names that stay)."""

    THRESHOLD = 7
    TARGET = [1, 2, 3]
    told = []  # (name, report), in the order combined, all instances

    def __init__(self, name):
        super().__init__(name)
        self.total = 0

    def combine(self, report):
        TellingProfile.told.append((self.name, dict(report)))
        self.total += int(report.get("count", 0))

    def reconfigure(self, cur_actives, all_actives):
        if self.total >= self.THRESHOLD:
            return [a for a in self.TARGET if a in all_actives]
        return None

    def just_reconfigured(self):
        self.total = 0


def old_handle_demand_report(rc, body):
    """``Reconfigurator._handle_demand_report`` as it stood before the
    list form, one name a body: the reference."""
    name = body["name"]
    if not rc.is_primary(name):
        rc.send(("RC", rc.primary_of(name)), "demand_report", body)
        return
    rec = rc.rc_app.get_record(name)
    if rec is None or rec.deleted:
        rc.demand.pop(name)
        rc.placement.note_name_gone(name)
        return
    rc.placement.note_report(body)
    prof = rc.demand.combine(name, body)
    if rec.state is not RCState.READY:
        return
    target = prof.reconfigure(list(rec.actives), sorted(rc.ar_ids))
    in_place = bool(target) and rc.reconfigure_in_place
    if not target:
        target = rc.placement.rebalance(
            name, prof, list(rec.actives), sorted(rc.ar_ids)
        )
    if not target or rc._bad_actives(target) or (
        sorted(target) == sorted(rec.actives) and not in_place
    ):
        return
    prof.just_reconfigured()
    rc.propose_op({
        "op": RECONFIGURE_INTENT, "name": name,
        "new_actives": list(target),
        "new_row": row_for(name, rec.epoch + 1, 0, rc.n_groups),
    })


def record_proposals(c, on=True):
    """On: the reconfigurators record what they would propose and
    propose nothing.  Off: they propose again."""
    for rc in c.reconfigurators:
        if on:
            rc.propose_op = c.proposed.append
        else:
            del rc.propose_op


@pytest.fixture(scope="module")
def cluster():
    """Four actives, three reconfigurators, twelve names on [0, 1, 2];
    the reconfigurators record what they would propose and propose
    nothing, so every record stays READY at epoch 0."""
    ar_cfg = EngineConfig(n_groups=32, window=8, req_lanes=4, n_replicas=4)
    rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    c = ReconfigurableCluster(
        ar_cfg, rc_cfg, HashChainApp, demand_profile_cls=TellingProfile)
    try:
        for name in NAMES:
            c.client_request(
                "create_service", {"name": name, "actives": [0, 1, 2]})
            ack = c.wait_for("create_ack", max_steps=120)
            assert ack and ack["ok"], ack
        # no flush but the ones a test asks for, and no echo rounds
        for ar in c.active_replicas:
            ar.demand_report_period_s = 1e9
        for rc in c.reconfigurators:
            rc.echo_probe_period_s = 0.0
        c.run(4)
        c.proposed = []
        record_proposals(c)
        yield c
    finally:
        c.close()


def forget(c):
    """Fresh profiles and load tables, nothing told, nothing proposed."""
    for rc in c.reconfigurators:
        rc.demand = AggregateDemandProfiler(TellingProfile)
        rc.placement.loads.clear()
    TellingProfile.told.clear()
    del c.proposed[:]


@pytest.fixture
def quiet(cluster):
    """The cluster with empty inboxes, fresh profiles, nothing counted
    and every reconfigurator up; frames are recorded as they are sent."""
    c = cluster
    c.run(2)
    for m in c.ars.managers:
        m.drain_demand()
    forget(c)
    c.dead_rcs.clear()
    c.frames = []

    def record(dst, kind, body):
        if kind == "demand_report":
            c.frames.append((dst, body))
        return True

    c.msg_filter = record
    yield c
    c.msg_filter = None
    c.dead_rcs.clear()


RING = ConsistentHashing([0, 1, 2])  # the three reconfigurators' ring


def ring_primary(name):
    return RING.get_node(name)


def count_demand(c, active, counts):
    """What ``counts`` proposes at ``active`` would have left in its
    manager: {name: count} unreported, at the names' epoch 0."""
    m = c.ars.managers[active]
    with m._state_lock:
        for name, n in counts.items():
            m.demand_counts[name] = m.demand_counts.get(name, 0) + n
            m.demand_backlog += n


def flush(c, active):
    """The active's demand flush, as if its period had passed."""
    ar = c.active_replicas[active]
    ar._maybe_report_demand(
        ar._last_demand_flush + ar.demand_report_period_s + 1.0)


def deliver(c):
    """Hand every queued control frame to its node, no engine step."""
    inboxes, c._inboxes = c._inboxes, {}
    for (role, idx), msgs in inboxes.items():
        node = (c.active_replicas[idx] if role == "AR"
                else c.reconfigurators[idx])
        for kind, body in msgs:
            node.handle_message(kind, body)


def outcome(c):
    """What the reconfigurators made of the reports: who was told what
    of which name, what would have been proposed, whose load is known."""
    told = sorted(
        (name, r["count"], r["epoch"], r["from"], tuple(sorted(r["load"].items())))
        for name, r in TellingProfile.told
    )
    at = {
        name: [rc.my_id for rc in c.reconfigurators
               if name in rc.demand._profiles]
        for name in NAMES
    }
    proposed = sorted(
        (op["name"], tuple(op["new_actives"]), op["new_row"])
        for op in c.proposed if op["op"] == RECONFIGURE_INTENT
    )
    loads = {
        rc.my_id: {a: ld.names for a, ld in rc.placement.loads.items()}
        for rc in c.reconfigurators
    }
    return told, at, proposed, loads


@pytest.mark.parametrize("n_names", [1, 5, 12])
def test_flush_is_one_frame_a_reconfigurator_and_tells_what_single_names_told(
        quiet, n_names):
    c = quiet
    counts = {name: 3 + i for i, name in enumerate(NAMES[:n_names])}
    count_demand(c, 0, counts)
    flush(c, 0)
    # at most one frame a reconfigurator, every name in the frame
    # addressed to the ring's first server for it, every count once
    assert 1 <= len(c.frames) <= min(3, n_names)
    assert len({dst for dst, _b in c.frames}) == len(c.frames)
    carried = {}
    for dst, body in c.frames:
        assert set(body) == {"from", "load", "reports"}
        assert body["from"] == 0
        assert body["load"] == c.active_replicas[0].load_summary()
        for name, epoch, count in body["reports"]:
            assert dst == ("RC", ring_primary(name))
            assert name not in carried
            carried[name] = (epoch, count)
    assert carried == {name: (0, n) for name, n in counts.items()}
    load = c.frames[0][1]["load"]
    deliver(c)
    assert len(c.frames) == len({dst for dst, _b in c.frames})  # none sent on
    got = outcome(c)

    # the reference: the same counts as single-name bodies, each handed
    # to the reconfigurator the old sender would have picked (any of the
    # three), through the old handler
    forget(c)
    c.msg_filter = None
    for i, (name, n) in enumerate(counts.items()):
        old_handle_demand_report(c.reconfigurators[i % 3], {
            "name": name, "epoch": 0, "count": n, "from": 0, "load": load,
        })
    inboxes, c._inboxes = c._inboxes, {}
    for (_role, idx), msgs in inboxes.items():
        for kind, body in msgs:
            assert kind == "demand_report"
            old_handle_demand_report(c.reconfigurators[idx], body)
    assert not c._inboxes  # a forward arrives at the primary
    want = outcome(c)
    assert got == want
    told, at, proposed, loads = got
    assert len(told) == n_names
    assert all(at[name] == [ring_primary(name)] for name in counts)
    # names at or over the profile's threshold move, the others stay
    assert [p[0] for p in proposed] == sorted(
        name for name, n in counts.items() if n >= TellingProfile.THRESHOLD)
    assert all(ld == {0: load["names"]} for ld in loads.values() if ld)


def test_dead_primary_entries_go_on_in_one_frame(quiet):
    c = quiet
    counts = {name: 2 for name in NAMES}
    count_demand(c, 1, counts)
    flush(c, 1)
    frames = dict((dst[1], body) for dst, body in c.frames)
    dead = max(frames, key=lambda rc: len(frames[rc]["reports"]))
    alive = [rc for rc in (0, 1, 2) if rc != dead]
    c.dead_rcs.add(dead)
    theirs = [e[0] for e in frames[dead]["reports"]]
    heir = {name: c.reconfigurators[alive[0]].primary_of(name)
            for name in theirs}
    assert set(heir.values()) <= set(alive)
    # the dead primary's frame lands at a live reconfigurator, which is
    # the next on the ring for some of its names and not for the others
    del c.frames[:]
    c._inboxes.clear()
    first = c.reconfigurators[alive[0]]
    before = first.metrics.snapshot()["counters"]["demand_reports_forwarded"]
    first.handle_message("demand_report", frames[dead])
    sent_on = [n for n in theirs if heir[n] == alive[1]]
    assert [n for n, _r in TellingProfile.told] == [
        n for n in theirs if heir[n] == alive[0]]
    if sent_on:
        assert len(c.frames) == 1
        dst, body = c.frames[0]
        assert dst == ("RC", alive[1])
        assert [e[0] for e in body["reports"]] == sent_on
        assert (body["from"], body["load"]) == (1, frames[dead]["load"])
    else:
        assert not c.frames
    after = first.metrics.snapshot()["counters"]["demand_reports_forwarded"]
    assert after - before == len(sent_on)
    deliver(c)
    assert len(c.frames) == (1 if sent_on else 0)  # and no further
    assert sorted(n for n, _r in TellingProfile.told) == sorted(theirs)
    for name in theirs:
        assert [rc.my_id for rc in c.reconfigurators
                if name in rc.demand._profiles] == [heir[name]]


def test_deleted_names_entry_is_dropped_and_the_rest_handled(quiet):
    c = quiet
    gone = "svc-gone"
    home = ring_primary(gone)
    rc = c.reconfigurators[home]
    mates = [n for n in NAMES if ring_primary(n) == home]
    assert mates, "no other name on this reconfigurator"
    record_proposals(c, on=False)
    try:
        c.client_request(
            "create_service", {"name": gone, "actives": [0, 1, 2]})
        assert c.wait_for("create_ack", max_steps=120)["ok"]
        # a profile from before the delete
        rc.handle_message("demand_report", {
            "from": 2, "load": {"names": 13, "rps": 1.0},
            "reports": [[gone, 0, 1]],
        })
        assert gone in rc.demand._profiles
        c.client_request("delete_service", {"name": gone})
        ack = c.wait_for("delete_ack", max_steps=240)
        assert ack and ack["ok"], ack
        c.run(4)
    finally:
        record_proposals(c)
    for m in c.ars.managers:
        m.drain_demand()
    TellingProfile.told.clear()
    rec = rc.rc_app.get_record(gone)
    assert rec is None or rec.deleted
    entries = [[mates[0], 0, 2], [gone, 0, 5]] + [[n, 0, 1] for n in mates[1:]]
    rc.handle_message("demand_report", {
        "from": 2, "load": {"names": 12, "rps": 2.0}, "reports": entries,
    })
    assert [(n, r["count"]) for n, r in TellingProfile.told] == [
        (e[0], e[2]) for e in entries if e[0] != gone]
    assert gone not in rc.demand._profiles
    assert rc.placement.loads[2].names == 12


def test_counters_add_up_over_flushes_of_real_proposes(quiet):
    c = quiet
    ar = c.active_replicas[0]
    m = c.ars.managers[0]
    before = m.metrics.snapshot()["counters"]
    fwd_before = sum(
        rc.metrics.snapshot()["counters"]["demand_reports_forwarded"]
        for rc in c.reconfigurators)
    drained = []
    inner = ar.coordinator.drain_demand

    def spy():
        out = inner()
        drained.append(out)
        return out

    ar.coordinator.drain_demand = spy
    try:
        rounds = [NAMES[:1], NAMES[:7], NAMES, []]
        for i, names in enumerate(rounds):
            for name in names:
                for k in range(2):
                    m.propose(name, f"r{i}-{k}")
            n_before = len(c.frames)
            flush(c, 0)
            assert len(c.frames) - n_before <= min(3, len(names))
            c.run(3)
    finally:
        ar.coordinator.drain_demand = inner
    after = m.metrics.snapshot()["counters"]
    assert [len(d) for d in drained] == [len(r) for r in rounds]
    assert all(n == 2 for d in drained for n, _e in d.values())
    own = [b for dst, b in c.frames if b["from"] == 0]
    assert after["demand_report_names"] - before["demand_report_names"] \
        == sum(len(d) for d in drained) \
        == sum(len(b["reports"]) for b in own)
    assert after["demand_report_frames"] - before["demand_report_frames"] \
        == len(own) <= 3 * 3
    # every reconfigurator up, the ring unchanged: nothing is sent on
    assert sum(
        rc.metrics.snapshot()["counters"]["demand_reports_forwarded"]
        for rc in c.reconfigurators) == fwd_before


def test_flush_settings_keep_their_values():
    Config.clear()
    assert Config.get_int(RC.DEMAND_REPORT_EVERY) == 64
    assert Config.get_float(RC.DEMAND_REPORT_PERIOD_S) == 1.0
