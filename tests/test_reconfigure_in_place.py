"""In-place reconfiguration (upstream's RECONFIGURE_IN_PLACE,
``ReconfigurationConfig.java:268``): a name's epoch changes although its
replica set stays what it was, while writers keep writing to it.  What a
writer may see of it is latency only: no error, nothing lost, repeated or
reordered, every acknowledgement's value the name's running sum.
"""

import collections

import pytest

from gigapaxos_tpu.models.apps import StatefulAdderApp
from gigapaxos_tpu.ops.engine import STOP_BIT, EngineConfig
from gigapaxos_tpu.reconfiguration import RCState
from gigapaxos_tpu.reconfiguration.active_replica import stop_request_id
from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster

NAMES = [f"n{i}" for i in range(4)]
ALL = [0, 1, 2]


def make_cluster(in_place):
    ar_cfg = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    c = ReconfigurableCluster(ar_cfg, rc_cfg, StatefulAdderApp)
    for rc in c.reconfigurators:
        rc.reconfigure_in_place = in_place
        rc.echo_probe_period_s = 0.0
    for name in NAMES:
        c.client_request("create_service", {"name": name, "actives": ALL})
        ack = c.wait_for("create_ack", max_steps=120)
        assert ack and ack["ok"], ack
    for _ in range(10):
        c.step()
    return c


@pytest.fixture(scope="module")
def cluster():
    c = make_cluster(in_place=True)
    yield c
    c.close()


class Writers:
    """One writer a name, its next write out when the last was answered;
    entry replica round-robin by write, so two of three are forwarded."""

    def __init__(self, c, names):
        self.c, self.names = c, names
        self.sent = collections.defaultdict(list)    # name -> [delta]
        self.acked = collections.defaultdict(list)   # name -> [response]
        self.refused = []
        self.busy = set()
        self.next_rid = 10_000
        self.issuing = True

    def pump(self):
        for k, name in enumerate(self.names):
            if name in self.busy or not self.issuing:
                continue
            delta = 1 + (len(self.sent[name]) * 7 + k) % 50
            entry = (len(self.sent[name]) + k) % 3
            mgr = self.c.ars.managers[entry]
            if mgr.names.get(name) is None:
                self.refused.append((name, entry, "unknown_name"))
                continue
            self.next_rid += 1
            self.sent[name].append(delta)
            self.busy.add(name)
            mgr.propose(name, str(delta), request_id=self.next_rid,
                        callback=lambda rid, resp, n=name: self.on_ack(n, resp))

    def on_ack(self, name, resp):
        self.acked[name].append(resp)
        self.busy.discard(name)

    def drain(self, max_steps=200):
        self.issuing = False
        for _ in range(max_steps):
            if not self.busy:
                return
            self.c.step()
        raise AssertionError(f"never answered: {sorted(self.busy)}")

    def check(self):
        """The sequential model: per name, each acknowledgement's value is
        the running sum, and every active's total is the whole sum."""
        assert not self.refused
        for name in self.names:
            running = 0
            assert len(self.acked[name]) == len(self.sent[name])
            for delta, resp in zip(self.sent[name], self.acked[name]):
                running += delta
                assert resp == str(running), (name, resp, running)
            for mgr in self.c.ars.managers:
                assert mgr.app.totals.get(name, 0) == running, \
                    (name, mgr.my_id)


def reconfigure(c, name, rid, max_steps=300, pump=None, rc=0):
    c.client_request("reconfigure", {
        "name": name, "new_actives": ALL, "rid": rid}, rc=rc)
    for _ in range(max_steps):
        for kind, body in c.drain_client():
            if kind == "reconfigure_ack" and body["name"] == name:
                return body
        if pump is not None:
            pump()
        c.step()
    return None


def epochs(c, name):
    return [m.current_epoch(name) for m in c.ars.managers]


def test_names_change_epoch_under_concurrent_writes(cluster):
    c = cluster
    w = Writers(c, NAMES)
    acked = collections.Counter()
    for round_ in range(3):
        for name in NAMES[:3]:              # NAMES[3] never changes epoch
            before = max(epochs(c, name))
            ack = reconfigure(c, name, f"r{round_}-{name}", pump=w.pump)
            assert ack and ack["ok"], ack
            assert sorted(ack["actives"]) == ALL
            # the acknowledgement carries the new epoch: the old plus one
            assert ack["epoch"] == before + 1
            acked[name] += 1
            for _ in range(3):
                w.pump()
                c.step()
    for _ in range(40):                     # commits, late starts, drops
        w.pump()
        c.step()
    w.drain()
    w.check()
    assert min(len(w.sent[n]) for n in NAMES) >= 5
    for name in NAMES:
        assert epochs(c, name) == [acked[name]] * 3
        rec = c.reconfigurators[0].rc_app.get_record(name)
        assert rec.epoch == acked[name] and rec.state is RCState.READY
    for mgr in c.ars.managers:
        assert mgr.old_epochs == {}         # every old epoch was dropped
        assert not mgr._stop_executed_rows and not mgr._epoch_carry
    # the layer's own account: one start, stop and drop a change and active
    changes = sum(acked.values())
    for mgr in c.ars.managers:
        counters = mgr.metrics.snapshot()["counters"]
        hists = mgr.metrics.snapshot()["hists"]
        assert counters["epochs_started"] == changes
        assert counters["epochs_stopped"] == changes
        assert counters["epochs_dropped"] == changes
        assert hists["epoch_gap_s"]["count"] == changes
        assert hists["phase_reconf_start_s"]["count"] >= changes
        assert hists["phase_reconf_drop_s"]["count"] >= changes
        assert hists["phase_reconf_stop_capture_s"]["count"] == changes
        assert hists["phase_lifecycle_await_step_s"]["count"] >= 2 * changes
    carried = sum(m.metrics.snapshot()["counters"]["requests_carried_over"]
                  for m in c.ars.managers)
    assert carried > 0      # some write did meet a stopped epoch
    rc_hists = c.reconfigurators[0].metrics.snapshot()["hists"]
    total = sum(r.metrics.snapshot()["hists"].get(
        "phase_rc_intent_to_complete_s", {"count": 0})["count"]
        for r in c.reconfigurators)
    assert total == changes
    assert "phase_rc_create_to_complete_s" in rc_hists or total


def test_a_retransmitted_reconfigure_starts_one_epoch_change(cluster):
    c = cluster
    name = NAMES[0]
    before = max(epochs(c, name))
    # the same request twice before either is answered, through two
    # reconfigurators, and once more after the change completed
    c.client_request("reconfigure", {
        "name": name, "new_actives": ALL, "rid": "twice"}, rc=1)
    ack = reconfigure(c, name, "twice", rc=2)
    assert ack and ack["ok"] and ack["epoch"] == before + 1
    for _ in range(30):
        c.step()
    c.drain_client()
    again = reconfigure(c, name, "twice")
    assert again and again["ok"] and again["epoch"] == before + 1
    for _ in range(30):
        c.step()
    assert epochs(c, name) == [before + 1] * 3
    # another request (another id) does start another change
    ack = reconfigure(c, name, "other")
    assert ack and ack["ok"] and ack["epoch"] == before + 2


def test_with_the_flag_off_a_same_set_reconfigure_is_a_no_op():
    c = make_cluster(in_place=False)
    try:
        name = NAMES[1]
        w = Writers(c, [name])
        for _ in range(12):
            w.pump()
            c.step()
        ack = reconfigure(c, name, "noop", pump=w.pump)
        assert ack and ack["ok"] and ack["epoch"] == 0
        assert sorted(ack["actives"]) == ALL
        for _ in range(20):
            w.pump()
            c.step()
        w.drain()
        w.check()
        assert epochs(c, name) == [0, 0, 0]
        for mgr in c.ars.managers:
            assert mgr.metrics.snapshot()["counters"]["epochs_stopped"] == 0
    finally:
        c.close()


def test_a_write_decided_behind_the_stop_executes_once_in_the_next_epoch():
    """The engine admits nothing behind a stop that its coordinator knows
    of, but a second coordinator may not know yet.  Such a decision is
    executed by no replica in the old epoch (the final state was taken at
    the stop) and is proposed again, under its request id, by the node
    that minted it: once, in the next epoch."""
    c = make_cluster(in_place=True)
    try:
        name = NAMES[2]
        w = Writers(c, [name])
        for _ in range(12):
            w.pump()
            c.step()
        w.drain()
        w.issuing = True
        base = c.ars.managers[0].app.totals[name]
        mgrs = c.ars.managers
        row = mgrs[0].names[name]
        # what a second coordinator would have done: the stop decided at
        # the next slot and a write of node 1's right behind it
        answered = []
        stop_rid = stop_request_id(name, 0)
        slot = int(mgrs[0].app_exec_slot[row])
        for m in mgrs:
            assert int(m.app_exec_slot[row]) == slot
            stop_vid = (2 << 24) | 0xFFFF00 | STOP_BIT
            write_vid = (1 << 24) | 0xFFFF01
            m.arena[stop_vid] = '{"__stop__": 0}'
            m.vid_meta[stop_vid] = (2, stop_rid)
            m.arena[write_vid] = "7"
            m.vid_meta[write_vid] = (1, 777_001)
            with m._state_lock:
                m.pending_exec.setdefault(row, {}).update(
                    {slot: (stop_vid, None), slot + 1: (write_vid, None)})
                m._drain_pending_exec()
        mgrs[1].outstanding.put(
            777_001, lambda rid, resp: answered.append(resp), 0)
        # executed nowhere in the old epoch; held by its minter alone
        for m in mgrs:
            assert m.app.totals[name] == base
            assert row in m._stop_executed_rows
        assert [bool(m._epoch_carry.get(name)) for m in mgrs] == \
            [False, True, False]
        # the stop above bypassed the device: start the next epoch by hand
        for m, ar in zip(mgrs, c.active_replicas):
            ar._on_stop_executed(name, row, 0)
            with m._state_lock:
                m._np("stopped")            # cache for the current state
                m._np_cache["stopped"] = m._np("stopped").copy()
                m._np_cache["stopped"][row] = 1
            assert m.create_paxos_instance(
                name, ALL, initial_state=str(base), version=1, row=row + 1)
        for _ in range(40):
            if answered:
                break
            c.step()
        assert answered == [str(base + 7)]
        for _ in range(10):
            c.step()
        for m in mgrs:
            assert m.app.totals[name] == base + 7      # once, everywhere
            assert m.current_epoch(name) == 1 and not m._epoch_carry
    finally:
        c.close()


def test_a_taken_row_is_refused_before_the_name_lets_go_of_its_row(cluster):
    """A collision on the probed row must leave the name where it was:
    a name that maps to no row answers its writers "unknown_name"."""
    c = cluster
    mgr = c.ars.managers[0]
    name, other = NAMES[3], NAMES[2]
    with mgr._state_lock:
        row = mgr.names[name]
        stopped = mgr._np("stopped").copy()
        stopped[row] = 1
        mgr._np_cache["stopped"] = stopped
        with pytest.raises(RuntimeError):
            mgr._create_locked(name, ALL, None, mgr.current_epoch(name) + 1,
                               mgr.names[other])
        mgr._np_cache.pop("stopped")
    assert mgr.names[name] == row and (name, 0) not in mgr.old_epochs
