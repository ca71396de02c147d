"""The legs of a commit (``commit_leg_*``, manager.py:COMMIT_LEGS): every
request leaves, on each node it touches and on that node's own tick
counter, how many ticks each leg of its commit took.

At the entry replica queue + away + gate tile ``commit_ticks`` to the
integer for every request that has all its marks (the others are in
``commit_ticks`` as ever and counted untiled); at the coordinator the
consensus leg is observed once a request of every vid it staged; a
traced request's merged trace shows the same ticks per phase; no stamp
outlives its vid.  Three managers on the stepped harness
(``testing/cluster.py``), then three served nodes on loopback sockets
for the legs beyond the ticks and for the threads' CPU clocks."""

import time

import numpy as np
import pytest

from gigapaxos_tpu.clients import PaxosClientAsync
from gigapaxos_tpu.manager import COMMIT_LEGS, Outstanding
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.net.node_config import NodeConfig
from gigapaxos_tpu.obs import tracemerge
from gigapaxos_tpu.obs.metrics import MetricsRegistry
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.reconfiguration import RCState
from gigapaxos_tpu.server import THREAD_CLOCKS, PaxosServer
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster
from gigapaxos_tpu.testing.ports import free_ports
from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster

CFG = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
ENTRY_LEGS = COMMIT_LEGS[:3]


def hist(m, key):
    return m.metrics.snapshot()["hists"][key]


def counter(m, key):
    return m.metrics.snapshot()["counters"][key]


def check_identity(managers, answered):
    """On every node: the three entry legs have one count, that of the
    requests with every mark, and add up to those requests'
    ``commit_ticks``, to the integer; ``commit_ticks`` and
    ``commit_entry_s`` hold every request a waiting callback was answered
    for at its execution; what was answered is tiled or counted
    untiled."""
    total = 0
    for m in managers:
        ticks, secs = hist(m, "commit_ticks"), hist(m, "commit_entry_s")
        legs = [hist(m, f"commit_leg_{leg}_ticks") for leg in ENTRY_LEGS]
        tiled = legs[0]["count"]
        assert [h["count"] for h in legs] == [tiled] * 3, (m.my_id, legs)
        assert secs["count"] == ticks["count"] >= tiled
        assert all(float(h["sum"]).is_integer() and (h["min"] or 0) >= 0
                   for h in legs)
        if tiled == ticks["count"]:
            assert sum(h["sum"] for h in legs) == ticks["sum"]
        else:
            assert sum(h["sum"] for h in legs) <= ticks["sum"]
        answered_here = counter(m, "commit_requests_answered")
        assert answered_here >= ticks["count"]  # + answered where proposed
        assert counter(m, "commit_legs_untiled") == answered_here - tiled
        assert counter(m, "commit_requests_forwarded") <= tiled
        total += answered_here
    assert total == answered
    return total


def check_coordinator(managers, decided):
    """(c) and (e): the consensus leg is observed once a request where
    its vid was staged, never under two ticks (two exchanges), a
    forwarded-in request's queue leg at most once; and no stamp is left
    once everything is decided."""
    n = sum(hist(m, "commit_leg_consensus_ticks")["count"] for m in managers)
    assert n == decided, (n, decided)
    for m in managers:
        h = hist(m, "commit_leg_consensus_ticks")
        if h["count"]:
            assert h["min"] >= 2, h
        assert hist(m, "commit_leg_coord_queue_ticks")["count"] \
            <= h["count"]
        assert m.vid_stamp == {}, (m.my_id, m.vid_stamp)


@pytest.fixture
def cluster():
    c = ManagerCluster(CFG, StatefulAdderApp)
    c.create("acct")
    row = c.managers[0].names["acct"]
    c.coord = c.managers[0].coordinator_of_row(row)
    c.row = row
    c.got = []
    c.cb = lambda rid, resp: c.got.append((rid, resp))
    yield c
    c.close()


def _at_coordinator(c):
    c.managers[c.coord].propose("acct", "1", callback=c.cb, request_id=11)
    c.run(8)
    m = c.managers[c.coord]
    assert counter(m, "commit_requests_forwarded") == 0
    assert hist(m, "commit_leg_queue_ticks")["sum"] == 0
    # entered where it is led: what it was away is its consensus leg
    assert hist(m, "commit_leg_away_ticks")["sum"] \
        == hist(m, "commit_leg_consensus_ticks")["sum"]
    assert hist(m, "commit_leg_coord_queue_ticks")["count"] == 0
    return 1, 1, 0


def _forwarded(c):
    entry = (c.coord + 1) % 3
    c.managers[entry].propose("acct", "1", callback=c.cb, request_id=12)
    c.run(10)
    m, lead = c.managers[entry], c.managers[c.coord]
    assert counter(m, "commit_requests_forwarded") == 1
    assert hist(m, "commit_leg_consensus_ticks")["count"] == 0
    assert hist(lead, "commit_leg_coord_queue_ticks")["count"] == 1
    assert hist(lead, "commit_ticks")["count"] == 0
    # away holds the coordinator's two legs and the two wires
    assert hist(m, "commit_leg_away_ticks")["sum"] > \
        hist(lead, "commit_leg_consensus_ticks")["sum"] \
        + hist(lead, "commit_leg_coord_queue_ticks")["sum"]
    return 1, 1, 0


def _coalesced_batch(c):
    """Twelve requests in one tick at the coordinator, five more from
    another entry: more than the ring's depth, so they ride batch vids —
    ONE stamp a batch, and the consensus leg once a request of it."""
    lead = c.managers[c.coord]
    entry = (c.coord + 1) % 3
    for i in range(12):
        lead.propose("acct", "1", callback=c.cb, request_id=100 + i)
    for i in range(5):
        c.managers[entry].propose("acct", "1", callback=c.cb,
                                  request_id=200 + i)
    c.run(1)
    # one vid, one stamp, twelve requests
    assert [len(st[2]) for st in lead.vid_stamp.values()] == [12]
    c.run(13)
    assert lead.metrics.get("commit_leg_coord_queue_ticks") == 5
    return 17, 17, 0


def _retransmission_in_flight(c):
    entry = (c.coord + 2) % 3
    m = c.managers[entry]
    m.propose("acct", "3", callback=c.cb, request_id=31)
    c.run(2)
    first = list(m.outstanding._map[31])
    m.propose("acct", "3", callback=c.cb, request_id=31)
    again = m.outstanding._map[31]
    # the callback and the TTL are refreshed; no mark moves
    assert again[2:] == first[2:] \
        and again[Outstanding.TICK_LEFT] is not None
    c.run(10)
    assert [m.app.totals["acct"] for m in c.managers] == [3, 3, 3]
    return 1, 1, 0


def _preempted_and_forwarded_again(c):
    """The coordinator takes a forward in, stages it, and is deposed
    before anybody hears its accept; the entry replica proposes the
    request again to the new coordinator (``_reforward_locked``).  The
    entry's legs keep the FIRST time the request left."""
    old, row = c.coord, c.row
    new, entry = (old + 1) % 3, (old + 2) % 3
    m = c.managers[entry]
    m.propose("acct", "5", callback=c.cb, request_id=51)
    c.run(2)                      # forwarded, taken in, staged at `old`
    left = m.outstanding._map[51][Outstanding.TICK_LEFT:]
    assert left[0] is not None and left[1] is True
    cut = np.full((3, 3), DELIVER)
    for r in range(3):
        if r != old:
            cut[r, old] = cut[old, r] = DROP
    want = np.zeros(CFG.n_groups, bool)
    want[row] = True
    c.step_all(delivery=cut, want_coord={new: want})
    for _ in range(4):
        c.step_all(delivery=cut)
    assert 51 not in [rid for rid, _ in c.got]
    assert m.outstanding._map[51][Outstanding.TICK_LEFT:] == left
    c.run(24)
    assert all(x.coordinator_of_row(row) == new for x in c.managers)
    assert [x.app.totals["acct"] for x in c.managers] == [5, 5, 5]
    assert counter(m, "requests_reforwarded") == 1
    assert counter(m, "commit_requests_forwarded") == 1
    # decided once at the new coordinator; the old one's copy was
    # preempted and went on to the new one too, which skips it
    return 1, None, 0


def _answered_from_the_cache(c):
    """(b): a retransmission that arrives after the execution is
    answered from the response cache where it is proposed: answered,
    untiled, in no histogram."""
    m = c.managers[c.coord]
    m.propose("acct", "2", callback=c.cb, request_id=21)
    c.run(8)
    m.propose("acct", "2", callback=c.cb, request_id=21)
    m.propose_batch([("acct", "2", 21, c.cb)])
    assert c.got == [(21, "2")] * 3
    assert counter(m, "commit_legs_untiled") == 2
    assert hist(m, "commit_ticks")["count"] == 1
    c.run(2)
    return 3, 1, 2


def _answered_with_a_mark_missing(c):
    """A request answered before its vid ever left the queue (its id
    executed under a copy another replica proposed, a slot replayed
    from the journal): in ``commit_ticks`` and ``commit_entry_s`` as
    before PR 37 — what ``tick.per_commit.*`` reads does not move — in
    no leg, and counted untiled."""
    m = c.managers[c.coord]
    with m._state_lock:
        m.outstanding.put(71, c.cb, m._tick_no)
    c.run(2)
    with m._state_lock:
        m._answer(71, "7")
    c.run(1)
    assert hist(m, "commit_ticks")["sum"] == 2
    assert hist(m, "commit_leg_queue_ticks")["count"] == 0
    return 1, 0, 1


STEPPED = {
    "entered_at_its_coordinator": _at_coordinator,
    "answered_with_a_mark_missing": _answered_with_a_mark_missing,
    "forwarded": _forwarded,
    "coalesced_batch": _coalesced_batch,
    "retransmission_in_flight": _retransmission_in_flight,
    "preempted_and_forwarded_again": _preempted_and_forwarded_again,
    "answered_from_the_cache": _answered_from_the_cache,
}


@pytest.mark.parametrize("case", sorted(STEPPED))
def test_the_entry_legs_tile_a_commit(cluster, case):
    """(a), (b), (c), (e) on the stepped harness."""
    c = cluster
    answered, decided, untiled = STEPPED[case](c)
    assert len(c.got) == answered
    check_identity(c.managers, answered)
    assert sum(counter(m, "commit_legs_untiled")
               for m in c.managers) == untiled
    if decided is not None:
        check_coordinator(c.managers, decided)
    else:
        # a deposed coordinator's copy may still wait for its slot
        assert all(v in m.vid_meta for m in c.managers
                   for v in m.vid_stamp)


# ---- with reconfigurators beside: epoch changes and wakes ------------------
NAMES = [f"n{i}" for i in range(4)]


@pytest.fixture(scope="module")
def rc_cluster():
    ar_cfg = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    c = ReconfigurableCluster(ar_cfg, rc_cfg, StatefulAdderApp)
    for rc in c.reconfigurators:
        rc.reconfigure_in_place = True
        rc.echo_probe_period_s = 0.0
    for name in NAMES:
        c.client_request("create_service",
                         {"name": name, "actives": [0, 1, 2]})
        ack = c.wait_for("create_ack", max_steps=120)
        assert ack and ack["ok"], ack
    for _ in range(10):
        c.step()
    yield c
    c.close()


def _drive(c, pump, until, max_steps=400):
    for _ in range(max_steps):
        if until():
            return
        pump()
        c.step()
    raise AssertionError("not reached")


def test_a_write_carried_across_an_epoch_change_keeps_its_first_marks(
        rc_cluster):
    """One writer a name, entry round robin, while every name changes
    epoch twice: writes queued behind a stop follow the name, writes
    decided behind it are proposed again — each is answered once and its
    three legs still tile its commit."""
    c = rc_cluster
    mgrs = c.ars.managers
    sent, acked, busy = [0], [], set()

    def pump():
        for k, name in enumerate(NAMES):
            if name in busy:
                continue
            entry = (sent[0] + k) % 3
            if mgrs[entry].names.get(name) is None:
                continue
            sent[0] += 1
            busy.add(name)
            mgrs[entry].propose(
                name, "1", request_id=50_000 + sent[0],
                callback=lambda rid, resp, n=name: (
                    acked.append(rid), busy.discard(n)))

    for round_ in range(2):
        for name in NAMES:
            c.client_request("reconfigure", {
                "name": name, "new_actives": [0, 1, 2],
                "rid": f"legs-{round_}-{name}"})
            got = []

            def done():
                got.extend(b for k, b in c.drain_client()
                           if k == "reconfigure_ack")
                return bool(got)

            _drive(c, pump, done)
            assert got[0]["ok"], got
    _drive(c, lambda: None, lambda: not busy)
    for _ in range(30):
        c.step()
    assert len(acked) == len(set(acked)) == sent[0] >= 16
    assert sum(counter(m, "requests_carried_over") for m in mgrs) > 0
    check_identity(mgrs, len(acked))
    assert sum(counter(m, "commit_legs_untiled") for m in mgrs) == 0
    for m in mgrs:  # (e): killed with its epoch, or decided
        assert all(v in m.vid_meta for v in m.vid_stamp)
        assert len(m.vid_stamp) <= len(m.inflight)


def test_a_write_held_for_a_wake_waits_in_its_queue_leg(rc_cluster):
    """A name is put to sleep; the first write to it is held at its
    entry replica until the row is back: the hold is inside the queue
    leg, and the three legs still tile the commit."""
    c = rc_cluster
    mgrs = c.ars.managers
    name = NAMES[3]
    rec = c.reconfigurators[0].rc_app.get_record(name)
    c.active_replicas[0].send(("RC", 0), "suggest_pause", {
        "name": name, "epoch": rec.epoch, "from": 0})
    _drive(c, lambda: None, lambda: c.reconfigurators[0].rc_app
           .get_record(name).state is RCState.PAUSED)
    assert all(m.sleeps_here(name) for m in mgrs)
    before = [hist(m, "commit_leg_queue_ticks") for m in mgrs]
    answered0 = sum(counter(m, "commit_requests_answered") for m in mgrs)
    got = []
    entry = mgrs[1]
    res = entry.propose_batch(
        [(name, "7", 70_001, lambda rid, resp: got.append(resp))])
    assert res[0][1] == "held"
    _drive(c, lambda: None, lambda: bool(got))
    for _ in range(10):
        c.step()
    assert len(got) == 1
    after = hist(entry, "commit_leg_queue_ticks")
    assert after["count"] == before[1]["count"] + 1
    # the reconfigurators' REACTIVATE round and the restore take ticks
    assert after["sum"] - before[1]["sum"] >= 3
    check_identity(mgrs, answered0 + 1)


# ---- one set of marks, two readers -----------------------------------------
@pytest.mark.parametrize("forwarded", [False, True],
                         ids=["at_its_coordinator", "forwarded"])
def test_a_traced_request_shows_its_legs_in_the_merged_trace(
        cluster, forwarded):
    """(d): with the tracer on, the merged trace's ticks per phase ARE
    the legs the histograms were handed for that request."""
    c = cluster
    for m in c.managers:
        m.tracer.enabled = True
    entry = (c.coord + 1) % 3 if forwarded else c.coord
    m, lead = c.managers[entry], c.managers[c.coord]
    m.propose("acct", "9", callback=c.cb, request_id=91)
    c.run(10)
    assert c.got == [(91, "9")]

    def phases(node):
        tr, = tracemerge.merge_node_dumps(
            {node.my_id: node.tracer.export(keys=[91])})
        acc = {}
        for hop in tr["hops"]:
            acc[hop["phase"]] = acc.get(hop["phase"], 0) + hop["dticks"]
        return acc, [e["event"] for e in tr["events"]]

    at_entry, events = phases(m)
    assert events[:2] == ["propose", "forward-out" if forwarded else "admit"]
    assert at_entry["admission-queue"] \
        == hist(m, "commit_leg_queue_ticks")["sum"]
    assert at_entry["away" if forwarded else "consensus"] \
        == hist(m, "commit_leg_away_ticks")["sum"]
    assert at_entry["execute-gate"] == hist(m, "commit_leg_gate_ticks")["sum"]
    at_lead, events = phases(lead)
    assert "admit" in events
    assert at_lead["consensus"] \
        == hist(lead, "commit_leg_consensus_ticks")["sum"]
    if forwarded:
        assert events[0] == "forward-in"
        assert at_lead["re-propose"] + at_lead["admission-queue"] \
            == hist(lead, "commit_leg_coord_queue_ticks")["sum"]
    # and the merge of all three nodes names both new marks' phases
    tr, = tracemerge.merge_node_dumps(
        {x.my_id: x.tracer.export(keys=[91]) for x in c.managers})
    assert tracemerge.node_ticks(tr)[entry] == hist(m, "commit_ticks")["sum"]


# ---- the registry's new faces ------------------------------------------------
def test_a_bulk_observation_reads_the_same_on_both_of_its_paths():
    """``register_hist`` gives an empty histogram; ``observe_bulk`` of a
    list (sorted and bisected in the interpreter: no call that gives up
    the interpreter lock on a tick thread), of a numpy array (numpy's
    path) and one ``observe`` a sample fill the same buckets; a
    collector runs at every look."""
    reg = MetricsRegistry()
    reg.register_hist("list", bounds=(1, 2, 4))
    empty = reg.snapshot()["hists"]["list"]
    assert empty["count"] == 0 and empty["sum"] == 0.0
    vals = [9, 3, 1, 3, 3, 2.0, 3, 1, 3, 4, 4.5, 0]
    reg.observe_bulk("list", vals)
    reg.observe_bulk("list", ())
    reg.observe_bulk("array", np.array(vals), bounds=(1, 2, 4))
    for x in vals:
        reg.observe("single", x, bounds=(1, 2, 4))
    hists = reg.snapshot()["hists"]
    assert hists["list"] == hists["array"] == hists["single"]
    assert hists["list"]["count"] == 12 and hists["list"]["sum"] == 36.5
    assert [n for _b, n in hists["list"]["buckets"]] == [3, 1, 6, 2]
    assert (hists["list"]["min"], hists["list"]["max"]) == (0.0, 9.0)
    looks = []
    reg.add_collector(lambda: looks.append(reg.count("looked")))
    reg.snapshot()
    assert "gp_looked_total" in reg.render() and len(looks) == 2


# ---- three served nodes: the legs beyond the ticks ---------------------------
@pytest.fixture(scope="module")
def served():
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [PaxosServer(i, nc, StatefulAdderApp(), CFG,
                           tick_interval=0.01, fd_timeout_s=5.0)
               for i in range(3)]
    built = [s.manager.metrics.snapshot() for s in servers]
    for s in servers:
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert client.create_paxos_instance("svc", [0, 1, 2], timeout=30)
        first = [s.manager.metrics.snapshot() for s in servers]
        total = 0
        for i in range(12):
            total += i + 1
            assert client.send_request_sync(
                "svc", str(i + 1), timeout=30, server=i % 3) == str(total)
        time.sleep(0.5)     # some turns of the lag probe
        yield servers, built, first
    finally:
        client.close()
        for s in servers:
            s.stop()


@pytest.mark.parametrize("key", ["transport_reply_lag_s",
                                 "transport_loop_lag_s",
                                 "commit_leg_flush_s"])
def test_a_served_node_times_what_lies_beyond_its_ticks(served, key):
    """(f): registered empty where the node is built, observed once it
    serves: a frame's wait for the flush, a reply's for the loop
    thread, and the loop's own lateness."""
    servers, built, _first = served
    for s, snap in zip(servers, built):
        assert snap["hists"][key]["count"] == 0
        h = hist(s.manager, key)
        assert h["count"] > 0 and 0.0 <= h["min"] <= h["max"] < 30.0, h
    if key == "commit_leg_flush_s":
        check_identity([s.manager for s in servers], 12)
        assert sum(counter(s.manager, "commit_requests_forwarded")
                   for s in servers) == 8


def test_the_collector_reads_the_threads_clocks_when_somebody_looks(served):
    """(g): four counters, at 0 where the node is built, that grow from
    look to look; a thread cannot have had more CPU than there was
    time, nor the process less than its threads."""
    servers, built, first = served
    if not hasattr(time, "pthread_getcpuclockid"):
        pytest.skip("no per-thread CPU clocks on this platform")
    for s, snap0, snap1 in zip(servers, built, first):
        assert all(snap0["counters"][k] == 0 for k in THREAD_CLOCKS)
        snap2 = s.manager.metrics.snapshot()
        for k in THREAD_CLOCKS:
            assert 0 < snap1["counters"][k] <= snap2["counters"][k], k
        c = snap2["counters"]
        assert c["thread_wall_s"] > snap1["counters"]["thread_wall_s"]
        assert c["thread_cpu_tick_s"] <= c["thread_wall_s"]
        assert c["thread_cpu_transport_s"] <= c["thread_wall_s"]
        assert c["thread_cpu_tick_s"] + c["thread_cpu_transport_s"] \
            <= c["process_cpu_s"] + 0.05


def test_stop_cancels_the_lag_probe_and_the_clocks_stand_still():
    ports = free_ports(1)
    nc = NodeConfig({0: ("127.0.0.1", ports[0])})
    s = PaxosServer(0, nc, StatefulAdderApp(),
                    EngineConfig(n_groups=4, window=8, req_lanes=4,
                                 n_replicas=1), tick_interval=0.01)
    s.start()
    time.sleep(0.35)
    probe = s.transport._lag_probe
    assert probe is not None and not probe.cancelled()
    s.stop()
    assert s.transport._lag_probe.cancelled()
    seen = hist(s.manager, "transport_loop_lag_s")["count"]
    clocks = [counter(s.manager, k) for k in THREAD_CLOCKS]
    assert seen >= 2
    time.sleep(0.25)
    assert hist(s.manager, "transport_loop_lag_s")["count"] == seen
    assert [counter(s.manager, k) for k in THREAD_CLOCKS] == clocks
