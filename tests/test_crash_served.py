"""One active goes dark under load and comes back (the emulated crash,
``server.py`` admin op ``crash``): three served nodes over sockets, a
closed loop of one writer a name whose client gives up on an entry replica
and moves to the next, checked against the sequential adder model."""

import threading
import time

import pytest

from gigapaxos_tpu.clients import PaxosClientAsync
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.net.codec import encode_json
from gigapaxos_tpu.net.node_config import NodeConfig
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.server import PaxosServer
from gigapaxos_tpu.testing.ports import free_ports
from gigapaxos_tpu.utils.config import Config

CFG = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
NAMES = [f"n{i:02d}" for i in range(12)]
DEAD = 1
FAILOVER_S = 1.5


@pytest.fixture
def cluster(request):
    """Three served nodes, failure detection at 1 s; ``allow`` (the
    fixture's parameter, default true) sets ALLOW_CRASH_EMULATION."""
    Config.clear()
    Config.set("ALLOW_CRASH_EMULATION", getattr(request, "param", True))
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [
        PaxosServer(i, nc, StatefulAdderApp(), CFG, tick_interval=0.01,
                    fd_timeout_s=1.0)
        for i in range(3)
    ]
    for s in servers:
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        yield servers, client
    finally:
        client.close()
        for s in servers:
            s.stop()
        Config.clear()


def wait_until(cond, timeout=30.0, interval=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def counters(server):
    return server.manager.metrics.snapshot()["counters"]


class Writers:
    """One closed-loop writer a name: its next request goes out when its
    last was answered; a request unanswered ``FAILOVER_S`` after its last
    send goes out again, same id, to the next server, and the writer
    stays there."""

    def __init__(self, client, names):
        self.client, self.names = client, names
        self.lock = threading.Lock()
        self.entry = {n: i % 3 for i, n in enumerate(names)}
        self.acked = {n: [] for n in names}    # (delta, response)
        self.pending = {}                      # id -> [name, delta, t_sent]
        self.moves = 0
        self.issuing = True
        self.k = 0

    def _issue_locked(self, name):
        self.k += 1
        rid = self.client.mint_id()
        self.pending[rid] = [name, 1 + self.k % 7, time.time()]
        return rid

    def _send(self, rid):
        name, delta, _t = self.pending[rid]
        self.client.send_request(name, str(delta), self._on_reply,
                                 server=self.entry[name], request_id=rid)

    def _on_reply(self, rid, response):
        with self.lock:
            ent = self.pending.pop(rid, None)
            if ent is None:
                return  # answered twice: the resend's reply
            self.acked[ent[0]].append((ent[1], response))
            nxt = self._issue_locked(ent[0]) if self.issuing else None
        if nxt is not None:
            self._send(nxt)

    def start(self):
        with self.lock:
            first = [self._issue_locked(n) for n in self.names]
        for rid in first:
            self._send(rid)

    def poll(self):
        now = time.time()
        again = []
        with self.lock:
            for rid, ent in self.pending.items():
                if now - ent[2] >= FAILOVER_S:
                    ent[2] = now
                    self.entry[ent[0]] = (self.entry[ent[0]] + 1) % 3
                    self.moves += 1
                    again.append(rid)
        for rid in again:
            self._send(rid)

    def run(self, seconds, also=lambda: None):
        deadline = time.time() + seconds
        while time.time() < deadline:
            time.sleep(0.05)
            self.poll()
            also()

    def drain(self, timeout=30.0):
        with self.lock:
            self.issuing = False
        deadline = time.time() + timeout
        while self.pending and time.time() < deadline:
            time.sleep(0.05)
            self.poll()
        return not self.pending

    def model(self):
        """Per name the running sum of the acknowledged deltas; every
        acknowledgement's value has to be its name's."""
        totals = {}
        for name, acks in self.acked.items():
            total = 0
            for delta, response in acks:
                total += delta
                assert response == str(total), (name, response, total)
            totals[name] = total
        return totals


def create_all(client, names):
    for n in names:
        assert client.create_paxos_instance(n, [0, 1, 2], timeout=30)


@pytest.mark.timeout(240)
def test_closed_loop_through_a_crash_and_the_return(cluster):
    servers, client = cluster
    create_all(client, NAMES)
    m0 = servers[0].manager
    led_by_dead = [n for n in NAMES
                   if m0.coordinator_of_row(m0.names[n]) == DEAD]
    assert led_by_dead, "the round-robin gives every node some rows"
    w = Writers(client, NAMES)
    w.start()
    w.run(1.0)
    before = sum(len(a) for a in w.acked.values())
    assert before > 0

    # the probe does nothing; the crash is answered, then nothing goes in
    # or out and nothing ticks until it is over
    assert client.admin_sync(DEAD, {"op": "crash", "for_s": 0})["ok"]
    assert counters(servers[DEAD])["crash_emulations"] == 0
    assert client.admin_sync(DEAD, {"op": "crash", "for_s": 2.5})["ok"]
    dead = servers[DEAD]

    def other_role_talks():
        """A node of ANOTHER id space with the dead node's number (a
        reconfigurator 1 beside active 1) keeps talking to the living:
        that is not hearing active 1."""
        for i in (0, 2):
            client.send_frame(tuple(client.servers[i]), encode_json(
                "echo", DEAD, {"ts": time.time(), "round": 0}))

    w.run(0.5, other_role_talks)
    quiet = (dead._tick, dead.transport.n_sent,
             counters(dead).get("blob_frames_received"),
             counters(dead).get("responses_flushed"))
    dropped = counters(dead)["frames_dropped_while_crashed"]
    w.run(1.5, other_role_talks)
    assert quiet == (dead._tick, dead.transport.n_sent,
                     counters(dead).get("blob_frames_received"),
                     counters(dead).get("responses_flushed"))
    assert counters(dead)["frames_dropped_while_crashed"] > dropped > 0
    assert counters(dead)["crash_emulations"] == 1
    # an admin op is dropped unanswered too
    assert client.admin_sync(DEAD, {"op": "stats"}, timeout=0.3) is None

    w.run(3.0)  # the return, and traffic through the returned node
    assert w.drain(), f"{len(w.pending)} unanswered"
    totals = w.model()
    assert sum(len(a) for a in w.acked.values()) > before
    assert w.moves > 0  # a third of the names entered at the dead node
    # all three actives hold every name's total, the returned one too
    assert wait_until(lambda: all(
        s.manager.app.totals.get(n, 0) == totals[n]
        for s in servers for n in NAMES)), (
        totals, [dict(s.manager.app.totals) for s in servers])
    # a name the dead node led is led by another, on every node
    for s in servers:
        for n in led_by_dead:
            assert s.manager.coordinator_of_row(s.manager.names[n]) != DEAD
    # the failover's account: the others suspected it after the timeout
    # and elected; the returned node caught up
    # (node 2 is next in line after node 1; node 0 would run only for a
    # coordinator long dead, three timeouts)
    suspect = servers[2].manager.metrics.snapshot()["hists"][
        "phase_fd_suspect_s"]
    # (on a loaded box a living peer can fall silent for the 1 s of this
    # test's timeout too: every silence is observed once)
    assert suspect["count"] >= 1 and 1.0 <= suspect["min"] < 2.5, suspect
    survivors = [counters(s) for s in (servers[0], servers[2])]
    assert sum(c["election_waves"] for c in survivors) >= 1
    assert any(s.manager.metrics.snapshot()["hists"].get(
        "phase_election_s", {}).get("count") for s in servers)
    assert any(s.manager.metrics.snapshot()["hists"].get(
        "coord_gap_s", {}).get("count") for s in servers)
    assert wait_until(lambda: dead.manager.metrics.snapshot()["hists"].get(
        "phase_catchup_s", {}).get("count") == 1)
    assert counters(dead)["rows_caught_up"] > 0


@pytest.mark.timeout(120)
@pytest.mark.parametrize("cluster", [False], indirect=True)
def test_crash_is_refused_where_the_configuration_does_not_allow_it(cluster):
    servers, client = cluster
    for for_s in (0, 5.0):
        answer = client.admin_sync(DEAD, {"op": "crash", "for_s": for_s})
        assert answer["ok"] is False and answer["error"]
    tick = servers[DEAD]._tick
    assert wait_until(lambda: servers[DEAD]._tick > tick, timeout=5.0)
    assert counters(servers[DEAD])["crash_emulations"] == 0
    # and the node serves on
    create_all(client, ["svc"])
    assert client.send_request_sync("svc", "3", timeout=30) == "3"


@pytest.mark.timeout(240)
def test_returned_node_more_than_a_window_behind_pulls_state(cluster):
    """More decisions on one name while the node was away than the ring
    holds (window 8): the decisions it needs have left every peer's ring,
    so it adopts a donor's app state and frontier."""
    servers, client = cluster
    create_all(client, ["hot", "cold"])
    assert client.send_request_sync("hot", "1", timeout=30) == "1"
    assert client.send_request_sync("cold", "5", timeout=30) == "5"
    assert wait_until(lambda: all(
        s.manager.app.totals.get("hot") == 1 for s in servers))
    assert client.admin_sync(DEAD, {"op": "crash", "for_s": 4.0})["ok"]
    total, entry = 1, 0
    deadline = time.time() + 3.0
    n = 0
    while time.time() < deadline or n < 3 * CFG.window:
        resp = client.send_request_sync(
            "hot", "2", timeout=30, server=entry, retransmit_every=1.5)
        total += 2
        n += 1
        assert resp == str(total), (resp, total)
    assert n > 2 * CFG.window
    dead = servers[DEAD]
    assert dead.manager.app.totals.get("hot") == 1  # it was away
    assert wait_until(lambda: all(
        s.manager.app.totals.get("hot") == total for s in servers), 60.0), [
        s.manager.app.totals.get("hot") for s in servers]
    assert counters(dead)["rows_caught_up_by_state_pull"] >= 1
    # and it serves on as a replica like the others
    assert client.send_request_sync(
        "hot", "1", timeout=30, server=DEAD) == str(total + 1)
    assert wait_until(lambda: all(
        s.manager.app.totals.get("hot") == total + 1 for s in servers))
    assert all(s.manager.app.totals.get("cold") == 5 for s in servers)


def test_an_election_wave_counts_the_values_it_carried_over():
    """Stepped: the coordinator's proposal is accepted by ONE other
    replica and learnt by nobody; the next in line is elected without
    hearing the old coordinator, finds the value in the promises and
    proposes it again — one wave, one carried value, decided once."""
    import numpy as np

    from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster

    cfg = EngineConfig(n_groups=6, window=8, req_lanes=4, n_replicas=3)
    c = ManagerCluster(cfg, StatefulAdderApp)
    c.create("acct")
    row = c.managers[0].names["acct"]
    old = c.managers[0].coordinator_of_row(row)
    new, third = (old + 1) % 3, (old + 2) % 3
    c.managers[old].propose("acct", "7", request_id=77)
    c.step_all()  # admitted: the proposal is in the old coordinator's blob
    cut = np.full((3, 3), DELIVER)
    cut[old, :] = DROP
    cut[old, old] = DELIVER
    cut[new, old] = DROP
    c.step_all(delivery=cut)  # ``third`` accepts; it never sees a second accept
    for r in range(3):
        if r != old:
            cut[r, old] = cut[old, r] = DROP
    want = np.zeros(cfg.n_groups, bool)
    want[row] = True
    c.managers[new].note_election(want)
    c.step_all(delivery=cut, want_coord={new: want})
    c.run(6, delivery=cut)
    snap = c.managers[new].metrics.snapshot()
    assert snap["counters"]["election_waves"] == 1
    assert snap["counters"]["pvalues_carried_over"] == 1
    assert snap["hists"]["phase_election_s"]["count"] == 1
    assert [c.managers[r].app.totals.get("acct") for r in (new, third)] \
        == [7, 7]
    c.run(8)  # healed: the old coordinator learns it, nobody repeats it
    assert [m.app.totals.get("acct") for m in c.managers] == [7, 7, 7]
    c.close()
