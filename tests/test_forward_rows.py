"""The wire unit of a forward is a destination, not a row (PR 44): what a
tick's ring build stages for names led elsewhere leaves
``drain_forward_out`` as ONE ``forward_rows`` frame a coordinator, and the
coordinator takes the frame in under one hold of its lock, entry by entry.

Three (five) managers on the stepped harness (``testing/cluster.py``),
checked against the sequential adder; then three served nodes on loopback
sockets for what only a node has: the frame cap it hands the drain, the
two counters, and a coordinator that has gone dark."""

import time

import numpy as np
import pytest

from gigapaxos_tpu.clients import PaxosClientAsync
from gigapaxos_tpu.manager import _forward_entry_bytes
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.net.codec import decode_json, encode_json
from gigapaxos_tpu.net.node_config import NodeConfig
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.server import PaxosServer
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster
from gigapaxos_tpu.testing.ports import free_ports
from gigapaxos_tpu.utils.config import Config

N_NAMES = 12
NAMES = [f"n{i:02d}" for i in range(N_NAMES)]


def counter(m, key):
    return m.metrics.snapshot()["counters"].get(key, 0)


class Harness:
    """A stepped cluster with ``NAMES`` created on every replica, the
    answers each entry replica's callbacks got, and the sequential
    model of what was submitted."""

    def __init__(self, n_replicas=3):
        self.c = ManagerCluster(
            EngineConfig(n_groups=32, window=8, req_lanes=4,
                         n_replicas=n_replicas), StatefulAdderApp)
        for n in NAMES:
            self.c.create(n)
        self.R = n_replicas
        self.managers = self.c.managers
        self.got = []           # (request id, response), as answered
        self.model = {}         # name -> total of what was submitted
        self.expect = {}        # request id -> the answer it is due
        self._rid = 1 << 40

    def coord(self, name):
        m = self.managers[0]
        return m.coordinator_of_row(m.names[name])

    def led_by(self, coord):
        return [n for n in NAMES if self.coord(n) == coord]

    def submit(self, name, delta, entry):
        self._rid += 1
        self.model[name] = self.model.get(name, 0) + delta
        self.expect[self._rid] = str(self.model[name])
        self.managers[entry].propose(
            name, str(delta), request_id=self._rid,
            callback=lambda rid, resp: self.got.append((rid, resp)))
        return self._rid

    def frames_in_flight(self):
        """(receiver, kind, body) of what the last round put on the host
        channel, less the payload gossip."""
        return [(j, kind, body) for j, inbox in enumerate(self.c.inboxes)
                for kind, body in inbox if kind != "payloads"]

    def check(self):
        """Every request committed once on every replica and answered
        once, at its entry, with its name's running total."""
        for m in self.managers:
            assert {n: m.app.totals.get(n, 0) for n in self.model} \
                == self.model, m.my_id
        assert sorted(self.got) == sorted(self.expect.items())

    def close(self):
        self.c.close()


@pytest.fixture
def h(request):
    h = Harness(getattr(request, "param", 3))
    yield h
    h.close()


# ---- (a) one drain, at most R-1 frames ------------------------------------
@pytest.mark.parametrize("h", [3, 5], indirect=True)
def test_a_ticks_forwards_are_one_frame_a_coordinator(h):
    entry = 0
    elsewhere = [n for n in NAMES if h.coord(n) != entry]
    assert len({h.coord(n) for n in elsewhere}) == h.R - 1
    for k, n in enumerate(NAMES):
        h.submit(n, 1 + k, entry)
        h.submit(n, 100, entry)  # a name's requests share its entry
    h.c.step_all()
    frames = h.frames_in_flight()
    assert {kind for _j, kind, _b in frames} == {"forward_rows"}
    assert len(frames) == h.R - 1  # not one a name
    for j, _kind, body in frames:
        # each coordinator gets the names it leads, as the ring build
        # staged them: name, epoch, the requests in their order
        assert [r["name"] for r in body["rows"]] == h.led_by(j)
        for r in body["rows"]:
            assert sorted(r) == ["epoch", "name", "reqs"]
            assert [q[2] for q in r["reqs"]] \
                == [str(1 + NAMES.index(r["name"])), "100"]
    assert sum(len(b["rows"]) for _j, _k, b in frames) == len(elsewhere)
    h.c.run(12)
    h.check()
    # nothing was forwarded a second time
    assert all(counter(m, "requests_reforwarded") == 0 for m in h.managers)


def test_one_row_gives_a_one_row_frame_through_the_same_code(h):
    name = next(n for n in NAMES if h.coord(n) != 0)
    h.submit(name, 7, 0)
    h.c.step_all()
    (j, kind, body), = h.frames_in_flight()
    assert (j, kind, len(body["rows"])) == (h.coord(name), "forward_rows", 1)
    h.c.run(10)
    h.check()


# ---- (b) FIFO around a stop inside one entry --------------------------------
@pytest.mark.parametrize("frames", ["one_of_many_rows", "one_a_row"])
def test_fifo_around_a_stop_inside_one_entry_of_a_frame(h, frames):
    """The case of test_batching.py's
    ``test_forward_batch_preserves_fifo_around_stop`` as one entry among
    others: what was queued BEFORE the stop commits before it — in one
    frame of many rows, and in frames of one row each."""
    coord = h.coord(NAMES[0])
    mine = h.led_by(coord)
    assert len(mine) >= 3
    stopped, others = mine[1], [mine[0], mine[2]]
    entry = (coord + 1) % 3
    h.submit(others[0], 3, entry)
    pre = [h.submit(stopped, 10 + i, entry) for i in range(5)]
    h.managers[entry].propose(stopped, "", stop=True)
    h.submit(others[1], 4, entry)
    if frames == "one_of_many_rows":
        h.c.step_all()
        (_j, _k, body), = [f for f in h.frames_in_flight()
                           if f[0] == coord]
    else:
        # the entries as the ring build stages them, handed over one a
        # frame
        m = h.managers[entry]
        with m._state_lock:
            m.build_request_ring()
            staged, m.forward_out = m.forward_out, []
        assert {k for _d, k, _b in staged} == {"forward_batch"}
        body = {"rows": [b for d, _k, b in staged if d == coord]}
        for row in body["rows"]:
            h.managers[coord].on_host_message(
                "forward_rows", {"rows": [row]})
    assert [r["name"] for r in body["rows"]] == mine[:3]
    assert [q[3] for q in body["rows"][1]["reqs"]] == [False] * 5 + [True]
    h.c.run(20)
    row = h.managers[0].names[stopped]
    for m in h.managers:
        assert int(np.asarray(m.state.stopped)[row]) == 1
    h.check()  # all five pre-stop writes, and the neighbours' writes
    assert [rid for rid, _ in h.got if rid in pre] == pre


# ---- (c) the epoch guard is per entry ---------------------------------------
def test_an_entry_the_guard_turns_away_does_not_touch_its_neighbours(h):
    coord = h.coord(NAMES[0])
    a = NAMES[0]
    m = h.managers[coord]
    entry = (coord + 1) % 3
    # two names that are in epoch 1 here, led by the same node
    behind, only_stop = "e1-behind", "e1-stop"
    free = [r for r in range(h.c.cfg.n_groups)
            if r % 3 == coord and r not in m.row_name]
    for n, row in zip((behind, only_stop), free):
        for x in h.managers:
            assert x.create_paxos_instance(n, [0, 1, 2], version=1, row=row)
    h.c.republish()
    assert h.coord(behind) == h.coord(only_stop) == coord

    def req(rid, value, stop=False):
        return [rid, entry, value, stop]

    frame = {"rows": [
        {"name": a, "epoch": 0, "reqs": [req(901, "5")]},
        # from a sender an epoch behind: its writes cross, its stop does not
        {"name": behind, "epoch": 0,
         "reqs": [req(902, "6"), req(903, "", True), req(904, "7")]},
        # an epoch behind with a stop alone: turned away whole
        {"name": only_stop, "epoch": 0, "reqs": [req(905, "", True)]},
        # a name unknown here and not asleep: turned away
        {"name": "nobody", "epoch": 0, "reqs": [req(906, "9")]},
        {"name": a, "epoch": 0, "reqs": [req(907, "1")]},
    ]}
    m.on_host_message("forward_rows", frame)
    assert counter(m, "requests_carried_over") == 2
    h.c.run(12)
    for x in h.managers:
        assert x.app.totals.get(a) == 6
        assert x.app.totals.get(behind) == 13
        assert x.app.totals.get(only_stop, 0) == 0
        assert "nobody" not in x.app.totals
        for n in (behind, only_stop):  # no stale stop stopped anything
            assert int(np.asarray(x.state.stopped)[x.names[n]]) == 0


# ---- (d) the frame cap -------------------------------------------------------
VALUES = {
    "ascii": "v" * 300,
    "quotes": '{"k":"' + 'a"\\' * 90 + '"}',
    "beyond_ascii": "é中" * 150,
}


@pytest.mark.parametrize("what", sorted(VALUES))
def test_a_drain_over_the_cap_leaves_as_more_frames_none_chunked(h, what):
    cap = 4096
    value = VALUES[what]
    m = h.managers[0]
    staged = [
        (1, "forward_batch",
         {"name": NAMES[i], "epoch": 0,
          "reqs": [[(1 << 62) + i, 0, value, False]] * 2,
          "tc": {str((1 << 62) + i): [1 << 62, 0, 1]}})
        for i in range(N_NAMES)
    ]
    with m._state_lock:
        m.forward_out.extend(staged)
    out = m.drain_forward_out(cap)
    assert len(out) >= 2 and {k for _d, k, _b in out} == {"forward_rows"}
    rows = []
    for dst, kind, body in out:
        frame = encode_json(kind, 0, body)
        # under the cap: server.py:send_frame_to_address sends it whole
        assert len(frame) <= cap, (len(frame), len(body["rows"]))
        assert decode_json(frame)[2] == body
        rows += body["rows"]
    assert all(x is y for x, (_d, _k, y) in zip(rows, staged))
    assert len(rows) == len(staged)
    # the estimate is one from above, and near for plain text
    for _d, _k, body in staged:
        exact = len(encode_json("x", 0, body)) - len(encode_json("x", 0, {}))
        assert exact <= _forward_entry_bytes(body)
        if what == "ascii":
            assert _forward_entry_bytes(body) < 1.5 * exact
    # no cap (the stepped harness): one frame; an entry over the cap by
    # itself still goes, alone
    with m._state_lock:
        m.forward_out.extend(staged)
    (dst, kind, body), = m.drain_forward_out()
    assert (dst, kind, len(body["rows"])) == (1, "forward_rows", N_NAMES)
    with m._state_lock:
        m.forward_out.extend(staged[:3])
    assert [len(b["rows"]) for _d, _k, b in m.drain_forward_out(100)] \
        == [1, 1, 1]


# ---- (e) the other kinds ----------------------------------------------------
def test_other_kinds_keep_their_order_and_their_frames(h):
    m = h.managers[0]
    fb = [{"name": NAMES[i], "epoch": 0, "reqs": [[i, 0, "1", False]]}
          for i in range(4)]
    other = [{"marker": i} for i in range(4)]
    staged = [
        (1, "forward_batch", fb[0]),
        (-1, "need_payloads", other[0]),
        (2, "forward_batch", fb[1]),
        (1, "state_request", other[1]),
        (1, "forward_batch", fb[2]),
        (2, "payloads", other[2]),
        (2, "forward_batch", fb[3]),
        (0, "state_reply", other[3]),
    ]
    with m._state_lock:
        m.forward_out.extend(staged)
    out = m.drain_forward_out()
    assert [(d, k) for d, k, _b in out] == [
        (1, "forward_rows"), (-1, "need_payloads"), (2, "forward_rows"),
        (1, "state_request"), (2, "payloads"), (0, "state_reply"),
    ]
    assert out[0][2]["rows"] == [fb[0], fb[2]]
    assert out[2][2]["rows"] == [fb[1], fb[3]]
    assert all(x is y for x, y in zip(
        [out[1][2], out[3][2], out[4][2], out[5][2]], other))
    assert m.drain_forward_out() == [] and m.forward_out == []


# ---- (f) a frame that never arrives -----------------------------------------
def test_a_dropped_frame_is_forwarded_again_after_the_election(h):
    """The coordinator falls silent before the frame reaches it (on a
    node: it is dark and drops what it reads); the rows it led elect the
    next in line and the entry replica proposes what it had forwarded
    again, under the same ids (``_reforward_locked``)."""
    old = h.coord(NAMES[0])
    new, entry = (old + 1) % 3, (old + 2) % 3
    lost = h.led_by(old)[:3]
    for k, n in enumerate(lost):
        h.submit(n, 2 + k, entry)
    cut = np.full((3, 3), DELIVER)
    for r in range(3):
        if r != old:
            cut[r, old] = cut[old, r] = DROP
    want = np.zeros(h.c.cfg.n_groups, bool)
    want[[h.managers[0].names[n] for n in lost]] = True
    h.c.step_all(delivery=cut, want_coord={new: want})
    assert h.frames_in_flight() == []  # the one frame was dropped
    for _ in range(6):
        h.c.step_all(delivery=cut)
    h.c.run(24)
    assert all(h.coord(n) == new for n in lost)
    assert counter(h.managers[entry], "requests_reforwarded") == len(lost)
    h.check()


# ---- three served nodes -----------------------------------------------------
CFG3 = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)


@pytest.fixture(scope="module")
def served():
    Config.clear()
    Config.set("ALLOW_CRASH_EMULATION", True)
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [
        PaxosServer(i, nc, StatefulAdderApp(), CFG3, tick_interval=0.01,
                    fd_timeout_s=1.0)
        for i in range(3)
    ]
    kinds = [[] for _ in servers]  # JSON kinds each node took in
    for s, seen in zip(servers, kinds):
        def on_json(k, sender, body, reply, _seen=seen,
                    _inner=s._on_json):
            _seen.append(k)
            return _inner(k, sender, body, reply)
        s._on_json = on_json
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        for n in NAMES:
            assert client.create_paxos_instance(n, [0, 1, 2], timeout=30)
        yield servers, client, kinds
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


def burst(client, names, entry, value, rounds=1):
    """``rounds`` requests a name, all entered at ``entry``, sent without
    waiting; returns the answers by name, in order."""
    answers = {n: [] for n in names}
    by_rid = {}

    def on_reply(rid, response):
        answers[by_rid[rid]].append(response)

    for _ in range(rounds):
        for n in names:
            rid = client.mint_id()
            by_rid[rid] = n
            client.send_request(n, value, on_reply, server=entry,
                                request_id=rid)
    return answers


def test_served_nodes_count_frames_and_rows_and_chunk_nothing(served):
    servers, client, kinds = served
    entry = servers[0]
    m = entry.manager
    elsewhere = [n for n in NAMES if m.coordinator_of_row(m.names[n]) != 0]
    assert counter(m, "forward_frames_sent") == 0  # registered at 0
    assert counter(m, "forward_rows_sent") == 0
    # a frame cap that two rows of 400-byte values pass
    entry.max_frame_bytes = 1500
    answers = burst(client, elsewhere, 0, "0" * 399 + "1", rounds=3)
    assert wait_until(
        lambda: all(len(a) == 3 for a in answers.values())), answers
    assert all(a == ["1", "2", "3"] for a in answers.values())
    frames = counter(m, "forward_frames_sent")
    rows = counter(m, "forward_rows_sent")
    assert rows >= frames >= 2
    assert rows <= 3 * len(elsewhere)  # a row a name a tick at most
    seen = kinds[1] + kinds[2]
    assert seen.count("forward_rows") == frames
    assert "forward_batch" not in seen and "chunk" not in seen
    entry.max_frame_bytes = Config.get_int("MAX_LOG_MESSAGE_SIZE")


def test_a_dark_coordinator_drops_the_frame_and_the_requests_recover(served):
    servers, client, kinds = served
    m = servers[0].manager
    dead = 1
    led = [n for n in NAMES if m.coordinator_of_row(m.names[n]) == dead]
    assert led
    base = {n: servers[0].manager.app.totals.get(n, 0) for n in led}
    assert client.admin_sync(dead, {"op": "crash", "for_s": 2.5})["ok"]
    dropped = counter(servers[dead].manager, "frames_dropped_while_crashed")
    n_rows = kinds[dead].count("forward_rows")
    answers = burst(client, led, 0, "5")
    # the dark node reads the frame and drops it, counted, unanswered
    assert wait_until(lambda: counter(
        servers[dead].manager, "frames_dropped_while_crashed") > dropped)
    time.sleep(0.3)
    assert kinds[dead].count("forward_rows") == n_rows
    assert not any(answers.values())
    # its peers suspect it after the timeout and elect; the entry
    # replica forwards again to who leads now (the client's own
    # retransmission would be the other way back, as before)
    assert wait_until(lambda: all(len(a) == 1 for a in answers.values()))
    assert counter(m, "requests_reforwarded") >= 1
    assert {n: int(a[0]) for n, a in answers.items()} \
        == {n: base[n] + 5 for n in led}
    assert wait_until(lambda: all(
        s.manager.app.totals.get(n) == base[n] + 5
        for s in servers for n in led), timeout=30)
