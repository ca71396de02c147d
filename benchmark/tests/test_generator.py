"""The closed-loop generator against a fake client."""

import collections

import pytest

from generators.closed import ClosedLoop

BASE = {"loop": "closed", "entry": "round_robin_by_name",
        "retransmit_s": 8.0, "fail_after_s": 30.0}


class FakeClient:
    """Answers like the adder service, when told to: ``deliver`` answers
    the oldest unanswered sends."""

    def __init__(self):
        self.next_id = 100
        self.sends = collections.deque()   # (addr, name, value, cb, rid)
        self.log = []
        self.totals = collections.defaultdict(int)
        self.executed = set()
        self.max_in_flight = 0
        self.in_flight = set()
        self.in_flight_by_name = collections.Counter()
        self.max_per_name = 0

    def mint_id(self):
        self.next_id += 1
        return self.next_id

    def send_prepared(self, addr, name, value, cb, request_id=None):
        assert request_id is not None
        self.sends.append((addr, name, value, cb, request_id))
        self.log.append((addr, name, value, request_id))
        if request_id not in self.in_flight:
            self.in_flight.add(request_id)
            self.in_flight_by_name[name] += 1
        self.max_in_flight = max(self.max_in_flight, len(self.in_flight))
        self.max_per_name = max(self.max_per_name,
                                self.in_flight_by_name[name])
        return request_id

    def deliver(self, n=1, drop=False):
        for _ in range(n):
            addr, name, value, cb, rid = self.sends.popleft()
            if drop:
                continue
            if rid not in self.executed:  # an id executes once
                self.executed.add(rid)
                self.totals[name] += int(value)
            if rid in self.in_flight:
                self.in_flight.discard(rid)
                self.in_flight_by_name[name] -= 1
            cb(rid, str(self.totals[name]), None)


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


NAMES = [f"n{i}" for i in range(6)]
TARGETS = [("h", 1), ("h", 2), ("h", 3)]


def make(traffic, seed=3, names=NAMES):
    client, clock = FakeClient(), Clock()
    loop = ClosedLoop(client, names, TARGETS, {**BASE, **traffic}, seed,
                      clock=clock)
    return client, clock, loop


@pytest.mark.parametrize("traffic", [
    {"in_flight": 6, "key_dist": "slot", "per_name_order": True},
    {"in_flight": 4, "key_dist": "uniform", "per_name_order": True},
    {"in_flight": 9, "key_dist": "uniform", "per_name_order": False},
])
def test_in_flight_never_exceeds_the_files_number(traffic):
    client, clock, loop = make(traffic)
    loop.start()
    for step in range(200):
        clock.t += 0.01
        client.deliver(1 + step % 3)
    assert client.max_in_flight == traffic["in_flight"]
    assert loop.outstanding() == traffic["in_flight"]
    if traffic["per_name_order"]:
        assert client.max_per_name == 1
    else:
        assert client.max_per_name > 1
    loop.stop()
    client.deliver(len(client.sends))
    assert loop.outstanding() == 0 and not client.sends
    assert all(r.t_ack is not None for r in loop.reqs)


def test_per_name_order_gives_running_sums_and_fixed_entry_replicas():
    client, clock, loop = make(
        {"in_flight": 6, "key_dist": "slot", "per_name_order": True})
    loop.start()
    for _ in range(60):
        client.deliver(2)
    loop.stop()
    client.deliver(len(client.sends))
    running = collections.defaultdict(int)
    for r in loop.reqs:
        running[r.name] += r.delta
        assert r.response == str(running[r.name])
    for addr, name, value, _ in client.log:
        assert addr == TARGETS[NAMES.index(name) % 3]
        assert len(value) == 10 and int(value) >= 1


def test_same_seed_same_traffic_and_large_seeds():
    logs = []
    for seed in (2**31 + 7, 2**31 + 7, 5):
        client, clock, loop = make(
            {"in_flight": 4, "key_dist": "uniform", "per_name_order": True},
            seed=seed)
        loop.start()
        for _ in range(50):
            client.deliver(1)
        logs.append([(n, v) for _, n, v, _ in client.log])
    assert logs[0] == logs[1] != logs[2]


def test_one_name_takes_every_client():
    client, clock, loop = make(
        {"in_flight": 50, "key_dist": "slot", "per_name_order": False},
        names=["only"])
    loop.start()
    assert client.max_in_flight == 50 and client.max_per_name == 50


def test_budget_stops_each_client():
    client, clock, loop = make(
        {"in_flight": 6, "key_dist": "slot", "per_name_order": True,
         "budget": 1})
    loop.start()
    client.deliver(6)
    assert loop.outstanding() == 0 and len(loop.reqs) == 6
    assert not client.sends


def test_retransmits_under_the_same_id_then_fails():
    client, clock, loop = make(
        {"in_flight": 2, "key_dist": "slot", "per_name_order": True})
    loop.start()
    client.deliver(2, drop=True)
    clock.t = 7.9
    loop.poll()
    assert not client.sends
    clock.t = 8.1
    loop.poll()
    assert [rid for *_, rid in client.sends] == [r.rid for r in loop.reqs]
    assert [r.sends for r in loop.reqs] == [2, 2]
    client.deliver(1)                      # the first is answered now
    assert loop.reqs[0].t_ack == 8.1 and loop.reqs[0].t_first == 0.0
    client.deliver(1, drop=True)
    clock.t = 30.5
    loop.poll()
    second = loop.reqs[1]
    assert second.failed and second.t_ack is None
    assert loop.outstanding() == 1         # the first client's next request
    # its client has stopped: nothing more goes to that name
    assert sum(1 for r in loop.reqs if r.name == second.name) == 1


def test_rejects_what_it_cannot_generate():
    with pytest.raises(ValueError):
        make({"in_flight": 7, "key_dist": "slot", "per_name_order": True})
    with pytest.raises(ValueError):
        make({"in_flight": 2, "key_dist": "zipf", "per_name_order": True})


def test_ramp_starts_the_clients_one_after_the_other():
    client, clock, loop = make(
        {"in_flight": 8, "key_dist": "uniform", "per_name_order": False,
         "ramp_s": 4.0})
    loop.start()
    assert len(client.sends) == 1 and loop.outstanding() == 8
    clock.t = 2.0
    loop.poll()
    assert len(client.sends) == 5
    client.deliver(5)                  # each answered client goes on
    assert len(client.sends) == 5
    clock.t = 4.0
    loop.poll()
    assert len(client.sends) == 8 and client.max_in_flight == 8
    loop.stop()
    client.deliver(8)
    assert loop.outstanding() == 0


def test_stop_during_the_ramp_starts_no_more_clients():
    client, clock, loop = make(
        {"in_flight": 8, "key_dist": "slot", "per_name_order": False,
         "ramp_s": 4.0})
    loop.start()
    loop.stop()
    clock.t = 5.0
    loop.poll()
    assert len(client.sends) == 1
    client.deliver(1)
    assert loop.outstanding() == 0
