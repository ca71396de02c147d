"""Wake on write (upstream's message-triggered unpause,
``PaxosManager.java:1814-1818,2350``) on the served path's entry side:
a write — or a forward — that reaches an active for a name it has paused
is HELD under its request id, the active asks the name's reconfigurator
for the resume once a sleep, and what was held is proposed in arrival
order when the row is live again.  The writer sees latency and nothing
else.

Three actives and three reconfigurators (``testing/rc_cluster.py``), 16
names on 64 rows, seeded deltas, a logical clock (the test advances it;
nothing sleeps).  Every acknowledgement and every replica's totals are
held against the sequential model the benchmark's checker is (per name:
the running sum, and the sum of the acknowledged deltas), which knows
nothing of residency."""

import collections
import time
import types

import numpy as np
import pytest

from gigapaxos_tpu import manager as manager_mod
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.protocoltask import ProtocolExecutor
from gigapaxos_tpu.reconfiguration import RCState
from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster

NAMES = [f"w{i:02d}" for i in range(16)]
DT = 0.5           # logical seconds a step
PERIOD = 30.0      # the deactivation period under test


class Clock:
    def __init__(self):
        self.t = time.time()

    def __call__(self):
        return self.t


class Cluster:
    """The cluster, its logical clock, its writers and the sequential
    model beside them."""

    def __init__(self, monkeypatch, seed=31):
        self.clock = Clock()
        shim = types.SimpleNamespace(
            time=self.clock, monotonic=time.monotonic, sleep=time.sleep)
        monkeypatch.setattr(manager_mod, "time", shim)
        monkeypatch.setattr(ProtocolExecutor, "clock",
                            staticmethod(self.clock))
        ar_cfg = EngineConfig(n_groups=64, window=8, req_lanes=4,
                              n_replicas=3)
        rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4,
                              n_replicas=3)
        self.c = ReconfigurableCluster(ar_cfg, rc_cfg, StatefulAdderApp)
        for ar in self.c.active_replicas:
            ar.deactivation_period_s = PERIOD
            ar._last_sweep = self.clock.t
        self.rng = np.random.default_rng(seed)
        self.next_rid = 1000
        self.sent = {}                      # rid -> (name, delta)
        self.acks = []                      # (rid, response), as they came
        self.sums = collections.defaultdict(int)   # the model
        self.checked = 0

    # -- driving ----------------------------------------------------------
    def step(self, n=1):
        for _ in range(n):
            self.clock.t += DT
            self.c.step(self.clock.t)

    def until(self, cond, max_steps=400, what="condition"):
        for _ in range(max_steps):
            if cond():
                return
            self.step()
        raise AssertionError(f"{what} not reached in {max_steps} steps")

    def create_all(self):
        for name in NAMES:
            self.c.client_request(
                "create_service", {"name": name, "actives": [0, 1, 2]})
        acked = set()

        def done():
            acked.update(b["name"] for k, b in self.c.drain_client()
                         if k == "create_ack" and b.get("ok"))
            return len(acked) == len(NAMES)

        self.until(done, 600, "creates")
        self.until(lambda: all(
            not m.pending_rows for m in self.c.ars.managers), 100, "commits")

    def write(self, name, entry=None, rid=None, delta=None):
        """One write at an ENTRY replica (round robin by name, as the
        cells send), the way ``server.py`` admits a frame; returns
        (request id, outcome)."""
        entry = NAMES.index(name) % 3 if entry is None else entry
        if rid is None:
            self.next_rid += 1
            rid = self.next_rid
            delta = int(self.rng.integers(1, 1000)) if delta is None else delta
            self.sent[rid] = (name, delta)
        name, delta = self.sent[rid]
        res = self.c.ars.managers[entry].propose_batch([
            (name, f"{delta:010d}", rid,
             lambda r, resp: self.acks.append((r, resp))),
        ])
        return rid, res[0][1]

    # -- the model ----------------------------------------------------------
    def check_acks(self):
        """Every acknowledgement so far, once each, against the running
        sum of its name (one writer a name at a time)."""
        seen = collections.Counter(r for r, _ in self.acks)
        assert all(n == 1 for n in seen.values()), seen
        for rid, resp in self.acks[self.checked:]:
            name, delta = self.sent[rid]
            self.sums[name] += delta
            assert int(resp) == self.sums[name], (name, rid, resp,
                                                  self.sums[name])
        self.checked = len(self.acks)

    def check_replicas(self):
        """Each name's total on all three actives is the sum of its
        acknowledged deltas."""
        self.check_acks()
        assert len(self.acks) == len(self.sent)
        for i, m in enumerate(self.c.ars.managers):
            for name in NAMES:
                assert m.app.totals.get(name, 0) == self.sums[name], (
                    i, name)

    # -- residency ----------------------------------------------------------
    def record(self, name):
        return self.c.reconfigurators[0].rc_app.get_record(name)

    def asleep(self, name):
        return [m.sleeps_here(name) for m in self.c.ars.managers]

    def suggest_pause(self, name, frm=0):
        self.c.active_replicas[frm].send(("RC", 0), "suggest_pause", {
            "name": name, "epoch": self.record(name).epoch, "from": frm})

    def pause(self, *names):
        for name in names:
            self.suggest_pause(name)
        self.until(lambda: all(self.record(n).state is RCState.PAUSED
                               for n in names), 200, f"pause of {names}")
        for name in names:
            assert self.asleep(name) == [True] * 3

    def counter(self, key):
        return [m.metrics.get(key) for m in self.c.ars.managers]

    def close(self):
        self.c.close()


@pytest.fixture
def k(monkeypatch):
    cl = Cluster(monkeypatch)
    cl.create_all()
    # the warm-up round: one write to every name
    for name in NAMES:
        cl.write(name)
    cl.until(lambda: len(cl.acks) == len(NAMES), 200, "warm-up round")
    cl.check_acks()
    yield cl
    cl.close()


def test_the_first_write_to_a_sleeping_name_is_acknowledged_like_any_other(k):
    """The sweep puts every name to sleep; then one write a name, at its
    entry replica: held, one wake request a name an active, acknowledged
    with the running sum across the sleep, and the restored state read
    on the two replicas that did not answer."""
    k.step(int((PERIOD + 1) / DT))          # idle past the period: a sweep
    k.until(lambda: all(k.record(n).state is RCState.PAUSED for n in NAMES),
            400, "the sweep's pause rounds")
    assert all(not m.names and len(m.paused) == 16
               for m in k.c.ars.managers)
    assert sum(k.counter("pause_evictions")) == 3 * 16
    # the record is journaled where there is a journal; here: in the table
    before = {n: [m.app.totals.get(n) for m in k.c.ars.managers]
              for n in NAMES}
    for name in NAMES[:6]:
        _, outcome = k.write(name)
        assert outcome == "held"
    k.until(lambda: len(k.acks) == 16 + 6, 300, "the wakes")
    k.check_acks()
    assert sum(k.counter("writes_held_for_wake")) == 6
    assert sum(k.counter("wake_requests_sent")) == 6    # once a sleep
    assert sum(k.counter("names_woken")) == 3 * 6
    k.step(6)                               # the laggards execute
    for name in NAMES[:6]:
        entry = NAMES.index(name) % 3
        assert k.asleep(name) == [False] * 3
        for i, m in enumerate(k.c.ars.managers):
            if i != entry:                  # restored, then the write
                assert m.app.totals[name] == k.sums[name] \
                    > before[name][i], (name, i)
    # the rest still sleep: no row on any active
    for name in NAMES[6:]:
        assert k.asleep(name) == [True] * 3
        assert all(name not in m.names for m in k.c.ars.managers)
    # a second write to a woken name takes the plain path
    _, outcome = k.write(NAMES[0])
    assert outcome == "queued"
    k.until(lambda: len(k.acks) == 16 + 7, 100, "a resident write")
    k.check_replicas()
    hist = k.c.ars.managers[0].metrics.snapshot()["hists"]
    assert hist["phase_wake_hold_s"]["count"] == 2      # names 0 and 3
    assert hist["phase_reconf_resume_s"]["count"] == 6
    assert hist["phase_reconf_pause_s"]["count"] >= 16  # and repeats


def test_writes_at_the_entry_replicas_while_the_sweep_pauses_names(k):
    """Traffic and the Deactivator beside each other for ten periods: a
    drifting hot set of four names is written every step, everything
    else falls asleep behind it and is woken by its next first write."""
    hot = collections.deque(NAMES[:4])
    cold = collections.deque(NAMES[4:])
    busy = {}
    held = 0
    for step in range(int(10 * PERIOD / DT)):
        for rid in [r for r, _ in k.acks[k.checked:]]:
            busy.pop(k.sent[rid][0], None)
        k.check_acks()
        if step % 40 == 20:                 # a cold name enters the hot set
            hot.append(cold.popleft())
            cold.append(hot.popleft())
        name = hot[int(k.rng.integers(0, len(hot)))]
        if name not in busy:                # one writer a name at a time
            rid, outcome = k.write(name)
            assert outcome in ("queued", "held"), outcome
            held += outcome == "held"
            busy[name] = rid
        k.step()
    k.until(lambda: len(k.acks) == len(k.sent), 300, "the drain")
    k.step(8)
    k.check_replicas()
    assert held >= 5
    assert sum(k.counter("pause_evictions")) >= 3 * 12
    assert sum(k.counter("names_woken")) >= 3 * 5
    # whoever sleeps at the end holds no row anywhere
    for name in NAMES:
        rows = [name in m.names for m in k.c.ars.managers]
        assert rows in ([True] * 3, [False] * 3) or \
            k.record(name).state is not RCState.READY, (name, rows)


def test_a_write_that_meets_the_pause_round_is_busy_and_the_round_called_off(k):
    """``pause_group`` -> "busy": the write is queued at its entry replica
    when the round arrives there."""
    name = NAMES[0]                         # enters at active 0
    k.suggest_pause(name)
    k.until(lambda: k.record(name).state is RCState.WAIT_PAUSE, 60,
            "the pause intent")
    rid, outcome = k.write(name)            # before pause_epoch is handled
    assert outcome == "queued"
    k.until(lambda: len(k.acks) == 17, 200, "the write")
    k.until(lambda: k.record(name).state is RCState.READY, 200, "READY")
    k.step(6)
    assert k.asleep(name) == [False] * 3
    k.check_replicas()


def test_a_write_where_the_round_is_acknowledged_here_and_not_yet_elsewhere(k):
    """Active 0 has freed the row and the round still waits for active 2:
    the write is held at 0, the wake calls the round off (WAIT_PAUSE ->
    resume), no pause_epoch follows the resume, and the name is back on
    all three."""
    name = NAMES[0]
    late = []

    def hold_back(dst, kind, body):
        if kind == "pause_epoch" and dst == ("AR", 2) \
                and body["name"] == name:
            late.append(body)
            return False
        return True

    k.c.msg_filter = hold_back
    k.suggest_pause(name)
    k.until(lambda: k.asleep(name)[:2] == [True, True], 100, "two pauses")
    assert k.record(name).state is RCState.WAIT_PAUSE and late
    rid, outcome = k.write(name)
    assert outcome == "held"
    k.until(lambda: len(k.acks) == 17, 300, "the write")
    k.c.msg_filter = None
    k.step(int(3 / DT))                     # past the round's retransmission
    assert k.record(name).state is RCState.READY
    assert k.asleep(name) == [False] * 3
    assert not k.c.reconfigurators[0].tasks.is_running(f"pause:{name}") \
        and not any(rc.tasks.is_running(f"pause:{name}")
                    for rc in k.c.reconfigurators)
    k.check_replicas()


def test_a_forward_that_finds_its_coordinator_asleep_is_held_there(k):
    """The coordinator has freed the row, the entry replica has not yet:
    the entry forwards, the coordinator holds the forward under its id
    and asks for the wake; the entry replica answers the client."""
    name = next(n for n in NAMES
                if k.c.ars.managers[0].coordinator_of_row(
                    k.c.ars.managers[0].names[n]) != NAMES.index(n) % 3)
    entry = NAMES.index(name) % 3
    coord = k.c.ars.managers[0].coordinator_of_row(
        k.c.ars.managers[0].names[name])

    def hold_back(dst, kind, body):
        return not (kind == "pause_epoch" and dst == ("AR", entry)
                    and body["name"] == name)

    k.c.msg_filter = hold_back
    k.suggest_pause(name)
    k.until(lambda: k.asleep(name)[coord], 100, "the coordinator's pause")
    assert not k.asleep(name)[entry]
    rid, outcome = k.write(name)
    assert outcome == "queued"              # the entry replica is awake
    k.until(lambda: k.counter("writes_held_for_wake")[coord] == 1, 20,
            "the forward, held")
    k.c.msg_filter = None
    k.until(lambda: len(k.acks) == 17, 300, "the write")
    k.until(lambda: k.record(name).state is RCState.READY, 200, "READY")
    k.step(6)
    assert k.counter("wake_requests_sent")[coord] == 1
    k.check_replicas()


def test_the_same_id_sent_again_while_held_executes_once(k):
    name = NAMES[1]
    k.pause(name)
    rid, outcome = k.write(name)
    assert outcome == "held"
    k.step(1)
    assert k.write(name, rid=rid)[1] == "inflight"      # a retransmission
    k.step(2)
    assert k.write(name, rid=rid)[1] in ("inflight", "held")
    k.until(lambda: len(k.acks) >= 17, 300, "the write")
    k.step(10)
    assert sum(k.counter("writes_held_for_wake")) == 1
    k.check_replicas()                      # answered once, executed once
    # and after the wake it is answered from the cache
    assert k.write(name, rid=rid)[1] == "cached"
    assert k.acks[-1] == (rid, str(k.sums[name]))


def test_names_first_written_inside_one_tick_wake_through_one_batch(k):
    names = NAMES[2:8]
    k.pause(*names)
    for name in names:
        assert k.write(name)[1] == "held"
    k.until(lambda: len(k.acks) == 16 + 6, 300, "the wakes")
    k.step(6)
    k.check_replicas()
    assert sum(k.counter("names_woken")) == 3 * 6
    # the resumes of a burst reach an active together: one fused restore
    assert all(n >= 2 for n in k.counter("names_woken_batched")), \
        k.counter("names_woken_batched")


def test_a_name_truly_unknown_is_still_unknown(k):
    m = k.c.ars.managers[0]
    fired = []
    res = m.propose_batch([("nobody", "0000000001", 77,
                            lambda r, resp: fired.append(r))])
    assert res == [(77, "unknown", None)] and not fired
    assert m.propose("nobody", "0000000001", request_id=78) is None
    assert not m.sleeps_here("nobody") and not m._wake_held
    assert m.drain_wake_requests() == []
    # a STOP is never held for a wake: it is the reconfigurator's own
    k.pause(NAMES[5])
    assert m.propose(NAMES[5], "", stop=True, request_id=79) is None
    assert not m._wake_held


def test_a_member_frozen_alone_is_woken_by_the_write_it_holds(k):
    """A pause round that was called off left ONE member with a pause
    record while the record is READY: the wake request is answered with
    the committed resume the sweep's probe would bring a period later."""
    name = NAMES[3]                         # enters at active 0
    assert k.c.ars.managers[0].pause_group(name, 0) == "ok"
    k.c.ars.republish()
    assert k.asleep(name) == [True, False, False]
    assert k.record(name).state is RCState.READY
    rid, outcome = k.write(name)
    assert outcome == "held"
    k.until(lambda: len(k.acks) == 17, 200, "the write")
    k.step(6)
    assert k.asleep(name) == [False] * 3
    k.check_replicas()
