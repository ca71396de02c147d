"""Admission under skewed keys: many writers on a few hot names beside a
tail of names with one, through three stepped managers
(``testing/cluster.py``), against a plain sequential adder written here.

The benchmark's cell ``g1k-zipf`` runs this regime on the chip; these are
its mechanisms at 256 rows, 16 names, window 16, 8 lanes: a row with 2 to
8 requests a tick stages them as single vids, one lane and one slot each;
over 8 the queue is coalesced into a batch; a full slot window turns
lanes back and the next tick stages them again.  The admission series
(``requests_staged``, ``requests_admitted``, ``window_full_rows``,
``proposal_requests``, ``requests_coalesced``, ``admission_rows``) have to
read what each schedule implies."""

import collections

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import ManagerCluster

CFG = EngineConfig(n_groups=256, window=16, req_lanes=8, n_replicas=3)
NAMES = [f"k{i:02d}" for i in range(16)]
ZIPF = 0.99


class RecordingAdder(StatefulAdderApp):
    """The adder, and the order in which this replica executed."""

    def __init__(self):
        super().__init__()
        self.log = []           # (name, request id), in execution order

    def execute(self, request, do_not_reply_to_client=False):
        self.log.append((request.get_service_name(), request.request_id))
        return super().execute(request, do_not_reply_to_client)


class SequentialAdder:
    """The plain reference: per name a running sum, one request after the
    other.  Takes the order, gives each request's answer and the totals."""

    def __init__(self):
        self.totals = collections.defaultdict(int)
        self.answers = {}

    def apply(self, name, rid, delta):
        assert rid not in self.answers, f"request {rid} executed twice"
        self.totals[name] += delta
        self.answers[rid] = str(self.totals[name])


def counter(m, key):
    return m.metrics.snapshot()["counters"][key]


def hist(m, key):
    return m.metrics.snapshot()["hists"][key]


def pooled(c, key):
    return sum(counter(m, key) for m in c.managers)


def pooled_hist(c, key):
    hs = [hist(m, key) for m in c.managers]
    return sum(h["sum"] for h in hs), sum(h["count"] for h in hs)


@pytest.fixture
def cluster():
    c = ManagerCluster(CFG, RecordingAdder)
    c.rows = {name: c.create(name) for name in NAMES}
    c.run(2)                    # the creates' first exchange
    c.acked = {}                # request id -> response

    def cb(rid, resp):
        assert rid not in c.acked, f"request {rid} answered twice"
        c.acked[rid] = resp
    c.cb = cb
    yield c
    c.close()


def coordinator(c, name):
    return c.managers[0].coordinator_of_row(c.rows[name])


def test_every_series_is_there_before_anything_was_staged(cluster):
    for m in cluster.managers:
        snap = m.metrics.snapshot()
        for key in ("requests_staged", "requests_admitted",
                    "requests_coalesced", "window_full_rows"):
            assert snap["counters"][key] == 0
        for key in ("proposal_requests", "admission_rows"):
            assert snap["hists"][key]["count"] == 0


@pytest.mark.parametrize("pipelined, seed, per_tick", [
    (False, 2**31 + 41, 24),    # ~7 a tick on the hottest name
    (True, 41, 40),             # ~12: the hottest row coalesces most ticks
    (True, 2**31 + 99, 12),
])
def test_a_zipfian_schedule_against_the_sequential_model(
        cluster, pipelined, seed, per_tick):
    c = cluster
    c.pipelined = pipelined
    rng = np.random.default_rng(seed)
    p = np.arange(1, len(NAMES) + 1, dtype=float) ** -ZIPF
    perm = rng.permutation(len(NAMES))
    sent = {}                   # request id -> (name, delta)
    rid = 1000
    for _tick in range(12):
        for rank in rng.choice(len(NAMES), size=per_tick, p=p / p.sum()):
            k = int(perm[rank])
            delta = int(rng.integers(1, 1000))
            rid += 1
            sent[rid] = (NAMES[k], delta)
            # a name's entry replica is fixed, as the benchmark's is
            c.managers[k % 3].propose(NAMES[k], f"{delta:010d}",
                                      callback=c.cb, request_id=rid)
        c.step_all()
    c.run(40)

    # every request executed exactly once, in one order on all three
    logs = [m.app.log for m in c.managers]
    assert logs[0] == logs[1] == logs[2]
    assert sorted(r for _n, r in logs[0]) == sorted(sent)
    model = SequentialAdder()
    for name, r in logs[0]:
        assert sent[r][0] == name
        model.apply(name, r, sent[r][1])
    # every acknowledged value is the model's, so unique on its name
    assert c.acked == model.answers
    by_name = collections.defaultdict(list)
    for r, resp in c.acked.items():
        by_name[sent[r][0]].append(resp)
    assert all(len(set(v)) == len(v) for v in by_name.values())
    expected = collections.defaultdict(int)
    for name, delta in sent.values():
        expected[name] += delta
    for m in c.managers:
        assert {n: t for n, t in m.app.totals.items() if t} == expected

    # the admission series: each request was first staged once, at its
    # coordinator; what was staged and not admitted was turned back
    # (a vid turned back alone and coalesced the tick after is in the
    # batch's count too: a request is in every vid that was staged for it)
    total, proposals = pooled_hist(c, "proposal_requests")
    back = pooled(c, "requests_staged") - pooled(c, "requests_admitted")
    assert len(sent) <= total <= len(sent) + back
    assert proposals <= len(sent)
    assert pooled(c, "requests_coalesced") == sum(
        _coalesced(m) for m in c.managers)
    assert (proposals < len(sent)) == (pooled(c, "requests_coalesced") > 0)
    for m in c.managers:
        staged, admitted = (counter(m, "requests_staged"),
                            counter(m, "requests_admitted"))
        assert admitted <= staged
        assert (counter(m, "window_full_rows") > 0) == (admitted < staged)
        assert hist(m, "proposal_requests")["count"] <= staged
        rows = hist(m, "admission_rows")
        assert rows["count"] <= counter(m, "host_dispatches")
        assert rows["count"] == 0 or 1 <= rows["min"] <= rows["max"] <= 16
    # more than one row admitted at once somewhere: row-parallel admission
    assert max(hist(m, "admission_rows")["max"] or 0
               for m in c.managers) > 1


def _coalesced(m):
    """From the histogram: its sum less the proposals of one request."""
    h = hist(m, "proposal_requests")
    singles = h["buckets"][0][1]            # bound 1: lone requests
    assert h["buckets"][0][0] == 1
    return int(h["sum"]) - singles


@pytest.mark.parametrize("batching", [False, True])
def test_a_row_offered_eight_vids_a_tick_fills_its_window(cluster, batching):
    """8 single vids a tick for 10 ticks on ONE row, at its coordinator:
    a commit takes more ticks than the window has slots for at that rate,
    so the engine turns lanes back.  Never more than SLOT_WINDOW slots
    undecided; decided in the order queued; with batching off the series
    read what the queue's length before and after each tick implies, with
    batching on what is turned back rides a batch the tick after."""
    c = cluster
    name = NAMES[3]
    row, lead = c.rows[name], c.managers[coordinator(c, name)]
    for m in c.managers:
        m.batching_enabled = batching
    K, W = CFG.req_lanes, CFG.window
    order, staged, admitted, turned_back = [], 0, 0, 0
    for tick in range(10 + 30):
        if tick < 10:
            for i in range(K):
                rid = 5000 + tick * K + i
                order.append(rid)
                lead.propose(name, "0000000001", callback=c.cb,
                             request_id=rid)
        before = len(lead.queues.get(row, ()))
        c.step_all()
        after = len(lead.queues.get(row, ()))
        if not batching:
            staged += min(before, K)
            admitted += before - after
            turned_back += (before - after) < min(before, K)
        undecided = int(np.asarray(lead.state.c_next_slot)[row]) \
            - int(np.asarray(lead.state.exec_slot)[row])
        assert 0 <= undecided <= W
    assert len(order) == 80
    for m in c.managers:
        assert [r for _n, r in m.app.log] == order
        assert m.app.totals[name] == 80
    assert [c.acked[r] for r in order] == [str(i + 1) for i in range(80)]

    total, proposals = pooled_hist(c, "proposal_requests")
    assert counter(lead, "window_full_rows") > 0, "the window never filled"
    assert counter(lead, "requests_staged") \
        > counter(lead, "requests_admitted")
    if batching:
        # a queue over 8 was coalesced: fewer proposals than requests
        assert proposals < 80 and counter(lead, "requests_coalesced") > 0
        # every vid staged for a request counts it: those turned back
        # alone are in the batch that took them the tick after as well
        assert 80 <= total <= 80 + counter(lead, "requests_staged") \
            - counter(lead, "requests_admitted")
    else:
        assert total == proposals == 80
        assert pooled(c, "requests_coalesced") == 0
        assert counter(lead, "requests_staged") == staged
        assert counter(lead, "requests_admitted") == admitted == 80
        assert counter(lead, "window_full_rows") == turned_back
    others = [m for m in c.managers if m is not lead]
    assert all(counter(m, "requests_staged") == 0 for m in others)


@pytest.mark.parametrize("n, coalesced, proposals", [
    (2, 0, 2),
    (8, 0, 8),      # a queue as long as the ring is deep: single vids
    (9, 9, 1),      # one longer: the whole queue rides one batch
    (30, 30, 1),
])
def test_the_coalescing_trigger(cluster, n, coalesced, proposals):
    """A row's queue is coalesced only when it is longer than
    ``max(req_lanes, MIN_PP_BATCH_SIZE - 1)`` = 8."""
    c = cluster
    name = NAMES[5]
    lead = c.managers[coordinator(c, name)]
    for i in range(n):
        lead.propose(name, "0000000002", callback=c.cb, request_id=7000 + i)
    c.run(8)
    assert len(c.acked) == n
    h = hist(lead, "proposal_requests")
    assert (h["sum"], h["count"]) == (n, proposals)
    assert h["max"] == (n if coalesced else 1)
    assert counter(lead, "requests_coalesced") == coalesced
    assert counter(lead, "requests_staged") == proposals \
        == counter(lead, "requests_admitted")
    assert counter(lead, "window_full_rows") == 0
    rows = hist(lead, "admission_rows")
    assert (rows["sum"], rows["count"]) == (1, 1)


def test_rows_a_dispatch_counts_the_rows_staged_together(cluster):
    """One request on each of five names in one tick, each at its
    coordinator: every coordinator's one dispatch staged its rows at
    once, and the five add up."""
    c = cluster
    for i, name in enumerate(NAMES[:5]):
        c.managers[coordinator(c, name)].propose(
            name, "0000000003", callback=c.cb, request_id=9000 + i)
    c.run(8)
    assert len(c.acked) == 5
    total, dispatches = pooled_hist(c, "admission_rows")
    leads = {coordinator(c, name) for name in NAMES[:5]}
    assert (total, dispatches) == (5, len(leads))
    assert pooled(c, "requests_staged") == 5 == pooled(c, "requests_admitted")


def test_what_is_proposed_while_a_step_is_in_flight_was_not_turned_back(
        cluster):
    """A served node proposes from its transport threads between
    ``step_dispatch`` and ``step_complete``: the row's queue is longer at
    the completion than what the dispatch staged, and the engine admitted
    all that WAS staged."""
    c = cluster
    name = NAMES[7]
    lead = c.managers[coordinator(c, name)]
    heard = np.ones(3, bool)
    for i in range(3):
        lead.propose(name, "0000000004", callback=c.cb, request_id=9500 + i)
    pend = lead.step_dispatch(None, heard)
    for i in range(2):
        lead.propose(name, "0000000004", callback=c.cb, request_id=9600 + i)
    lead.step_complete(pend)
    assert len(lead.queues[c.rows[name]]) == 2
    assert counter(lead, "requests_staged") == 3 \
        == counter(lead, "requests_admitted")
    assert counter(lead, "window_full_rows") == 0
    c.republish()
    c.run(10)
    assert len(c.acked) == 5 and counter(lead, "window_full_rows") == 0
