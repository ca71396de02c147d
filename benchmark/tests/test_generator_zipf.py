"""The zipfian closed loop against a fake client: what it draws against
the exact probabilities, that a seed fixes the requests, what it refuses
and what it passes through to ``closed.py``; and ``max_share.py`` on
snapshots made by hand."""

import collections

import numpy as np
import pytest

import run
from generators import closed, closed_zipf
from test_generator import Clock, FakeClient, TARGETS

BASE = {"loop": "closed_zipf", "entry": "round_robin_by_name",
        "retransmit_s": 20.0, "fail_after_s": 30.0, "in_flight": 50,
        "key_dist": "zipfian", "zipf_constant": 0.99, "scrambled": True,
        "per_name_order": False}
NAMES = [f"g{i:03d}" for i in range(100)]


def drive(traffic=None, seed=2**31 + 41, names=NAMES, requests=20000):
    client, clock = FakeClient(), Clock()
    loop = closed_zipf.ClosedZipfLoop(
        client, names, TARGETS, {**BASE, **(traffic or {})}, seed,
        clock=clock)
    loop.start()
    while len(loop.reqs) < requests:
        clock.t += 0.001
        client.deliver(7)
    loop.stop()
    client.deliver(len(client.sends))
    return client, loop


def test_the_exact_probabilities_are_the_sources():
    """YCSB's constant over upstream's 1,000 groups: the shares the
    configuration's file states."""
    p = closed_zipf.probabilities(1000, 0.99)
    assert p.sum() == pytest.approx(1.0)
    assert 1 / p[0] == pytest.approx(7.729, abs=1e-3)
    assert p[0] == pytest.approx(0.1294, abs=1e-4)
    assert p[:10].sum() == pytest.approx(0.3825, abs=1e-4)
    assert p[:100].sum() == pytest.approx(0.6850, abs=1e-4)
    assert p[-1] == pytest.approx(0.00014, abs=1e-5)
    assert int((500 * p >= 1).sum()) == 67
    assert (1 - (1 - p) ** 500).sum() == pytest.approx(214.25, abs=0.01)


def test_the_draws_shares_against_the_exact_probabilities():
    client, loop = drive()
    n = len(loop.reqs)
    p = closed_zipf.probabilities(len(NAMES), 0.99)
    drawn = collections.Counter(r.name for r in loop.reqs)
    by_rank = np.array([drawn[loop.perm[r]] for r in range(len(NAMES))]) / n
    # four standard deviations of a binomial share, rank by rank
    assert np.all(np.abs(by_rank - p) <= 4 * np.sqrt(p * (1 - p) / n))
    assert abs(by_rank[:10].sum() - p[:10].sum()) < 0.015
    s = loop.summary()
    assert s["requests"] == n and s["names_drawn"] == len(drawn)
    assert s["hottest_name"] == NAMES[loop.perm[0]]
    assert s["hottest_share"] == pytest.approx(by_rank[0])
    assert s["hottest_expected"] == pytest.approx(p[0])
    # many writers on the hot name at once, one entry replica a name
    assert client.max_in_flight == BASE["in_flight"]
    assert client.max_per_name > 3
    for addr, name, value, _ in client.log:
        assert addr == TARGETS[NAMES.index(name) % 3]
        assert len(value) == 10 and 1 <= int(value) < 1000
    assert all(r.t_ack is not None for r in loop.reqs)


def test_same_seed_same_requests_another_seed_another_permutation():
    logs, perms = [], []
    for seed in (2**31 + 7, 2**31 + 7, 5):
        client, loop = drive(seed=seed, requests=3000)
        logs.append([(n, v) for _a, n, v, _r in client.log][:3000])
        perms.append(loop.perm)
    assert logs[0] == logs[1] and perms[0] == perms[1]
    assert perms[0] != perms[2] and logs[0] != logs[2]
    assert sorted(perms[0]) == list(range(len(NAMES)))


def test_unscrambled_rank_r_is_name_r():
    _client, loop = drive({"scrambled": False}, requests=5000)
    assert loop.perm == list(range(len(NAMES)))
    drawn = collections.Counter(r.name for r in loop.reqs)
    assert drawn.most_common(1)[0][0] == 0


def test_slot_passes_through_for_the_warm_up_round():
    """``run_cell`` makes the warm-up round from the cell's traffic with
    ``key_dist`` slot, ``per_name_order`` true and ``budget`` 1."""
    client = FakeClient()
    warm = closed_zipf.Loop(client, NAMES, TARGETS, {
        **BASE, "in_flight": len(NAMES), "key_dist": "slot",
        "per_name_order": True, "budget": 1, "ramp_s": 0.0}, 3)
    assert type(warm) is closed.ClosedLoop
    warm.start()
    client.deliver(len(client.sends))
    assert [r.name for r in warm.reqs] == list(range(len(NAMES)))
    assert warm.outstanding() == 0 and not client.sends
    assert type(closed_zipf.Loop(client, NAMES, TARGETS, BASE, 3)) \
        is closed_zipf.ClosedZipfLoop


@pytest.mark.parametrize("traffic, message", [
    ({"per_name_order": True}, "per_name_order"),
    ({"zipf_constant": 0.0}, "zipf_constant"),
    ({"entry": "anywhere"}, "entry"),
])
def test_what_the_loop_refuses(traffic, message):
    with pytest.raises(ValueError, match=message):
        closed_zipf.Loop(FakeClient(), NAMES, TARGETS,
                         {**BASE, **traffic}, 1)


def test_the_cells_traffic_file_is_the_issues_table():
    mix = run.load_json(run.HERE, "traffic", "closed-500-zipf99.json")
    assert mix.pop("why")
    assert mix == {
        "loop": "closed_zipf", "in_flight": 500, "key_dist": "zipfian",
        "zipf_constant": 0.99, "scrambled": True, "per_name_order": False,
        "entry": "round_robin_by_name", "retransmit_s": 20.0,
        "fail_after_s": 30.0, "ramp_s": 5.0}


# ---- layer_metrics/max_share.py on snapshots made by hand ----------------
def _snap(**counters):
    return {"counters": counters, "hists": {}}


@pytest.mark.parametrize("before, after, expected", [
    # growth 60, 30, 10: the busiest active admitted 60 of 100
    ([_snap(requests_admitted=40), _snap(requests_admitted=0),
      _snap(requests_admitted=5)],
     [_snap(requests_admitted=100), _snap(requests_admitted=30),
      _snap(requests_admitted=15)], 60.0),
    # even
    ([_snap(requests_admitted=1)] * 3, [_snap(requests_admitted=8)] * 3,
     100 / 3),
    # an active that has no such counter yet counts as 0
    ([_snap(), _snap(requests_admitted=0), _snap()],
     [_snap(requests_admitted=9), _snap(requests_admitted=1), _snap()],
     90.0),
    # a program without the counter; a counter that did not grow
    ([_snap()] * 3, [_snap()] * 3, None),
    ([_snap(requests_admitted=4)] * 3, [_snap(requests_admitted=4)] * 3,
     None),
])
def test_max_share_reader(before, after, expected):
    spec = {"name": "m", "unit": "%", "reader": "module",
            "module": "max_share", "counter": "requests_admitted"}
    got = run.layer_metrics([spec], {"before": before, "after": after})
    if expected is None:
        assert got == {}
    else:
        assert got == {"m": {"value": pytest.approx(expected), "unit": "%"}}
