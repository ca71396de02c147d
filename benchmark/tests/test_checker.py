"""The comparison that decides ``correct``, on fabricated runs: it passes
a sound one and fails each broken guarantee."""

import pytest

import checker
from generators.closed import Req

NAMES = ["a", "b", "c"]


def sound_run(per_name=4):
    """Requests in order of first send with the sums a sound service
    acknowledges, and the totals its three replicas end with."""
    reqs, totals = [], {n: 0 for n in NAMES}
    for k in range(per_name):
        for i, n in enumerate(NAMES):
            r = Req(i, 10 * (k + 1) + i, i, float(k))
            totals[n] += r.delta
            r.t_ack, r.response = k + 0.5, str(totals[n])
            reqs.append(r)
    return reqs, [dict(totals) for _ in range(3)]


def verdict(reqs, totals, ordered=True):
    return {w: v for w, v, limit, _ in
            checker.compare([(reqs, ordered)], totals, NAMES, 3)}


def test_a_sound_run_passes():
    reqs, totals = sound_run()
    assert set(verdict(reqs, totals).values()) == {0}
    assert set(verdict(reqs, totals, ordered=False).values()) == {0}


def test_a_dropped_acknowledged_write_fails():
    reqs, totals = sound_run()
    lost = reqs[4]            # name b's second write, applied nowhere
    for t in totals:
        t["b"] -= lost.delta
    for r in reqs[5:]:
        if r.name == lost.name:
            r.response = str(int(r.response) - lost.delta)
    v = verdict(reqs, totals)
    assert v["ack_value_mismatches"] == 2 and v["replica_total_mismatches"] == 3


def test_a_write_executed_twice_fails():
    reqs, totals = sound_run()
    twice = reqs[4]
    for t in totals:
        t["b"] += twice.delta
    for r in reqs[4:]:
        if r.name == twice.name:
            r.response = str(int(r.response) + twice.delta)
    v = verdict(reqs, totals)
    assert v["ack_value_mismatches"] == 3 and v["replica_total_mismatches"] == 3


def test_one_replica_a_delta_behind_fails_by_the_read_back_alone():
    reqs, totals = sound_run()
    totals[2]["c"] -= reqs[-1].delta
    v = verdict(reqs, totals)
    assert v["ack_value_mismatches"] == 0
    assert v["replica_total_mismatches"] == 1
    assert verdict(reqs, totals[:2])["replicas_missing"] == 1


def test_a_failed_write_may_or_may_not_have_executed_but_alike_everywhere():
    reqs, totals = sound_run()
    last = reqs[-1]
    last.t_ack, last.response, last.failed = None, None, True
    assert set(verdict(reqs, totals).values()) == {0}     # it did execute
    for t in totals:
        t["c"] -= last.delta
    assert set(verdict(reqs, totals).values()) == {0}     # it did not
    totals[0]["c"] += last.delta
    assert verdict(reqs, totals)["replica_total_mismatches"] > 0


def test_many_writers_on_one_name():
    """Without per-name order the acknowledged values are distinct, at
    least the write's own delta and at most everything sent."""
    reqs, totals = sound_run()
    only_a = [r for r in reqs if r.name == 0]
    by_ack = sorted(only_a, key=lambda r: -r.delta)       # another order
    total = 0
    for r in by_ack:
        total += r.delta
        r.response = str(total)
    totals = [{"a": total} for _ in range(3)]
    assert set(verdict(only_a, totals, ordered=False).values()) == {0}
    assert verdict(only_a, totals, ordered=True)["ack_value_mismatches"] > 0
    by_ack[1].response = by_ack[0].response               # two alike
    assert verdict(only_a, totals, False)["ack_value_mismatches"] == 1
    by_ack[1].response = str(total + 1)                   # above all sent
    assert verdict(only_a, totals, False)["ack_value_mismatches"] == 1


def test_groups_follow_one_another():
    """The warm-up round's sums are the base of the window's."""
    reqs, totals = sound_run()
    v = checker.compare([(reqs[:3], True), (reqs[3:], True)], totals, NAMES, 3)
    assert all(value == 0 for _, value, _, _ in v)
    v = checker.compare([(reqs[:3], True), (reqs[3:], False)], totals, NAMES, 3)
    assert all(value == 0 for _, value, _, _ in v)
