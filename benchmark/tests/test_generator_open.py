"""The open-loop generator against a fake client."""

import json
import os

import pytest

from generators import open as open_loop
from test_generator import NAMES, TARGETS, Clock, FakeClient

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = {"loop": "open", "entry": "round_robin_by_name", "retransmit_s": 8.0,
        "fail_after_s": 30.0, "rate_per_s": 100.0,
        "key_dist": "uniform", "per_name_order": False}


def make(seed=3, **traffic):
    client, clock = FakeClient(), Clock()
    loop = open_loop.OpenLoop(client, NAMES, TARGETS, {**BASE, **traffic},
                              seed, clock=clock)
    return client, clock, loop


def test_sends_on_schedule_whether_or_not_anything_was_answered():
    client, clock, loop = make()
    loop.start(thread=False)
    assert loop.send_due() == 0            # nothing is due at time 0
    for step in range(1, 501):             # 5 s in 10 ms steps, no reply
        clock.t = step * 0.01
        loop.send_due()
    assert 400 < len(loop.reqs) < 600      # 100 a second for 5 s
    assert loop.outstanding() == len(loop.reqs) == len(client.sends)
    assert client.max_per_name > 1         # no per-name order
    for addr, name, value, _ in client.log:
        assert addr == TARGETS[NAMES.index(name) % 3]
        assert len(value) == 10 and int(value) >= 1
    loop.stop()
    clock.t = 6.0
    assert loop.send_due() == 0            # stopped: nothing more goes out
    client.deliver(len(client.sends))
    assert loop.outstanding() == 0
    assert all(r.t_ack == 6.0 for r in loop.reqs)


def test_the_clock_of_a_request_starts_when_it_was_due():
    client, clock, loop = make()
    clock.t = 100.0
    loop.start(thread=False)
    clock.t = 101.0                        # the injector comes a second late
    n = loop.send_due()
    assert n > 50
    due = [r.t_first for r in loop.reqs]
    assert due == sorted(due) and 100.0 < due[0] < due[-1] <= 101.0
    assert all(r.t_sent == 101.0 for r in loop.reqs)
    gaps = [b - a for a, b in zip(due, due[1:])]
    assert min(gaps) > 0 and max(gaps) > 3 * min(gaps)   # not a metronome


def test_same_seed_same_schedule_and_large_seeds():
    logs = []
    for seed in (2**31 + 7, 2**31 + 7, 5):
        client, clock, loop = make(seed=seed)
        loop.start(thread=False)
        clock.t = 2.0
        loop.send_due()
        logs.append([(r.t_first, r.name, r.delta) for r in loop.reqs])
    assert logs[0] == logs[1] != logs[2]


def test_retransmits_under_the_same_id_then_fails_from_the_due_time():
    client, clock, loop = make(rate_per_s=2.0)
    loop.start(thread=False)
    clock.t = 1.0
    while not loop.reqs:
        clock.t += 0.5
        loop.send_due()
    loop.stop()
    first = loop.reqs[0]
    client.deliver(len(client.sends), drop=True)
    clock.t = first.t_sent + 8.1
    loop.poll()
    assert [rid for *_, rid in client.sends][0] == first.rid
    assert first.sends == 2
    client.deliver(len(client.sends), drop=True)
    clock.t = first.t_first + 30.1
    loop.poll()
    assert first.failed and first.t_ack is None and first.rid in loop.failed


def test_an_error_reply_is_a_refusal():
    client, clock, loop = make()
    loop.start(thread=False)
    clock.t = 0.1
    loop.send_due()
    _, name, _, cb, rid = client.sends.popleft()
    cb(rid, None, "unknown_name")
    assert loop.errors == [(name, "unknown_name")] and rid in loop.failed


def test_budget_falls_through_to_the_closed_loop():
    """The harness's warm-up round builds ``Loop`` from the cell's own
    generator module with ``budget`` 1 and the closed loop's parameters."""
    client = FakeClient()
    warm = open_loop.Loop(client, NAMES, TARGETS, {
        **BASE, "in_flight": len(NAMES), "key_dist": "slot",
        "per_name_order": True, "budget": 1, "ramp_s": 0.0}, 7)
    assert isinstance(warm, open_loop.closed.ClosedLoop)
    warm.start()
    client.deliver(len(NAMES))
    assert warm.outstanding() == 0 and len(warm.reqs) == len(NAMES)
    assert isinstance(open_loop.Loop(client, NAMES, TARGETS, BASE, 7),
                      open_loop.OpenLoop)


def test_rejects_what_it_cannot_generate():
    with pytest.raises(ValueError):
        make(key_dist="slot")
    with pytest.raises(ValueError):
        make(per_name_order=True)


def test_the_injector_thread_follows_the_wall_clock():
    client = FakeClient()
    loop = open_loop.OpenLoop(client, NAMES, TARGETS,
                              {**BASE, "rate_per_s": 500.0}, 11)
    loop.start()
    import time
    time.sleep(0.5)
    loop.stop()
    assert 150 < len(loop.reqs) < 350
    late = [r.t_sent - r.t_first for r in loop.reqs]
    assert min(late) >= 0 and sorted(late)[len(late) // 2] < 0.05


def test_the_cells_traffic_file_is_what_the_generator_takes():
    mix = json.load(open(os.path.join(BENCH, "traffic", "open-280.json")))
    loop = open_loop.Loop(FakeClient(), NAMES, TARGETS, mix, 1)
    assert loop.rate == 280.0
    assert loop.retransmit_s == 20.0 and loop.fail_after_s == 30.0
