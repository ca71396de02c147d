"""The closed loop over a drifting hot set (``generators/closed_wake.py``)
against a fake client, and ``g1k-wake`` end to end on the CPU at a tiny
size (``test_run_cell_new_loops.py``'s way)."""

import json
import re

import pytest

import run
import trace_reduce
from generators import closed, closed_wake
from test_generator import TARGETS, Clock, FakeClient

NAMES = [f"n{i:02d}" for i in range(40)]
BASE = {"loop": "closed_wake", "entry": "round_robin_by_name",
        "retransmit_s": 8.0, "fail_after_s": 30.0, "in_flight": 3,
        "key_dist": "uniform", "per_name_order": True,
        "hot_names": 4, "wake_every": 8, "settle_max_s": 10.0}


class SleepyClient(FakeClient):
    """Says, like the ``stats`` admin op, how many names sleep on each
    active: what ``asleep`` holds, one more at every call where it is a
    callable's answer."""

    def __init__(self, asleep, sweep=None):
        super().__init__()
        self.asleep, self.asked, self.sweep = asleep, [], sweep

    def admin_sync(self, active, body, timeout=5.0):
        self.asked.append((active, body["op"]))
        n = self.asleep(len(self.asked)) if callable(self.asleep) \
            else self.asleep
        if n is None:
            return None
        out = {"residency": {"paused_names": n}}
        if self.sweep is not None:      # (period, the clock at the last)
            out["layer"] = {"sweep": {
                "period_s": self.sweep[0],
                "since_s": (self.clock.t - self.sweep[1]) % self.sweep[0]}}
        return out


def make(asleep=36, seed=3, sweep=None, **traffic):
    client, clock = SleepyClient(asleep, sweep), Clock()
    client.clock = clock
    naps = []

    def sleep(s):
        naps.append(s)
        clock.t += s

    loop = closed_wake.ClosedWakeLoop(
        client, NAMES, TARGETS, {**BASE, **traffic}, seed, clock=clock,
        sleep=sleep)
    return client, clock, loop, naps


def run_requests(client, loop, n):
    while len(loop.reqs) < n:
        client.deliver(1)


def test_one_request_in_wake_every_goes_to_the_next_cold_name_in_order():
    client, clock, loop, _ = make()
    loop.start()                       # every active says 36 asleep: settled
    first = len(loop.reqs)             # the clients' first, hot, requests
    assert first == 3 and all(r.name < 4 for r in loop.reqs)
    run_requests(client, loop, first + 80)
    after = loop.reqs[first:first + 80]
    woke = [k for k, r in enumerate(after) if r in loop.wakes]
    assert woke == list(range(0, 80, 8))
    # the cold names in index order, each once
    assert [after[k].name for k in woke] == list(range(4, 14))
    # every other request stays inside the hot set as it then was: the
    # last four names that entered it
    hot = [0, 1, 2, 3]
    for k, r in enumerate(after):
        if k in woke:
            hot = hot[1:] + [r.name]
        assert r.name in hot, (k, r.name, hot)
    assert client.max_per_name == 1     # per_name_order
    assert client.max_in_flight == 3


def test_the_same_seed_draws_the_same_requests():
    def names_and_deltas(seed):
        client, _, loop, _ = make(seed=seed)
        loop.start()
        run_requests(client, loop, 60)
        return [(r.name, r.delta) for r in loop.reqs[:60]]

    assert names_and_deltas(2**31 + 5) == names_and_deltas(2**31 + 5)
    assert names_and_deltas(2**31 + 5) != names_and_deltas(2**31 + 6)


def test_past_the_last_name_the_cold_names_come_round_again():
    client, _, loop, _ = make(wake_every=2)
    loop.start()
    run_requests(client, loop, 3 + 2 * 50)
    woke = [r.name for r in loop.wakes]
    assert woke[:36] == list(range(4, 40))
    assert woke[36:40] == [0, 1, 2, 3]   # long out of the hot set by then
    assert client.max_per_name == 1


def test_start_waits_on_the_hot_set_until_every_active_reports_the_rest_asleep():
    # the third round of questions is the first in which all three say 36
    client, clock, loop, naps = make(
        asleep=lambda n: 36 if n > 6 else (None if n == 2 else 20))
    answered = []
    real_poll = loop.poll
    loop.poll = lambda: (answered.append(len(loop.reqs)),
                         client.deliver(len(client.sends)), real_poll())
    loop.start()
    # nine questions about who sleeps, one about where the sweep stands
    assert len(client.asked) == 10 and naps == [closed_wake.SETTLE_POLL_S] * 2
    assert loop.asleep_at_settle == [36, 36, 36]
    # while it waited the clients wrote, to the hot set alone, and no
    # request was counted towards a wake
    assert len(loop.reqs) > 3 and all(r.name < 4 for r in loop.reqs)
    assert loop.wakes == [] and loop.k == 0
    client.deliver(1)
    assert loop.wakes and loop.wakes[0].name == 4


def test_the_first_wake_waits_for_its_place_in_the_sweep_period():
    """Everyone sleeps 4 s after a sweep of a 30 s period: the first wake
    goes out ``SWEEP_PHASE_S`` after that sweep, whatever the pause
    rounds took; past that phase, a period later."""
    for since, waited in ((4.0, 13.0), (17.0, 0.0), (25.0, 22.0)):
        client, clock, loop, naps = make(sweep=(30.0, -since))
        loop.start()
        assert sum(naps) == pytest.approx(waited), since
        assert loop.t_settled == pytest.approx(waited) and loop.k == 0
        assert all(r.name < 4 for r in loop.reqs) and not loop.wakes
    # a service that says nothing of its sweep is not waited for
    client, clock, loop, naps = make()
    loop.start()
    assert naps == [] and loop.t_settled == 0.0


def test_names_that_never_fall_asleep_end_the_run_in_set_up():
    client, clock, loop, naps = make(asleep=0, settle_max_s=5.0)
    with pytest.raises(RuntimeError, match="asleep"):
        loop.start()
    assert not loop.issuing and sum(naps) <= 5.0 + closed_wake.SETTLE_POLL_S


def test_a_budget_dict_yields_the_plain_closed_loop():
    client = SleepyClient(0)
    warm = closed_wake.Loop(client, NAMES, TARGETS, {
        **BASE, "in_flight": len(NAMES), "key_dist": "slot", "budget": 1},
        seed=1)
    assert type(warm) is closed.ClosedLoop
    warm.start()
    client.deliver(len(NAMES))
    assert sorted(r.name for r in warm.reqs) == list(range(len(NAMES)))
    assert not warm.outstanding() and client.asked == []
    assert type(closed_wake.Loop(client, NAMES, TARGETS, BASE, seed=1)) \
        is closed_wake.ClosedWakeLoop


def test_a_client_library_without_admin_sync_cannot_run_the_loop():
    with pytest.raises(RuntimeError, match="admin_sync"):
        closed_wake.Loop(FakeClient(), NAMES, TARGETS,
                         {**BASE, "budget": 1}, seed=1)


def test_the_summary_tells_wakes_from_resident_writes(capsys):
    client, clock, loop, _ = make()
    loop.start()
    for _ in range(40):
        clock.t += 0.25
        client.deliver(1)
    loop.stop()
    loop.fail_outstanding()
    line = [json.loads(l) for l in capsys.readouterr().err.splitlines()
            if l.startswith('{"wakes"')][-1]["wakes"]
    assert line["issued"] == len(loop.wakes) >= 5
    assert line["acked"] + line["failed"] >= line["issued"] - 3
    assert line["asleep_at_settle"] == line["asleep_at_stop"] == [36] * 3
    assert line["wake_ms"]["n"] == line["acked"] == sum(line["acked_by_s"])
    assert line["resident_ms"]["n"] > line["wake_ms"]["n"]


# ---------------------------------------------------------------------------
# the cell, on the CPU, at a tiny size
# ---------------------------------------------------------------------------
@pytest.fixture
def tiny(tmp_path, monkeypatch):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(run, "PEAKS_FILE", str(peaks))
    monkeypatch.setattr(run, "WARM_TRAFFIC_S", 1.5)
    monkeypatch.setattr(run, "WARM_ROUND_RAMP_S", 0.5)
    monkeypatch.setattr(run, "SETTLE_S", 0.5)
    monkeypatch.setattr(run, "TRACE_S", 1.0)
    monkeypatch.setattr(run, "READ_BACK_S", 2.0)
    monkeypatch.setattr(closed_wake, "SETTLE_POLL_S", 0.5)
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))

    def small(config):
        # the sweep every 2 s takes what sat idle for 2 s; a name woken
        # is left alone for 1 s
        return {**config, "names": 24,
                "settings": {**config["settings"], "ENGINE_ROWS": 256,
                             "DEACTIVATION_PERIOD_S": 2.0,
                             "PAUSE_EVICTION_HYSTERESIS_S": 1.0},
                "engine": {**config["engine"], "rows": 256}}
    return small


def lines(capsys, key):
    out = capsys.readouterr()
    return [json.loads(l) for l in (out.out + out.err).splitlines()
            if l.startswith('{"' + key)]


def test_names_sleep_and_wake_in_a_sound_traced_run(tiny, capsys):
    _, config, traffic, specs, e2e = run.load_cell("g1k-wake")
    assert config["settings"]["PAUSE_OPTION"] is True
    assert config["settings"]["DEACTIVATION_PERIOD_S"] == 30
    traffic = {**traffic, "in_flight": 3, "hot_names": 4, "wake_every": 4,
               "settle_max_s": 40.0}
    result = run.run_cell(tiny(config), traffic, specs, e2e, seed=2**31 + 31,
                          seconds=6.0, trace=True, expect_platform="cpu")
    out = capsys.readouterr()
    both = (out.out + out.err).splitlines()
    checks = {c["check"]: c for c in map(json.loads, (
        l for l in both if l.startswith('{"check')))}
    wakes = [json.loads(l) for l in both if l.startswith('{"wakes')][-1]["wakes"]
    assert result["correct"] is True and result["failed"] == 0, checks
    assert checks["refusals"]["value"] == 0
    assert checks["ack_value_mismatches"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0
    assert wakes["asleep_at_settle"] == [20, 20, 20]
    assert wakes["acked"] >= 5 and wakes["failed"] == 0, wakes
    assert min(wakes["asleep_at_stop"]) > 0, wakes
    got = result["metrics"]
    # no device program of the restore's name on the CPU's planes
    assert set(got) == {s["name"] for s in specs} \
        - {"residency.restore_device_ms.wke"}, set(got)
    assert got["residency.wakes_per_s.wke"]["value"] > 0
    assert got["residency.held_per_wake.wke"]["value"] > 0
    for name in ("residency.wake_ms.wke", "residency.resume_ms.wke",
                 "residency.unpause_ms.wke", "residency.pause_ms.wke"):
        assert got[name]["value"] > 0


def test_a_broken_guarantee_in_a_name_that_sleeps_is_not_correct(tiny, capsys):
    import faults

    _, config, traffic, specs, e2e = run.load_cell("g1k-wake")
    config = tiny(config)
    traffic = {**traffic, "in_flight": 3, "hot_names": 4, "wake_every": 4,
               "settle_max_s": 40.0}
    # the warm-up write of the first COLD name: it then sleeps, and the
    # first write after its wake answers with a sum that lacks it
    with faults.FAULTS["dropped_write"](run.cell_names(config)[4], nth=1):
        result = run.run_cell(config, traffic, specs, e2e, seed=11,
                              seconds=3.0, trace=False,
                              expect_platform="cpu")
    assert result["correct"] is False
    failed = {c["check"] for c in lines(capsys, "check") if not c["ok"]}
    assert "replica_total_mismatches" in failed
