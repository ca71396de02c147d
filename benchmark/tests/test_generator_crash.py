"""The closed loop with one active going dark (``generators/
closed_crash.py``) against a fake client, and ``g1k-crash`` end to end on
the CPU at a tiny size (``test_generator_wake.py``'s way)."""

import json
import re

import pytest

import run
import trace_reduce
from generators import closed, closed_crash
from test_generator import TARGETS, Clock, FakeClient

NAMES = [f"n{i}" for i in range(6)]
BASE = {"loop": "closed_crash", "entry": "round_robin_by_name",
        "retransmit_s": 8.0, "fail_after_s": 30.0, "in_flight": 6,
        "key_dist": "slot", "per_name_order": True,
        "crash_active": 1, "crash_at_s": 14.0, "down_s": 14.0,
        "failover_s": 8.0}


class CrashClient(FakeClient):
    """Answers the ``crash`` admin op as ``answers[active]`` says (a dict,
    or None for no answer), and keeps what it was asked."""

    def __init__(self, answers=None):
        super().__init__()
        self.answers = answers or {}
        self.asked = []

    def admin_sync(self, active, body, timeout=5.0):
        self.asked.append((active, dict(body)))
        return self.answers.get(active, {"op": body["op"], "ok": True})


def make(answers=None, seed=3, **traffic):
    client, clock = CrashClient(answers), Clock()
    loop = closed_crash.ClosedCrashLoop(
        client, NAMES, TARGETS, {**BASE, **traffic}, seed, clock=clock)
    return client, clock, loop


def test_start_probes_every_active_and_a_program_without_the_op_ends_there():
    client, _, loop = make()
    loop.start()
    assert client.asked == [(i, {"op": "crash", "for_s": 0})
                            for i in range(3)]
    assert len(loop.reqs) == 6 and loop.crash is None
    # the parent answers unknown_op; a node that does not allow it, its
    # refusal; a node that does not answer, nothing
    for bad in ({"op": "crash", "ok": False, "error": "unknown_op"},
                {"op": "crash", "ok": False,
                 "error": "crash_emulation_not_allowed"}, None):
        client, _, loop = make(answers={2: bad})
        with pytest.raises(RuntimeError, match="crash probe"):
            loop.start()
        assert not loop.reqs and not loop.issuing


def test_the_crash_is_sent_once_at_its_time_and_does_not_hold_the_loop():
    client, clock, loop = make()
    loop.start()
    probes = len(client.asked)
    clock.t = 13.9
    loop.poll()
    assert len(client.asked) == probes and loop.crash is None
    clock.t = 14.1
    loop.poll()
    loop._crash_thread.join(5.0)
    assert client.asked[probes:] == [(1, {"op": "crash", "for_s": 14.0})]
    assert loop.crash["sent_s"] == pytest.approx(14.1)
    assert loop.crash["answer"]["ok"] is True
    for clock.t in (15.0, 20.0, 40.0):
        loop.poll()
    assert len(client.asked) == probes + 1
    # not after the loop was stopped either
    client, clock, loop = make()
    loop.start()
    loop.stop()
    clock.t = 20.0
    loop.poll()
    assert loop.crash is None


def test_an_unanswered_request_moves_to_the_next_active_and_its_client_stays():
    client, clock, loop = make(crash_at_s=1e9)
    loop.start()
    first = {r.name: r for r in loop.reqs}
    assert [a for a, *_ in client.log] == [TARGETS[i % 3] for i in range(6)]
    # names 1 and 4 enter at active 1, which answers nothing; the others
    # are answered 5 s in, and their clients' next requests go out then
    clock.t = 5.0
    for _ in range(6):
        client.deliver(1, drop=client.sends[0][0] == TARGETS[1])
    client.sends.clear()  # ... and are not answered for now
    clock.t = 7.9
    sent = len(client.log)
    loop.poll()
    assert len(client.log) == sent
    clock.t = 8.0
    loop.poll()
    # the same ids, to the NEXT active in index order
    assert sorted(client.log[sent:]) == sorted(
        (TARGETS[2], NAMES[n], f"{first[n].delta:010d}", first[n].rid)
        for n in (1, 4))
    assert first[1].sends == 2 and loop.moves[first[1].rid] == 1
    # unanswered there too: on to active 0, eight seconds after THAT send
    client.sends.clear()
    clock.t = 15.9
    loop.poll()     # (the other four, sent at 5.0, have moved at 13.0)
    sent = len(client.log)
    clock.t = 16.0
    loop.poll()
    assert sorted((a, rid) for a, _n, _v, rid in client.log[sent:]) == \
        sorted((TARGETS[0], first[n].rid) for n in (1, 4))
    # answered there: the client's next requests enter at active 0 too
    client.sends = type(client.sends)(
        s for s in client.sends if s[4] in (first[1].rid, first[4].rid))
    client.deliver(2)
    client.deliver(2)
    later = [(a, n) for a, n, _v, rid in client.log
             if n in (NAMES[1], NAMES[4])
             and rid not in (first[1].rid, first[4].rid)]
    assert len(later) == 4 and all(a == TARGETS[0] for a, _n in later)
    assert loop.shift[1] == loop.shift[4] == 2
    assert client.max_per_name == 1 and not loop.failed and not loop.errors


def test_failover_s_is_the_only_resend():
    with pytest.raises(ValueError, match="retransmit_s"):
        make(retransmit_s=20.0)
    with pytest.raises(ValueError, match="crash_active"):
        make(crash_active=3)


def test_a_budget_dict_yields_the_plain_closed_loop():
    client = CrashClient()
    warm = closed_crash.Loop(client, NAMES, TARGETS, {
        **BASE, "budget": 1}, seed=1)
    assert type(warm) is closed.ClosedLoop
    warm.start()
    client.deliver(len(NAMES))
    assert sorted(r.name for r in warm.reqs) == list(range(len(NAMES)))
    assert not warm.outstanding() and client.asked == []
    assert type(closed_crash.Loop(client, NAMES, TARGETS, BASE, seed=1)) \
        is closed_crash.ClosedCrashLoop


def test_the_same_seed_draws_the_same_requests():
    def deltas(seed):
        client, _, loop = make(seed=seed)
        loop.start()
        while len(loop.reqs) < 40:
            client.deliver(1)
        return [(r.name, r.delta) for r in loop.reqs[:40]]

    assert deltas(2**31 + 5) == deltas(2**31 + 5)
    assert deltas(2**31 + 5) != deltas(2**31 + 6)


def test_the_summary_says_what_the_crash_cost(capsys):
    client, clock, loop = make(crash_at_s=2.0, down_s=3.0, failover_s=1.0,
                               retransmit_s=1.0)
    loop.start()
    for step in range(40):
        clock.t += 0.25
        loop.poll()
        dark = 2.0 <= clock.t < 5.0
        for _ in range(len(client.sends)):
            addr = client.sends[0][0]
            client.deliver(1, drop=dark and addr == TARGETS[1])
    loop.stop()
    client.deliver(len(client.sends))  # the drain
    loop.fail_outstanding()
    line = [json.loads(l) for l in capsys.readouterr().err.splitlines()
            if l.startswith('{"crash"')][-1]["crash"]
    assert line["sent_s"] == pytest.approx(2.0) and line["answer"]["ok"]
    assert line["active"] == 1 and line["down_s"] == 3.0
    assert line["requests_moved"] == 2 == line["moves"]
    assert line["clients_by_moves"] == [[0, 4], [1, 2]]
    assert sum(line["acked_by_s"]) == sum(
        1 for r in loop.reqs if r.t_ack is not None)
    assert line["longest_gap_ms"]["names"] == 6
    assert line["longest_gap_ms"]["max"] >= 1000.0 > \
        line["longest_gap_ms"]["p50"]
    assert line["failed"] == 0


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
    monkeypatch.setattr(run, "READ_BACK_S", 5.0)
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))

    def small(config, **settings):
        return {**config, "names": 24,
                "settings": {**config["settings"], "ENGINE_ROWS": 256,
                             "FAILURE_DETECTION_TIMEOUT_S": 1.0, **settings},
                "engine": {**config["engine"], "rows": 256}}
    return small


TINY_TRAFFIC = {"in_flight": 24, "ramp_s": 0.5, "crash_at_s": 2.5,
                "down_s": 2.5, "failover_s": 1.5, "retransmit_s": 1.5}


def test_one_active_goes_dark_in_the_window_and_the_run_is_correct(
        tiny, capsys):
    _, config, traffic, specs, e2e = run.load_cell("g1k-crash")
    assert config["settings"]["ALLOW_CRASH_EMULATION"] is True
    assert config["settings"]["FAILURE_DETECTION_TIMEOUT_S"] == 6.0
    assert e2e == ["committed_rps", "setup_s"]
    # dark 1 s into the window of 7 s, back 3.5 s in, traced 3 to 4 s in
    result = run.run_cell(tiny(config), {**traffic, **TINY_TRAFFIC}, specs,
                          e2e, seed=2**31 + 35, seconds=7.0, trace=True,
                          expect_platform="cpu")
    out = capsys.readouterr()
    both = (out.out + out.err).splitlines()
    checks = {c["check"]: c for c in map(json.loads, (
        l for l in both if l.startswith('{"check')))}
    crash = [json.loads(l) for l in both
             if l.startswith('{"crash')][-1]["crash"]
    assert result["correct"] is True and result["failed"] == 0, checks
    for check in ("refusals", "ack_value_mismatches",
                  "replica_total_mismatches", "compiles_in_window"):
        assert checks[check]["value"] == 0, checks[check]
    assert crash["answer"]["ok"] is True and crash["failed"] == 0
    assert crash["requests_moved"] > 0, crash
    got = result["metrics"]
    assert set(got) == {s["name"] for s in specs}, \
        {s["name"] for s in specs} - set(got)
    assert 1000.0 <= got["failover.detect_ms.crs"]["value"] < 2500.0
    for name in ("failover.election_ms.crs", "failover.unserved_ms.crs",
                 "failover.catchup_ms.crs", "tick.elections.sat"):
        assert got[name]["value"] > 0, name
    # a fallback ran: a whole frame, a whole vector up
    assert got["transport.blob_delta_share.sat"]["value"] < 100.0
    assert got["tick.gather_scatter_share.sat"]["value"] < 100.0


def test_a_node_that_does_not_allow_the_crash_ends_the_run_in_set_up(tiny):
    _, config, traffic, specs, e2e = run.load_cell("g1k-crash")
    with pytest.raises(RuntimeError, match="ALLOW_CRASH_EMULATION"):
        run.run_cell(tiny(config, ALLOW_CRASH_EMULATION=False),
                     {**traffic, **TINY_TRAFFIC}, specs, e2e, seed=5,
                     seconds=2.0, trace=False, expect_platform="cpu")


def test_a_repeated_write_across_the_crash_is_not_correct(tiny, capsys):
    import faults

    _, config, traffic, specs, e2e = run.load_cell("g1k-crash")
    config = tiny(config)
    with faults.FAULTS["double_execute"](run.cell_names(config)[1], nth=2):
        result = run.run_cell(config, {**traffic, **TINY_TRAFFIC}, specs, e2e,
                              seed=12, seconds=6.0, trace=False,
                              expect_platform="cpu")
    assert result["correct"] is False
    failed = {c["check"] for c in map(json.loads, (
        l for l in capsys.readouterr().out.splitlines()
        if l.startswith('{"check'))) if not c["ok"]}
    assert failed & {"ack_value_mismatches", "replica_total_mismatches"}
