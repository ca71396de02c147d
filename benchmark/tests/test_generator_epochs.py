"""The closed loop with epoch changes beside it, against a fake client."""

import json
import os
import time

import pytest

from generators import closed_epochs
from test_generator import NAMES, TARGETS, Clock, FakeClient

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = {"loop": "closed_epochs", "entry": "round_robin_by_name",
        "retransmit_s": 8.0, "fail_after_s": 30.0, "in_flight": 6,
        "key_dist": "slot", "per_name_order": True,
        "reconfigure_per_s": 50.0}


class EpochClient(FakeClient):
    """Answers ``reconfigure`` as reconfigurators with (or without)
    RECONFIGURE_IN_PLACE would."""

    def __init__(self, in_place=True, answer=lambda name, n: "rise"):
        super().__init__()
        self.in_place, self.answer = in_place, answer
        self.epochs, self.calls = {}, []

    def reconfigure(self, name, new_actives, timeout=15.0):
        self.calls.append((name, tuple(new_actives), timeout))
        how = self.answer(name, len(self.calls)) if self.in_place else "same"
        if how == "silent":
            return None
        if how == "refuse":
            return {"name": name, "ok": False, "reason": "not-ready"}
        if how == "rise":
            self.epochs[name] = self.epochs.get(name, 0) + 1
        elif how == "jump":
            self.epochs[name] = self.epochs.get(name, 0) + 2
        return {"name": name, "ok": True, "actives": list(new_actives),
                "epoch": self.epochs.get(name, 0)}


def make(client, seed=3, **traffic):
    clock = Clock()
    loop = closed_epochs.Loop(client, NAMES, TARGETS, {**BASE, **traffic},
                              seed, clock=clock)
    return clock, loop


def drive(loop, n, due=0.0):
    """The schedule by hand: the first ``n`` reconfigurations, called on
    this thread (the scheduler and the pool are the wall clock's)."""
    out = []
    for k in range(n):
        rc = loop.issue(loop.order[k % len(loop.order)], due + k)
        loop.call(rc)
        out.append(rc)
    return out


def test_names_follow_a_seeded_permutation_and_epochs_rise_by_one():
    client = EpochClient()
    clock, loop = make(client)
    loop.issuing = True
    loop.t_start = 0.0
    done = drive(loop, 2 * len(NAMES) + 1)
    order = [NAMES[rc.name] for rc in done]
    assert sorted(order[:len(NAMES)]) == sorted(NAMES)      # a permutation
    assert order[len(NAMES):2 * len(NAMES)] == order[:len(NAMES)]  # again
    assert all(args == (0, 1, 2) and t == 15.0 for _, args, t in client.calls)
    assert not loop.errors and loop.fatal is None
    assert loop.epoch[done[0].name] == 3 and loop.calls_in_flight == 0
    _, other = make(EpochClient(), seed=4)
    _, same = make(EpochClient(), seed=3)
    assert same.order == loop.order != other.order
    _, big = make(EpochClient(), seed=2**31 + 5)
    assert sorted(big.order) == list(range(len(NAMES)))


@pytest.mark.parametrize("how, said", [
    ("silent", "unanswered"), ("refuse", "refused: not-ready"),
    ("jump", "epoch 2 after 0"), ("same", "epoch 1 after 1"),
])
def test_what_is_not_one_epoch_up_is_a_refusal(how, said):
    # the first call is sound; the second is not
    client = EpochClient(answer=lambda name, n: "rise" if n == 1 else how)
    clock, loop = make(client)
    loop.issuing, loop.t_start = True, 0.0
    first = loop.issue(0, 0.0)
    loop.call(first)
    second = loop.issue(0 if how == "same" else 1, 1.0)
    loop.call(second)
    assert loop.errors == [(NAMES[second.name], "reconfigure: " + said)]
    assert loop.fatal is None
    loop.poll()                       # a refusal is counted, not raised


def test_an_epoch_that_did_not_rise_at_the_first_call_raises():
    """The parent's tree: no RECONFIGURE_IN_PLACE, so a same-set
    reconfigure is acknowledged at the epoch the name had."""
    client = EpochClient(in_place=False)
    clock, loop = make(client)
    loop.issuing, loop.t_start = True, 0.0
    loop.poll()
    drive(loop, 1)
    with pytest.raises(RuntimeError, match="RECONFIGURE_IN_PLACE"):
        loop.poll()
    assert loop.errors


def test_budget_issues_no_reconfiguration_and_is_the_closed_loop():
    client = EpochClient()
    clock, warm = make(client, budget=1)
    warm.start()
    assert warm.threads == [] and warm.per_s == 0.0
    client.deliver(len(NAMES))
    assert warm.outstanding() == 0 and len(warm.reqs) == len(NAMES)
    assert not client.calls


def test_the_schedule_runs_on_the_wall_clock_beside_the_writes(capsys):
    client = EpochClient()
    loop = closed_epochs.Loop(client, NAMES, TARGETS, BASE, 11)
    loop.start()
    assert len(client.sends) == len(NAMES)      # the foreground, as closed
    t_end = time.perf_counter() + 0.5
    while time.perf_counter() < t_end:
        client.deliver(min(2, len(client.sends)))
        loop.poll()
        time.sleep(0.01)
    loop.stop()
    # 50 a second for 0.5 s, or for as long as a busy box made of it
    ran_s = time.perf_counter() - loop.t_start
    client.deliver(len(client.sends))
    for _ in range(100):
        if not loop.outstanding():
            break
        time.sleep(0.01)
    assert loop.outstanding() == 0
    loop.fail_outstanding()
    assert 15 <= len(loop.reconfs) <= 50 * ran_s + 2
    due = [rc.t_due - loop.t_start for rc in loop.reconfs]
    assert due == pytest.approx([(k + 1) / 50.0 for k in range(len(due))])
    assert not loop.errors and len(loop.reqs) > len(NAMES)
    said = json.loads(capsys.readouterr().err.splitlines()[-1])
    said = said["reconfigurations"]
    assert said["issued"] == said["ok"] == len(loop.reconfs)
    assert sum(said["ok_by_second"]) == said["ok"] and said["errors"] == 0


def test_the_cells_traffic_file_is_closed_1_per_name_with_a_schedule():
    mix = json.load(open(os.path.join(
        BENCH, "traffic", "closed-1-per-name-epochs.json")))
    plain = json.load(open(os.path.join(
        BENCH, "traffic", "closed-1-per-name.json")))
    for key in plain:
        if key not in ("loop", "why"):
            assert mix[key] == plain[key], key
    assert mix["loop"] == "closed_epochs"
    assert mix["reconfigure_per_s"] == int(mix["reconfigure_per_s"]) > 0
