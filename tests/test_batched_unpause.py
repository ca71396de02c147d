"""Batched unpause parity + admission-aware eviction ordering.

The density campaign's correctness pin: ``resume_group_batch`` (ONE
fused device install for N woken rows) must be bit-exact with the
per-name ``resume_group`` loop on EVERY engine leaf — including the
forced-pause shapes chaos finds #23/#24 exposed (a record captured with
the app lagging the engine frontier, and window remnants / held vids
riding the record).  Two managers are fed byte-identical histories, one
wakes per-name and one batched, and all 19 state leaves plus the host
bookkeeping must agree."""

import numpy as np
import pytest

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.utils.config import Config

NAMES = [f"par{i}" for i in range(8)]


def ticks(m, n=3):
    for _ in range(n):
        m.tick_host(None, np.array([True]))


def _mk(tmp_path, tag, G=64, W=8):
    cfg = EngineConfig(n_groups=G, window=W, req_lanes=4, n_replicas=1)
    return PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path / tag),
        checkpoint_every=10 ** 9, sync_journal=False,
    )


def _drive_and_sleep(m):
    """Identical history for both managers: varied decided traffic, two
    names left NON-QUIESCENT (requests still queued at pause — the
    forced-pause record carries them as held vids / window remnants),
    then one batched hibernate of everything."""
    m.create_paxos_batch(NAMES, [0])
    for rnd in range(3):
        for i, nm in enumerate(NAMES[: 6]):
            m.propose(nm, str(10 + rnd + i))
        ticks(m, 3)
    ticks(m, 4)
    # in-flight at pause: proposed, NOT ticked
    m.propose(NAMES[6], "777")
    m.propose(NAMES[7], "888")
    assert m.hibernate_batch(NAMES) == len(NAMES)
    assert len(m.names) == 0


def _leafdict(m):
    return {f: np.asarray(getattr(m.state, f))
            for f in m.state._fields}


def _assert_parity(m1, m2):
    l1, l2 = _leafdict(m1), _leafdict(m2)
    for f in l1:
        assert np.array_equal(l1[f], l2[f]), f"leaf {f} diverged"
    assert m1.names == m2.names
    assert m1.app.totals == m2.app.totals
    assert {r: list(q) for r, q in m1.queues.items() if q} == \
           {r: list(q) for r, q in m2.queues.items() if q}
    assert m1._needs_state == m2._needs_state
    assert np.array_equal(m1.app_exec_slot, m2.app_exec_slot)


def test_batched_resume_bit_exact_vs_sequential(tmp_path):
    m1 = _mk(tmp_path, "seq")
    m2 = _mk(tmp_path, "bat")
    try:
        _drive_and_sleep(m1)
        _drive_and_sleep(m2)
        _assert_parity(m1, m2)  # identical histories to start from

        for nm in NAMES:  # per-name loop: N device installs
            assert m1.restore(nm)
        res = m2.restore_batch(NAMES)  # ONE fused install
        assert res == len(NAMES)

        _assert_parity(m1, m2)  # bit-exact right after the wake
        ticks(m1, 6)  # held vids re-propose and decide identically
        ticks(m2, 6)
        _assert_parity(m1, m2)
        # the in-flight requests actually landed exactly once
        for nm, want in ((NAMES[6], 777), (NAMES[7], 888)):
            assert m1.app.totals.get(nm) == want
    finally:
        m1.close()
        m2.close()


def test_batched_resume_nonquiescent_record_parks_needs_state(tmp_path):
    """Chaos-find #23 shape: a forced-pause record whose ``app_exec``
    lags the engine frontier must park the row in ``_needs_state`` (the
    app cannot serve until a state pull catches it up) — identically on
    both wake paths."""
    m1 = _mk(tmp_path, "seq23")
    m2 = _mk(tmp_path, "bat23")
    try:
        for m in (m1, m2):
            m.create_paxos_batch(NAMES[:2], [0])
            for _ in range(3):
                m.propose(NAMES[0], "5")
                ticks(m, 3)
            row = m.names[NAMES[0]]
            # simulate the app lagging the frontier at pause time (the
            # #23 interleaving: forced pause raced the execute drain)
            m.app_exec_slot[row] = max(0, int(m.app_exec_slot[row]) - 2)
            assert m.pause_group(NAMES[0], 0, force=True) == "ok"
            assert m.pause_group(NAMES[1], 0, force=True) == "ok"
        assert m1.restore(NAMES[0]) and m1.restore(NAMES[1])
        assert m2.restore_batch(NAMES[:2]) == 2
        _assert_parity(m1, m2)
        assert m1.names[NAMES[0]] in m1._needs_state
        assert m2.names[NAMES[0]] in m2._needs_state
        assert m2.names[NAMES[1]] not in m2._needs_state
    finally:
        m1.close()
        m2.close()


def test_restore_batch_mixed_known_unknown(tmp_path):
    m = _mk(tmp_path, "mix")
    try:
        m.create_paxos_batch(NAMES[:4], [0])
        assert m.hibernate_batch(NAMES[:4]) == 4
        # unknown names and already-awake names don't poison the batch
        assert m.restore_batch([NAMES[0], "ghost", NAMES[1]]) == 2
        assert m.restore_batch([NAMES[0], NAMES[2]]) == 2  # 1 awake + 1
        assert set(m.names) == {NAMES[0], NAMES[1], NAMES[2]}
    finally:
        m.close()


def test_eviction_candidates_cold_first_heat_tiebreak(tmp_path):
    """Sweep order: oldest activity first, PR-18 group heat as the
    tiebreak; queued/pending/recently-resumed names never listed."""
    m = _mk(tmp_path, "evict")
    try:
        Config.set("PAUSE_EVICTION_HYSTERESIS_S", "3600")
        pool = ["cold", "warmish", "hot_old", "busy", "fresh", "flappy"]
        m.create_paxos_batch(pool, [0])
        # heat: hot_old sees real traffic, others stay cold
        for _ in range(4):
            m.propose("hot_old", "1")
            ticks(m, 3)
        m.pull_group_heat()  # drain the device accumulator into _heat_host
        now = __import__("time").time()
        for nm, age in (("cold", 500), ("warmish", 500),
                        ("hot_old", 500), ("busy", 500), ("fresh", 1)):
            m.row_activity[m.names[nm]] = now - age
        m.propose("busy", "9")  # queued admission: not idle by definition
        order = m.eviction_candidates(idle_s=60.0)
        listed = [nm for nm, _e in order]
        assert "busy" not in listed  # queued work
        assert "fresh" not in listed  # inside the idle cut
        # equal activity times: heat breaks the tie, coldest first
        assert listed.index("hot_old") > listed.index("cold")
        assert listed.index("hot_old") > listed.index("warmish")
        # limit takes the head of the sorted order, not an arbitrary set
        capped = m.eviction_candidates(idle_s=60.0, limit=2)
        assert [nm for nm, _e in capped] == listed[:2]

        # hysteresis: a just-resumed name is exempt from the next sweep
        assert m.hibernate("flappy")
        assert m.restore("flappy")
        m.row_activity[m.names["flappy"]] = now - 500
        assert "flappy" not in [
            nm for nm, _e in m.eviction_candidates(idle_s=60.0)
        ]
        Config.set("PAUSE_EVICTION_HYSTERESIS_S", "0.0")
        assert "flappy" in [
            nm for nm, _e in m.eviction_candidates(idle_s=60.0)
        ]
    finally:
        Config.clear()
        m.close()
