"""Recovery-at-scale smoke (slow): a node hosting thousands of groups
restarts, serves a hot name BEFORE background hydration completes, and
converges.  Asserts phase/ordering facts only — never wall-clock (full
restart-to-serving numbers live in ``scripts/recovery_probe.py`` output,
committed as RECOVERY_r01.json)."""

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.utils.config import Config

G = 4096
N_NAMES = 2048
HOT = 64


def _ticks(m, n=6):
    for _ in range(n):
        m.tick_host(None, np.array([True]))


@pytest.mark.slow
def test_restart_serves_hot_before_hydration_completes(tmp_path):
    from gigapaxos_tpu.manager import PaxosManager
    from gigapaxos_tpu.recovery.hydration import Hydrator

    Config.set("RECOVERY_CHECKPOINT_SHARDS", "8")
    Config.set("RECOVERY_HOT_NAMES", str(HOT))
    Config.set("RECOVERY_REPLAY_WORKERS", "4")
    cfg = EngineConfig(n_groups=G, window=8, req_lanes=4, n_replicas=1)
    names = [f"svc{i:05d}" for i in range(N_NAMES)]

    m = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9, sync_journal=False,
    )
    for lo in range(0, N_NAMES, 512):
        m.create_paxos_batch(names[lo:lo + 512], [0])
    # traffic on a recent slice (these become the manifest's hot hints)
    active = names[-32:]
    for i, nm in enumerate(active):
        m.propose(nm, str(i + 1))
    _ticks(m, 10)
    m.checkpoint_now()
    m.logger.drain_checkpoints()
    # post-checkpoint tail so replay has real work
    m.propose(active[0], "100")
    _ticks(m, 8)
    expected = {nm: int(i) + 1 for i, nm in enumerate(active)}
    expected[active[0]] += 100
    m.close()

    # restart with the background worker held, so the ordering assertion
    # ("hot served while cold backlog outstanding") is deterministic
    held = []
    orig = Hydrator.start_background
    try:
        Hydrator.start_background = lambda self: held.append(self)
        m2 = PaxosManager(
            0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
            checkpoint_every=10 ** 9, sync_journal=False,
        )
    finally:
        Hydrator.start_background = orig
    try:
        # ORDERING FACT 1: the node is serving (construction returned)
        # while most names are still cold
        st = m2.recovery_stats()
        assert st["phase"] == "recovering"
        assert st["hydration_backlog"] >= N_NAMES - HOT - 64
        assert st["hot_hydrated"] > 0

        # ORDERING FACT 2: a hot name answers correctly NOW — before any
        # background hydration ran
        hot_name = active[-1]
        assert m2.names[hot_name] not in m2.hydrating_rows, (
            "recency hints must make recently-active names hot"
        )
        got = {}
        m2.propose(hot_name, "5", callback=lambda r, v: got.update(v=v))
        _ticks(m2, 8)
        assert got.get("v") == str(expected[hot_name] + 5), got
        assert m2.recovery_phase == "recovering"  # still recovering

        # ORDERING FACT 3: a cold name's request does not execute until
        # hydration, then drains with state intact
        cold_name = names[0]
        assert m2.names[cold_name] in m2.hydrating_rows
        got2 = {}
        m2.propose(cold_name, "9", callback=lambda r, v: got2.update(v=v))
        _ticks(m2, 3)
        assert not got2

        # release the held worker and converge
        assert held, "lazy restart must have scheduled background work"
        held[0].start_background()
        import time

        deadline = time.time() + 120
        while m2.recovery_phase != "serving" and time.time() < deadline:
            time.sleep(0.05)
        assert m2.recovery_phase == "serving"
        _ticks(m2, 8)
        assert got2.get("v") == "9"
        for nm, exp in expected.items():
            want = exp + (5 if nm == hot_name else 0)
            assert m2.app.totals.get(nm) == want, (nm, m2.app.totals.get(nm))
    finally:
        m2.close()
