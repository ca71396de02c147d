"""Density-at-scale smoke (slow): a population of names several times
larger than the engine boots through the batched create + hibernate
path, churns a rotating hot window through the packed spill store, and
converges.  Asserts residency/correctness facts only — never wall-clock
(the 1M-name numbers live in ``scripts/density_probe.py`` output,
committed as DENSITY_r01.json)."""

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.utils.config import Config

G = 1024
N_NAMES = 8192  # 8x the engine: most of the population is always asleep
WINDOW = 256  # awake working set per churn round


def _ticks(m, n=3):
    for _ in range(n):
        m.tick_host(None, np.array([True]))


@pytest.mark.slow
def test_population_exceeds_engine_and_churns(tmp_path):
    from gigapaxos_tpu.manager import PaxosManager

    Config.set("PACKED_SPILL", "true")
    Config.set("PAUSE_BATCH_SIZE", "64")  # store RAM tier = 256 records
    Config.set("SPILL_SEGMENT_BYTES", "65536")
    cfg = EngineConfig(n_groups=G, window=8, req_lanes=4, n_replicas=1)
    names = [f"d{i:05d}" for i in range(N_NAMES)]
    m = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9, sync_journal=False,
    )
    try:
        # boot: the population never fits — every chunk sleeps on creation
        for lo in range(0, N_NAMES, G):
            chunk = names[lo:lo + G]
            m.create_paxos_batch(chunk, [0])
            assert m.hibernate_batch(chunk) == len(chunk)
        res = m.residency_stats()
        assert res["paused_names"] == N_NAMES
        assert res["active_names"] == 0
        # the RAM tier is capacity-bounded regardless of population
        assert res["paused_in_memory"] <= 4 * 64
        assert (res["paused_in_memory"] + res["paused_on_disk"]
                == N_NAMES)
        assert res["store"]["kind"] == "packed"
        assert res["store"]["segments"] > 1

        # churn: a rotating window wakes batched, proposes, sleeps again
        expected = {}
        for rnd in range(6):
            lo = rnd * WINDOW * 3  # strided heads: every round mostly cold
            window = [names[(lo + i) % N_NAMES] for i in range(WINDOW)]
            cold = [nm for nm in window if nm not in m.names]
            assert m.restore_batch(cold) == len(cold)
            for i, nm in enumerate(window[: 64]):
                m.propose(nm, str(rnd + i + 1))
                expected[nm] = expected.get(nm, 0) + rnd + i + 1
            _ticks(m, 4)
            fell_out = [nm for nm in list(m.names) if nm not in set(window)]
            m.hibernate_batch(fell_out)
            assert len(m.names) <= WINDOW
        _ticks(m, 6)

        # convergence: wake everything that saw traffic; totals exact
        touched = sorted(expected)
        cold = [nm for nm in touched if nm not in m.names]
        assert m.restore_batch(cold) == len(cold)
        _ticks(m, 6)
        bad = {nm: (m.app.totals.get(nm), expected[nm])
               for nm in touched if m.app.totals.get(nm) != expected[nm]}
        assert not bad, f"lost/duplicated traffic across churn: {bad}"

        # conservation still holds at the end
        res = m.residency_stats()
        assert res["active_names"] + res["paused_names"] == N_NAMES
    finally:
        Config.clear()
        m.close()


@pytest.mark.slow
def test_batched_wake_burst_matches_sequential_at_scale(tmp_path):
    """A >=512-name wake burst through ``restore_batch`` lands the same
    awake set and app state as the per-name loop (scale companion to
    the bit-exact leaf parity in test_batched_unpause)."""
    from gigapaxos_tpu.manager import PaxosManager

    Config.set("PACKED_SPILL", "true")
    cfg = EngineConfig(n_groups=2048, window=8, req_lanes=4, n_replicas=1)
    names = [f"b{i:04d}" for i in range(1024)]
    m = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9, sync_journal=False,
    )
    try:
        m.create_paxos_batch(names, [0])
        for i, nm in enumerate(names[:128]):
            m.propose(nm, str(i + 1))
        _ticks(m, 6)
        want = dict(m.app.totals)
        assert m.hibernate_batch(names) == len(names)

        burst = names[: 512]
        assert m.restore_batch(burst) == len(burst)
        assert m.hibernate_batch(burst) == len(burst)
        for nm in burst:  # the N=1 path over the same set
            assert m.restore(nm)
        _ticks(m, 4)
        assert set(m.names) == set(burst)
        for nm in burst:
            assert m.app.totals.get(nm, 0) == want.get(nm, 0)
    finally:
        Config.clear()
        m.close()
