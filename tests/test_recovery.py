"""Recovery-plane tests: sharded checkpoints (manifest hashes, torn-shard
fallback, kill mid-shard-write), segmented parallel replay parity,
mid-replay crash idempotence, and the lazy-hydration gates — run against
both the native journal path and ``GP_NO_NATIVE=1``."""

import os

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig, init_state
from gigapaxos_tpu.storage import BlockType, Journal, PaxosLogger
from gigapaxos_tpu.storage.checkpoint import (
    MANIFEST,
    load_checkpoint_view,
    save_checkpoint,
)
from gigapaxos_tpu.utils.config import Config

CFG = EngineConfig(n_groups=8, window=4, req_lanes=2, n_replicas=3)


@pytest.fixture(params=["native", "python"])
def native_mode(request, monkeypatch):
    """Run journal-touching tests under both CRC/append paths."""
    import gigapaxos_tpu.native as nat

    if request.param == "python":
        monkeypatch.setenv("GP_NO_NATIVE", "1")
    nat._lib = None
    nat._tried = False
    yield request.param
    nat._lib = None
    nat._tried = False


def _state_arrays(cfg):
    return {
        k: np.asarray(v).copy() for k, v in init_state(cfg)._asdict().items()
    }


def _logger(tmp_path, shards=4, **kw):
    Config.set("RECOVERY_CHECKPOINT_SHARDS", str(shards))
    return PaxosLogger(0, str(tmp_path), **kw)


def _seed_groups(lg, n=4):
    lg.log_create(
        np.arange(n), np.full(n, 0b111), np.zeros(n, np.int64),
        np.zeros(n, np.int64),
    )


# ---------------------------------------------------------------------------
# sharded checkpoints
# ---------------------------------------------------------------------------

def test_sharded_recover_matches_legacy(tmp_path, native_mode):
    """The same history recovered through a sharded checkpoint and a
    legacy single-pair checkpoint must be identical."""
    dirs = {}
    for mode, shards in (("sharded", 4), ("legacy", 1)):
        d = tmp_path / mode
        lg = _logger(d, shards=shards)
        _seed_groups(lg)
        lg.log_accepts(
            np.array([0, 1]), np.array([0, 0]),
            np.array([32, 32]), np.array([100, 200]),
        )
        rec = lg.recover(CFG.window, seed_arrays=_state_arrays(CFG))
        lg.checkpoint(
            rec.arrays, {"svc0": "s0", "svc1": "s1"},
            {"names": {"svc0": 0, "svc1": 1}},
        )
        lg.log_decisions(np.array([0]), np.array([0]), np.array([100]))
        lg.close()
        lg2 = _logger(d, shards=shards)
        dirs[mode] = lg2.recover(CFG.window)
        lg2.close()
    a, b = dirs["sharded"], dirs["legacy"]
    for k in a.arrays:
        assert (a.arrays[k] == b.arrays[k]).all(), k
    assert a.meta["app_states"] == b.meta["app_states"]
    assert a.decisions == b.decisions


def test_torn_shard_falls_back_to_prev_anchor(tmp_path, native_mode):
    """Corrupting one shard of the newest generation must fail its
    manifest hash; recovery falls back to the previous generation's
    anchor and REPLAYS the journal gap — end state identical."""
    lg = _logger(tmp_path, shards=4)
    _seed_groups(lg)
    rec0 = lg.recover(CFG.window, seed_arrays=_state_arrays(CFG))
    lg.checkpoint(rec0.arrays, {"svc": "gen1"}, {"names": {"svc": 0}})
    # post-gen1 history, then a second checkpoint covering it
    lg.log_decisions(np.array([0, 0]), np.array([0, 1]), np.array([7, 8]))
    lg.log_payloads({7: "p7", 8: "p8"})
    rec1 = lg.recover(CFG.window)
    lg.checkpoint(rec1.arrays, {"svc": "gen2"}, {"names": {"svc": 0}})
    lg.close()

    # tear a generation-2 shard mid-body (simulated partial write)
    view = load_checkpoint_view(str(tmp_path))
    assert view.generation == 2
    import json

    with open(os.path.join(str(tmp_path), MANIFEST)) as f:
        man = json.load(f)
    victim = os.path.join(str(tmp_path), man["shards"][0]["file"])
    with open(victim, "r+b") as f:
        f.seek(40)
        f.write(b"TORNTORN")

    lg2 = _logger(tmp_path, shards=4)
    rec2 = lg2.recover(CFG.window)
    # fell back to generation 1 ... (earlier anchor)
    assert rec2.stats["checkpoint_generation"] == 1
    assert rec2.meta["app_states"] == {"svc": "gen1"}
    # ... and the journal replay closed the gap: both decisions are back
    assert rec2.decisions[0] == {0: 7, 1: 8}
    assert rec2.payloads == {7: "p7", 8: "p8"}
    lg2.close()


def test_kill_mid_checkpoint_shard_write(tmp_path, native_mode, monkeypatch):
    """A crash AFTER some shards are written but BEFORE the manifest
    lands must leave the previous generation fully loadable (the orphan
    shards are invisible without their manifest)."""
    lg = _logger(tmp_path, shards=4)
    _seed_groups(lg)
    rec0 = lg.recover(CFG.window, seed_arrays=_state_arrays(CFG))
    lg.checkpoint(rec0.arrays, {"svc": "gen1"}, {"names": {"svc": 0}})

    import gigapaxos_tpu.storage.checkpoint as ck

    real_write = ck._fsync_write

    def die_at_manifest(path, data):
        if MANIFEST in path:
            raise OSError("simulated crash mid-checkpoint")
        real_write(path, data)

    monkeypatch.setattr(ck, "_fsync_write", die_at_manifest)
    with pytest.raises(OSError):
        lg.checkpoint(rec0.arrays, {"svc": "gen2"}, {"names": {"svc": 0}})
    monkeypatch.setattr(ck, "_fsync_write", real_write)
    lg.close()

    lg2 = _logger(tmp_path, shards=4)
    rec = lg2.recover(CFG.window)
    assert rec.stats["checkpoint_generation"] == 1
    assert rec.meta["app_states"] == {"svc": "gen1"}
    lg2.close()


def test_gc_preserves_prev_manifest_shards_after_rename_crash(tmp_path):
    """A crash BETWEEN the manifest demote and promote renames leaves
    only PREV_MANIFEST on disk; the next save's shard GC must keep that
    generation's shards — they are the torn-shard fallback target."""
    import numpy as np

    from gigapaxos_tpu.storage.checkpoint import (
        PREV_MANIFEST,
        load_checkpoint_view,
        save_checkpoint_sharded,
    )

    d = str(tmp_path)
    arrays = {"a": np.arange(8)}
    meta = {"names": {}, "app_states": {}}
    save_checkpoint_sharded(d, arrays, meta, 2)                   # gen 1
    save_checkpoint_sharded(d, {"a": np.arange(8) + 1}, meta, 2)  # gen 2
    # simulate the crash window: demote done, promote never happened
    os.replace(os.path.join(d, MANIFEST), os.path.join(d, PREV_MANIFEST))
    save_checkpoint_sharded(d, {"a": np.arange(8) + 2}, meta, 2)  # gen 3
    # tear generation 3: the fallback must still find gen 2's shards
    view = load_checkpoint_view(d)
    assert view.generation == 3
    import json

    with open(os.path.join(d, MANIFEST)) as f:
        victim = json.load(f)["shards"][0]["file"]
    with open(os.path.join(d, victim), "r+b") as f:
        f.seek(30)
        f.write(b"XXXX")
    fb = load_checkpoint_view(d)
    assert fb is not None and fb.generation == 2
    assert (fb.arrays["a"] == np.arange(8) + 1).all()


# ---------------------------------------------------------------------------
# segmented replay
# ---------------------------------------------------------------------------

def _multi_file_history(tmp_path, shards, workers):
    Config.set("RECOVERY_REPLAY_WORKERS", str(workers))
    lg = _logger(tmp_path, shards=shards, max_file_size=512)
    _seed_groups(lg, n=6)
    for i in range(40):
        g = i % 6
        lg.log_accepts(
            np.array([g]), np.array([i // 6]),
            np.array([32 + i]), np.array([1000 + i]),
        )
        lg.log_decisions(
            np.array([g]), np.array([i // 6]), np.array([1000 + i])
        )
        lg.log_payloads({1000 + i: f"req{i}"})
    return lg


def test_segmented_replay_parity(tmp_path, native_mode):
    """Parallel segmented replay must produce byte-identical recovered
    state to the sequential scan, across a multi-file journal."""
    recs = {}
    for label, workers in (("seq", 1), ("par", 4)):
        d = tmp_path / label
        lg = _multi_file_history(d, shards=4, workers=workers)
        assert len(lg.journal.file_indices()) > 3, "wants many segments"
        rec = lg.recover(CFG.window, seed_arrays=_state_arrays(CFG))
        recs[label] = rec
        lg.close()
    a, b = recs["seq"], recs["par"]
    for k in a.arrays:
        assert (a.arrays[k] == b.arrays[k]).all(), k
    assert a.payloads == b.payloads
    assert a.decisions == b.decisions
    assert b.stats["segments"] > 3


def test_mid_replay_crash_is_idempotent(tmp_path, native_mode):
    """Replay mutates nothing durable: recovering, 'crashing' (just
    abandoning the result), and recovering again must agree — and a torn
    journal tail mid-segment stops the scan cleanly at the tear."""
    lg = _multi_file_history(tmp_path, shards=4, workers=4)
    first = lg.recover(CFG.window, seed_arrays=_state_arrays(CFG))
    lg.close()

    # torn tail: truncate into the middle of the last file's last block
    idxs = sorted(
        f for f in os.listdir(tmp_path) if f.startswith("journal_")
    )
    last = os.path.join(str(tmp_path), idxs[-1])
    size = os.path.getsize(last)
    with open(last, "r+b") as f:
        f.truncate(size - 3)

    lg2 = _logger(tmp_path, shards=4)
    again = lg2.recover(CFG.window, seed_arrays=_state_arrays(CFG))
    third = lg2.recover(CFG.window, seed_arrays=_state_arrays(CFG))
    lg2.close()
    # idempotent across repeated replays of the same (torn) journal
    for k in again.arrays:
        assert (again.arrays[k] == third.arrays[k]).all(), k
    assert again.payloads == third.payloads
    # the tear cost exactly the blocks at/after it, nothing else: the
    # re-scan reached every payload the first scan saw except the tail
    assert set(again.payloads) <= set(first.payloads)
    assert len(first.payloads) - len(again.payloads) <= 1


# ---------------------------------------------------------------------------
# lazy hydration (manager level, deterministic: background worker off)
# ---------------------------------------------------------------------------

def _ticks(m, n=6):
    for _ in range(n):
        m.tick_host(None, np.array([True]))


@pytest.fixture
def no_background(monkeypatch):
    from gigapaxos_tpu.recovery.hydration import Hydrator

    monkeypatch.setattr(Hydrator, "start_background", lambda self: None)


def _restartable_manager(tmp_path, n_names=10):
    from gigapaxos_tpu.manager import PaxosManager

    cfg = EngineConfig(n_groups=16, window=8, req_lanes=4, n_replicas=1)
    m = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9,
    )
    names = [f"svc{i}" for i in range(n_names)]
    m.create_paxos_batch(names, [0])
    for i, nm in enumerate(names):
        m.propose(nm, str(i + 1))
        _ticks(m)
    return m, cfg, names


def test_lazy_restart_serves_hot_gates_cold(tmp_path, no_background):
    from gigapaxos_tpu.manager import PaxosManager

    Config.set("RECOVERY_CHECKPOINT_SHARDS", "4")
    Config.set("RECOVERY_HOT_NAMES", "3")
    Config.set("RECOVERY_HYDRATION_BATCH", "2")
    m, cfg, names = _restartable_manager(tmp_path)
    m.checkpoint_now()
    m.logger.drain_checkpoints()
    m.propose("svc0", "100")  # post-checkpoint journal tail
    _ticks(m)
    m.close()

    m2 = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9,
    )
    try:
        assert m2.recovery_phase == "recovering"
        st = m2.recovery_stats()
        assert st["hydration_backlog"] == 10 - 3
        assert st["hot_hydrated"] == 3
        hot = [n for n in names if m2.names[n] not in m2.hydrating_rows]
        cold = [n for n in names if m2.names[n] in m2.hydrating_rows]
        assert len(hot) == 3 and len(cold) == 7
        # hot names carry correct state NOW; cold are not restored yet
        for nm in hot:
            exp = 101 if nm == "svc0" else int(nm[3:]) + 1
            assert m2.app.totals.get(nm) == exp, (nm, m2.app.totals)
        for nm in cold:
            assert nm not in m2.app.totals
        # a cold name's request queues but does NOT execute while cold
        got = {}
        m2.propose(cold[0], "1000", callback=lambda r, v: got.update(v=v))
        _ticks(m2, 3)
        assert not got
        # pause/donor/read surfaces refuse un-hydrated names
        epoch = m2.current_epoch(cold[0])
        assert m2.pause_group(cold[0], epoch) == "busy"
        assert not m2.app_caught_up(cold[0])
        assert not m2.local_read_ok(cold[0])
        assert m2.local_read_ok(hot[0])
        # checkpointing is deferred while recovering (a snapshot now
        # would persist blank cold states as a newer generation)
        m2.checkpoint_now()
        assert m2.metrics.get("recovery_checkpoint_deferred") == 1
        # the queued request promoted its name: it hydrates first
        assert m2.hydrator.hydrate_batch() > 0
        assert m2.names[cold[0]] not in m2.hydrating_rows
        # drain fully: phase flips, held traffic executes, totals agree
        assert m2.hydrate_all(60)
        assert m2.recovery_phase == "serving"
        _ticks(m2)
        for nm in names:
            exp = 101 if nm == "svc0" else int(nm[3:]) + 1
            if nm == cold[0]:
                exp += 1000
            assert m2.app.totals.get(nm) == exp, (nm, m2.app.totals)
        assert got.get("v") is not None
    finally:
        m2.close()


def test_eager_mode_restores_everything_up_front(tmp_path):
    from gigapaxos_tpu.manager import PaxosManager

    Config.set("RECOVERY_CHECKPOINT_SHARDS", "4")
    Config.set("RECOVERY_LAZY_HYDRATION", "false")
    m, cfg, names = _restartable_manager(tmp_path, n_names=6)
    m.checkpoint_now()
    m.logger.drain_checkpoints()
    m.close()
    m2 = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9,
    )
    try:
        assert m2.recovery_phase == "serving"
        assert not m2.hydrating_rows and m2.hydrator is None
        for i, nm in enumerate(names):
            assert m2.app.totals.get(nm) == i + 1
    finally:
        m2.close()


def test_background_hydration_drains(tmp_path):
    """Liveness: with the background worker ON, a lazy restart reaches
    phase=serving on its own (generous deadline, no hard wall-clock)."""
    import time

    from gigapaxos_tpu.manager import PaxosManager

    Config.set("RECOVERY_CHECKPOINT_SHARDS", "4")
    Config.set("RECOVERY_HOT_NAMES", "2")
    Config.set("RECOVERY_HYDRATION_BATCH", "1")
    m, cfg, names = _restartable_manager(tmp_path)
    m.checkpoint_now()
    m.logger.drain_checkpoints()
    m.close()
    m2 = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=str(tmp_path),
        checkpoint_every=10 ** 9,
    )
    try:
        deadline = time.time() + 60
        while m2.recovery_phase != "serving" and time.time() < deadline:
            time.sleep(0.02)
        assert m2.recovery_phase == "serving"
        for i, nm in enumerate(names):
            assert m2.app.totals.get(nm) == i + 1
    finally:
        m2.close()
