"""Request coalescing (the true RequestBatcher semantics): many client
requests decided as ONE consensus slot, unpacked at execution with
per-request dedup and callbacks.

Ref: ``RequestBatcher.java:40-158`` (entry batching with adaptive sleep),
``RequestPacket.java:189-246`` (nested `batched` array — up to
MAX_BATCH_SIZE=2000 requests per proposal), ``PaxosManager.java:1226``
(proposeBatched).  Without this, a group's throughput is capped at
req_lanes per tick; with it, at req_lanes * MAX_BATCH_SIZE per tick.
"""

import numpy as np
import pytest

from gigapaxos_tpu.manager import BATCH_BIT, decode_batch, encode_batch
from gigapaxos_tpu.models.apps import HashChainApp, NoopPaxosApp
from gigapaxos_tpu.ops.engine import STOP_BIT, EngineConfig
from gigapaxos_tpu.testing.cluster import ManagerCluster
from gigapaxos_tpu.utils.config import Config


def small_cfg():
    return EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)


def test_batch_codec_roundtrip():
    subs = [
        (1 << 61, 0, "plain"),
        (12345, 2, ""),
        ((1 << 53) + 7, 1, 'json {"a": [1, 2]} é中'),
    ]
    assert decode_batch(encode_batch(subs)) == subs


def test_hot_group_burst_commits_in_few_ticks():
    """500 requests to ONE group: without coalescing this needs >=125
    ticks (K=4 lanes); with it the whole burst rides a handful of slots.
    All callbacks must fire and the SHA-chained state must converge
    identically on every replica (ordering + exactly-once)."""
    c = ManagerCluster(small_cfg(), HashChainApp)
    c.create("hot", members=[0, 1, 2])
    done = {}
    N = 500
    for i in range(N):
        c.submit("hot", f"v{i}", entry=0,
                 callback=lambda rid, r: done.setdefault(rid, r))
    c.run(20)
    assert len(done) == N, f"only {len(done)}/{N} callbacks fired"
    # replica coordinating "hot" used batch vids (not 125+ singleton slots)
    frontier = int(np.asarray(c.managers[0].state.exec_slot)[
        c.managers[0].names["hot"]])
    assert frontier <= 40, f"{frontier} slots used for {N} requests"
    states = [m.app.state.get("hot") for m in c.managers]
    counts = [m.app.n_executed.get("hot") for m in c.managers]
    assert states[0] is not None and len(set(states)) == 1, states
    assert counts == [N, N, N], counts
    c.close()


def test_batched_requests_from_forwarding_entry():
    """Requests entering at a NON-coordinator replica are forwarded,
    coalesced by the coordinator, and their callbacks still fire at the
    original entry replica."""
    c = ManagerCluster(small_cfg(), HashChainApp)
    c.create("fwd", members=[0, 1, 2])
    coord = c.managers[0].coordinator_of_row(c.managers[0].names["fwd"])
    entry = (coord + 1) % 3
    done = {}
    N = 100
    for i in range(N):
        c.submit("fwd", f"v{i}", entry=entry,
                 callback=lambda rid, r: done.setdefault(rid, r))
    c.run(25)
    assert len(done) == N, f"only {len(done)}/{N} callbacks at entry"
    states = [m.app.state.get("fwd") for m in c.managers]
    assert len(set(states)) == 1
    c.close()


def test_stop_never_rides_a_batch():
    """A queue of plain requests plus an epoch-final stop: the stop is
    decided as its own slot (STOP_BIT and BATCH_BIT never combine) and
    the group ends stopped with every prior request executed."""
    c = ManagerCluster(small_cfg(), HashChainApp)
    c.create("s", members=[0, 1, 2])
    done = {}
    N = 40
    for i in range(N):
        c.submit("s", f"v{i}", entry=0,
                 callback=lambda rid, r: done.setdefault(rid, r))
    c.submit("s", "", entry=0, stop=True)
    c.run(25)
    m0 = c.managers[0]
    assert m0.is_stopped("s")
    assert len(done) == N
    # no vid in any journal/arena ever carried both bits
    for m in c.managers:
        for vid in list(m.arena) + list(m.retained):
            assert not ((vid & STOP_BIT) and (vid & BATCH_BIT)), hex(vid)
    counts = [m.app.n_executed.get("s") for m in c.managers]
    assert len(set(counts)) == 1, counts
    c.close()


def test_retransmit_of_batched_request_dedups():
    """A request id retransmitted while its original rides a batch must
    not execute twice; a retransmit after commit gets the cached
    response."""
    c = ManagerCluster(small_cfg(), HashChainApp)
    c.create("d", members=[0, 1, 2])
    rid = 1 << 55
    responses = []
    # enough neighbors to force coalescing of the tracked request
    for i in range(30):
        c.submit("d", f"n{i}", entry=0)
    c.managers[0].propose("d", "tracked", request_id=rid,
                          callback=lambda r, resp: responses.append(resp))
    # retransmit BEFORE commit: in-flight dedup repointed to the batch vid
    c.managers[0].propose("d", "tracked", request_id=rid,
                          callback=lambda r, resp: responses.append(resp))
    c.run(20)
    # retransmit AFTER commit: answered from the response cache
    c.managers[0].propose("d", "tracked", request_id=rid,
                          callback=lambda r, resp: responses.append(resp))
    c.run(2)
    assert len(responses) >= 2  # original + cached retransmit
    assert len(set(r for r in responses if r is not None)) == 1
    n = c.managers[0].app.n_executed["d"]
    assert n == 31, f"{n} executions for 31 logical requests"
    c.close()


def test_unbatched_mode_still_works():
    """BATCHING_ENABLED=false must fall back to one-request-per-slot."""
    Config.set("BATCHING_ENABLED", "false")
    try:
        c = ManagerCluster(small_cfg(), HashChainApp)
        c.create("u", members=[0, 1, 2])
        done = {}
        for i in range(20):
            c.submit("u", f"v{i}", entry=0,
                     callback=lambda rid, r: done.setdefault(rid, r))
        c.run(15)
        assert len(done) == 20
        for m in c.managers:
            for vid in list(m.retained):
                assert not (vid & BATCH_BIT)
        c.close()
    finally:
        Config.clear()


def test_batch_survives_crash_recovery(tmp_path):
    """Batch payloads are journaled like any payload: a replica restarted
    mid-stream replays decided batches and converges to the same chain."""
    dirs = [str(tmp_path / f"n{r}") for r in range(3)]
    cfg = small_cfg()
    c = ManagerCluster(cfg, HashChainApp, log_dirs=dirs)
    c.create("r", members=[0, 1, 2])
    for i in range(60):
        c.submit("r", f"v{i}", entry=0)
    c.run(15)
    states = [m.app.state.get("r") for m in c.managers]
    assert len(set(states)) == 1 and states[0] is not None
    c.close()

    from gigapaxos_tpu.manager import PaxosManager

    m = PaxosManager(0, HashChainApp(), cfg, log_dir=dirs[0])
    assert m.app.state.get("r") == states[0]
    assert m.app.n_executed.get("r") == 60
    m.close()


def test_forward_batch_preserves_fifo_around_stop():
    """A non-coordinator entry forwards its whole queue run as ONE
    entry of a forward_rows frame; requests queued BEFORE a stop must commit
    before it (proposing the stop first would bump the epoch and drop
    them as stale — review find on the batched forward path)."""
    cfg = small_cfg()
    c = ManagerCluster(cfg, HashChainApp)
    c.create("f", members=[0, 1, 2])
    row = c.managers[0].names["f"]
    coord = c.managers[0].coordinator_of_row(row)
    entry = (coord + 1) % 3  # a NON-coordinator entry replica
    for i in range(5):
        c.submit("f", f"pre{i}", entry=entry)
    c.submit("f", "", entry=entry, stop=True)
    c.run(20)
    for m in c.managers:
        # all five pre-stop requests executed (the chain advanced 5+ --
        # the stop itself also chains), and the group is stopped
        assert m.app.n_executed.get("f", 0) >= 5, m.app.n_executed
        assert int(np.asarray(m.state.stopped)[row]) == 1
    states = {m.app.state.get("f") for m in c.managers}
    assert len(states) == 1
    c.close()


def test_propose_batch_outcomes():
    """The batched ingress reports the same per-request outcomes the
    singleton path implements: queued, cached (callback fired from the
    response cache), inflight (callback re-registered), unknown."""
    cfg = EngineConfig(n_groups=4, window=8, req_lanes=4, n_replicas=1)
    c = ManagerCluster(cfg, HashChainApp)
    m = c.managers[0]
    c.create("b", members=[0])
    rid = 1 << 56
    got = []
    res = m.propose_batch([
        ("b", "v0", rid, lambda r, resp: got.append(resp)),
        ("nope", "v1", rid + 1, None),
    ])
    assert [r[1] for r in res] == ["queued", "unknown"]

    # same id again while the original is still undecided -> inflight
    res = m.propose_batch([("b", "v0", rid, lambda r, resp: got.append(resp))])
    assert res[0][1] == "inflight"

    c.run(8)  # decide + execute
    assert got, "callback never fired"
    first_resp = got[-1]

    # after execution the id answers from the cache, callback fires
    res = m.propose_batch([("b", "v0", rid, lambda r, resp: got.append(resp))])
    assert res[0][1] == "cached" and res[0][2] == first_resp
    assert got[-1] == first_resp

    # vid-counter exhaustion fails PER ITEM: cached entries in the same
    # frame still answer (no whole-frame raise, no discarded responses)
    from gigapaxos_tpu.manager import VID_COUNTER_MASK

    m._next_counter = VID_COUNTER_MASK + 1
    res = m.propose_batch([
        ("b", "v0", rid, lambda r, resp: got.append(resp)),
        ("b", "fresh", rid + 7, None),
    ])
    assert [r[1] for r in res] == ["cached", "exhausted"]
    assert got[-1] == first_resp
    c.close()
