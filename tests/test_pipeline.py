"""Serving pipeline: double-buffered dispatch must be STEP-FOR-STEP
state-identical to the serial tick on a recorded request schedule, and
lifecycle ops must serialize against an in-flight step (never interleave
with the device compute + post-step window)."""

import threading
import time

import numpy as np
import pytest

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import ManagerCluster
from gigapaxos_tpu.utils.config import Config

CFG = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)


def stepped_cluster(pipelined: bool) -> ManagerCluster:
    """Three managers exchanging packed blob vectors, each stepped by
    ``tick_host`` (serial) or by dispatch/complete (pipelined)."""
    c = ManagerCluster(CFG, HashChainApp)
    c.pipelined = pipelined
    return c


@pytest.mark.parametrize("whole_planes, deep_queue", [
    (False, False),
    (True, False),   # the pipelined side pulls the whole planes every dispatch
    (False, True),   # a row holds 3K vids: K a dispatch, the rest in order
])
def test_pipeline_state_parity(whole_planes, deep_queue):
    """Identical schedule through serial and pipelined dispatch: every
    engine leaf equal after every cluster step, and identical client
    responses — also with the donated, pipelined side forced down the
    digest's overflow path, and with 3K requests queued on one row at
    its coordinator (coalescing off): the ring stages the row's first K
    a dispatch and the rest keep their order, so the twelve execute in
    the order they were proposed."""
    if deep_queue:
        Config.set("BATCHING_ENABLED", "false")
    serial, piped = stepped_cluster(False), stepped_cluster(True)
    if whole_planes:
        for m in piped.managers:
            m._digest_rows = -1
    deep = 3 * CFG.req_lanes
    try:
        resp_s, resp_p = [], []
        names = ["pa", "pb", "pc"]
        for c in (serial, piped):
            for nm in names:
                c.create(nm)
        rid = 1 << 56
        for step_no in range(40):
            for c, resp in ((serial, resp_s), (piped, resp_p)):
                if step_no % 3 == 0:
                    nm = names[step_no % len(names)]
                    c.managers[step_no % 3].propose(
                        nm, f"v{step_no}",
                        callback=(
                            lambda r, x, _t=step_no, _o=resp:
                            _o.append((_t, r, x))
                        ),
                        request_id=rid + step_no,
                    )
                if step_no == 20:
                    c.managers[1].propose(
                        names[0], "v0",
                        callback=(
                            lambda r, x, _o=resp:
                            _o.append(("dup", r, x))
                        ),
                        request_id=rid + 0,
                    )
                if deep_queue and step_no == 10:
                    row = c.managers[0].names[names[1]]
                    lead = c.managers[c.managers[0].coordinator_of_row(row)]
                    for i in range(deep):
                        lead.propose(
                            names[1], f"deep{i}",
                            callback=(
                                lambda r, x, _o=resp:
                                _o.append(("deep", r, x))
                            ),
                            request_id=rid + 1000 + i,
                        )
                    queued = list(lead.queues[row])
                    c.step_all()
                    # K staged and admitted, the rest as they stood
                    assert lead._last_ring_rows[row] == CFG.req_lanes
                    assert lead.queues[row] == queued[CFG.req_lanes:]
                    continue
                c.step_all()
            # step-for-step: EVERY leaf of EVERY replica identical
            for ms, mp in zip(serial.managers, piped.managers):
                for leaf in ms.state._fields:
                    a = np.asarray(getattr(ms.state, leaf))
                    b = np.asarray(getattr(mp.state, leaf))
                    assert np.array_equal(a, b), (
                        step_no, ms.my_id, leaf,
                    )
                assert np.array_equal(
                    ms.app_exec_slot, mp.app_exec_slot
                ), (step_no, ms.my_id)
        assert sorted(resp_s, key=str) == sorted(resp_p, key=str)
        assert len(resp_s) >= 10  # the schedule actually decided things
        if deep_queue:
            for resp in (resp_s, resp_p):
                assert [r for tag, r, _x in resp if tag == "deep"] == [
                    rid + 1000 + i for i in range(deep)]
    finally:
        serial.close()
        piped.close()


def _pipelined_rounds(c, landing, n_rounds, on_round=None):
    """Drive ``c``'s managers as three nodes that tick at one cadence:
    a round is every node's ``step_dispatch``, then every node's
    ``step_complete``.  What the peers published at the end of a round
    LANDS at each receiver (``GatherNews.hear``: it is there for the
    next ``drain``) at one of three moments of the next round —
    ``before_dispatch`` (the stepped harness's lockstep),
    ``in_flight`` (after the receiver's dispatch has read its stack,
    before its completion) or ``after_completion``."""
    R = c.cfg.n_replicas
    heard = np.ones(R, bool)

    def land(vecs):
        for i in range(R):
            for j in range(R):
                if i != j:
                    c._held[i][j] = c._news[i].hear(j, vecs[j], c._held[i][j])

    for t in range(n_rounds):
        if on_round is not None:
            on_round(t)
        landed = list(c.vecs)  # what the last round published
        if landing == "before_dispatch":
            land(landed)
        pends = [m.step_dispatch(c._news[i].drain(c._held[i]), heard)
                 for i, m in enumerate(c.managers)]
        if landing == "in_flight":
            land(landed)
        deltas = [m.step_complete(pend)[2]
                  for m, pend in zip(c.managers, pends)]
        c.vecs = [m.mirror.vec.copy() for m in c.managers]
        if landing == "after_completion":
            land(landed)
        for i, delta in enumerate(deltas):  # payloads, at once
            for j in range(R):
                if j != i and delta["arena"]:
                    c.managers[j].on_host_message("payloads", delta)


@pytest.mark.parametrize("landing, ticks", [
    ("before_dispatch", 3),   # the floor: stage, two exchanges
    ("in_flight", 5),         # the dispatch in flight has read its stack:
    ("after_completion", 5),  # ... folded as late as a blob after it
])
def test_ticks_from_staged_to_decided_by_when_a_peers_blob_lands(
        landing, ticks):
    """ROADMAP S4's yardstick: the coordinator's consensus leg, in its
    own ticks, from the dispatch that first stages a request to the
    completion that shows it decided.  A blob that lands while the
    receiver's step is in flight is folded by the NEXT dispatch — one
    dispatch late at each end of each of the two exchanges — exactly as
    one that lands after the completion: what the tree does today, and
    the number a cure of the receiver's half has to move."""
    c = ManagerCluster(CFG, HashChainApp)
    try:
        row = c.create("s4")
        lead = c.managers[c.managers[0].coordinator_of_row(row)]
        staged, decided = {}, {}

        def on_round(t):
            if t >= 6 and t % 8 == 6:  # one request at a time, alone
                rid = (1 << 56) + t
                staged[rid] = lead._tick_no  # the dispatch of this round
                lead.propose(
                    "s4", f"v{t}", request_id=rid,
                    callback=lambda r, x: decided.setdefault(
                        r, lead._tick_no))

        _pipelined_rounds(c, landing, 30, on_round)
        assert len(staged) == 3 and decided.keys() == staged.keys()
        assert [decided[r] - staged[r] for r in staged] == [ticks] * 3
        # the manager's own account of the leg agrees
        hist = lead.metrics.snapshot()["hists"]["commit_leg_consensus_ticks"]
        assert (hist["count"], hist["sum"]) == (3, 3 * ticks)
        for m in c.managers:  # and everybody executed all three
            assert m.app_exec_slot[row] == 3
    finally:
        c.close()


def test_stepped_cluster_and_served_node_share_one_step_instance():
    """The stepped harness runs the program a node serves: one compiled
    step instance per shape, whoever calls it and by which entry point,
    and one face of it (no ``heat`` flavour to ask for)."""
    from gigapaxos_tpu.parallel.spmd import make_step

    cfg = EngineConfig(n_groups=12, window=8, req_lanes=4, n_replicas=3)
    sentinel = make_step(cfg, donate=True, io="packed_host")
    with pytest.raises(TypeError):
        make_step(cfg, io="packed_host", heat=True)
    c = ManagerCluster(cfg, HashChainApp)
    m = PaxosManager(0, HashChainApp(), cfg)
    try:
        for x in c.managers + [m]:
            assert x._dispatch_step is sentinel
            assert set(x.engine_compile_stats()) == {
                "dispatch", "lifecycle", "gather"}
        c.create("one")
        c.run(20)
        m.step_complete(m.step_dispatch(
            None, np.array([True, False, False])))
        assert sentinel.n_compiles == 1, sentinel.stats()
        assert sentinel.n_retraces == 0, sentinel.stats()
    finally:
        c.close()
        m.close()


def test_after_warm_engine_the_tick_path_compiles_nothing():
    """``warm_engine`` warms the shapes a dispatch has: after it, a tick
    with nothing staged (the standing null ring), one with a request
    (a ring sent up) and one whose digest overflows (the whole planes
    pulled) hand XLA no program to compile — by JAX's own account of
    its backend compiles, not by the sentinel's."""
    import jax.monitoring

    cfg = EngineConfig(n_groups=24, window=8, req_lanes=4, n_replicas=3)
    m = PaxosManager(0, HashChainApp(), cfg)
    compiled = []

    def listener(event, _secs, **_kw):
        if "backend_compile" in event:
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        m.warm_engine()
        m.create_paxos_instance("w", [0])
        assert compiled  # the listener hears a compile when there is one
        del compiled[:]
        heard, done = np.array([True, False, False]), []
        m.tick_host(None, heard)
        m.propose("w", "v1", callback=lambda r, x: done.append(x))
        m.step_complete(m.step_dispatch(None, heard))
        m._digest_rows = -1
        m.propose("w", "v2", callback=lambda r, x: done.append(x))
        for _ in range(3):
            m.step_complete(m.step_dispatch(None, heard))
        assert len(done) == 2
        assert m.metrics.get("step_digest_overflows") == 3
        assert compiled == []
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
        m.close()


def test_lifecycle_waits_for_inflight_step():
    """A state-replacing op (create) arriving during the in-flight
    window must WAIT for step_complete — interleaving would let the
    post-step host cycle process step outputs against rows the lifecycle
    op rewrote."""
    m = PaxosManager(0, HashChainApp(), CFG)
    try:
        m.create_paxos_instance("x", [0])
        heard = np.array([True, False, False])
        pend = m.step_dispatch(None, heard)
        done = threading.Event()

        def create_side():
            m.create_paxos_instance("y", [0])
            done.set()

        t = threading.Thread(target=create_side, daemon=True)
        t.start()
        # the create must be BLOCKED while the step is in flight
        assert not done.wait(0.3), (
            "lifecycle op interleaved with an in-flight step"
        )
        m.step_complete(pend)
        assert done.wait(5.0), "lifecycle op never resumed after complete"
        t.join(5.0)
        assert "y" in m.names
    finally:
        m.close()


def test_flush_coalescing_metrics():
    """A loopback round trip populates the flush metrics (one frame per
    peer per cycle: responses_flushed counter + flush_batch_size hist),
    and the stats admin op reports the live codec."""
    from tests.test_server import boot_cluster, wait_until

    servers, client, _ = boot_cluster()
    try:
        assert client.create_paxos_instance("fm", [0, 1, 2], timeout=30)
        for i in range(4):
            assert client.send_request_sync(
                "fm", str(i + 1), timeout=30
            ) is not None
        mx = [s.manager.metrics for s in servers]
        # the client randomizes entry replicas — count across the cluster
        # (a flush counts AFTER it hands the frame over, so the client
        # can hold the fourth reply a moment before the counter moves)
        assert wait_until(lambda: sum(
            m.get("responses_flushed") for m in mx) >= 4, timeout=5)
        assert any(
            "flush_batch_size" in m.snapshot()["hists"] for m in mx
        )
        st = client.admin_sync(0, {"op": "stats"}, timeout=10)
        assert st and st["ok"]
        serving = st["serving"]
        assert serving["codec"]["binary_frames"] is True
        assert serving["codec"]["impl"] in ("gp_codec.so", "python-struct")
        assert serving["serving_workers"] == 1
    finally:
        client.close()
        for s in servers:
            s.stop()


@pytest.mark.timeout(120)
def test_pipelined_loopback_under_overlap():
    """Sanity: concurrent client load through real sockets stays correct
    under the pipelined tick loop — responses arrive and replicas
    converge (the overlap window is exercised by the live tick loop)."""
    from tests.test_server import boot_cluster, wait_until

    servers, client, _ = boot_cluster()
    try:
        assert client.create_paxos_instance("ov", [0, 1, 2], timeout=30)
        total = 0
        for i in range(8):
            resp = client.send_request_sync("ov", str(i + 1), timeout=30)
            total += i + 1
            assert resp == str(total)
        assert wait_until(lambda: all(
            s.manager.app.totals.get("ov") == total for s in servers
        ))
        # overlap metrics populated by the pipelined loop
        assert any(
            "pipeline_overlap_s" in s.manager.metrics.snapshot()["hists"]
            for s in servers
        )
    finally:
        client.close()
        for s in servers:
            s.stop()
