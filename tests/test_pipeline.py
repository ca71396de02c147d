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


@pytest.mark.parametrize("steps_per_dispatch, whole_planes", [
    (1, False), (4, False),
    (1, True),   # the pipelined side pulls the whole planes every dispatch
])
def test_pipeline_state_parity(steps_per_dispatch, whole_planes):
    """Identical schedule through serial and pipelined dispatch: every
    engine leaf equal after every cluster step, and identical client
    responses — with one substep a dispatch and with four (each
    substep's digest read in turn), and with the donated, pipelined side
    forced down the digest's overflow path."""
    Config.set("ENGINE_STEPS_PER_DISPATCH", str(steps_per_dispatch))
    serial, piped = stepped_cluster(False), stepped_cluster(True)
    assert serial.managers[0].steps_per_dispatch == steps_per_dispatch
    if whole_planes:
        for m in piped.managers:
            m._digest_rows = -1
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
    finally:
        serial.close()
        piped.close()


def test_stepped_cluster_and_served_node_share_one_step_instance():
    """The stepped harness runs the program a node serves: one compiled
    step instance per shape, whoever calls it and by which entry point,
    and one face of it (no ``heat`` flavour to ask for)."""
    from gigapaxos_tpu.parallel.spmd import make_step

    cfg = EngineConfig(n_groups=12, window=8, req_lanes=4, n_replicas=3)
    sentinel = make_step(cfg, None, 1, donate=True, io="packed_host")
    with pytest.raises(TypeError):
        make_step(cfg, None, 1, io="packed_host", heat=True)
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
