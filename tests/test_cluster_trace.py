"""Cluster-wide observability: cross-node trace propagation + merge
(client → entry AR → coordinator forward → decide on all replicas →
execute → response, ONE causal timeline out of N nodes' trace_dump
rings), the black-box flight recorder (divergence / mid-load dumps),
and the TLS HTTP stats surface."""

import json
import os
import ssl
import subprocess
import time
import urllib.request

import pytest

from gigapaxos_tpu.clients import PaxosClientAsync
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.net.node_config import NodeConfig
from gigapaxos_tpu.obs import tracemerge
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.server import PaxosServer
from gigapaxos_tpu.testing.ports import free_ports
from gigapaxos_tpu.utils.config import Config


def _boot_cluster(n, groups=8):
    cfg = EngineConfig(n_groups=groups, window=8, req_lanes=4,
                       n_replicas=n)
    ports = free_ports(n)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [
        PaxosServer(i, nc, StatefulAdderApp(), cfg, tick_interval=0.01)
        for i in range(n)
    ]
    for s in servers:
        s.start()
    return servers, ports


# ---- the acceptance path: one traced request, one merged timeline -----
@pytest.mark.timeout(180)
def test_traced_request_merges_into_one_causal_timeline(monkeypatch):
    """A sampled request (GP_TRACE_SAMPLE=1) through a live loopback
    cluster, entering at a NON-coordinator (so the coordinator-forward
    hop is on the path): every node's trace_dump merges into ONE
    timeline sharing the trace id, containing every hop — recv/propose/
    forward-out at the entry, forward-in/propose at the coordinator,
    decide+execute on ALL replicas, respond-flush at the entry — with
    non-negative per-hop latencies.  Servers run with tracing DISABLED:
    the origin's sampling decision alone makes every hop record."""
    monkeypatch.setenv("GP_TRACE_SAMPLE", "1")
    servers, ports = _boot_cluster(3)
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert all(not s.tracer.enabled for s in servers)
        assert client.create_paxos_instance("tr0", [0, 1, 2], timeout=30)
        m0 = servers[0].manager
        row = m0.names["tr0"]
        coord = m0.coordinator_of_row(row)
        entry = (coord + 1) % 3
        resp = client.send_request_sync("tr0", "7", timeout=30,
                                        server=entry)
        assert resp == "7"

        # all replicas executed (the decide/execute fan-out is complete)
        deadline = time.time() + 30
        while time.time() < deadline:
            if all(s.manager.app.totals.get("tr0") == 7 for s in servers):
                break
            time.sleep(0.1)

        # fan trace_dump over the cluster and merge (scripts/gp_trace.py
        # does exactly this against a deployed cluster)
        dumps = {}
        for i in range(3):
            r = client.admin_sync(i, {"op": "trace_dump"}, timeout=10)
            assert r and r["ok"], r
            assert r["enabled"] is False  # forced recording, not GP_TRACE
            dumps[r["node"]] = r["events"]
        traces = tracemerge.merge_node_dumps(dumps)
        # the create-plane admin ops aren't traced; exactly the sampled
        # request's timeline comes back
        assert len(traces) == 1, [t["keys"] for t in traces]
        tr = traces[0]

        # ONE shared trace id stamped at the client
        assert tr["trace_id"], tr
        tids = {e["detail"]["tid"] for e in tr["events"]
                if "tid" in e["detail"]}
        assert tids == {tr["trace_id"]}

        by = {}
        for e in tr["events"]:
            by.setdefault(e["event"], set()).add(e["node"])
        # entry hops
        assert entry in by.get("recv", set())
        assert entry in by.get("propose", set())
        assert entry in by.get("forward-out", set())
        assert entry in by.get("respond-flush", set())
        # coordinator hops (hop counter bumped across the forward)
        assert coord in by.get("forward-in", set())
        assert coord in by.get("propose", set())
        fwd_in = [e for e in tr["events"] if e["event"] == "forward-in"]
        assert fwd_in and all(
            e["detail"].get("hop", 0) >= 1 for e in fwd_in
        )
        # decide + execute landed on EVERY replica, with the decided
        # slot's (group, slot, ballot) attribution
        assert by.get("decide") == {0, 1, 2}, by
        assert by.get("execute") == {0, 1, 2}, by
        for e in tr["events"]:
            if e["event"] == "decide":
                assert e["detail"]["row"] == row
                assert "slot" in e["detail"] and "ballot" in e["detail"]
        # causal order with non-negative per-hop latencies
        assert tr["events"][0]["event"] == "recv"
        assert all(h["dt_s"] >= 0.0 for h in tr["hops"])
        assert tr["total_s"] >= 0.0
        # the per-hop phase attribution names the forward + consensus legs
        phases = {h["phase"] for h in tr["hops"]}
        assert "forward-wire" in phases
        assert "ingress" in phases
        # ... and the response carried the context back to the client
        # (S/JSON trace field round trip) — rendering smoke-check too
        text = tracemerge.render_trace(tr)
        assert "forward-wire" in text and "@ node" in text
    finally:
        client.close()
        for s in servers:
            s.stop()


# ---- trace_dump + flightdump against a node mid-load ------------------
@pytest.mark.timeout(180)
def test_trace_dump_and_flightdump_mid_load(tmp_path, monkeypatch):
    """The two new admin ops answer against a node under live traffic:
    trace_dump streams the ring (name-filtered), flightdump writes the
    engine-history rings to disk and reports the path."""
    monkeypatch.setenv("GP_TRACE_SAMPLE", "1")
    Config.set("FLIGHT_DIR", str(tmp_path / "flight"))
    servers, ports = _boot_cluster(2)
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert client.create_paxos_instance("mid", [0, 1], timeout=30)
        # live load: a stream of requests in flight while we dump
        for i in range(40):
            client.send_request("mid", "1")
        assert client.send_request_sync("mid", "1", timeout=30) is not None

        r = client.admin_sync(0, {"op": "trace_dump", "name": "mid"},
                              timeout=10)
        assert r and r["ok"] and r["node"] == 0
        assert r["events"], "mid-load trace_dump returned an empty ring"
        assert any(
            ev[1] == "propose"
            for evs in r["events"].values() for ev in evs
        )

        f = client.admin_sync(0, {"op": "flightdump"}, timeout=10)
        assert f and f["ok"], f
        assert f["steps"] > 0 and f["decided"] > 0, f
        assert os.path.isfile(f["path"]), f
        doc = json.loads(open(f["path"]).read())
        assert doc["node"] == 0 and doc["reason"] == "admin"
        assert doc["steps"] and doc["decided"]
        # decided entries are (group, slot, ballot, vid) with the slot
        # sequence for the loaded group
        row = servers[0].manager.names["mid"]
        mine = [d for d in doc["decided"] if d[0] == row]
        assert mine, doc["decided"][:5]
        assert all(len(d) == 4 for d in mine)
    finally:
        client.close()
        for s in servers:
            s.stop()


# ---- divergence → black box on disk -----------------------------------
@pytest.mark.timeout(300)
def test_soak_divergence_dumps_flight_recorder(tmp_path):
    """Force an exactly-once divergence in the stepped chaos harness and
    assert the flight recorder lands on disk, attached to the failure,
    containing the divergent group's last-K decided entries."""
    from gigapaxos_tpu.models.apps import HashChainApp
    from gigapaxos_tpu.testing.chaos import (
        SoakDivergence,
        probe_exactly_once,
    )
    from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster

    Config.set("FLIGHT_DIR", str(tmp_path / "flight"))
    ar_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=4, window=8, req_lanes=4, n_replicas=3)
    c = ReconfigurableCluster(ar_cfg, rc_cfg, HashChainApp)
    try:
        for m in c.ars.managers:
            m.tracer.enabled = True
        c.client_request(
            "create_service", {"name": "dv", "actives": [0, 1, 2]}
        )
        for _ in range(40):
            c.step()
        rid = (1 << 55) + 777
        c.ars.managers[0].propose("dv", "v0", request_id=rid)
        deadline = time.time() + 120
        while time.time() < deadline:
            c.step()
            if all(m.app.state.get("dv") for m in c.ars.managers):
                break
        assert c.ars.managers[0].app.state.get("dv"), "request never ran"
        # wait until every member is caught up (app cursor == frontier)
        # so the probe actually compares them
        row = c.ars.managers[0].names["dv"]
        while time.time() < deadline:
            if all(
                int(m.app_exec_slot[m.names["dv"]])
                == int(m._np("exec_slot")[m.names["dv"]]) > 0
                for m in c.ars.managers
            ):
                break
            c.step()
        # the breach: one member's app state silently diverges
        c.ars.managers[0].app.state["dv"] = "CORRUPTED"
        with pytest.raises(SoakDivergence) as ei:
            probe_exactly_once(c, ["dv"])
        paths = ei.value.diag.get("flight_dumps")
        assert paths, "divergence carried no flight dumps"
        # the dumps are the failure message too (post-mortemable from
        # the artifact alone)
        assert "flight_dumps" in str(ei.value)
        found_divergent_group = False
        for p in paths:
            assert os.path.isfile(p)
            doc = json.loads(open(p).read())
            decided = [d for d in doc["decided"] if d[0] == row]
            if decided:
                found_divergent_group = True
                # (group, slot, ballot, vid): the decided sequence the
                # post-mortem diffs across members
                assert all(len(d) == 4 for d in decided)
                slots = [d[1] for d in decided]
                assert slots == sorted(slots)
        assert found_divergent_group, (paths, row)
    finally:
        c.close()


# ---- RC + AR HTTP stats surface under TLS -----------------------------
def _make_cert(tmp_path):
    key = tmp_path / "key.pem"
    crt = tmp_path / "cert.pem"
    r = subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(crt), "-days", "1",
         "-subj", "/CN=127.0.0.1"],
        capture_output=True,
    )
    if r.returncode != 0:
        pytest.skip("openssl unavailable for cert generation")
    return str(key), str(crt)


@pytest.mark.timeout(300)
def test_rc_http_stats_and_metrics_under_tls(tmp_path):
    """The RC and AR HTTP fronts serve /stats + /metrics over HTTPS when
    the cluster runs a TLS mode (previously only plaintext was
    exercised): the node cert is presented and verified, and a plaintext
    client is rejected."""
    from gigapaxos_tpu.models import NoopPaxosApp
    from gigapaxos_tpu.paxos_config import PC
    from gigapaxos_tpu.reconfigurable_node import ReconfigurableNode

    key, crt = _make_cert(tmp_path)
    ports = free_ports(2)
    Config.set("active.AR0", f"127.0.0.1:{ports[0]}")
    Config.set("reconfigurator.RC0", f"127.0.0.1:{ports[1]}")
    # fast stats cadence so the process gauges refresh within the poll
    Config.set("STATS_LOG_PERIOD_S", "0.5")
    Config.set("SSL_MODE", "SERVER_AUTH")
    Config.set("SSL_KEY_FILE", key)
    Config.set("SSL_CERT_FILE", crt)
    Config.set("SSL_CA_FILE", crt)
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=1)
    nodes = [
        ReconfigurableNode("AR0", NoopPaxosApp, ar_cfg=cfg, rc_cfg=cfg,
                           tick_interval=0.01),
        ReconfigurableNode("RC0", NoopPaxosApp, ar_cfg=cfg, rc_cfg=cfg,
                           tick_interval=0.01),
    ]
    for n in nodes:
        n.start()
    try:
        ctx = ssl.create_default_context(cafile=crt)
        ctx.check_hostname = False  # node identity = address book
        off = Config.get_int(PC.HTTP_PORT_OFFSET)
        for port, want in (
            (ports[1] + off, "placement"),   # RC front
            (ports[0] + off, "counters"),    # AR front: its registry
        ):
            with urllib.request.urlopen(
                f"https://127.0.0.1:{port}/stats", timeout=10,
                context=ctx,
            ) as resp:
                body = json.loads(resp.read())
            assert want in body, (port, body)
            with urllib.request.urlopen(
                f"https://127.0.0.1:{port}/metrics", timeout=10,
                context=ctx,
            ) as resp:
                text = resp.read().decode()
            assert text.startswith("gp_")  # the node's registry, rendered
        # the RC /metrics carries its engine registry; the process
        # gauges land there at the stats cadence (refreshed by the tick
        # loop) — poll briefly rather than assume the cadence fired
        deadline = time.time() + 30
        seen = ""
        while time.time() < deadline:
            with urllib.request.urlopen(
                f"https://127.0.0.1:{ports[1] + off}/metrics",
                timeout=10, context=ctx,
            ) as resp:
                seen = resp.read().decode()
            if "gp_process_rss_bytes" in seen:
                break
            time.sleep(0.5)
        assert "gp_process_rss_bytes" in seen
        assert "gp_process_open_fds" in seen
        # plaintext to the TLS port must NOT succeed
        with pytest.raises(Exception):
            urllib.request.urlopen(
                f"http://127.0.0.1:{ports[1] + off}/stats", timeout=5
            )
    finally:
        for n in nodes:
            n.stop()
