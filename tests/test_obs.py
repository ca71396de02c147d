"""Observability plane tests: tracer ring semantics, DEBUG gating, lazy
logging, GP_LOG grammar, the metrics registry, the ``stats`` admin op
over a live loopback cluster, the unknown-admin-op reply, the chaos-diag
trace ride-along, and the obs-hygiene static gate."""

import io
import logging
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from gigapaxos_tpu.obs import gplog
from gigapaxos_tpu.obs.metrics import Histogram, MetricsRegistry
from gigapaxos_tpu.obs.reqtrace import RequestTracer

REPO = Path(__file__).resolve().parent.parent


# ---- tracer ----------------------------------------------------------
def test_tracer_ring_bound_and_fifo_eviction():
    t = RequestTracer(0, capacity=4, enabled=True)
    for rid in range(10):
        t.note(rid, "recv", name="svc", node=0)
        t.note(rid, "execute", slot=rid)
    assert len(t) == 4
    # FIFO: only the newest 4 keys survive
    assert all(rid in t for rid in range(6, 10))
    assert all(rid not in t for rid in range(6))
    # a new event on a surviving key appends, not re-inserts
    t.note(7, "respond-flush")
    assert [e[1] for e in t.events(7)] == ["recv", "execute", "respond-flush"]


def test_tracer_disabled_records_nothing():
    t = RequestTracer(1, capacity=16, enabled=False)
    t.note(42, "recv", name="svc", node=1)
    t.note(42, "execute", slot=3)
    assert len(t) == 0
    assert t.events(42) == []
    assert "no trace" in t.dump(42)
    assert "no traces" in t.dump_name("svc")


def test_tracer_dump_timeline_and_name_index():
    t = RequestTracer(2, enabled=True)
    t.note(7, "recv", name="a", node=2)
    t.note(7, "propose", name="a", vid=99, row=3)
    t.note(8, "recv", name="a", node=2)
    t.note(9, "recv", name="b", node=2)
    d = t.dump(7)
    assert "request 7 @ node 2" in d
    assert "recv" in d and "propose" in d and "vid=99" in d
    assert "ms" in d  # relative-timestamp lines
    assert t.keys_for_name("a") == [7, 8]
    nd = t.dump_name("a")
    assert "request 7" in nd and "request 8" in nd and "request 9" not in nd


def test_tracer_default_gate_follows_gp_log(monkeypatch):
    gplog.reset_for_tests()
    try:
        monkeypatch.delenv("GP_TRACE", raising=False)
        monkeypatch.setenv("GP_LOG", "")
        assert RequestTracer(0).enabled is False
        monkeypatch.setenv("GP_LOG", "trace:DEBUG")
        gplog.configure(stream=io.StringIO(), force=True)
        assert RequestTracer(0).enabled is True
        monkeypatch.setenv("GP_LOG", "")
        monkeypatch.setenv("GP_TRACE", "1")
        gplog.reset_for_tests()
        assert RequestTracer(0).enabled is True
    finally:
        gplog.reset_for_tests()


# ---- logging ---------------------------------------------------------
class _Sentinel:
    """__str__ counter: proves %-args only format past the level check."""

    def __init__(self):
        self.n = 0

    def __str__(self):
        self.n += 1
        return "S"


def test_gplog_lazy_formatting_below_level():
    gplog.reset_for_tests()
    try:
        sink = io.StringIO()
        gplog.configure(stream=sink, force=True)  # default WARNING
        log = gplog.node_logger("lazytest", 7)
        s = _Sentinel()
        log.debug("value=%s", s)
        log.info("value=%s", s)
        assert s.n == 0, "args formatted below the enabled level"
        log.warning("value=%s", s)
        assert s.n == 1
        out = sink.getvalue()
        assert "[node 7]" in out and "value=S" in out
        assert "gp.lazytest" in out
    finally:
        gplog.reset_for_tests()


def test_gplog_env_grammar():
    gplog.reset_for_tests()
    try:
        gplog.configure(stream=io.StringIO(), force=True)
        gplog.apply_env_levels("INFO,server:DEBUG, rc:ERROR")
        assert logging.getLogger("gp").level == logging.INFO
        assert logging.getLogger("gp.server").level == logging.DEBUG
        assert logging.getLogger("gp.rc").level == logging.ERROR
        # unparseable fragments are skipped, never raise
        gplog.apply_env_levels("server:NOTALEVEL,garbage")
        assert logging.getLogger("gp.server").level == logging.DEBUG
    finally:
        gplog.reset_for_tests()


def test_warn_once_dedup():
    gplog.reset_for_tests()
    try:
        sink = io.StringIO()
        gplog.configure(stream=sink, force=True)
        log = gplog.node_logger("oncetest", 3)
        for _ in range(5):
            gplog.warn_once(log, "kindX", "dropping frame of kind %s", "X")
        gplog.warn_once(log, "kindY", "dropping frame of kind %s", "Y")
        out = sink.getvalue()
        assert out.count("kind X") == 1
        assert out.count("kind Y") == 1
    finally:
        gplog.reset_for_tests()


# ---- metrics ---------------------------------------------------------
def test_histogram_buckets_and_stats():
    h = Histogram(bounds=(1.0, 10.0, 100.0))
    for x in (0.5, 5, 5, 50, 500):
        h.observe(x)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert snap["min"] == 0.5 and snap["max"] == 500
    assert snap["buckets"] == [
        [1.0, 1], [10.0, 2], [100.0, 1], ["+inf", 1]
    ]


def test_histogram_always_ships_inf_bucket():
    # no overflow observed: the terminal bucket must still render (with
    # the Prometheus "+Inf" spelling) or histogram_quantile returns NaN
    m = MetricsRegistry(node=1)
    m.observe("lat_s", 0.5, bounds=(1.0, 10.0))
    snap = m.snapshot()["hists"]["lat_s"]
    assert snap["buckets"] == [[1.0, 1], [10.0, 0], ["+inf", 0]]
    text = m.render()
    assert 'le="+Inf"} 1' in text


def test_render_counters_full_precision():
    # %g's 6 significant digits would quantize large counters and break
    # rate() over successive scrapes
    m = MetricsRegistry(node=1)
    m.count("decisions_executed", 10_000_000_019)
    assert 'gp_decisions_executed_total{node="1"} 10000000019' in m.render()


def test_tracer_per_key_event_cap_keeps_anchor():
    t = RequestTracer(0, enabled=True)
    t.note("epoch:n0", "rc-propose:create_intent", name="n0")
    for i in range(2 * RequestTracer.EVENTS_PER_KEY):
        t.note("epoch:n0", "start-epoch-round", attempt=i)
    evs = t.events("epoch:n0")
    assert len(evs) == RequestTracer.EVENTS_PER_KEY
    assert evs[0][1] == "rc-propose:create_intent"  # t0 anchor survives
    assert evs[-1][2]["attempt"] == 2 * RequestTracer.EVENTS_PER_KEY - 1


def test_metrics_registry_roundtrip():
    m = MetricsRegistry(node=5)
    m.count("decisions_executed", 3)
    m.count("decisions_executed", 4)
    m.gauge("frontier_stall_groups", 2)
    m.observe("engine_step_s", 0.002)
    assert m.get("decisions_executed") == 7
    assert m.get("frontier_stall_groups") == 2
    snap = m.snapshot()
    assert snap["node"] == 5
    assert snap["counters"]["decisions_executed"] == 7
    assert snap["hists"]["engine_step_s"]["count"] == 1
    text = m.render()
    assert 'gp_decisions_executed_total{node="5"} 7' in text
    assert "gp_engine_step_s_bucket" in text
    line = m.summary_line()
    assert "decisions_executed:7" in line


# ---- the stats admin op over a live loopback cluster -----------------
def test_stats_admin_roundtrip_and_unknown_op():
    from gigapaxos_tpu.clients import PaxosClientAsync
    from gigapaxos_tpu.models import StatefulAdderApp
    from gigapaxos_tpu.net.node_config import NodeConfig
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.server import PaxosServer
    from gigapaxos_tpu.testing.ports import free_ports

    cfg = EngineConfig(n_groups=6, window=8, req_lanes=4, n_replicas=2)
    ports = free_ports(2)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [
        PaxosServer(i, nc, StatefulAdderApp(), cfg, tick_interval=0.01)
        for i in range(2)
    ]
    for s in servers:
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        # unknown op answers instead of hanging the waiter to timeout
        r = client.admin_sync(0, {"op": "frobnicate", "name": "x"},
                              timeout=10)
        assert r is not None, "unknown admin op never answered"
        assert r["ok"] is False and r["error"] == "unknown_op"

        assert client.create_paxos_instance("obs", [0, 1], timeout=30)
        assert client.send_request_sync("obs", "5", timeout=30) == "5"
        # the response fires at the ENTRY replica (possibly node 1), and
        # node 0's engine can run a tick behind it — poll until node 0's
        # own counter reflects the committed decision
        deadline = time.time() + 30
        while True:
            r = client.admin_sync(0, {"op": "stats"}, timeout=10)
            assert r is not None and r["ok"] is True
            eng = r["engine"]
            if eng["counters"].get("decisions_executed", 0) >= 1:
                break
            assert time.time() < deadline, eng["counters"]
            time.sleep(0.2)
        assert "engine_step_s" in eng["hists"]
        # the mesh actually backing the state arrays rides along — an
        # unsharded deployment must be visible at runtime
        assert eng["mesh"]["n_devices"] >= 1
        assert eng["mesh"]["platform"] == "cpu"
        assert isinstance(eng["mesh"]["shape"], dict)
        # blob publishing happened, so the wire-cost counters are live
        assert eng["counters"].get("blob_bytes_sent", 0) > 0
        # the tick accounts for itself through the same registry: its
        # envelope, its spans and its counts ride the same stats op
        assert {"tick_s", "phase_tick_gather_s", "phase_post_step_s",
                "phase_step_device_wait_cpu_s"} <= set(eng["hists"])
        assert eng["counters"].get("ticks", 0) >= 1
        assert "profiler" not in r  # the process-global EWMA dump is gone
    finally:
        client.close()
        for s in servers:
            s.stop()


# ---- chaos-diag trace ride-along -------------------------------------
def test_name_diag_carries_merged_cross_member_trace():
    """The soak failure payload: with tracing on (as run_soak enables
    it), _name_diag carries the offending name's MERGED cross-member
    timeline — one causal story per request with every member's
    propose/decide/execute hops interleaved and per-phase latency
    attribution — so a SoakDivergence message shows each request's
    whole cluster journey, not N per-member fragments."""
    from gigapaxos_tpu.models.apps import HashChainApp
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.testing.chaos import SoakDivergence, _name_diag
    from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster

    ar_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=4, window=8, req_lanes=4, n_replicas=3)
    c = ReconfigurableCluster(ar_cfg, rc_cfg, HashChainApp)
    try:
        for m in c.ars.managers:
            m.tracer.enabled = True
        for rc in c.reconfigurators:
            rc.tracer.enabled = True
        c.client_request(
            "create_service", {"name": "tn", "actives": [0, 1, 2]}
        )
        for _ in range(40):
            c.step()
        rid = (1 << 55) + 12345
        c.ars.managers[0].propose("tn", "v0", request_id=rid)
        deadline = time.time() + 60
        while time.time() < deadline:
            c.step()
            if all(m.app.state.get("tn") for m in c.ars.managers):
                break
        assert c.ars.managers[0].app.state.get("tn"), "request never executed"
        diag = _name_diag(c, "tn", [0, 1, 2])
        # ONE merged timeline carries the request across all members
        merged = diag.get("merged_trace", "")
        assert str(rid) in merged, merged
        assert "propose" in merged and "execute" in merged
        for a in (0, 1, 2):  # every member's hops interleave in it
            assert f"@ node {a}" in merged, (a, merged)
        assert "phases:" in merged  # per-hop latency attribution
        # the RC epoch timeline rides along too
        assert "rc_epoch_trace" in diag
        assert any("rc-applied" in v or "rc-propose" in v
                   for v in diag["rc_epoch_trace"].values())
        # and the failure message a soak would raise CONTAINS the timeline
        msg = str(SoakDivergence("synthetic", {"members": diag}))
        assert str(rid) in msg and "+" in msg
        # engine metrics moved during the run
        assert c.ars.managers[0].metrics.get("decisions_executed") >= 1
    finally:
        c.close()


# ---- cross-node trace plumbing (sampling, export, merge) --------------
def test_trace_sampling_gate(monkeypatch):
    from gigapaxos_tpu.obs import reqtrace

    monkeypatch.delenv("GP_TRACE_SAMPLE", raising=False)
    assert reqtrace.trace_sample_rate() == 0.0
    assert reqtrace.maybe_mint_trace(3) is None
    monkeypatch.setenv("GP_TRACE_SAMPLE", "1")
    assert reqtrace.trace_sample_rate() == 1.0
    tc = reqtrace.maybe_mint_trace(3)
    assert tc is not None and tc[1] == 3 and tc[2] == 0 and tc[0] > 0
    monkeypatch.setenv("GP_TRACE_SAMPLE", "garbage")
    assert reqtrace.trace_sample_rate() == 0.0
    monkeypatch.setenv("GP_TRACE_SAMPLE", "7")  # clamped
    assert reqtrace.trace_sample_rate() == 1.0


def test_tracer_force_records_when_disabled():
    """The cross-node sampling contract: a request carrying a trace
    context records on EVERY node regardless of the local gate."""
    t = RequestTracer(4, enabled=False)
    t.note(99, "decide", name="svc", force=True, tid=123, slot=5)
    t.note(99, "ignored")  # unforced + disabled: dropped
    evs = t.events(99)
    assert [e[1] for e in evs] == ["decide"]
    assert evs[0][2]["tid"] == 123


def test_tracer_export_shapes():
    t = RequestTracer(1, enabled=True)
    t.note(5, "recv", name="a", node=1)
    t.note(5, "propose", name="a", vid=9)
    t.note(6, "recv", name="b", node=1)
    out = t.export(keys=[5])
    assert set(out) == {"5"}
    assert [e[1] for e in out["5"]] == ["recv", "propose"]
    assert out["5"][0][0] <= out["5"][1][0]  # wall-clock ordered
    by_name = t.export(name="a")
    assert set(by_name) == {"5"}
    everything = t.export()
    assert set(everything) == {"5", "6"}
    assert t.export(limit=1) == {"6": everything["6"]}


def test_tracemerge_attribution_and_skew_clamp():
    from gigapaxos_tpu.obs import tracemerge

    t0 = 1000.0
    dumps = {
        1: {"42": [
            [t0, "recv", {"tid": 7, "hop": 0}],
            [t0 + 0.001, "propose", {"tid": 7, "hop": 0}],
            [t0 + 0.002, "forward-out", {"tid": 7, "hop": 0, "to": 0}],
        ]},
        # node 0's clock runs exactly the hop behind: the forward-in
        # lands at the SAME wall stamp as the forward-out — the hop
        # counter breaks the tie causally and the latency clamps to 0
        0: {"42": [
            [t0 + 0.002, "forward-in", {"tid": 7, "hop": 1}],
            [t0 + 0.004, "decide", {"tid": 7, "slot": 0, "ballot": 3}],
        ]},
    }
    traces = tracemerge.merge_node_dumps(dumps)
    assert len(traces) == 1
    tr = traces[0]
    assert tr["trace_id"] == 7
    assert [e["event"] for e in tr["events"]] == [
        "recv", "propose", "forward-out", "forward-in", "decide"
    ]
    assert all(h["dt_s"] >= 0.0 for h in tr["hops"])
    phases = [h["phase"] for h in tr["hops"]]
    assert "ingress" in phases and "forward-wire" in phases
    wire = [h for h in tr["hops"] if h["phase"] == "forward-wire"][0]
    assert wire["dt_s"] == 0.0  # the skewed hop clamps, never negative
    assert wire["from_node"] == 1 and wire["to_node"] == 0
    text = tracemerge.render_trace(tr)
    assert "tid=0x7" in text and "@ node 1" in text
    # untraced keys correlate by request id and still merge
    plain = tracemerge.merge_node_dumps({
        0: {"9": [[t0, "recv", {}]]},
        1: {"9": [[t0 + 0.01, "execute", {"slot": 1}]]},
    })
    assert len(plain) == 1 and plain[0]["trace_id"] is None
    assert len(plain[0]["events"]) == 2


def test_process_gauges_collect():
    from gigapaxos_tpu.obs.metrics import collect_process_gauges

    m = MetricsRegistry(node=9)
    collect_process_gauges(m)
    snap = m.snapshot()["gauges"]
    assert snap.get("process_rss_bytes", 0) > 0
    assert snap.get("process_open_fds", 0) > 0
    assert snap.get("process_threads", 0) >= 1
    assert "process_gc_collections" in snap
    assert "gp_process_rss_bytes" in m.render()


def test_flight_recorder_rings_and_dump(tmp_path):
    from gigapaxos_tpu.obs.flight import FlightRecorder
    from gigapaxos_tpu.utils.config import Config

    Config.set("FLIGHT_DIR", str(tmp_path))
    fl = FlightRecorder(2, steps=4, decided=6)
    fl.record_step(tick=1, admitted=0, decided=0, preempts=0,
                   coordinator_flips=0, ballot_rises=0,
                   frontier_stalls=0, inflight=0)  # idle: not recorded
    for i in range(10):
        fl.record_step(tick=i, admitted=1, decided=1, preempts=0,
                       coordinator_flips=0, ballot_rises=0,
                       frontier_stalls=0, inflight=2)
        fl.record_decided(3, i, 17, 100 + i)
    snap = fl.snapshot()
    assert len(snap["steps"]) == 4        # ring bound
    assert len(snap["decided"]) == 6      # last-K only
    assert snap["decided"][-1] == [3, 9, 17, 109]
    assert fl.decided_for_group(3) and not fl.decided_for_group(4)
    path = fl.dump(reason="unit test?/x")  # reason is sanitized
    assert path and path.endswith(".json")
    import json as _json

    doc = _json.loads(open(path).read())
    assert doc["node"] == 2 and doc["reason"] == "unit test?/x"
    assert len(doc["decided"]) == 6
    # once-gating: second dump for the same reason suppressed
    assert fl.dump(reason="boom", once=True)
    assert fl.dump(reason="boom", once=True) is None


# ---- hygiene gate ----------------------------------------------------
def test_obs_hygiene_gate():
    """No bare print()/std-stream writes outside obs/, and the
    METRICS.md inventory matches the registered metric names both ways —
    runs the same AST pass future CI uses, as a tier-1 test."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_obs_hygiene.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "metric inventory" in proc.stdout


# ---- the span primitive and what the tick counts ----------------------
@pytest.mark.parametrize("cpu, record, expect", [
    (True, True, {"phase_a_b_s", "phase_a_b_cpu_s"}),
    (False, True, {"phase_a_b_s"}),
    (True, False, set()),     # a phase recorded only when it had work
])
def test_span_observes_wall_and_cpu_into_the_named_histograms(
        cpu, record, expect):
    from gigapaxos_tpu.obs.spans import span

    reg = MetricsRegistry(node=4)
    with span(reg, "a.b", cpu=cpu, record=record, node=4, tick=9):
        c_end = time.thread_time() + 0.02
        while time.thread_time() < c_end:    # 20 ms on the CPU, however
            pass                             # long that takes under load
        time.sleep(0.03)                     # and 30 ms off it
    hists = reg.snapshot()["hists"]
    assert set(hists) == expect
    if record:
        wall = hists["phase_a_b_s"]
        assert wall["count"] == 1 and 0.05 <= wall["sum"] < 1.0
    if cpu and record:
        # the spin is CPU time, the sleep is not: wall minus CPU is the
        # time the thread was not running
        assert 0.019 <= hists["phase_a_b_cpu_s"]["sum"] <= wall["sum"] - 0.025


def test_span_without_a_registry_is_the_annotation_alone():
    from gigapaxos_tpu.obs.spans import span

    with span(None, "nowhere", node=0):
        pass


@pytest.fixture(scope="module")
def ticked_cluster(tmp_path_factory):
    """Three journaled servers on loopback, four sampled writes through a
    NON-coordinator entry (so forwards are on the path), then the
    nodes' registries, the entry's ticks and the merged request
    timelines — one boot for the tests below."""
    import os

    from gigapaxos_tpu.clients import PaxosClientAsync
    from gigapaxos_tpu.models import StatefulAdderApp
    from gigapaxos_tpu.net.node_config import NodeConfig
    from gigapaxos_tpu.obs import tracemerge
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.server import PaxosServer
    from gigapaxos_tpu.testing.ports import free_ports

    old = os.environ.get("GP_TRACE_SAMPLE")
    os.environ["GP_TRACE_SAMPLE"] = "1"
    # 2,048 rows, not 8: a tick's spans then weigh milliseconds against
    # the glue between them (since PR 30 a tick of 8 rows copies and
    # uploads nothing, and under six test workers the glue's waits for
    # the interpreter lock came to a fifth of it)
    cfg = EngineConfig(n_groups=2048, window=8, req_lanes=4, n_replicas=3)
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    logs = tmp_path_factory.mktemp("ticked")
    servers = [PaxosServer(i, nc, StatefulAdderApp(), cfg,
                           log_dir=str(logs / f"n{i}"),
                           tick_interval=0.01) for i in range(3)]
    for s in servers:
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert client.create_paxos_instance("tk0", [0, 1, 2], timeout=30)
        m0 = servers[0].manager
        entry = (m0.coordinator_of_row(m0.names["tk0"]) + 1) % 3
        for k in range(4):
            assert client.send_request_sync(
                "tk0", "1", timeout=30, server=entry) == str(k + 1)
        deadline = time.time() + 30
        while time.time() < deadline and not all(
                s.manager.app.totals.get("tk0") == 4 for s in servers):
            time.sleep(0.05)
        time.sleep(0.3)  # a few more ticks: the last flush is recorded
        snaps = [s.manager.metrics.snapshot() for s in servers]
        traces = tracemerge.merge_node_dumps(
            {s.my_id: s.tracer.export() for s in servers})
        yield {"entry": entry, "snaps": snaps, "traces": traces,
               "cfg": cfg}
    finally:
        client.close()
        for s in servers:
            s.stop()
        if old is None:
            os.environ.pop("GP_TRACE_SAMPLE", None)
        else:
            os.environ["GP_TRACE_SAMPLE"] = old


def test_commit_ticks_equal_the_dticks_of_the_entrys_hops(ticked_cluster):
    """Per write: at least one tick from registration to answer, and the
    ticks the entry replica counted (``commit_ticks``) are the ``dticks``
    of the merged timeline's hops that end on the entry, summed."""
    entry = ticked_cluster["entry"]
    traces = ticked_cluster["traces"]
    assert len(traces) == 4
    per_trace = []
    for tr in traces:
        hops = [h for h in tr["hops"] if h["to_node"] == entry
                and h["dticks"] is not None]
        assert hops and all(h["dticks"] >= 0 for h in hops)
        assert [e["node"] for e in tr["events"]
                if e["event"] == "respond-flush"] == [entry]
        per_trace.append(sum(h["dticks"] for h in hops))
        # every leg the issue names carries the tick it happened in
        for e in tr["events"]:
            if e["event"] in ("propose", "forward-in", "decide",
                              "execute", "respond-flush"):
                assert "tick" in e["detail"], e
    assert min(per_trace) >= 1
    hist = ticked_cluster["snaps"][entry]["hists"]["commit_ticks"]
    assert hist["count"] == 4 and hist["min"] >= 1
    assert hist["sum"] == sum(per_trace)
    assert ticked_cluster["snaps"][entry]["hists"]["commit_entry_s"][
        "count"] == 4
    from gigapaxos_tpu.obs import tracemerge

    # what scripts/gp_trace.py prints: ticks per leg, and per node
    assert tracemerge.node_ticks(traces[0])[entry] == per_trace[0]
    text = tracemerge.render_trace(traces[0])
    assert "ticks: " in text and f"node{entry}={per_trace[0]}" in text
    assert any(" @ node %d +" % entry in line
               for line in text.splitlines())


TICK_THREAD_TOP_LEVEL = (
    "tick_gather", "step_lock_wait", "step_ring_build", "step_dispatch",
    "publish", "forward", "flush", "step_device_wait",
    "post_step_lock_wait", "post_step", "callbacks", "tick_finish",
    "layer",
)


def test_top_level_spans_tile_the_tick(ticked_cluster):
    """After N ticks the tick thread's top-level spans sum to the ticks'
    own time within a stated margin: at least 80 % of ``tick_s`` on the
    CPU's millisecond ticks (the chip's ticks are a hundred times longer
    and the share is a benchmark metric), and no more than ``tick_s`` +
    ``idle_cycle_s`` (publish and flush also run in idle cycles; spans
    do not overlap: 2 % for clock reads)."""
    for snap in ticked_cluster["snaps"]:
        hists = snap["hists"]
        assert hists["tick_s"]["count"] == snap["counters"]["ticks"] >= 4
        busy = hists["tick_s"]["sum"] + hists["idle_cycle_s"]["sum"]
        spanned = sum(hists[f"phase_{k}_s"]["sum"]
                      for k in TICK_THREAD_TOP_LEVEL
                      if f"phase_{k}_s" in hists)
        assert 0.8 * hists["tick_s"]["sum"] <= spanned <= 1.02 * busy, (
            spanned, hists["tick_s"]["sum"], busy)
        # children lie inside their parent
        assert hists["phase_journal_s"]["sum"] \
            + hists["phase_execute_s"]["sum"] \
            <= hists["phase_post_step_s"]["sum"]
        # CPU time never exceeds wall time, span by span
        for k in TICK_THREAD_TOP_LEVEL:
            if f"phase_{k}_cpu_s" in hists:
                assert hists[f"phase_{k}_cpu_s"]["sum"] \
                    <= hists[f"phase_{k}_s"]["sum"] * 1.05 + 1e-3


def test_blob_accounting_adds_up(ticked_cluster):
    """Per node: what was written is what was queued less what was
    superseded (less at most one frame per peer still waiting); what a
    dispatch folded plus what was replaced unread is what arrived; a
    tick either folded a fresh blob or is counted as having none."""
    from gigapaxos_tpu.ops.engine import blob_vec_len

    snaps = ticked_cluster["snaps"]
    for snap in snaps:
        c, h = snap["counters"], snap["hists"]
        written = c.get("blob_frames_written", 0)
        superseded = c.get("blob_frames_superseded", 0)
        assert 0 <= c["blob_frames_sent"] - superseded - written <= 2
        # a frame is a delta or a full one; full ones open a connection
        # (two peers) and deltas are smaller, by the size rule
        full = 13 + 4 * blob_vec_len(ticked_cluster["cfg"])
        assert c["blob_frames_delta"] + c["blob_frames_full"] == written
        assert 1 <= c["blob_frames_full"] <= 2 < c["blob_frames_delta"]
        assert h["blob_delta_rows"]["count"] == c["blob_frames_delta"]
        assert c["blob_bytes_sent"] == c["blob_bytes_written"] \
            < written * full
        assert snap["gauges"]["blob_frame_bytes"] <= full
        assert c["blob_base_mismatch"] == 0
        folded = h["blob_age_ticks"]["count"]
        replaced = c.get("blob_frames_replaced_unread", 0)
        assert 0 <= c["blob_frames_received"] - folded - replaced <= 2
        assert h["blob_age_ticks"]["min"] >= 1
        assert 0 <= c.get("ticks_without_fresh_blob", 0) <= c["ticks"]
        assert c["journal_writes"] >= 4 and c["journal_bytes_written"] > 0
        assert c.get("ticks_inflight_noprog", 0) \
            <= c.get("ticks_noprog", 0) <= c["ticks"]
    assert sum(s["counters"]["blob_frames_received"] for s in snaps) \
        == sum(s["counters"].get("blob_frames_written", 0) for s in snaps)


def test_superseded_blob_is_counted_and_never_as_written():
    """A second blob queued before the first left supersedes it:
    ``blob_frames_superseded`` grows, and ``blob_bytes_written`` counts
    only the frames that reached the socket."""
    from gigapaxos_tpu.net.node_config import NodeConfig
    from gigapaxos_tpu.net.transport import MessageTransport

    nc = NodeConfig({0: ("127.0.0.1", 0), 1: ("127.0.0.1", 0)})
    reg = MetricsRegistry(node=0)
    release, got, last = threading.Event(), [], threading.Event()
    n_frames, size = 12, 4 * 1024 * 1024  # outgrows the socket buffers

    def slow_reader(payload, peer, reply):
        release.wait(30)
        got.append(payload[:4])
        if payload[:4] == (n_frames - 1).to_bytes(4, "big"):
            last.set()

    sender = MessageTransport(0, nc, lambda *a: None, metrics=reg,
                              listen_host="127.0.0.1", listen_port=0,
                              latest_encoder=lambda item, base: (item, None, item))
    reader = MessageTransport(1, nc, slow_reader,
                              listen_host="127.0.0.1", listen_port=0)
    try:
        for nid, t in ((0, sender), (1, reader)):
            t.start()
            nc.add(nid, "127.0.0.1", t.listen_port)
        for i in range(n_frames):
            assert sender.send_latest_to_id(
                1, "blob", i.to_bytes(4, "big") + bytes(size))
            time.sleep(0.005)
        release.set()
        assert last.wait(30), "the newest frame never arrived"
        deadline = time.time() + 10
        while reg.get("blob_frames_written") < len(got) \
                and time.time() < deadline:
            time.sleep(0.01)
        c = reg.snapshot()["counters"]
        assert c["blob_frames_superseded"] >= 1
        assert c["blob_frames_written"] == len(got) \
            == n_frames - c["blob_frames_superseded"]
        assert c["blob_bytes_written"] == c["blob_bytes_sent"] \
            == len(got) * (size + 4)
        assert c["blob_frames_full"] == len(got)
        assert c["blob_frames_delta"] == 0
        hists = reg.snapshot()["hists"]
        assert hists["phase_blob_send_s"]["count"] \
            == hists["phase_blob_encode_s"]["count"] == len(got)
    finally:
        release.set()
        sender.stop()
        reader.stop()


def test_journal_counts_where_the_write_is_made(tmp_path):
    from gigapaxos_tpu.storage.journal import BlockType, Journal

    reg = MetricsRegistry(node=0)
    j = Journal(str(tmp_path), sync=True, metrics=reg)
    try:
        j.append(BlockType.PAYLOADS, b"x" * 100)
        j.append_many([(BlockType.PAYLOADS, b"y" * 10, 0),
                       (BlockType.KILL, b"z" * 8, 2)])
        c = reg.snapshot()["counters"]
        assert c["journal_bytes_written"] == j.position[1] \
            == 3 * 17 + 118          # three 17-byte headers
        # one write for the append; the batch is one writev natively,
        # one write per block on the pure-Python path
        assert c["journal_writes"] == (2 if j._native is not None else 3)
        assert c["journal_fsyncs"] == c["journal_writes"]
    finally:
        j.close()

