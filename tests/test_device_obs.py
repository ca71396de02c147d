"""Device-plane observatory (obs/device.py + manager/server hooks).

Covers the three tentpole instruments end to end:

* the retrace/compile sentinel — counts compiles, flags shape-unstable
  steps as retraces after warmup, and the HARD invariant that the
  deployed hot dispatch compiles exactly once across a multi-tick
  loopback run;
* group-heat telemetry — the on-device ``[G]`` accumulator bit-matches
  a longhand host recount of every step's decided+admitted counts
  over a chaos-seeded ManagerCluster run, and the bulk histogram fold
  bit-matches scalar observes;
* cost attribution — ``step_cost`` AOT split, provenance JSON
  round-trip, the ``profile`` admin op writing into (and bounding) its
  dump directory.
"""

import json
import os
import time

import numpy as np
import pytest


# ---- retrace/compile sentinel ----------------------------------------

def test_sentinel_counts_compiles_and_flags_shape_instability():
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.obs.device import StepSentinel

    @jax.jit
    def f(x):
        return x * 2

    s = StepSentinel(f, label="unit-test-step")
    s(jnp.ones((4,), jnp.int32))
    assert s.n_compiles == 1 and s.n_retraces == 0
    # same shape again: cache hit, no new compile
    s(jnp.ones((4,), jnp.int32))
    assert s.n_compiles == 1
    s.assert_no_retraces()

    # warmup declared over: the next compile — a SHAPE-UNSTABLE call —
    # must be recorded as a retrace, not just a compile
    s.mark_warm()
    s(jnp.ones((4,), jnp.int32))
    assert s.n_retraces == 0
    s(jnp.ones((5,), jnp.int32))
    assert s.n_compiles == 2 and s.n_retraces == 1
    with pytest.raises(RuntimeError, match="retrace"):
        s.assert_no_retraces()

    kinds = [e["kind"] for e in s.events()]
    assert kinds == ["compile", "retrace"]
    st = s.stats()
    assert st["label"] == "unit-test-step"
    assert st["compiles"] == 2 and st["retraces"] == 1 and st["warm"]
    assert st["last"]["kind"] == "retrace"
    # events are JSON-clean: they ride the stats admin op verbatim
    json.dumps(s.events())


def test_sentinel_is_transparent_to_aot_and_step_cost():
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.obs.device import StepSentinel, step_cost

    @jax.jit
    def f(x):
        return x + 1

    s = StepSentinel(f, label="aot")
    x = jnp.ones((8,), jnp.int32)
    cost = step_cost(s, x)
    assert cost["lowering_s"] > 0 and cost["compile_s"] > 0
    assert "flops" in cost and "bytes_accessed" in cost
    assert isinstance(cost["memory"], dict)
    # AOT ran through .lower()/.compile() without touching the jit
    # dispatch cache: the sentinel saw zero compiles
    assert s.n_compiles == 0
    # passthrough attribute access reaches the wrapped jit function
    assert s.fn is f
    s.lower(x)  # must not raise


def test_hot_dispatch_compiles_exactly_once_loopback():
    """THE tentpole invariant: across a multi-tick loopback run with
    real client traffic, the deployed hot dispatch step compiles exactly
    once (warmup) and never retraces — and the retrace sentinel's
    engine.compile block + counters surface that through the stats op.
    Also exercises the `profile` admin op against a live node."""
    import tempfile

    from gigapaxos_tpu.clients import PaxosClientAsync
    from gigapaxos_tpu.models.apps import StatefulAdderApp
    from gigapaxos_tpu.net.node_config import NodeConfig
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.server import PaxosServer
    from gigapaxos_tpu.testing.ports import free_ports

    # distinctive shape: this test owns its make_step cache entry, so
    # the shared sentinel's lifetime counts are this run's counts
    cfg = EngineConfig(n_groups=7, window=8, req_lanes=4, n_replicas=3)
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [
        PaxosServer(i, nc, StatefulAdderApp(), cfg, tick_interval=0.01)
        for i in range(3)
    ]
    for s in servers:
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert client.create_paxos_instance("obsdev", [0, 1, 2],
                                            timeout=30)
        total = 0
        for i in range(12):
            total += i
            assert client.send_request_sync(
                "obsdev", str(i), timeout=30
            ) == str(total)
        # let every node run a healthy number of further ticks
        time.sleep(0.5)

        for s in servers:
            sent = s.manager._dispatch_step
            assert sent.warm, "first dispatch should have marked warm"
            assert sent.n_compiles == 1, sent.stats()
            assert sent.n_retraces == 0, sent.stats()
            sent.assert_no_retraces()

        # the same picture through the admin plane
        r = client.admin_sync(0, {"op": "stats"}, timeout=10)
        assert r and r["ok"]
        eng = r["engine"]
        comp = eng["compile"]
        assert comp["dispatch"]["compiles"] == 1
        assert comp["dispatch"]["retraces"] == 0
        assert eng["counters"].get("engine_compiles", 0) >= 1
        assert eng["counters"].get("engine_retraces", 0) == 0
        # heat rode along: the decided+admitted traffic shows up in the
        # stats block's heat summary with a real top-groups table
        heat = eng["heat"]
        assert heat["total"] > 0 and heat["active_groups"] >= 1
        assert heat["top_groups"][0]["heat"] > 0

        # `profile` admin op: writes a capture into the requested dir
        with tempfile.TemporaryDirectory() as td:
            r = client.admin_sync(
                0, {"op": "profile", "dir": td, "seconds": 0.02},
                timeout=15,
            )
            assert r and r["ok"], r
            assert r["dir"].startswith(td) and os.path.isdir(r["dir"])
            assert r["seconds"] > 0
    finally:
        client.close()
        for s in servers:
            s.stop()


# ---- group-heat telemetry --------------------------------------------

def test_group_heat_bitmatches_host_recount_chaos_run():
    """The on-device heat accumulator is exact, not approximate: over a
    chaos-seeded stepped run (random proposals, random link drops, an
    election kick), every manager's pulled heat equals a longhand host
    recount of per-step ``n_committed + n_admitted``."""
    from gigapaxos_tpu.models.apps import HashChainApp
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster

    cfg = EngineConfig(n_groups=8, window=4, req_lanes=2, n_replicas=3)
    R, G = cfg.n_replicas, cfg.n_groups
    c = ManagerCluster(cfg, HashChainApp)
    try:
        # longhand recount: intercept every dispatch's digest BEFORE
        # the engine's own post-step work consumes it
        expected = [np.zeros(G, np.int64) for _ in range(R)]

        def _wrap(m, exp):
            orig = m._post_step_locked

            def wrapped(out):
                exp[:] += np.asarray(out.n_committed).astype(np.int64)
                exp[:] += np.asarray(out.n_admitted).astype(np.int64)
                return orig(out)

            m._post_step_locked = wrapped

        for rid, m in enumerate(c.managers):
            _wrap(m, expected[rid])

        names = ["heat0", "heat1", "heat2"]
        for nm in names:
            c.create(nm)
        rng = np.random.default_rng(20260807)
        for step in range(40):
            for _ in range(int(rng.integers(0, 4))):
                nm = names[int(rng.integers(0, len(names)))]
                c.submit(nm, f"v{step}-{rng.integers(1 << 20)}",
                         entry=int(rng.integers(0, R)))
            delivery = np.where(
                rng.random((R, R)) < 0.2, DROP, DELIVER
            )
            np.fill_diagonal(delivery, DELIVER)
            c.step_all(delivery=delivery)
        # settle with clean links so in-flight traffic drains
        c.run(10)

        saw_heat = False
        for rid, m in enumerate(c.managers):
            delta = m.pull_group_heat()
            assert delta.dtype == np.int64
            np.testing.assert_array_equal(m._heat_host, expected[rid])
            saw_heat = saw_heat or expected[rid].any()
            # drained on pull: a second pull returns zeros while the
            # cumulative host view is unchanged
            again = m.pull_group_heat()
            assert not again.any()
            np.testing.assert_array_equal(m._heat_host, expected[rid])
            # the summary agrees with the longhand vector
            summ = m.group_heat_stats()
            assert summ["total"] == int(expected[rid].sum())
            assert summ["active_groups"] == int(
                (expected[rid] > 0).sum()
            )
        assert saw_heat, "chaos run decided/admitted nothing"
    finally:
        c.close()


def test_heat_summary_longhand():
    from gigapaxos_tpu.obs.device import heat_summary

    heat = np.zeros(200, np.int64)
    heat[7] = 100
    heat[13] = 30
    heat[99] = 1
    s = heat_summary(heat, topk=2, name_of={7: "hot"}.get)
    assert s["total"] == 131 and s["active_groups"] == 3
    assert [r["row"] for r in s["top_groups"]] == [7, 13]
    assert s["top_groups"][0]["name"] == "hot"
    assert "name" not in s["top_groups"][1]
    # hot set = top 1% = ceil(200/100) = 2 rows -> 130/131 of traffic
    assert s["hot_set"]["rows"] == 2
    assert s["hot_set"]["traffic_share"] == pytest.approx(130 / 131)
    assert heat_summary(np.zeros(4, np.int64))["total"] == 0


def test_observe_bulk_bitmatches_scalar_observe():
    from gigapaxos_tpu.obs.device import HEAT_BOUNDS
    from gigapaxos_tpu.obs.metrics import MetricsRegistry

    rng = np.random.default_rng(7)
    samples = rng.integers(1, 100_000, size=500).astype(np.float64)
    a = MetricsRegistry(node=0)
    b = MetricsRegistry(node=0)
    for x in samples:
        a.observe("group_heat", float(x), bounds=HEAT_BOUNDS)
    b.observe_bulk("group_heat", samples, bounds=HEAT_BOUNDS)
    sa = a.snapshot()["hists"]["group_heat"]
    sb = b.snapshot()["hists"]["group_heat"]
    assert sa["buckets"] == sb["buckets"]
    assert sa["count"] == sb["count"]
    assert sa["min"] == sb["min"] and sa["max"] == sb["max"]
    assert sa["sum"] == pytest.approx(sb["sum"])
    # empty fold registers nothing
    b.observe_bulk("other", np.array([]))
    assert "other" not in b.snapshot()["hists"]


# ---- cost attribution / provenance / profiler -------------------------

def test_provenance_roundtrips_json():
    from gigapaxos_tpu.obs.device import provenance

    p = provenance(donate=True, extra={"run": "unit"})
    assert json.loads(json.dumps(p)) == p
    for key in ("jax", "jaxlib", "backend", "platform", "device_kind",
                "n_devices", "xla_flags", "python", "donation"):
        assert key in p, key
    assert p["donation"] is True and p["run"] == "unit"
    assert p["platform"] == "cpu"  # conftest pins the test backend


def test_capture_profile_writes_and_bounds_dump_dir(tmp_path):
    from gigapaxos_tpu.obs.device import capture_profile

    root = str(tmp_path / "profiles")
    caps = [
        capture_profile(root, seconds=0.01, max_dumps=2)
        for _ in range(4)
    ]
    for cap in caps[-2:]:
        assert os.path.isdir(cap["dir"])
    dumps = [d for d in os.listdir(root)
             if os.path.isdir(os.path.join(root, d))]
    assert len(dumps) <= 2, dumps
    assert sum(c["rotated_out"] for c in caps) >= 2
    # the per-capture wall clamp holds even against absurd requests
    cap = capture_profile(root, seconds=99.0, max_dumps=2,
                          max_seconds=0.05)
    assert cap["seconds"] < 1.0


def test_span_leaves_a_gp_event_in_the_host_plane(tmp_path):
    """Inside a ``jax.profiler`` session the span's annotation lies in
    the ``/host:CPU`` plane of the same ``.xplane.pb`` that holds the
    device's operations, named ``gp.<phase>`` with node and tick as
    stats (not as part of the name); outside a session nothing is
    written and the histograms are observed all the same."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from gigapaxos_tpu.obs.metrics import MetricsRegistry
    from gigapaxos_tpu.obs.spans import span

    reg = MetricsRegistry(node=2)
    with span(reg, "step.device_wait", cpu=True, node=2, tick=6):
        pass                                   # no session: histogram only
    jax.profiler.start_trace(str(tmp_path))
    try:
        for tick in (7, 8):
            with span(reg, "step.device_wait", cpu=True, node=2,
                      tick=tick):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    assert reg.snapshot()["hists"]["phase_step_device_wait_s"]["count"] == 3
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                         "*.xplane.pb"))[0]
    found = [
        (e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith("gp.")
    ]
    assert [n for n, _ in found] == ["gp.step.device_wait"] * 2
    assert [st["tick"] for _, st in found] == [7, 8]
    assert all(st["node"] == 2 for _, st in found)


# ---- SLO gate ---------------------------------------------------------

def test_slo_budget_parse_and_breach():
    from gigapaxos_tpu.obs import tracemerge as tm
    from gigapaxos_tpu.paxos_config import PC
    from gigapaxos_tpu.utils.config import Config

    # the shipped default must parse (every phase name real)
    budgets = tm.parse_slo_budgets(Config.get_str(PC.SLO_BUDGETS_MS))
    assert budgets["total"] == pytest.approx(2.0)
    with pytest.raises(ValueError, match="unknown phase"):
        tm.parse_slo_budgets("execute=10")
    trace = {
        "hops": [
            {"phase": "ingress", "dt_s": 0.040},
            {"phase": "ingress", "dt_s": 0.020},
            {"phase": "consensus", "dt_s": 0.100},
        ],
        "total_s": 0.160,
    }
    over = tm.slo_breaches(trace, budgets)
    assert [b["phase"] for b in over] == ["ingress"]  # 60ms > 50ms
    assert not tm.slo_breaches(trace, {"consensus": 0.5})
