"""Deployable reconfigurable node over real sockets — loopback_rc_simple
parity (ref: ``tests/loopback_rc_simple/testing.properties`` +
``ReconfigurableNode.java:223-300``): boot 3 actives + 3 reconfigurators
as socket servers from properties config, then drive create -> requests ->
migrate -> delete through the reconfiguration-aware client
(``ReconfigurableAppClientAsync`` analog), including a request served
from a stale actives cache mid-migration."""

import socket
import time

from gigapaxos_tpu.testing.ports import free_ports

import pytest

from gigapaxos_tpu.clients.reconfigurable_client import ReconfigurableAppClient
from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.reconfigurable_node import ReconfigurableNode
from gigapaxos_tpu.utils.config import Config


@pytest.fixture(scope="module")
def cluster():
    ports = free_ports(6)
    Config.clear()
    for i in range(3):
        Config.set(f"active.AR{i}", f"127.0.0.1:{ports[i]}")
        Config.set(f"reconfigurator.RC{i}", f"127.0.0.1:{ports[3 + i]}")
    ar_cfg = EngineConfig(n_groups=32, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    nodes = [
        ReconfigurableNode(f"AR{i}", HashChainApp, ar_cfg=ar_cfg, rc_cfg=rc_cfg)
        for i in range(3)
    ] + [
        ReconfigurableNode(f"RC{i}", HashChainApp, ar_cfg=ar_cfg, rc_cfg=rc_cfg)
        for i in range(3)
    ]
    for n in nodes:
        n.start()
    client = ReconfigurableAppClient.from_properties()
    yield nodes, client
    client.close()
    for n in nodes:
        n.stop()
    Config.clear()


def ar_server(nodes, i):
    return nodes[i].servers[0]


def test_create_request_migrate_delete_over_sockets(cluster):
    nodes, client = cluster

    # --- create through the RCs --------------------------------------
    ack = client.create_name("svc", actives=[0, 1, 2], timeout=30)
    assert ack and ack.get("ok"), ack
    assert sorted(ack["actives"]) == [0, 1, 2]

    # --- resolve + app requests through epoch 0 ----------------------
    # under a loaded box the 6 in-process nodes can stall tens of seconds
    # on cold jax compiles; wait on the record itself before resolving
    deadline = time.time() + 90
    while time.time() < deadline:
        rec = nodes[3].servers[0].rc_app.get_record("svc")
        if rec is not None and rec.actives:
            break
        time.sleep(0.25)
    acts = None
    for _ in range(6):
        acts = client.request_actives("svc", timeout=10, force=True)
        if acts:
            break
    assert acts is not None and sorted(acts) == [0, 1, 2]
    for i in range(5):
        resp = client.send_request_sync("svc", f"r{i}", timeout=20)
        assert resp is not None, f"request r{i} timed out"

    apps = [ar_server(nodes, i).manager.app for i in range(3)]
    deadline = time.time() + 10
    while time.time() < deadline:
        states = [a.state.get("svc") for a in apps]
        if states[0] is not None and states[0] == states[1] == states[2]:
            break
        time.sleep(0.1)
    assert states[0] == states[1] == states[2], states

    # --- migrate [0,1,2] -> [1,2] (node 0 leaves) ---------------------
    ack = client.reconfigure("svc", [1, 2], timeout=40)
    assert ack and ack.get("ok"), ack
    assert sorted(ack["actives"]) == [1, 2] and ack["epoch"] == 1

    # old epoch drops off node 0 (best-effort; bounded wait)
    deadline = time.time() + 20
    while time.time() < deadline:
        if ar_server(nodes, 0).manager.names.get("svc") is None:
            break
        time.sleep(0.1)
    assert ar_server(nodes, 0).manager.names.get("svc") is None

    # --- stale-cache request lands at the departed active ------------
    # poison the cache so the next request targets node 0, which no
    # longer hosts the name: unknown_name -> invalidate -> re-resolve
    with client._lock:
        client._actives_cache["svc"] = (time.time() + 60, [0])
    resp = client.send_request_sync("svc", "post-migration", timeout=20)
    assert resp is not None, "mid-migration request did not recover"
    acts = None
    for _ in range(3):
        acts = client.request_actives("svc", force=True)
        if acts:
            break
    assert acts is not None and sorted(acts) == [1, 2]

    # state continuity on the new epoch
    a1 = ar_server(nodes, 1).manager.app
    a2 = ar_server(nodes, 2).manager.app
    deadline = time.time() + 10
    while time.time() < deadline:
        if a1.state.get("svc") == a2.state.get("svc") and \
                a1.n_executed.get("svc", 0) >= 6:
            break
        time.sleep(0.1)
    assert a1.state.get("svc") == a2.state.get("svc")
    assert a1.n_executed.get("svc", 0) >= 6  # 5 pre + 1 post migration

    # --- delete -------------------------------------------------------
    ack = client.delete_name("svc", timeout=40)
    assert ack and ack.get("ok"), ack
    deadline = time.time() + 10
    while time.time() < deadline:
        if all(ar_server(nodes, i).manager.names.get("svc") is None
               for i in (1, 2)):
            break
        time.sleep(0.1)
    for i in (1, 2):
        assert ar_server(nodes, i).manager.names.get("svc") is None
    # record purged on every reconfigurator (DELETE_FINAL application may
    # lag the client ack by a few ticks on non-primary RCs)
    deadline = time.time() + 20
    while time.time() < deadline:
        if all(nodes[i].servers[0].rc_app.get_record("svc") is None
               for i in (3, 4, 5)):
            break
        time.sleep(0.1)
    for i in (3, 4, 5):
        assert nodes[i].servers[0].rc_app.get_record("svc") is None


def test_http_front_ends(cluster):
    """REST parity: create/resolve via the reconfigurator's HTTP API and
    execute an app request via an active's HTTP API (HttpReconfigurator
    .java:79 / HttpActiveReplica.java:29 analogs)."""
    import json as _json
    import urllib.request

    from gigapaxos_tpu.paxos_config import PC
    from gigapaxos_tpu.utils.config import Config

    nodes, client = cluster
    off = Config.get_int(PC.HTTP_PORT_OFFSET)
    rc = nodes[3].servers[0]
    ar = nodes[0].servers[0]
    assert rc._http is not None and ar._http is not None
    rc_url = f"http://127.0.0.1:{rc.transport.listen_port + off}"
    ar_url = f"http://127.0.0.1:{ar.transport.listen_port + off}"

    def post(url, payload, timeout=30):
        req = urllib.request.Request(
            url, data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, _json.loads(r.read())

    code, body = post(rc_url, {
        "type": "CREATE", "name": "httpsvc", "actives": [0, 1, 2],
    })
    assert code == 200 and body["ok"], body

    with urllib.request.urlopen(
        f"{rc_url}/?name=httpsvc", timeout=20
    ) as r:
        resolved = _json.loads(r.read())
    assert resolved["ok"] and sorted(resolved["actives"]) == [0, 1, 2]

    code, body = post(ar_url, {"name": "httpsvc", "request": "via-http"})
    assert code == 200 and body["response"] is not None, body

    code, body = post(rc_url, {"type": "DELETE", "name": "httpsvc"})
    assert code == 200 and body["ok"], body


def test_a_write_straight_to_an_active_wakes_the_name_it_paused(cluster):
    """Wake on write over sockets (PR 31): the name is paused on all
    three actives; a write sent straight to one of them, as a load
    generator sends (``send_prepared``: no resolution, no retry), is
    acknowledged like any other; a name nobody created is refused."""
    import threading

    nodes, client = cluster
    ack = client.create_name("nap", actives=[0, 1, 2], timeout=30)
    assert ack and ack.get("ok"), ack
    for i in range(3):
        assert client.send_request_sync("nap", f"n{i}", timeout=20) is not None
    ars = [ar_server(nodes, i) for i in range(3)]

    def wait(cond, seconds, what):
        deadline = time.time() + seconds
        while time.time() < deadline:
            if cond():
                return
            time.sleep(0.1)
        raise AssertionError(what)

    wait(lambda: len({s.manager.app.n_executed.get("nap") for s in ars}) == 1
         and ars[0].manager.app.n_executed.get("nap") == 3, 20, "3 executed")
    with ars[0]._layer_lock:
        ars[0].active_replica.send(("RC", 0), "suggest_pause", {
            "name": "nap", "epoch": 0, "from": 0})
    wait(lambda: all(s.manager.sleeps_here("nap") for s in ars), 30,
         "the pause round")
    # the stats admin op through the client library: who sleeps, and
    # where the sweep stands
    for i in range(3):
        stats = client.admin_sync(i, {"op": "stats"}, timeout=10)
        assert stats["residency"]["paused_names"] >= 1, stats["residency"]
        assert stats["layer"]["sweep"]["period_s"] > 0
        assert stats["layer"]["sweep"]["pause_option"] is True
    assert client.admin_sync(0, {"op": "nonesuch"})["error"] == "unknown_op"

    answers, done = [], threading.Event()

    def cb(rid, response, error):
        answers.append((response, error))
        done.set()

    client.send_prepared(tuple(client.actives[1]), "nap", "after-the-nap", cb)
    assert done.wait(30), "the first write to a sleeping name went unanswered"
    assert answers[0][1] is None and answers[0][0] is not None, answers
    wait(lambda: all(s.manager.app.n_executed.get("nap") == 4 for s in ars),
         20, "executed on all three after the wake")
    assert len({s.manager.app.state["nap"] for s in ars}) == 1
    assert ars[1].manager.metrics.get("writes_held_for_wake") == 1
    assert ars[1].manager.metrics.get("wake_requests_sent") >= 1

    done.clear()
    client.send_prepared(tuple(client.actives[1]), "nobody", "x", cb)
    assert done.wait(10) and answers[-1] == (None, "unknown_name")
