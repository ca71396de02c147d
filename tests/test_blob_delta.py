"""The blob exchange sends what changed: ``d`` frames carry the rows that
differ from the vector the sender last wrote to THAT connection, the
receiver patches them into the vector it holds, and after every frame it
accepts its copy equals the sender's publish vector bit for bit.  Full
``D`` frames open every connection and are chosen by what the code sees."""

import functools
import threading
import time

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.net.codec import (
    decode_blob_delta,
    decode_blob_vec,
    decode_json,
    decode_kind,
    encode_blob_frame,
    encode_blob_vec,
    patch_blob_vec,
)
from gigapaxos_tpu.net.node_config import NodeConfig
from gigapaxos_tpu.net.transport import MessageTransport
from gigapaxos_tpu.obs.metrics import MetricsRegistry
from gigapaxos_tpu.ops.engine import (
    EngineConfig,
    blob_vec_len,
    split_blob_vec,
)
from gigapaxos_tpu.server import PaxosServer
from gigapaxos_tpu.testing.ports import free_ports
from tests.test_server import wait_until

CFG = EngineConfig(n_groups=32, window=4, req_lanes=4, n_replicas=3)
N = blob_vec_len(CFG)
ROW_WORDS = N // CFG.n_groups
FULL_BYTES = 13 + 4 * N


def random_vec(rng):
    return rng.integers(-2, 1 << 30, N, dtype=np.int32)


def touch_rows(rng, vec, rows):
    """A new publish vector: `vec` with some word of each named row
    changed (any of the eight leaves)."""
    out = vec.copy()
    leaves = split_blob_vec(out, CFG)
    for g in rows:
        leaf = leaves[rng.integers(len(leaves))]
        if leaf.ndim == 1:
            leaf[g] += 1
        else:
            leaf[g, rng.integers(CFG.window)] += 1
    return out


def receiver(cfg=CFG):
    """Node 1 of three as a server that is never started: its ingress and
    its gather are driven by hand (no tick thread, no listener)."""
    nc = NodeConfig({i: ("127.0.0.1", 1) for i in range(3)})
    srv = PaxosServer(1, nc, StatefulAdderApp(), cfg)
    sent = []
    srv.transport.send_to_id = lambda nid, frame: sent.append((nid, frame))
    return srv, sent


def deliver(srv, frame):
    srv._on_message(frame, ("127.0.0.1", 0), lambda b: None)


# ---- (1) the property: sender and receiver agree after every frame ----
@pytest.mark.parametrize("seed", [1, 2, 3, 2147483747])
def test_receiver_equals_sender_after_every_applied_frame(seed):
    """Random publish vectors (a few rows, no row, or — a state replaced
    outside the tick — every row), bursts in which the slot is replaced
    before its turn, connections cut, and a peer that asks for a resync:
    over real sockets, into a real server's ingress.  After EVERY frame
    the receiver accepts, the vector it holds is the one the sender
    published at that tick, which is what a full frame decodes to."""
    rng = np.random.default_rng(seed)
    srv, _asked = receiver()
    srv.transport.start()
    nc = NodeConfig({0: ("127.0.0.1", 0),
                     1: ("127.0.0.1", srv.transport.listen_port)})
    reg = MetricsRegistry(node=0)
    sender = MessageTransport(
        0, nc, lambda *a: None, listen_host="127.0.0.1", listen_port=0,
        metrics=reg,
        latest_encoder=functools.partial(encode_blob_frame, 0, CFG))
    published = {}  # tick -> the vector published at it
    wrong = []
    inner = srv._on_message

    def checked(payload, peer, reply):
        inner(payload, peer, reply)
        with srv._blob_lock:
            tick = srv._peer_blob_tick.get(0)
            held = srv._peer_blobs[0].copy() if tick is not None else None
        if tick is not None and not np.array_equal(held, published[tick]):
            wrong.append((decode_kind(payload), tick))

    srv.transport.handler = checked
    tick, vec = 0, random_vec(rng)

    def publish(new):
        nonlocal tick, vec
        tick, vec = tick + 1, new
        published[tick] = new
        assert sender.send_latest_to_id(1, "blob", (tick, new))

    def settled():
        return srv._peer_blob_tick.get(0) == tick

    try:
        sender.start()
        for _step in range(120):
            op = rng.integers(10)
            if op < 5:  # a tick's worth: a few rows, sometimes none
                k = int(rng.integers(0, 6))
                publish(touch_rows(
                    rng, vec, rng.choice(CFG.n_groups, k, replace=False)))
            elif op == 5:  # a burst: the slot is replaced before its turn
                for _ in range(4):
                    publish(touch_rows(rng, vec, rng.choice(
                        CFG.n_groups, 3, replace=False)))
            elif op == 6:  # state replaced outside the tick: every row
                publish(random_vec(rng))
            elif op == 7:  # the connection goes
                for w in list(sender._writers.values()):
                    sender._loop.call_soon_threadsafe(w.close)
            elif op == 8:  # the peer says it lost the base
                sender.forget_latest_base(1)
            else:  # let the exchange catch up
                assert wait_until(settled), "the newest vector never came"
            if rng.integers(3) == 0:
                time.sleep(0.002)
        publish(touch_rows(rng, vec, [0]))
        if not wait_until(settled, timeout=2):
            # a frame written into a connection cut under it is gone, as
            # on any network; the next one opens a new connection
            publish(touch_rows(rng, vec, [1]))
            assert wait_until(settled), "the newest vector never came"
        assert not wrong, wrong
        s2, t2, full = decode_blob_vec(encode_blob_vec(0, tick, vec), CFG)
        np.testing.assert_array_equal(srv._peer_blobs[0], full)
        c = reg.snapshot()["counters"]
        assert c["blob_frames_delta"] > 0 and c["blob_frames_full"] > 0
        assert c["blob_frames_delta"] + c["blob_frames_full"] \
            == c["blob_frames_written"]
    finally:
        sender.stop()
        srv.transport.stop()
        srv.manager.close()


# ---- (2) base mismatch -------------------------------------------------
def test_delta_without_its_base_is_dropped_counted_and_healed():
    rng = np.random.default_rng(5)
    srv, asked = receiver()
    mx = srv.manager.metrics
    enc = functools.partial(encode_blob_frame, 0, CFG)
    v1 = random_vec(rng)
    v2 = touch_rows(rng, v1, [3, 4])
    v3 = touch_rows(rng, v2, [4, 9])
    try:
        d12, rows = enc((2, v2), (1, v1))
        assert decode_kind(d12) == "d" and rows == 2
        # no vector of that sender is held at all
        deliver(srv, d12)
        assert mx.get("blob_base_mismatch") == 1
        assert 0 not in srv._peer_blobs
        assert mx.get("blob_frames_received") == 0
        assert srv.fd.is_node_up(0)  # it was heard all the same
        # the sender is asked for the whole vector, once per period
        assert [(nid, decode_json(f)[0]) for nid, f in asked] \
            == [(0, "blob_resync")]
        deliver(srv, d12)
        assert mx.get("blob_base_mismatch") == 2 and len(asked) == 1
        # a full frame is accepted whatever is held
        full1, rows = enc((1, v1), None)
        assert decode_kind(full1) == "D" and rows is None
        deliver(srv, full1)
        np.testing.assert_array_equal(srv._peer_blobs[0], v1)
        # a delta against another base than the one held: dropped,
        # and what is held stays whole
        d23, _ = enc((3, v3), (2, v2))
        deliver(srv, d23)
        assert mx.get("blob_base_mismatch") == 3
        np.testing.assert_array_equal(srv._peer_blobs[0], v1)
        assert srv._peer_blob_tick[0] == 1
        # the next frame it accepts leaves it with the right vector
        deliver(srv, d12)
        np.testing.assert_array_equal(srv._peer_blobs[0], v2)
        deliver(srv, d23)
        np.testing.assert_array_equal(srv._peer_blobs[0], v3)
        assert mx.get("blob_frames_received") == 3
        # the sender's side of the resync: its next frame stands alone
        srv._on_message(asked[0][1], ("127.0.0.1", 0), lambda b: None)
        assert srv.transport._base_forgotten
    finally:
        srv.manager.close()


# ---- (3) a reader racing the patch ------------------------------------
def test_reader_racing_the_patch_never_sees_a_row_of_two_ticks():
    """Every word of a row carries the tick that wrote it; the stack
    the step would read of the gather's update must show each of the
    peer's rows uniform."""
    srv, _ = receiver()
    enc = functools.partial(encode_blob_frame, 0, CFG)
    stop = threading.Event()
    n_ticks = [0]

    def writer():
        rng = np.random.default_rng(11)
        base = (1, np.ones(N, np.int32))
        deliver(srv, enc(base, None)[0])
        while not stop.is_set():
            tick = base[0] + 1
            vec = base[1].copy()
            rows = rng.choice(CFG.n_groups, 8, replace=False)
            for leaf in split_blob_vec(vec, CFG):
                leaf[rows] = tick  # a row is written whole, all leaves
            frame, n = enc((tick, vec), base)
            assert n == len(rows)
            deliver(srv, frame)
            base = (tick, vec)
            n_ticks[0] = tick

    t = threading.Thread(target=writer, daemon=True)
    try:
        t.start()
        assert wait_until(lambda: n_ticks[0] > 2)
        seen = set()
        for _ in range(300):
            update, heard, _want = srv._gather()
            assert heard[0]
            gathered = srv.manager.gathered_host(update)
            by_row = np.concatenate([
                leaf.reshape(CFG.n_groups, -1)
                for leaf in split_blob_vec(gathered[0], CFG)], axis=1)
            assert by_row.shape == (CFG.n_groups, ROW_WORDS)
            assert (by_row.min(axis=1) == by_row.max(axis=1)).all(), \
                "a row mixed from two ticks"
            seen.add(int(by_row.max()))
        assert len(seen) > 3  # the reader did race a moving vector
    finally:
        stop.set()
        t.join(10)
        srv.manager.close()


# ---- (4) the size rule, observed and never switched --------------------
def test_size_rule_all_rows_full_none_header_only():
    rng = np.random.default_rng(7)
    enc = functools.partial(encode_blob_frame, 0, CFG)
    v1 = random_vec(rng)
    # every row changed: the delta would be larger, so a D frame
    frame, rows = enc((2, v1 + 1), (1, v1))
    assert decode_kind(frame) == "D" and rows is None
    assert len(frame) == FULL_BYTES
    # the largest delta that is still smaller, and the first that is not
    most = (FULL_BYTES - 25 - 1) // (4 * (1 + ROW_WORDS))
    frame, rows = enc((2, touch_rows(rng, v1, range(most))), (1, v1))
    assert decode_kind(frame) == "d" and rows == most
    assert len(frame) < FULL_BYTES
    frame, rows = enc((2, touch_rows(rng, v1, range(most + 1))), (1, v1))
    assert decode_kind(frame) == "D" and rows is None
    # nothing changed: a header, and still a blob to the receiver
    frame, rows = enc((2, v1.copy()), (1, v1))
    assert decode_kind(frame) == "d" and rows == 0 and len(frame) == 25
    sender, tick, base_tick, idx, blocks = decode_blob_delta(frame, CFG)
    assert (sender, tick, base_tick, len(idx)) == (0, 2, 1, 0)
    srv, _ = receiver()
    try:
        deliver(srv, enc((1, v1), None)[0])
        srv._gather()  # folds tick 1
        srv._kick.clear()
        srv.fd.last_heard[0] = 0.0
        before = srv.manager.metrics.get("ticks_without_fresh_blob")
        deliver(srv, frame)
        assert srv.fd.is_node_up(0)  # marks the peer heard
        assert srv._kick.is_set() and srv._blob_dirty  # wakes the loop
        assert srv._peer_blob_tick[0] == 2
        np.testing.assert_array_equal(srv._peer_blobs[0], v1)
        srv._gather()  # and the dispatch that folds it counts it fresh
        mx = srv.manager.metrics
        assert mx.get("ticks_without_fresh_blob") == before
        assert mx.snapshot()["hists"]["blob_age_ticks"]["count"] == 2
        assert mx.get("blob_frames_received") == 2
    finally:
        srv.manager.close()


def test_patch_equals_the_full_frame_leaf_by_leaf():
    rng = np.random.default_rng(9)
    v1 = random_vec(rng)
    v2 = touch_rows(rng, v1, [0, 5, 31])
    frame, rows = encode_blob_frame(2, CFG, (8, v2), (7, v1))
    assert rows == 3 and len(frame) == 25 + 4 * 3 * (1 + ROW_WORDS)
    sender, tick, base_tick, idx, blocks = decode_blob_delta(frame, CFG)
    assert (sender, tick, base_tick) == (2, 8, 7)
    assert idx.tolist() == [0, 5, 31]
    held = v1.copy()
    patch_blob_vec(held, idx, blocks, CFG)
    np.testing.assert_array_equal(held, v2)
    # a frame of another shape, or naming a row outside the engine, is
    # refused whole
    with pytest.raises(ValueError, match="size"):
        decode_blob_delta(frame[:-4], CFG)
    bad = bytearray(frame)
    bad[25:29] = np.int32(CFG.n_groups).tobytes()
    with pytest.raises(ValueError, match="row"):
        decode_blob_delta(bytes(bad), CFG)


# ---- (5) three nodes on loopback ---------------------------------------
@pytest.mark.timeout(180)
def test_three_nodes_commit_over_delta_frames():
    from gigapaxos_tpu.clients import PaxosClientAsync

    cfg = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
    full = 13 + 4 * blob_vec_len(cfg)
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [PaxosServer(i, nc, StatefulAdderApp(), cfg,
                           tick_interval=0.01) for i in range(3)]
    for s in servers:
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert client.create_paxos_instance("dl0", [0, 1, 2], timeout=30)
        for k in range(20):
            assert client.send_request_sync(
                "dl0", "1", timeout=30, server=k % 3) == str(k + 1)
        assert wait_until(lambda: all(
            s.manager.app.totals.get("dl0") == 20 for s in servers))
        for s in servers:
            snap = s.manager.metrics.snapshot()  # one: the nodes run on
            c, h = snap["counters"], snap["hists"]
            # one full frame per connection opened (one to each peer)
            assert c["blob_frames_full"] == len(s.transport._writers) == 2
            assert c["blob_frames_delta"] > 20
            assert c["blob_base_mismatch"] == 0
            assert c["blob_frames_delta"] + c["blob_frames_full"] \
                == c["blob_frames_written"]
            # one busy row of 64: a tick's frames are far under the
            # whole vector's size
            per_frame = c["blob_bytes_written"] / c["blob_frames_written"]
            assert per_frame < full / 8, (per_frame, full)
            assert h["blob_delta_rows"]["max"] < 16
            assert h["phase_blob_encode_s"]["count"] \
                == c["blob_frames_written"]
    finally:
        client.close()
        for s in servers:
            s.stop()
