"""The blob exchange sends what changed: ``d`` frames carry the rows that
changed since the tick the sender last wrote to THAT connection, the
receiver patches them into the vector it holds, and after every frame it
accepts its copy equals the sender's publish vector bit for bit.  Full
``D`` frames open every connection and are chosen by what the code sees.

The sender finds the rows in its mirror's ``[G]`` row ticks
(``net/mirror.py``: the step named them); ``codec.encode_blob_frame``,
which finds them by comparing two whole vectors, is the reference its
frames are held against byte for byte."""

import functools
import threading
import time

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.net.codec import (
    changed_rows,
    decode_blob_delta,
    decode_blob_vec,
    decode_json,
    decode_kind,
    encode_blob_frame,
    encode_blob_vec,
    patch_blob_vec,
    rows_of,
)
from gigapaxos_tpu.net.mirror import PublishMirror
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


def publish(mirror, new):
    """What a completion does with a step's news — the rows found here by
    the compare the step spares the host.  -> the mirror's tick."""
    if mirror.vec is None:
        mirror.replace(new.copy())
    else:
        rows = changed_rows(new, mirror.vec, CFG)
        mirror.patch(rows, rows_of(new, rows, CFG))
    return mirror.tick


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
    mirror = PublishMirror(CFG, 0)
    sender = MessageTransport(
        0, nc, lambda *a: None, listen_host="127.0.0.1", listen_port=0,
        metrics=reg, latest_encoder=mirror.encode)
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
    vec = random_vec(rng)

    def send(new):
        nonlocal vec
        vec = new
        # known before an encoder can cut a frame of that tick: a marker
        # still waiting for its turn is served from the mirror as it is
        published[mirror.tick + 1] = new
        assert sender.send_latest_to_id(1, "blob", publish(mirror, new))

    def settled():
        return srv._peer_blob_tick.get(0) == mirror.tick

    try:
        sender.start()
        for _step in range(120):
            op = rng.integers(10)
            if op < 5:  # a tick's worth: a few rows, sometimes none
                k = int(rng.integers(0, 6))
                send(touch_rows(
                    rng, vec, rng.choice(CFG.n_groups, k, replace=False)))
            elif op == 5:  # a burst: the slot is replaced before its turn
                for _ in range(4):
                    send(touch_rows(rng, vec, rng.choice(
                        CFG.n_groups, 3, replace=False)))
            elif op == 6:  # state replaced outside the tick: every row
                send(random_vec(rng))
            elif op == 7:  # the connection goes
                for w in list(sender._writers.values()):
                    sender._loop.call_soon_threadsafe(w.close)
            elif op == 8:  # the peer says it lost the base
                sender.forget_latest_base(1)
            else:  # let the exchange catch up
                assert wait_until(settled), "the newest vector never came"
            if rng.integers(3) == 0:
                time.sleep(0.002)
        send(touch_rows(rng, vec, [0]))
        if not wait_until(settled, timeout=2):
            # a frame written into a connection cut under it is gone, as
            # on any network; the next one opens a new connection
            send(touch_rows(rng, vec, [1]))
            assert wait_until(settled), "the newest vector never came"
        assert not wrong, wrong
        s2, t2, full = decode_blob_vec(
            encode_blob_vec(0, mirror.tick, vec), CFG)
        np.testing.assert_array_equal(srv._peer_blobs[0], full)
        np.testing.assert_array_equal(mirror.vec, full)
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
    """The rule's three cases, from the mirror's encoder and from the
    reference that compares two vectors: the same bytes."""
    rng = np.random.default_rng(7)
    v1 = random_vec(rng)

    def enc(new):
        """The frame for a connection at tick 1 = v1, once ``new`` is
        published at tick 2."""
        mirror = PublishMirror(CFG, 0)
        assert (publish(mirror, v1), publish(mirror, new)) == (1, 2)
        frame, rows, tick = mirror.encode(None, 1)
        assert tick == 2
        assert (frame, rows) == encode_blob_frame(0, CFG, (2, new), (1, v1))
        return frame, rows

    # every row changed: the delta would be larger, so a D frame
    frame, rows = enc(v1 + 1)
    assert decode_kind(frame) == "D" and rows is None
    assert len(frame) == FULL_BYTES
    # the largest delta that is still smaller, and the first that is not
    most = (FULL_BYTES - 25 - 1) // (4 * (1 + ROW_WORDS))
    frame, rows = enc(touch_rows(rng, v1, range(most)))
    assert decode_kind(frame) == "d" and rows == most
    assert len(frame) < FULL_BYTES
    frame, rows = enc(touch_rows(rng, v1, range(most + 1)))
    assert decode_kind(frame) == "D" and rows is None
    # nothing changed: a header, and still a blob to the receiver
    frame, rows = enc(v1.copy())
    assert decode_kind(frame) == "d" and rows == 0 and len(frame) == 25
    enc = functools.partial(encode_blob_frame, 0, CFG)
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


# ---- (5) the sender's base is a tick ------------------------------------
@pytest.mark.parametrize("k", [1, 2, 5])
def test_a_connection_superseded_for_k_ticks_gets_the_union_of_their_rows(k):
    """A peer that missed k ticks is sent every row any of them changed,
    at its newest value, in ONE ``d`` frame — the bytes the reference
    cuts from the two vectors the connection's ticks stand for."""
    rng = np.random.default_rng(100 + k)
    mirror = PublishMirror(CFG, 2)
    vec = random_vec(rng)
    base = publish(mirror, vec)
    base_vec = vec.copy()
    touched = set()
    for _ in range(k):
        rows = rng.choice(CFG.n_groups, 4, replace=False)
        touched |= set(int(g) for g in rows)
        vec = touch_rows(rng, vec, rows)
        publish(mirror, vec)
    frame, n, tick = mirror.encode("marker", base)
    assert (n, tick) == (len(touched), base + k)
    assert (frame, n) == encode_blob_frame(
        2, CFG, (tick, vec), (base, base_vec))
    sender, t, base_tick, idx, blocks = decode_blob_delta(frame, CFG)
    assert (sender, t, base_tick) == (2, base + k, base)
    assert idx.tolist() == sorted(touched)
    patch_blob_vec(base_vec, idx, blocks, CFG)
    np.testing.assert_array_equal(base_vec, vec)
    # a connection that kept up is sent the last tick's rows alone, and
    # one that holds this very tick a header
    assert mirror.encode("marker", tick - 1)[1] == (4 if k > 1 else n)
    frame, n, t2 = mirror.encode("marker", tick)
    assert (len(frame), n, t2) == (25, 0, tick)


def test_new_connection_and_forgotten_base_get_the_whole_vector():
    """Through a real transport: the first frame on a connection is a
    ``D`` frame, the next a ``d`` frame against its tick; after
    ``forget_latest_base`` (the peer's ``blob_resync``) and after a
    reconnect the vector goes whole again.  A marker superseded before
    its turn costs no frame."""
    rng = np.random.default_rng(77)
    got = []
    rx = MessageTransport(
        1, NodeConfig({1: ("127.0.0.1", 0)}),
        lambda payload, peer, reply: got.append(payload),
        listen_host="127.0.0.1", listen_port=0)
    rx.start()
    nc = NodeConfig({0: ("127.0.0.1", 0), 1: ("127.0.0.1", rx.listen_port)})
    reg = MetricsRegistry(node=0)
    mirror = PublishMirror(CFG, 0)
    tx = MessageTransport(0, nc, lambda *a: None, listen_host="127.0.0.1",
                          listen_port=0, metrics=reg,
                          latest_encoder=mirror.encode)
    vec = random_vec(rng)

    def send(rows):
        nonlocal vec
        vec = touch_rows(rng, vec, rows)
        n = len(got)
        assert tx.send_latest_to_id(1, "blob", publish(mirror, vec))
        assert wait_until(lambda: len(got) > n)
        return got[-1]

    try:
        tx.start()
        first = send([1])
        assert first == encode_blob_vec(0, 1, vec)
        delta = send([2, 3])
        assert decode_kind(delta) == "d"
        assert decode_blob_delta(delta, CFG)[1:3] == (2, 1)
        tx.forget_latest_base(1)
        assert send([4]) == encode_blob_vec(0, 3, vec)
        assert decode_blob_delta(send([5]), CFG)[1:3] == (4, 3)
        for w in list(tx._writers.values()):
            tx._loop.call_soon_threadsafe(w.close)
        time.sleep(0.05)
        # written into the cut connection or onto a new one: either way
        # the next frame the peer reads that names tick 6 or later came
        # over a new connection and stands alone
        n = len(got)
        send([6])
        if decode_kind(got[-1]) != "D":
            send([7])
        assert any(decode_kind(f) == "D" for f in got[n:])
        c = reg.snapshot()["counters"]
        assert c["blob_frames_full"] >= 3 and c["blob_frames_delta"] >= 2
    finally:
        tx.stop()
        rx.stop()


# ---- (6) an encoder racing the patch ------------------------------------
def test_encoder_racing_the_patch_never_frames_a_row_of_two_ticks():
    """The sender-side twin of (3): every word of a row carries the tick
    that wrote it, one thread patches the mirror as completions do, and
    the frames cut meanwhile — deltas against bases that fall behind,
    whole vectors — must show every row uniform, no row newer than the
    frame's tick, and every delta row newer than its base."""
    mirror = PublishMirror(CFG, 0)
    mirror.replace(np.ones(N, np.int32))  # tick 1, every word 1
    stop = threading.Event()

    def writer():
        rng = np.random.default_rng(11)
        while not stop.is_set():
            tick = mirror.tick + 1
            rows = np.sort(rng.choice(CFG.n_groups, 8, replace=False)
                           ).astype(np.int32)
            words = [np.full((4, 8, w), tick, np.int32)
                     for w in (1, CFG.window)]
            mirror.patch(rows, words)  # a row is written whole

    def by_row(vec):
        return np.concatenate([leaf.reshape(CFG.n_groups, -1)
                               for leaf in split_blob_vec(vec, CFG)], axis=1)

    t = threading.Thread(target=writer, daemon=True)
    held, base = None, None
    seen, kinds = set(), {"d": 0, "D": 0}
    try:
        t.start()
        assert wait_until(lambda: mirror.tick > 3)
        for i in range(400):
            if i % 50 == 49:
                base = None  # a new connection now and then
            frame, n, tick = mirror.encode(None, base)
            kind = decode_kind(frame)
            kinds[kind] += 1
            if kind == "D":
                _s, t_frame, vec = decode_blob_vec(frame, CFG)
                held = vec.copy()
            else:
                _s, t_frame, base_tick, rows, blocks = decode_blob_delta(
                    frame, CFG)
                assert base_tick == base and n == rows.size
                if n:
                    words = np.concatenate(
                        [b.transpose(1, 0, 2).reshape(n, -1)
                         for b in blocks], axis=1)
                    assert words.shape == (n, ROW_WORDS)
                    assert (words.min(axis=1) == words.max(axis=1)).all(), \
                        "a row mixed from two ticks"
                    assert base < words.min() and words.max() <= tick
                patch_blob_vec(held, rows, blocks, CFG)
            assert t_frame == tick
            rows_now = by_row(held)
            assert (rows_now.min(axis=1) == rows_now.max(axis=1)).all()
            # the newest row IS the frame's tick: nothing was left out
            assert rows_now.max() == tick
            seen.add(tick)
            base = tick
            if i % 7 == 0:
                time.sleep(0.001)  # fall a few ticks behind
        assert len(seen) > 20 and kinds["d"] > 300 and kinds["D"] >= 8
    finally:
        stop.set()
        t.join(10)


# ---- (7) three nodes on loopback ---------------------------------------
@pytest.mark.timeout(180)
def test_three_nodes_commit_over_delta_frames():
    from gigapaxos_tpu.clients import PaxosClientAsync

    cfg = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
    full = 13 + 4 * blob_vec_len(cfg)
    ports = free_ports(3)
    nc = NodeConfig({i: ("127.0.0.1", p) for i, p in enumerate(ports)})
    servers = [PaxosServer(i, nc, StatefulAdderApp(), cfg,
                           tick_interval=0.01) for i in range(3)]
    # the property of (1) on the served path: whatever a node accepts of
    # a peer's frame leaves it with the vector that peer's mirror held at
    # the frame's tick (kept here as the mirror moves on)
    history = [{} for _ in servers]
    wrong, checked = [], [0]

    def remember(i, mirror):
        # known BEFORE the mirror shows it: a frame of that tick can be
        # cut, sent and checked before the patching thread runs again
        patch, replace = mirror.patch, mirror.replace

        def kept_patch(rows, blocks):
            vec = mirror.vec.copy()
            patch_blob_vec(vec, rows, blocks, cfg)
            history[i][mirror.tick + 1] = vec
            patch(rows, blocks)

        def kept_replace(vec):
            history[i][mirror.tick + 1] = vec.copy()
            replace(vec)

        mirror.patch, mirror.replace = kept_patch, kept_replace

    def check(srv):
        inner = srv._on_blob

        def on_blob(kind, payload):
            inner(kind, payload)
            sender = (decode_blob_vec if kind == "D" else decode_blob_delta
                      )(payload, cfg)[0]
            with srv._blob_lock:
                tick = srv._peer_blob_tick.get(sender)
                held = srv._peer_blobs[sender].copy() \
                    if tick is not None else None
            if tick is not None:
                checked[0] += 1
                if not np.array_equal(held, history[sender].get(tick)):
                    wrong.append((srv.my_id, sender, kind, tick))

        srv._on_blob = on_blob

    for i, s in enumerate(servers):
        remember(i, s.manager.mirror)
        check(s)
        s.start()
    client = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        assert client.create_paxos_instance("dl0", [0, 1, 2], timeout=30)
        for k in range(20):
            assert client.send_request_sync(
                "dl0", "1", timeout=30, server=k % 3) == str(k + 1)
        assert wait_until(lambda: all(
            s.manager.app.totals.get("dl0") == 20 for s in servers))
        assert not wrong and checked[0] > 100, (wrong[:5], checked)
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
            # every frame written was encoded at its turn (the nodes run
            # on: one frame a peer may be between its encode and its drain)
            assert 0 <= h["phase_blob_encode_s"]["count"] \
                - c["blob_frames_written"] <= 2
    finally:
        client.close()
        for s in servers:
            s.stop()
