"""The gathered stack stays on the device (``ops/engine.py:init_stack``):
a peer's ``d`` frame lands as rows scattered into it by the step, a ``D``
frame (and news of more rows than an update holds) as the peer's whole
vector, and the step takes its own row from its state.  Whatever the
frames of a tick were, the matrix the step reads must be the one the host
used to assemble — bit for bit; a row of a peer that is not heard must
reach nothing; and no update size may retrace the step.  Counts and bits
only, no times."""

import numpy as np
import pytest

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.net.codec import (
    decode_json,
    decode_kind,
    encode_blob_frame,
    rows_of,
)
from gigapaxos_tpu.net.gather import (
    GatherNews,
    GatherUpdate,
    _merge_frames,
    empty_update_vec,
)
from gigapaxos_tpu.net.node_config import NodeConfig
from gigapaxos_tpu.ops.engine import (
    EngineConfig,
    blob_vec_len,
    split_blob_vec,
    update_rows,
    update_vec_len,
)
from gigapaxos_tpu.server import PaxosServer
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster


# 2,048 rows: an update holds 1,024 of them, so C + 1 rows overflow
CFG = EngineConfig(n_groups=2048, window=4, req_lanes=4, n_replicas=3)
C = update_rows(CFG)
N = blob_vec_len(CFG)
ME, PEERS = 1, (0, 2)


def receiver():
    """Node 1 of three as a server that is never started: its ingress,
    its gather and its manager's step are driven by hand."""
    nc = NodeConfig({i: ("127.0.0.1", 1) for i in range(3)})
    srv = PaxosServer(ME, nc, StatefulAdderApp(), CFG)
    asked = []
    srv.transport.send_to_id = lambda nid, frame: asked.append((nid, frame))
    return srv, asked


def deliver(srv, frame):
    srv._on_message(frame, ("127.0.0.1", 0), lambda b: None)


def touch(rng, vec, rows):
    """`vec` with every leaf of the named rows rewritten."""
    out = vec.copy()
    for leaf in split_blob_vec(out, CFG):
        leaf[rows] = rng.integers(0, 1 << 20, leaf[rows].shape)
    return out


class Peer:
    """A sender as the transport runs it: the newest vector, and the
    base the connection last carried (None: the next frame is whole)."""

    def __init__(self, ident, rng):
        self.id, self.tick, self.base = ident, 0, None
        self.vec = rng.integers(0, 1 << 20, N, dtype=np.int32)

    def frame(self, vec, *, lost=False):
        self.tick += 1
        self.vec = vec
        frame, _n = encode_blob_frame(
            self.id, CFG, (self.tick, vec), self.base)
        self.base = (self.tick, vec)
        return None if lost else frame


def test_device_stack_equals_the_host_assembled_stack_before_every_step():
    """A seeded run of ``d`` and ``D`` frames from both peers — repeated
    rows within a tick, an empty delta, C and C + 1 rows, a lost frame
    with its base mismatch and resync, a create and a kill between ticks
    — and before EVERY step the matrix the device would read equals what
    the host holds: each heard peer's vector and my own publish vector."""
    rng = np.random.default_rng(2147483659)
    srv, asked = receiver()
    m = srv.manager
    peers = {p: Peer(p, rng) for p in PEERS}
    mx = m.metrics

    def tick(news):
        """Deliver ``news`` (peer, frame), gather, compare, step."""
        kinds = {}
        for p, frame in news:
            kinds.setdefault(p, []).append(decode_kind(frame))
            deliver(srv, frame)
        update, heard, want = srv._gather()
        got = m.gathered_host(update)
        np.testing.assert_array_equal(got[ME], m.blob_vec())
        for p in PEERS:
            assert heard[p] == (p in srv._peer_blobs)
            if heard[p]:
                np.testing.assert_array_equal(got[p], srv._peer_blobs[p])
        m.step_complete(m.step_dispatch(update, heard, want))
        return update, kinds

    try:
        # tick 1: nothing heard yet; my row alone, from the state
        update, _ = tick([])
        assert update == ((), None, 0, 0)
        # a connection's first frame is whole
        update, kinds = tick([(p, peers[p].frame(peers[p].vec))
                              for p in PEERS])
        assert kinds == {0: ["D"], 2: ["D"]}
        assert [p for p, _v in update.whole] == [0, 2] and update.rows is None
        # a few rows from each
        update, kinds = tick([
            (0, peers[0].frame(touch(rng, peers[0].vec, [3, 9, 2047]))),
            (2, peers[2].frame(touch(rng, peers[2].vec, [0, 9]))),
        ])
        assert kinds == {0: ["d"], 2: ["d"]}
        assert (update.n_rows, update.n_scattered, update.whole) == (5, 2, ())
        # two frames of one peer in one tick name the same rows: the
        # later wins, and no (peer, row) goes up twice
        f1 = peers[0].frame(touch(rng, peers[0].vec, [5, 9, 100]))
        f2 = peers[0].frame(touch(rng, peers[0].vec, [9, 100, 7]))
        update, _ = tick([(0, f1), (0, f2)])
        assert (update.n_rows, update.n_scattered) == (4, 1)
        idx = update.rows[:update.n_rows]
        assert list(idx) == [5, 7, 9, 100]
        # an empty delta is news with no row
        update, kinds = tick([(2, peers[2].frame(peers[2].vec.copy()))])
        assert kinds == {2: ["d"]}
        assert (update.n_rows, update.n_scattered) == (0, 1)
        # exactly C rows fit; with one more from the other peer, that
        # peer's vector goes up whole
        many = rng.choice(CFG.n_groups, C, replace=False)
        update, kinds = tick([
            (0, peers[0].frame(touch(rng, peers[0].vec, many)))])
        assert kinds == {0: ["d"]}
        assert (update.n_rows, update.whole) == (C, ())
        update, kinds = tick([
            (0, peers[0].frame(touch(rng, peers[0].vec, many))),
            (2, peers[2].frame(touch(rng, peers[2].vec, [11]))),
        ])
        assert kinds == {0: ["d"], 2: ["d"]}
        assert update.n_rows == C and [p for p, _ in update.whole] == [2]
        # C + 1 rows of one peer: a d frame still, applied whole
        update, kinds = tick([(2, peers[2].frame(touch(
            rng, peers[2].vec,
            rng.choice(CFG.n_groups, C + 1, replace=False))))])
        assert kinds == {2: ["d"]}
        assert update.rows is None and [p for p, _ in update.whole] == [2]
        # a frame is lost: the next delta names a base that is not held,
        # is dropped, the stack keeps what it held, a resync is asked
        peers[0].frame(touch(rng, peers[0].vec, [1, 2]), lost=True)
        before = mx.get("blob_base_mismatch")
        update, _ = tick([(0, peers[0].frame(touch(rng, peers[0].vec, [2])))])
        assert mx.get("blob_base_mismatch") == before + 1
        assert update == ((), None, 0, 0)
        assert [(nid, decode_json(f)[0]) for nid, f in asked] \
            == [(0, "blob_resync")]
        peers[0].base = None  # the sender's answer stands alone
        update, kinds = tick([(0, peers[0].frame(peers[0].vec))])
        assert kinds == {0: ["D"]} and [p for p, _ in update.whole] == [0]
        # rows that arrive behind a whole vector in one tick are in it
        peers[2].base = None
        f1 = peers[2].frame(touch(rng, peers[2].vec, [40]))
        f2 = peers[2].frame(touch(rng, peers[2].vec, [41]))
        update, kinds = tick([(2, f1), (2, f2)])
        assert kinds == {2: ["D", "d"]}
        assert update.rows is None and [p for p, _ in update.whole] == [2]
        # a create and a kill between ticks show in my row, with no pull
        # of the publish vector by the tick (the checks above make one)
        m.create_paxos_instance("gd-a", [0, 1, 2])
        tick([])
        m.create_paxos_instance("gd-b", [0, 1, 2])
        m.kill("gd-a")
        tick([(0, peers[0].frame(touch(rng, peers[0].vec, [77])))])
        # then a seeded tail of whatever comes
        for _ in range(25):
            news = []
            for p in PEERS:
                for _f in range(int(rng.integers(0, 3))):
                    k = int(rng.choice([0, 1, 4, 30, C + 5], p=[
                        .15, .3, .3, .2, .05]))
                    if rng.integers(12) == 0:
                        peers[p].base = None
                    news.append((p, peers[p].frame(touch(
                        rng, peers[p].vec,
                        rng.choice(CFG.n_groups, k, replace=False)))))
            tick(news)
        c = mx.snapshot()
        assert c["counters"]["gather_updates_whole"] >= 7
        assert c["counters"]["gather_updates_scattered"] >= 10
        assert c["counters"]["gather_upload_bytes"] > 0
        assert c["hists"]["gather_update_rows"]["max"] == C
        assert m._dispatch_step.n_retraces == 0, m._dispatch_step.stats()
    finally:
        m.close()


def test_the_tick_pulls_no_publish_vector_after_a_lifecycle_operation():
    """``_gather`` used to pull ``publish_snapshot()`` whenever the state
    had changed outside the tick; the step now takes the row from the
    state it is given, and nothing on the tick path packs it."""
    import gigapaxos_tpu.manager as manager_mod

    assert not hasattr(PaxosManager, "publish_snapshot")
    assert not hasattr(PaxosServer, "_gather_bufs")
    srv, _ = receiver()
    m = srv.manager
    try:
        srv.tick_once()
        m.create_paxos_instance("gd-c", [0, 1, 2])
        packed = []
        orig = manager_mod._publish_vec_jit
        manager_mod._publish_vec_jit = lambda s: packed.append(1) or orig(s)
        try:
            srv.tick_once()
            m.kill("gd-c")
            srv.tick_once()
        finally:
            manager_mod._publish_vec_jit = orig
        assert not packed
        assert not hasattr(srv, "_gather_bufs")
        np.testing.assert_array_equal(m.gathered_host()[ME], m.blob_vec())
    finally:
        m.close()


@pytest.mark.parametrize("frames, want_rows", [
    # one frame, ascending: taken as it is
    ([[3, 5, 8]], [3, 5, 8]),
    # the later frame wins where both name a row
    ([[3, 5, 8], [5, 9]], [3, 5, 8, 9]),
    ([[1], [1], [1]], [1]),
    # a frame whose rows do not ascend (no sender of ours writes one) is
    # sorted before the scatter is promised sorted indices
    ([[8, 3]], [3, 8]),
    ([[]], []),
])
def test_merged_frames_name_each_row_once_and_the_later_wins(
        frames, want_rows):
    rng = np.random.default_rng(len(frames))
    vecs = [rng.integers(0, 99, N, dtype=np.int32) for _ in frames]
    rows, blocks = _merge_frames([
        (np.array(r, np.int32), rows_of(v, np.array(r, np.int64), CFG))
        for r, v in zip(frames, vecs)
    ])
    assert list(rows) == want_rows
    for row, got in zip(rows, zip(*[b.transpose(1, 0, 2) for b in blocks])):
        last = max(i for i, r in enumerate(frames) if row in r)
        want = rows_of(vecs[last], np.array([row]), CFG)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w[:, 0])


def test_an_undispatched_update_is_made_good_by_whole_vectors():
    """A drained update that reached no step cannot be taken back: the
    server then sends every peer it holds up whole."""
    rng = np.random.default_rng(5)
    srv, _ = receiver()
    m = srv.manager
    peers = {p: Peer(p, rng) for p in PEERS}
    try:
        for p in PEERS:
            deliver(srv, peers[p].frame(peers[p].vec))
        srv.tick_once()
        deliver(srv, peers[0].frame(touch(rng, peers[0].vec, [4])))
        orig = m.step_dispatch
        m.step_dispatch = lambda *a: (_ for _ in ()).throw(RuntimeError("x"))
        with pytest.raises(RuntimeError):
            srv.tick_once()
        m.step_dispatch = orig
        update, heard, _want = srv._gather()
        assert [p for p, _ in update.whole] == [0, 2]
        got = m.gathered_host(update)
        for p in PEERS:
            np.testing.assert_array_equal(got[p], srv._peer_blobs[p])
    finally:
        m.close()


# ---- an unheard peer's row reaches nothing -----------------------------
SMALL = EngineConfig(n_groups=16, window=8, req_lanes=4, n_replicas=3)


def _watch(m, seen):
    orig = m._complete_locked

    def wrapped(pend, digest_np, news_np, whole):
        seen.append((np.asarray(pend["out_vec"]).copy(), digest_np.copy(),
                     np.array(pend["blob_vec"]), news_np.copy()))
        return orig(pend, digest_np, news_np, whole)

    m._complete_locked = wrapped


@pytest.mark.parametrize("same_tags", [False, True])
def test_garbage_in_an_unheard_peers_row_changes_no_leaf_and_no_output(
        same_tags):
    """Two clusters on one schedule with links that drop; in one of them
    every row of the stack whose peer is not heard is overwritten with
    garbage before each step (with the row's true instance tags, too, so
    that ``heard`` alone is what masks it).  Every state leaf, every
    output vector, digest and blob, and every response stay equal."""
    from gigapaxos_tpu.ops.engine import set_peer_rows
    import jax.numpy as jnp

    rng = np.random.default_rng(41)
    clean = ManagerCluster(SMALL, HashChainApp)
    dirty = ManagerCluster(SMALL, HashChainApp)
    seen = {id(c): [[] for _ in range(3)] for c in (clean, dirty)}
    resp = {id(c): [] for c in (clean, dirty)}
    n = blob_vec_len(SMALL)
    try:
        for c in (clean, dirty):
            for i, m in enumerate(c.managers):
                _watch(m, seen[id(c)][i])
            for nm in ("ua", "ub", "uc"):
                c.create(nm)
        for step_no in range(36):
            delivery = np.full((3, 3), DELIVER)
            if step_no % 4:  # a partition that moves
                cut = step_no % 3
                delivery[cut, :] = DROP
                delivery[:, cut] = DROP
                delivery[cut, cut] = DELIVER
            for i, m in enumerate(dirty.managers):
                for j in range(3):
                    if i == j or delivery[i, j] == DELIVER:
                        continue
                    junk = rng.integers(-5, 1 << 20, n, dtype=np.int32)
                    if same_tags:
                        split_blob_vec(junk, SMALL).tag[:] = \
                            np.asarray(m.state.tag)
                    m._stack = set_peer_rows(
                        m._stack, jnp.asarray(junk), jnp.int32(j), cfg=SMALL)
                    dirty._held[i][j] = None  # whole again when heard
            for c in (clean, dirty):
                if step_no % 2 == 0:
                    c.managers[step_no % 3].propose(
                        ("ua", "ub", "uc")[step_no % 3], f"v{step_no}",
                        callback=lambda r, x, _o=resp[id(c)], _t=step_no:
                        _o.append((_t, r, x)),
                        request_id=(1 << 56) + step_no)
                c.step_all(delivery=delivery)
            for a, b in zip(clean.managers, dirty.managers):
                for leaf in a.state._fields:
                    assert np.array_equal(
                        np.asarray(getattr(a.state, leaf)),
                        np.asarray(getattr(b.state, leaf)),
                    ), (step_no, a.my_id, leaf)
        for sa, sb in zip(seen[id(clean)], seen[id(dirty)]):
            assert len(sa) == len(sb) == 36
            for a, b in zip(sa, sb):  # out, digest, blob, its news
                assert len(a) == len(b) == 4
                for va, vb in zip(a, b):
                    assert np.array_equal(va, vb)
        assert sorted(resp[id(clean)], key=str) \
            == sorted(resp[id(dirty)], key=str)
        assert len(resp[id(clean)]) >= 5  # the schedule decided things
    finally:
        clean.close()
        dirty.close()


def test_no_update_size_retraces_the_step():
    """Updates of 0 rows (none, and a vector of padding), 1 row and C
    rows are one shape to the step: one compile, no retrace."""
    cfg = EngineConfig(n_groups=2048, window=4, req_lanes=4, n_replicas=3)
    m = PaxosManager(0, HashChainApp(), cfg)
    rng = np.random.default_rng(9)
    heard = np.array([True, True, False])
    vec = rng.integers(0, 1 << 20, blob_vec_len(cfg), dtype=np.int32)

    def rows_update(k):
        news = GatherNews(cfg)
        rows = np.sort(rng.choice(cfg.n_groups, k, replace=False)
                       ).astype(np.int32)
        news.rows(1, rows, rows_of(vec, rows, cfg))
        return news.drain({1: vec})

    try:
        m.tick_host(GatherUpdate(((1, vec),), None, 0, 0), heard)
        sent = m._dispatch_step
        compiles = sent.n_compiles
        for update in (None, rows_update(0), rows_update(1), rows_update(C),
                       rows_update(C - 1), None):
            if update is not None:
                assert update.rows.shape == (update_vec_len(cfg),)
                assert update.whole == ()
            m.step_complete(m.step_dispatch(update, heard))
        assert sent.n_compiles == compiles, sent.stats()
        assert sent.n_retraces == 0, sent.stats()
        np.testing.assert_array_equal(m.gathered_host()[1], vec)
        assert empty_update_vec(cfg)[:C].min() >= cfg.n_replicas * cfg.n_groups
    finally:
        m.close()


# ---- what else a dispatch no longer sends up every tick ----------------
def test_the_election_mask_stands_until_its_inputs_move():
    """``want_coord`` hands back the SAME read-only array while who is up,
    the ballots and the memberships stand, and a new answer when one of
    them moves — bit for bit what the thirty passes give."""
    from gigapaxos_tpu.failure_detection import FailureDetector
    from gigapaxos_tpu.ops.ballot import encode_ballot

    rng = np.random.default_rng(3)
    G = 64
    bal = encode_ballot(rng.integers(1, 9, G), rng.integers(0, 3, G)
                        ).astype(np.int32)
    mask = rng.choice([0b111, 0b011, 0b101, 0], G).astype(np.int32)
    fd = FailureDetector(0, [0, 1, 2], timeout_s=5.0)
    first = fd.want_coord(bal, mask, 3)
    assert not first.flags.writeable
    assert fd.want_coord(bal.copy(), mask.copy(), 3) is first
    fresh = lambda: FailureDetector(0, [0, 1, 2], timeout_s=5.0)
    np.testing.assert_array_equal(first, fresh()._want_coord(
        np.ones(3, bool), np.zeros(3, bool), bal, mask, 3))
    # a ballot moves
    bal2 = bal.copy()
    bal2[5] = encode_ballot(9, (int(bal[5]) + 1) % 3)
    second = fd.want_coord(bal2, mask, 3)
    assert second is not first
    np.testing.assert_array_equal(second, fresh().want_coord(bal2, mask, 3))
    # a node goes quiet
    fd.last_heard[1] -= 60.0
    third = fd.want_coord(bal2, mask, 3)
    assert third is not second and third.any()
    assert fd.want_coord(bal2, mask, 3) is third
    # a membership moves
    mask2 = mask.copy()
    mask2[7] ^= 0b100
    assert fd.want_coord(bal2, mask2, 3) is not third


def test_small_dispatch_inputs_go_up_when_they_change():
    """``heard`` by its bytes, the failure detector's standing answer by
    identity, a ring with no request not at all; an election mask the
    caller may still write to goes up every time."""
    m = PaxosManager(0, HashChainApp(), SMALL)
    try:
        heard = np.array([True, False, False])
        a = m._heard_locked(heard)
        assert m._heard_locked(heard.copy()) is a
        b = m._heard_locked(np.array([True, True, False]))
        assert b is not a and list(np.asarray(b)) == [True, True, False]
        standing = np.zeros(SMALL.n_groups, bool)
        standing[3] = True
        standing.setflags(write=False)
        w = m._want_locked(standing)
        assert m._want_locked(standing) is w
        assert m._want_locked(None) is not w
        assert not np.asarray(m._want_locked(None)).any()
        mine = np.zeros(SMALL.n_groups, bool)
        assert not np.asarray(m._want_locked(mine)).any()
        mine[2] = True  # written in place between two dispatches
        assert np.asarray(m._want_locked(mine))[2]
        # an empty ring is the one kept on the device
        m.create_paxos_instance("sm", [0, 1, 2])
        seen = []
        sentinel = m._dispatch_step

        class Watched:  # the sentinel, with the request ring looked at
            def __call__(self, *args):
                seen.append(args[4])
                return sentinel(*args)

            def __getattr__(self, name):
                return getattr(sentinel, name)

        m._dispatch_step = Watched()
        m.tick_host(None, heard)
        assert seen[-1] is m._null_ring
        m.propose("sm", "v")
        m.tick_host(None, heard)
        assert seen[-1] is not m._null_ring
        assert (np.asarray(seen[-1]) != -1).sum() == 1
    finally:
        m.close()
