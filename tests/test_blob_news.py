"""The step names the rows its blob changed (``ops/engine.py:make_news``)
and the host patches ONE mirror of its publish vector with them
(``net/mirror.py``).  The mirror must equal the step's own ``blob_vec``
bit for bit after EVERY step — whatever rewrote a row between two steps —
and the rows the step names must be the rows a compare of the two whole
vectors finds (``codec.changed_rows``, the reference); a step that
changes more rows than the news holds must pull the whole vector, once;
and on a tick whose news fits no whole vector crosses to the host."""

import numpy as np
import pytest

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.net.codec import (
    changed_rows,
    decode_blob_delta,
    decode_blob_vec,
    decode_kind,
    patch_blob_vec,
    rows_of,
)
from gigapaxos_tpu.net.mirror import news_blocks
from gigapaxos_tpu.ops.engine import (
    Blob,
    EngineConfig,
    blob_vec_len,
    split_blob_vec,
    split_news_vec,
    update_rows,
    update_vec_len,
)
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster

CFG = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
NAMES = [f"bn{i}" for i in range(8)]


def _watch(m, seen):
    """Hold every completion's news, and the mirror it leaves, against
    the dispatch's own whole vector."""
    orig = m._complete_locked

    def wrapped(pend, digest_np, news_np, whole):
        cfg = m.cfg
        before = None if m.mirror.vec is None else m.mirror.vec.copy()
        tick = m.mirror.tick
        result = orig(pend, digest_np, news_np, whole)
        fresh = np.array(pend["blob_vec"])
        assert m.mirror.tick == tick + 1
        assert np.array_equal(m.mirror.vec, fresh), (m.my_id, m._tick_no)
        n, rows, body = split_news_vec(news_np, cfg)
        if before is None:
            assert whole is not None  # a node's first tick
        else:
            want = changed_rows(fresh, before, cfg)
            assert n == want.size, (m.my_id, m._tick_no)
            if n <= update_rows(cfg):
                assert whole is None
                assert np.array_equal(rows, want)
                for got, exp in zip(news_blocks(body, n, cfg),
                                    rows_of(fresh, want, cfg)):
                    assert np.array_equal(got, exp)
            else:
                assert whole is not None
            # the rows stamped with this tick are the rows that changed
            assert np.array_equal(
                np.flatnonzero(m.mirror.row_tick == m.mirror.tick), want)
        if pend["state"] is m.state:
            # what the tick path reads of the new state without a pull
            for leaf in ("bal", "exec_slot"):
                assert np.array_equal(
                    m._np(leaf), np.asarray(getattr(m.state, leaf)))
        seen["steps"] += 1
        if before is not None:  # the first news is against nothing
            seen["rows"] += n
            seen["max"] = max(seen["max"], n)
        return result

    m._complete_locked = wrapped


class _Follower:
    """A peer connection that is superseded now and then: it takes the
    frame the mirror cuts for its base tick, as the transport would, and
    must hold the sender's vector after every frame it applies."""

    def __init__(self, m, rng):
        self.m, self.rng = m, rng
        self.base, self.held = None, None
        self.kinds = {"d": 0, "D": 0}
        self.missed = 0

    def after_step(self):
        if self.rng.random() < 0.4:  # its marker was superseded
            self.missed += 1
            return
        cfg, mirror = self.m.cfg, self.m.mirror
        if self.rng.random() < 0.05:
            self.base = None  # the connection went, or a resync
        frame, n, tick = mirror.encode(None, self.base)
        kind = decode_kind(frame)
        self.kinds[kind] += 1
        if kind == "D":
            sender, t, vec = decode_blob_vec(frame, cfg)
            self.held = vec.copy()
            assert n is None
        else:
            sender, t, base_tick, rows, blocks = decode_blob_delta(frame, cfg)
            assert base_tick == self.base and n == rows.size
            patch_blob_vec(self.held, rows, blocks, cfg)
        assert (sender, t) == (self.m.my_id, tick) == (self.m.my_id,
                                                       mirror.tick)
        assert np.array_equal(self.held, mirror.vec)
        self.base = tick


# ---- (a) parity over a run with lifecycle operations between steps ----
@pytest.mark.parametrize("seed", [20260930, 20260933])
def test_mirror_equals_the_steps_vector_after_every_step(seed):
    """Admits, accepts, decisions, election pulses, dropped links — and
    between steps names created, killed, paused and restored, and a state
    replaced behind the manager's back: after EVERY step the mirror is
    the step's ``blob_vec``, and the news names the rows a compare of
    the whole vectors finds."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    c = ManagerCluster(CFG, HashChainApp)
    seen = {"steps": 0, "rows": 0, "max": 0}
    R, G = CFG.n_replicas, CFG.n_groups
    done = []
    try:
        rows = {nm: c.create(nm) for nm in NAMES}
        for m in c.managers:
            _watch(m, seen)
        followers = [_Follower(m, rng) for m in c.managers]
        live, asleep, rid = list(NAMES), [], 1 << 56
        n_lifecycle = 0
        for step in range(70):
            for _ in range(int(rng.integers(0, 3))):
                nm = live[int(rng.integers(0, len(live)))]
                rid += 1
                c.managers[int(rng.integers(0, R))].propose(
                    nm, f"v{rid & 0xffff}", request_id=rid,
                    callback=lambda r, x: done.append((r, x)))
            op = step % 10
            if op == 2:  # a create
                nm = f"late{step}"
                rows[nm] = c.create(nm)
                live.append(nm)
                n_lifecycle += 1
            elif op == 4 and len(live) > 4:  # a kill
                nm = live.pop()
                for m in c.managers:
                    assert m.kill(nm)
                n_lifecycle += 1
            elif op == 6 and len(live) > 4:  # a pause ...
                nm = live.pop(0)
                c.run(10)  # drained first: a pause wants the name idle
                for m in c.managers:
                    assert m.pause_group(nm, 0) == "ok"
                asleep.append(nm)
                n_lifecycle += 1
            elif op == 8 and asleep:  # ... and its restore, batched
                nm = asleep.pop(0)
                for m in c.managers:
                    assert m.resume_group_batch(
                        [(nm, 0, [0, 1, 2], rows[nm], False)]) == {nm: True}
                live.append(nm)
                n_lifecycle += 1
            elif op == 9:  # a state replaced: promises raised in free rows
                free = np.array([g for g in range(G)
                                 if g not in c.managers[0].row_name][:3])
                for m in c.managers:
                    with m._state_lock:
                        m.state = m.state._replace(
                            bal=m.state.bal.at[jnp.asarray(free)].add(8))
                n_lifecycle += 1
            c.republish()
            delivery = np.where(rng.random((R, R)) < 0.15, DROP, DELIVER)
            np.fill_diagonal(delivery, DELIVER)
            want = None
            if step % 7 == 3:
                mask = np.zeros(G, bool)
                mask[[rows[nm] for nm in live[:2]]] = True
                want = {int(rng.integers(0, R)): mask}
            c.step_all(delivery=delivery, want_coord=want)
            for f in followers:
                f.after_step()
        c.run(12)
        for f in followers:
            f.after_step()
        assert len(done) > 30 and n_lifecycle > 25
        assert seen["steps"] >= 3 * 82 and seen["rows"] > 300
        for m in c.managers:
            snap = m.metrics.snapshot()
            assert snap["counters"]["blob_news_overflows"] == 1  # the first
            assert snap["counters"]["blob_news_dispatches"] \
                == m.mirror.tick == seen["steps"] // 3
            assert snap["hists"]["blob_news_rows"]["count"] == m.mirror.tick
            assert snap["counters"]["coordinator_flips"] > 0
        for f in followers:
            assert f.missed > 10 and f.kinds["d"] > 20 and f.kinds["D"] >= 1
    finally:
        c.close()


# ---- (b) more rows than the news holds --------------------------------
def test_overflow_pulls_the_whole_vector_once_and_every_peer_follows(
        monkeypatch):
    """2,048 rows, 1,100 of them created between two steps, against a
    news of 1,024: the step reports the count, the host pulls the whole
    vector for that dispatch and for no other, and the frame cut for a
    peer that held the vector before takes it to the one after."""
    cfg = EngineConfig(n_groups=2048, window=4, req_lanes=2, n_replicas=3)
    assert update_rows(cfg) == 1024
    pulls = []
    orig_pull = PaxosManager._pull_blob_vec
    monkeypatch.setattr(
        PaxosManager, "_pull_blob_vec",
        lambda self, pend: pulls.append(1) or orig_pull(self, pend))
    c = ManagerCluster(cfg, HashChainApp)
    seen = {"steps": 0, "rows": 0, "max": 0}
    try:
        for m in c.managers:
            _watch(m, seen)
        c.create("first")
        c.run(3)
        assert len(pulls) == 3  # each node's first tick, and only it
        m0 = c.managers[0]
        before, base = m0.mirror.vec.copy(), m0.mirror.tick
        names = [f"ov{i}" for i in range(1100)]
        for m in c.managers:
            assert m.create_paxos_batch(names, [0, 1, 2]) == 1100
        c.republish()
        c.step_all()
        assert len(pulls) == 6 and seen["max"] == 1100
        for m in c.managers:
            assert m.metrics.get("blob_news_overflows") == 2
        # the next frame to a peer that holds the vector before: the
        # 1,100 rows as a delta, patched in, give the vector after
        frame, n, tick = m0.mirror.encode(None, base)
        assert decode_kind(frame) == "d" and n == 1100 and tick == base + 1
        _s, _t, base_tick, rows, blocks = decode_blob_delta(frame, cfg)
        assert base_tick == base
        patch_blob_vec(before, rows, blocks, cfg)
        assert np.array_equal(before, m0.mirror.vec)
        assert np.array_equal(m0.mirror.vec, m0.blob_vec())
        # and the ticks after fit again: nothing whole comes down
        done = {}
        for i, nm in enumerate(names[:40]):
            coord = m0.coordinator_of_row(m0.names[nm])
            c.managers[coord].propose(
                nm, "w", request_id=(1 << 56) + i,
                callback=lambda r, x: done.setdefault(r, x))
        c.run(8)
        assert len(done) == 40 and len(pulls) == 6
        for m in c.managers:
            assert m.metrics.get("blob_news_overflows") == 2
            assert m.metrics.get("blob_news_dispatches") == m.mirror.tick
    finally:
        c.close()


def test_a_completion_that_ended_before_its_patch_is_healed_by_a_pull():
    """The device's published vector moves on at dispatch; if the
    post-step raises, the mirror stays behind it — and the next
    completion pulls the whole vector instead of patching rows onto a
    base the device no longer has."""
    c = ManagerCluster(CFG, HashChainApp)
    try:
        c.create("heal")
        c.run(2)
        m = c.managers[0]
        orig = m._post_step_locked

        def boom(digest):
            m._post_step_locked = orig
            raise RuntimeError("the journal's disk is gone")

        m._post_step_locked = boom
        c.submit("heal", "v1")
        with pytest.raises(RuntimeError):
            c.step_all()
        assert m._mirror_behind
        before = m.metrics.get("blob_news_overflows")
        c.run(3)
        assert m.metrics.get("blob_news_overflows") == before + 1
        assert not m._mirror_behind
        assert np.array_equal(m.mirror.vec, m.blob_vec())
    finally:
        c.close()


# ---- the device's gather, chunk by chunk ------------------------------
@pytest.mark.parametrize("n_changed", [0, 1, 256, 257, 300])
def test_news_gathers_every_changed_row_chunk_by_chunk(n_changed):
    """The device gathers the changed rows' words a chunk of 256 rows at
    a time, as many chunks as hold them; at 300 rows the second chunk
    runs past the end and is taken 256 rows back from it.  One word of
    one leaf is enough to name a row."""
    import jax

    from gigapaxos_tpu.ops.engine import make_news, pack_blob

    cfg = EngineConfig(n_groups=300, window=4, req_lanes=2, n_replicas=3)
    G, W = cfg.n_groups, cfg.window
    assert update_rows(cfg) == G
    rng = np.random.default_rng(n_changed)
    old = rng.integers(-2, 1 << 30, blob_vec_len(cfg), dtype=np.int32)
    new = old.copy()
    leaves = split_blob_vec(new, cfg)
    changed = np.sort(rng.choice(G, n_changed, replace=False))
    for g in changed:
        leaf = leaves[rng.integers(len(leaves))]
        if leaf.ndim == 1:
            leaf[g] ^= 1
        else:
            leaf[g, rng.integers(W)] ^= 1
    news = np.asarray(jax.jit(
        lambda b, p: make_news(b, p, cfg)
    )(Blob(*[np.asarray(leaf) for leaf in split_blob_vec(new, cfg)]), old))
    assert news.shape == (1 + update_vec_len(cfg),)
    n, rows, body = split_news_vec(news, cfg)
    assert n == n_changed and np.array_equal(rows, changed)
    assert np.array_equal(rows, changed_rows(new, old, cfg))
    assert (news[1 + n:1 + G] == G).all()  # G past the last
    for got, exp in zip(news_blocks(body, n, cfg), rows_of(new, changed, cfg)):
        assert np.array_equal(got, exp)
    # the vector the step hands back is the packed blob itself
    assert np.array_equal(np.asarray(pack_blob(
        Blob(*split_blob_vec(new, cfg)))), new)


# ---- no whole vector on a tick whose news fits ------------------------
def test_served_ticks_pull_no_whole_vector_and_compare_none(monkeypatch):
    """Three managers under writes: after each node's first tick the
    whole publish vector is never pulled, and neither a completion nor
    an encode runs the whole-vector compare."""
    import gigapaxos_tpu.net.mirror as mirror_mod

    pulls, compares = [], []
    orig_pull = PaxosManager._pull_blob_vec
    monkeypatch.setattr(
        PaxosManager, "_pull_blob_vec",
        lambda self, pend: pulls.append(1) or orig_pull(self, pend))
    orig_cmp = mirror_mod.changed_rows
    monkeypatch.setattr(mirror_mod, "changed_rows",
                        lambda *a: compares.append(1) or orig_cmp(*a))
    c = ManagerCluster(CFG, HashChainApp)
    c.pipelined = True
    try:
        for nm in NAMES:
            c.create(nm)
        c.run(2)
        assert len(pulls) == 3 and not compares
        done = []
        bases = [m.mirror.tick for m in c.managers]
        for k in range(30):
            c.submit(NAMES[k % len(NAMES)], f"v{k}", entry=k % 3,
                     callback=lambda r, x: done.append(x))
            c.step_all()
            for i, m in enumerate(c.managers):
                frame, n, bases[i] = m.mirror.encode(None, bases[i])
                assert decode_kind(frame) == "d" and n < 16
        c.run(8)
        assert len(done) == 30
        assert len(pulls) == 3 and not compares
        for m in c.managers:
            assert m.metrics.get("blob_news_overflows") == 1
            assert m.metrics.get("blob_news_dispatches") == 40
    finally:
        c.close()
