"""The step's digest (``ops/engine.py:make_digest``): what the post-step
reads of a dispatch is reduced on the device to the busy rows.  It must
equal what ``np.nonzero`` over the whole pulled planes gives — rows,
lanes, values and order — on a run with elections, preempts and
multi-slot commits; a dispatch with more busy rows than the digest holds
must take the whole-plane path and decide the same; the work-in-flight
flag must equal the expression the host used to evaluate on four pulled
leaves; and the journal must not be able to tell the two paths apart."""

import os

import numpy as np
import pytest

from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.ballot import NULL
from gigapaxos_tpu.ops.engine import (
    EngineConfig,
    StepDigest,
    digest_from_planes,
    digest_rows,
    split_digest_vec,
    split_out_vec,
)
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster

CFG = EngineConfig(n_groups=16, window=8, req_lanes=4, n_replicas=3)
NAMES = [f"dg{i}" for i in range(6)]


def _state_np(m, *leaves):
    return [np.asarray(getattr(m.state, leaf)) for leaf in leaves]


def _old_work_in_flight(m) -> bool:
    """``engine_work_in_flight`` as the host evaluated it before the
    step worked it out: three passes over four pulled leaves."""
    acc_slot, acc_vid, exec_slot, prop = _state_np(
        m, "acc_slot", "acc_vid", "exec_slot", "c_prop_vid")
    live = (
        (acc_slot != NULL) & (acc_vid != NULL)
        & (acc_slot >= exec_slot[:, None])
    )
    return bool(live.any() or (prop != NULL).any())


def _watch(m, seen):
    """Hold every step's digest against the whole planes of the same
    dispatch, at the moment the post-step is handed it."""
    orig = m._complete_locked

    def wrapped(pend, digest_np, *news):
        planes = _state_np(m, "acc_slot", "acc_bal", "acc_vid")
        got, n_busy, _quorum = split_digest_vec(digest_np, m.cfg)
        out = split_out_vec(np.asarray(pend["out_vec"]), m.cfg)
        want = digest_from_planes(out, *planes, _old_work_in_flight(m))
        assert n_busy == len(want.rows) <= digest_rows(m.cfg)
        for f in StepDigest._fields:
            assert np.array_equal(getattr(got, f), getattr(want, f)), (
                m.my_id, m._tick_no, f)
        # the order the post-step walks is np.nonzero's over the planes
        k, lane = np.nonzero(got.acc_new)
        g_full, lane_full = np.nonzero(out.acc_new)
        assert np.array_equal(got.rows[k], g_full)
        assert np.array_equal(lane, lane_full)
        seen["steps"] += 1
        seen["busy"] += n_busy
        seen["multi_slot"] += int((out.n_committed > 1).sum())
        seen["live"].add(got.live)
        result = orig(pend, digest_np, *news)
        assert m.engine_work_in_flight() == _old_work_in_flight(m)
        return result

    m._complete_locked = wrapped


def _drive(c, seed: int, n_steps: int, rid0: int = 1 << 56):
    """A seeded schedule: bursts of writes (several to one name in one
    step, so that a step commits more than one slot), dropped links, and
    election pulses against names that have proposals in flight."""
    rng = np.random.default_rng(seed)
    R, G = c.cfg.n_replicas, c.cfg.n_groups
    rows = [c.managers[0].names[nm] for nm in NAMES]
    done = []
    rid = rid0
    for step in range(n_steps):
        for _ in range(int(rng.integers(0, 3))):
            nm = NAMES[int(rng.integers(0, len(NAMES)))]
            entry = int(rng.integers(0, R))
            for _ in range(int(rng.integers(1, 6))):
                rid += 1
                c.managers[entry].propose(
                    nm, f"v{rid & 0xffff}", request_id=rid,
                    callback=lambda r, x: done.append((r, x)),
                )
        delivery = np.where(rng.random((R, R)) < 0.15, DROP, DELIVER)
        np.fill_diagonal(delivery, DELIVER)
        want = None
        if step % 7 == 3:
            mask = np.zeros(G, bool)
            mask[rng.choice(rows, 2, replace=False)] = True
            want = {int(rng.integers(0, R)): mask}
        c.step_all(delivery=delivery, want_coord=want)
    c.run(12)  # settle over clean links
    return done


def _assert_replicas_agree(c):
    """Every replica executed the same sequence in every row."""
    ref = c.managers[0]
    for m in c.managers[1:]:
        assert np.array_equal(m.app_exec_slot, ref.app_exec_slot)
        for leaf in ("app_hash", "exec_slot", "n_execd"):
            assert np.array_equal(*(_state_np(x, leaf)[0] for x in (m, ref)))


def _counter(c, key):
    return sum(m.metrics.snapshot()["counters"].get(key, 0)
               for m in c.managers)


@pytest.mark.parametrize("seed", [20260928, 20261005])
def test_digest_equals_whole_planes(seed):
    """(a) and (c): over a seeded run with elections, preempts and
    multi-slot commits, every field the post-step reads from the digest
    equals the whole planes', and the flag equals the old expression."""
    c = ManagerCluster(CFG, HashChainApp)
    seen = {"steps": 0, "busy": 0, "multi_slot": 0, "live": set()}
    try:
        for nm in NAMES:
            c.create(nm)
        for m in c.managers:
            _watch(m, seen)
        done = _drive(c, seed, 60)
        assert len(done) > 40
        assert seen["steps"] == 72 * 3 and seen["busy"] > 100
        assert seen["live"] == {True, False}
        assert seen["multi_slot"] > 0
        assert _counter(c, "preempts") > 0
        assert _counter(c, "coordinator_flips") > 0
        assert _counter(c, "step_digest_dispatches") == seen["steps"]
        assert _counter(c, "step_digest_overflows") == 0
        _assert_replicas_agree(c)
    finally:
        c.close()


def _journal_bytes(log_dir):
    out = {}
    for root, _dirs, files in os.walk(log_dir):
        for f in sorted(files):
            if f.startswith("journal_"):
                with open(os.path.join(root, f), "rb") as fh:
                    out[f] = fh.read()
    return out


@pytest.mark.parametrize("seed", [7, 47])
def test_journal_cannot_tell_digest_from_whole_planes(tmp_path, seed):
    """(a): two clusters driven identically, one reading the digest and
    one forced down the whole-plane path on every dispatch, write
    byte-identical journals and answer identically."""
    runs = {}
    for arm in ("digest", "planes"):
        dirs = [str(tmp_path / f"{arm}{r}") for r in range(3)]
        c = ManagerCluster(CFG, HashChainApp, log_dirs=dirs,
                           checkpoint_every=10 ** 9)
        try:
            for m in c.managers:
                m._rid_nonce = 1 << 20  # a batch's id is minted from it
                if arm == "planes":
                    m._digest_rows = -1  # no step fits: always overflow
            for nm in NAMES:
                c.create(nm)
            done = _drive(c, seed, 50)
            overflows = _counter(c, "step_digest_overflows")
            dispatches = _counter(c, "step_digest_dispatches")
            assert overflows == (dispatches if arm == "planes" else 0)
            _assert_replicas_agree(c)
        finally:
            c.close()
        runs[arm] = (done, [_journal_bytes(d) for d in dirs])
    assert len(runs["digest"][0]) > 30
    assert runs["digest"][0] == runs["planes"][0]
    for r in range(3):
        a, b = runs["digest"][1][r], runs["planes"][1][r]
        assert a.keys() == b.keys() and a
        assert sum(len(v) for v in a.values()) > 1000
        for name in a:
            assert a[name] == b[name], (r, name)


def test_overflow_takes_whole_planes_and_decides_the_same():
    """(b): 2,048 rows, every one busy in the same step, against a
    digest of 1,024: the step reports the overflow, the host pulls the
    whole planes, and every row decides and executes its write — the
    same as with a digest that is made to hold them all."""
    cfg = EngineConfig(n_groups=2048, window=4, req_lanes=2, n_replicas=3)
    assert digest_rows(cfg) == 1024
    c = ManagerCluster(cfg, HashChainApp)
    try:
        m0 = c.managers[0]
        names = [f"ov{i}" for i in range(cfg.n_groups)]
        for m in c.managers:
            assert m.create_paxos_batch(names, [0, 1, 2]) == cfg.n_groups
        c.republish()
        done = {}
        for i, nm in enumerate(names):
            coord = m0.coordinator_of_row(m0.names[nm])
            c.managers[coord].propose(
                nm, "w", request_id=(1 << 56) + i,
                callback=lambda r, x: done.setdefault(r, x),
            )
        c.run(8)
        assert len(done) == cfg.n_groups
        overflows = _counter(c, "step_digest_overflows")
        assert overflows >= 3  # each replica saw 2,048 busy rows at once
        snap = m0.metrics.snapshot()
        assert snap["hists"]["step_digest_rows"]["max"] == cfg.n_groups
        assert snap["counters"]["decisions_executed"] == cfg.n_groups
        assert (m0.app_exec_slot == 1).all()
        _assert_replicas_agree(c)
    finally:
        c.close()


@pytest.mark.parametrize("n_busy", [0, 1, 256, 257, 300])
def test_digest_gathers_every_busy_row_chunk_by_chunk(n_busy):
    """The device gathers the busy rows' lanes a chunk of 256 rows at a
    time, as many chunks as hold them; at 300 rows the second chunk
    runs past the end and is taken 256 rows back from it.  Whatever the
    count, the digest equals the whole planes'."""
    import jax

    from gigapaxos_tpu.ops.engine import (EngineState, StepOutputs,
                                          init_state, make_digest)

    cfg = EngineConfig(n_groups=300, window=4, req_lanes=2, n_replicas=3)
    G, W = cfg.n_groups, cfg.window
    rng = np.random.default_rng(n_busy)
    busy = np.zeros(G, bool)
    busy[rng.choice(G, n_busy, replace=False)] = True
    kind = rng.integers(0, 3, G)  # what makes a busy row busy
    plane = lambda: rng.integers(1, 1 << 20, (G, W)).astype(np.int32)
    out = StepOutputs(
        n_committed=np.where(busy & (kind == 0), 2, 0).astype(np.int32),
        exec_base=rng.integers(0, 99, G).astype(np.int32),
        exec_vid=plane(),
        n_admitted=rng.integers(0, 3, G).astype(np.int32),
        maj_exec=rng.integers(0, 99, G).astype(np.int32),
        app_hash=rng.integers(0, 1 << 30, G).astype(np.int32),
        acc_new=(busy & (kind == 1))[:, None] * rng.integers(
            0, 2, (G, W)).astype(np.int32) | (busy & (kind == 1))[:, None]
        * np.eye(1, W, dtype=np.int32),
        bal_new=rng.integers(0, 2, G).astype(np.int32),
        preempted_vid=np.where(
            (busy & (kind == 2))[:, None] & (np.arange(W) == 1), 7, NULL
        ).astype(np.int32),
    )
    state = init_state(cfg)._replace(
        acc_slot=plane(), acc_bal=plane(), acc_vid=plane())
    vec = jax.jit(lambda o, s: make_digest(o, s, cfg, np.array([3, 11])))(
        StepOutputs(*out), EngineState(*state))
    got, n, quorum = split_digest_vec(np.asarray(vec), cfg)
    want = digest_from_planes(
        out, *(np.asarray(getattr(state, f))
               for f in ("acc_slot", "acc_bal", "acc_vid")), got.live)
    assert n == n_busy and np.array_equal(want.rows, np.flatnonzero(busy))
    assert quorum == (3, 11)  # the step's two sums ride beside the flag
    for f in StepDigest._fields:
        assert np.array_equal(getattr(got, f), getattr(want, f)), f
