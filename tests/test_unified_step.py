"""Unified-step parity suite (the make_step factory).

The mesh-parameterized, N-steps-resident step must be BIT-EXACT against
the pre-refactor program: every state leaf and every StepOutputs field,
across mesh shapes (single device, the 1-D ('g',) group shard, the
(g, r) acceptor-per-chip mesh), steps_per_dispatch N in {1, 4}, and a
non-divisible group count.  The packed_host flavor must implement the
frozen-peer dispatch semantics (N serial ticks during which no new peer
frame lands, self row refreshed from the advancing state).  And the
pinned chaos seeds must stay green with ENGINE_STEPS_PER_DISPATCH > 1 —
the full deployed runtime (manager ring staging, post-step slab
requeue, journal-before-send) on the multi-step path.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.ops.ballot import NULL, ballot_coord
from gigapaxos_tpu.ops.engine import (
    EngineConfig,
    make_blob,
    pack_blob,
    split_out_vec,
    stack_blob,
    step,
    unpack_gathered,
)
from gigapaxos_tpu.net.gather import empty_update_vec
from gigapaxos_tpu.parallel.mesh import make_group_mesh, make_mesh
from gigapaxos_tpu.parallel.spmd import build_replica_states, make_step
from gigapaxos_tpu.utils.config import Config


def golden_step(cfg, states, req, want):
    """The pre-refactor single-chip program, written out longhand: an
    eager per-replica loop over the pure engine step with the stacked
    compact blobs as the gather — no vmap, no jit, no factory code
    shared with the implementation under test."""
    R = cfg.n_replicas
    per = [jax.tree.map(lambda x: x[r], states) for r in range(R)]
    blobs = jax.tree.map(lambda *xs: jnp.stack(xs), *[make_blob(s) for s in per])
    heard = jnp.ones((R,), bool)
    news, outs = [], []
    for r, s in enumerate(per):
        ns, o = step(s, blobs, heard, req[r], want[r], jnp.int32(r), cfg)
        news.append(ns)
        outs.append(o)
    stack = lambda xs: jax.tree.map(lambda *ys: jnp.stack(ys), *xs)
    return stack(news), stack(outs)


def _coord_routed_requests(cfg, states, n_steps, vid0=1):
    """One request per group per step, routed at the (static) initial
    coordinator row — precomputed so the N>1 ring can stage the exact
    same schedule ahead of time."""
    R, G, K = cfg.n_replicas, cfg.n_groups, cfg.req_lanes
    coord = ballot_coord(np.asarray(states.bal)[0])
    reqs = []
    vid = vid0
    for _ in range(n_steps):
        req = np.full((R, G, K), NULL, np.int32)
        for g in range(G):
            req[int(coord[g]), g, 0] = vid
            vid += 1
        reqs.append(req)
    return reqs


def _assert_trees_equal(a, b, what):
    for name in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name)), np.asarray(getattr(b, name)),
            err_msg=f"{what}: {name}",
        )


MESHES = {
    "single_device": lambda: None,
    "gshard8": lambda: make_group_mesh(8),
    "gr_mesh": lambda: make_mesh(n_replicas=3, n_group_shards=2),
}

GOLDEN_CFG = EngineConfig(n_groups=13, window=8, req_lanes=4, n_replicas=3)
GOLDEN_STEPS = 8  # total engine steps (two dispatches at N=4)


@functools.lru_cache(maxsize=1)
def _golden_trajectory():
    """The longhand trajectory, computed ONCE for every (mesh, N) cell:
    the schedule is fixed, so the golden is mesh- and N-independent by
    definition — that IS the claim under test."""
    cfg, S = GOLDEN_CFG, GOLDEN_STEPS
    states = build_replica_states(cfg)
    reqs = _coord_routed_requests(cfg, states, S)
    # an election pulse at step 0 only: want_coord fires at substep 0 of
    # a dispatch by design, so a mid-ring pulse has no N=1 equivalent
    wants = [np.zeros((3, 13), bool) for _ in range(S)]
    wants[0][0, 0] = True
    outs = []
    for t in range(S):
        states, o = golden_step(
            cfg, states, jnp.asarray(reqs[t]), jnp.asarray(wants[t])
        )
        outs.append(o)
    return states, outs, reqs, wants


@pytest.mark.parametrize("mesh_key", sorted(MESHES))
@pytest.mark.parametrize("n", [1, 4])
def test_unified_step_matches_golden(mesh_key, n):
    """make_step == the longhand pre-refactor program, for every state
    leaf and every per-substep StepOutputs field — across mesh shapes,
    N in {1, 4}, and a NON-divisible G (13 over 8 and over 2 shards:
    GSPMD pads internally; the old shard_map path never could)."""
    cfg, S = GOLDEN_CFG, GOLDEN_STEPS
    mesh = MESHES[mesh_key]()
    fn = make_step(cfg, mesh, n, donate=False)
    states_g, golden_outs, reqs, wants = _golden_trajectory()
    states_u = build_replica_states(cfg)

    unified_outs = []
    for d in range(S // n):
        sl = slice(d * n, (d + 1) * n)
        if n == 1:
            req = jnp.asarray(reqs[d])
        else:
            req = jnp.asarray(np.stack(reqs[sl]))
        states_u, out = fn(states_u, req, jnp.asarray(wants[d * n]))
        if n == 1:
            unified_outs.append(out)
        else:
            unified_outs.extend(
                jax.tree.map(lambda x: x[i], out) for i in range(n)
            )

    _assert_trees_equal(states_g, states_u, f"state[{mesh_key},N={n}]")
    for t, (a, b) in enumerate(zip(golden_outs, unified_outs)):
        _assert_trees_equal(a, b, f"outs[{mesh_key},N={n},t={t}]")
    # the schedule did real work (not vacuous parity)
    assert int(np.asarray(states_u.exec_slot).min()) >= S - 4


def test_stacked_multistep_equals_sequential():
    """N=4 residency == 4 sequential N=1 dispatches from the same
    states: the fori_loop body IS the single-step program."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    fn1 = make_step(cfg, None, 1, donate=False)
    fn4 = make_step(cfg, None, 4, donate=False)
    s1 = build_replica_states(cfg)
    s4 = build_replica_states(cfg)
    reqs = _coord_routed_requests(cfg, s1, 4)
    want = jnp.zeros((3, 8), bool)
    outs1 = []
    for t in range(4):
        s1, o = fn1(s1, jnp.asarray(reqs[t]), want)
        outs1.append(o)
    s4, o4 = fn4(s4, jnp.asarray(np.stack(reqs)), want)
    _assert_trees_equal(s1, s4, "state")
    for i, o in enumerate(outs1):
        _assert_trees_equal(o, jax.tree.map(lambda x: x[i], o4), f"t={i}")


def test_packed_flavor_frozen_peer_parity():
    """packed_host at N=4 == 4 serial legacy host ticks during which no
    peer frame lands: every substep takes MY row of the stack from the
    advancing state (whatever stood in it), the peers' rows stay.  Checks
    the final state, every per-substep out-ring row (field-by-field via
    split_out_vec), the returned blob_vec, and the stack handed back."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    N, my_id = 4, 0
    states = build_replica_states(cfg)
    per = [jax.tree.map(lambda x: x[r], states) for r in range(3)]
    gvec = jnp.stack([pack_blob(make_blob(s)) for s in per])
    heard = jnp.ones((3,), bool)
    reqs = [
        np.full((8, 4), NULL, np.int32) for _ in range(N)
    ]
    coord = ballot_coord(np.asarray(states.bal)[0])
    vid = 1
    for t in range(N):
        for g in range(8):
            if int(coord[g]) == my_id:
                reqs[t][g, 0] = vid
            vid += 1
    want = jnp.zeros((8,), bool)

    # golden: serial single-step host ticks with frozen peer rows
    st = per[my_id]
    g0 = unpack_gathered(gvec, cfg)
    golden_rows = []
    for i in range(N):
        g = jax.tree.map(
            lambda gl, bl: gl.at[my_id].set(bl), g0, make_blob(st)
        )
        st, out = step(st, g, heard, jnp.asarray(reqs[i]), want,
                       jnp.int32(my_id), cfg=cfg)
        golden_rows.append(out)
    golden_blob = np.asarray(pack_blob(make_blob(st)))

    # the stack: the peers' rows as gathered, garbage where mine goes
    # (held rows minor, as ops/engine.py:init_stack lays it out)
    stack = stack_blob(jax.tree.map(lambda leaf: leaf.at[my_id].set(12345), g0))
    fn = make_step(cfg, None, N, donate=False, io="packed_host")
    published = pack_blob(make_blob(per[my_id]))
    st_u, stack_u, out_rings, blob_vec, _heat, _digests, news = fn(
        per[my_id], stack, jnp.asarray(empty_update_vec(cfg)), heard,
        jnp.asarray(np.stack(reqs)), want,
        jnp.int32(my_id), jnp.zeros((8,), jnp.int32), published,
    )
    _assert_trees_equal(st, st_u, "state")
    _assert_trees_equal(g, stack_blob(stack_u), "stack")  # the last substep's
    rows = np.asarray(out_rings)
    assert rows.shape[0] == N
    for i, g_out in enumerate(golden_rows):
        u_out = split_out_vec(rows[i], cfg)
        _assert_trees_equal(g_out, u_out, f"out_ring[{i}]")
    np.testing.assert_array_equal(golden_blob, np.asarray(blob_vec))
    # the dispatch's news, once, against the final state: the rows in
    # which the fresh vector differs from the published one
    from gigapaxos_tpu.net.codec import changed_rows, rows_of
    from gigapaxos_tpu.net.mirror import news_blocks
    from gigapaxos_tpu.ops.engine import split_news_vec

    n_news, rows, body = split_news_vec(np.asarray(news), cfg)
    want_rows = changed_rows(golden_blob, np.asarray(published), cfg)
    assert n_news == want_rows.size > 0
    np.testing.assert_array_equal(rows, want_rows)
    for got, exp in zip(news_blocks(body, n_news, cfg),
                        rows_of(golden_blob, want_rows, cfg)):
        np.testing.assert_array_equal(got, exp)
    # peers are frozen for the whole dispatch, so commits need a later
    # exchange — ADMISSION is the local progress that proves the ring
    # slabs actually fed the substeps
    admitted = sum(int(np.asarray(o.n_admitted).sum()) for o in golden_rows)
    assert admitted > 0


def test_make_step_validates_and_memoizes():
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    with pytest.raises(ValueError):
        make_step(cfg, None, 0)
    with pytest.raises(ValueError):
        make_step(cfg, None, 1, io="nope")
    assert make_step(cfg, None, 2) is make_step(cfg, None, 2)


# the deployed-runtime gate: the recorded chaos schedules (traffic +
# loss + duplicate retransmits + migrations + pauses) must settle and
# pass the exactly-once audit when every manager runs the multi-step
# dispatch path.  Each pinned seed runs through the harness where its
# schedule was RECORDED green: 662625602 (the PR-2 unpaired-dedup-
# install breach shape, also the PR-8 ballot-cache wedge witness) is a
# run_soak shape; 20260804 is the worker-shard family's schedule
# (test_serving_workers.py) — through plain run_soak it is wall-clock
# flaky even at N=1, so that pairing would gate on timing, not on the
# multistep path.
def test_chaos_pinned_seed_multistep_662625602():
    from gigapaxos_tpu.testing.chaos import run_soak

    Config.set("ENGINE_STEPS_PER_DISPATCH", "4")
    # run_soak's finally clears Config (including the key set above)
    run_soak(662625602, rounds=30)


def test_chaos_pinned_seed_multistep_20260804_sharded():
    from gigapaxos_tpu.testing.chaos import run_sharded_soak

    Config.set("ENGINE_STEPS_PER_DISPATCH", "4")
    # run_sharded_soak's finally clears Config (including the key above)
    out = run_sharded_soak(20260804, workers=2, rounds=30, n_names=6)
    assert out["workers"] == 2
