"""Unified-step parity suite (the make_step factory).

The mesh-parameterized step must be BIT-EXACT against the pre-refactor
program: every state leaf and every StepOutputs field, across mesh
shapes (single device, the 1-D ('g',) group shard, the (g, r)
acceptor-per-chip mesh) and a non-divisible group count.  The
packed_host flavor must take MY row of the stack from the state it
steps, whatever stood there, and leave the peers' rows as gathered.
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
    coordinator row."""
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
GOLDEN_STEPS = 8


@functools.lru_cache(maxsize=1)
def _golden_trajectory():
    """The longhand trajectory, computed ONCE for every mesh: the
    schedule is fixed, so the golden is mesh-independent by definition —
    that IS the claim under test."""
    cfg, S = GOLDEN_CFG, GOLDEN_STEPS
    states = build_replica_states(cfg)
    reqs = _coord_routed_requests(cfg, states, S)
    # an election pulse at step 0 only
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
def test_unified_step_matches_golden(mesh_key):
    """make_step == the longhand pre-refactor program, for every state
    leaf and every StepOutputs field — across mesh shapes and a
    NON-divisible G (13 over 8 and over 2 shards: GSPMD pads internally;
    the old shard_map path never could)."""
    cfg, S = GOLDEN_CFG, GOLDEN_STEPS
    fn = make_step(cfg, MESHES[mesh_key](), donate=False)
    states_g, golden_outs, reqs, wants = _golden_trajectory()
    states_u = build_replica_states(cfg)

    unified_outs = []
    for t in range(S):
        states_u, out = fn(
            states_u, jnp.asarray(reqs[t]), jnp.asarray(wants[t]))
        unified_outs.append(out)

    _assert_trees_equal(states_g, states_u, f"state[{mesh_key}]")
    for t, (a, b) in enumerate(zip(golden_outs, unified_outs)):
        _assert_trees_equal(a, b, f"outs[{mesh_key},t={t}]")
    # the schedule did real work (not vacuous parity)
    assert int(np.asarray(states_u.exec_slot).min()) >= S - 4


@pytest.mark.parametrize("my_id", [0, 1, 2])
def test_packed_flavor_frozen_peer_parity(my_id):
    """packed_host == one legacy host tick: the step takes MY row of the
    stack from the state it steps (whatever stood in it), the peers'
    rows stay.  Checks the new state, the out vector (field-by-field via
    split_out_vec), the returned blob_vec, the stack handed back and the
    blob's news."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    states = build_replica_states(cfg)
    per = [jax.tree.map(lambda x: x[r], states) for r in range(3)]
    gvec = jnp.stack([pack_blob(make_blob(s)) for s in per])
    heard = jnp.ones((3,), bool)
    req = np.full((8, 4), NULL, np.int32)
    coord = ballot_coord(np.asarray(states.bal)[0])
    for g in range(8):
        if int(coord[g]) == my_id:
            req[g, 0] = g + 1
    want = jnp.zeros((8,), bool)

    # golden: the single-step host tick with the peers' rows as gathered
    g0 = unpack_gathered(gvec, cfg)
    g = jax.tree.map(
        lambda gl, bl: gl.at[my_id].set(bl), g0, make_blob(per[my_id]))
    st, golden_out = step(per[my_id], g, heard, jnp.asarray(req), want,
                          jnp.int32(my_id), cfg=cfg)
    golden_blob = np.asarray(pack_blob(make_blob(st)))

    # the stack: the peers' rows as gathered, garbage where mine goes
    # (held rows minor, as ops/engine.py:init_stack lays it out)
    stack = stack_blob(jax.tree.map(lambda leaf: leaf.at[my_id].set(12345), g0))
    fn = make_step(cfg, donate=False, io="packed_host")
    published = pack_blob(make_blob(per[my_id]))
    st_u, stack_u, out_vec, blob_vec, _heat, _digest, news = fn(
        per[my_id], stack, jnp.asarray(empty_update_vec(cfg)), heard,
        jnp.asarray(req), want,
        jnp.int32(my_id), jnp.zeros((8,), jnp.int32), published,
    )
    _assert_trees_equal(st, st_u, "state")
    _assert_trees_equal(g, stack_blob(stack_u), "stack")
    _assert_trees_equal(
        golden_out, split_out_vec(np.asarray(out_vec), cfg), "out_vec")
    np.testing.assert_array_equal(golden_blob, np.asarray(blob_vec))
    # the step's news: the rows in which the fresh vector differs from
    # the published one
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
    # commits need a later exchange — ADMISSION is the local progress
    # that proves the ring fed the step
    assert int(np.asarray(golden_out.n_admitted).sum()) > 0


def test_make_step_validates_and_memoizes():
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    with pytest.raises(ValueError):
        make_step(cfg, io="nope")
    with pytest.raises(TypeError):
        make_step(cfg, None, 1)  # mesh is the last positional
    assert make_step(cfg, donate=False) is make_step(cfg, None, donate=False)
    assert make_step(cfg, io="packed_host") is not make_step(cfg)
