"""SPMD sharding tests on the virtual 8-device CPU mesh: the multi-chip
replica-axis path must produce bit-identical results to the host-simulated
cluster, and commits must flow end-to-end through shard_map + all_gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.ops.ballot import NULL, ballot_coord
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.parallel.mesh import make_mesh, pick_mesh_shape
from gigapaxos_tpu.parallel.spmd import (
    build_replica_states,
    make_step,
    replicate_inputs,
)

build_states = build_replica_states


def drive(step_fn, states, cfg, n_steps, vid0=1):
    """Feed one request per group per step to the right coordinator row."""
    R, G, K = cfg.n_replicas, cfg.n_groups, cfg.req_lanes
    vid = vid0
    total = 0
    for _ in range(n_steps):
        req = np.full((R, G, K), NULL, np.int32)
        coord = ballot_coord(np.asarray(states.bal)[0])  # coord of each group
        for g in range(G):
            req[int(coord[g]), g, 0] = vid
            vid += 1
        want = np.zeros((R, G), bool)
        states, out = step_fn(states, jnp.asarray(req), jnp.asarray(want))
        total += int(np.asarray(out.n_committed)[0].sum())
    return states, total


def test_pick_mesh_shape():
    assert pick_mesh_shape(8) == (4, 2)
    assert pick_mesh_shape(6) == (2, 3)
    assert pick_mesh_shape(3) == (1, 3)
    assert pick_mesh_shape(1) == (1, 1)
    assert pick_mesh_shape(8, n_replicas=2) == (4, 2)


def test_single_chip_vmap_commits():
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    states = build_states(cfg)
    fn = make_step(cfg)
    states, total = drive(fn, states, cfg, 12)
    fr = np.asarray(states.exec_slot)
    assert (fr == fr[0]).all()
    assert fr.min() >= 8  # 12 injected minus pipeline latency
    h = np.asarray(states.app_hash)
    assert (h == h[0]).all() and (h[0] != 0).all()


@pytest.mark.parametrize("G", [8, 9])
def test_spmd_matches_single_chip(G):
    """shard_map over (g=2, r=3) must produce identical state to vmap.

    G=9 does not divide over the two group shards: the factory asks for
    no even split (the compiler pads the constrained axis), which is why
    the check the deleted (g, r) alias made went with it.  Only
    ``replicate_inputs`` places [R, G] arrays shard by shard and so needs
    G to divide; inputs left unplaced run, and decide what vmap decides."""
    cfg = EngineConfig(n_groups=G, window=8, req_lanes=4, n_replicas=3)
    mesh = make_mesh(n_replicas=3, n_group_shards=2)
    vm = make_step(cfg)
    sm = make_step(cfg, mesh)

    states_v = build_states(cfg)
    states_s = build_states(cfg)
    req = np.full((3, G, 4), NULL, np.int32)
    req[0, 0, :2] = [5, 6]
    req[1, 1, 0] = 7
    req[(G - 1) % 3, G - 1, 0] = 8  # the last row, at its coordinator
    want = np.zeros((3, G), bool)

    if G % 2 == 0:
        states_s, req_s, want_s = replicate_inputs(
            mesh, states_s, jnp.asarray(req), jnp.asarray(want)
        )
    for t in range(6):
        r = jnp.asarray(req) if t == 0 else jnp.full((3, G, 4), NULL, jnp.int32)
        w = jnp.asarray(want)
        states_v, out_v = vm(states_v, r, w)
        states_s, out_s = sm(states_s, r, w)
    for name in states_v._fields:
        a = np.asarray(getattr(states_v, name))
        b = np.asarray(getattr(states_s, name))
        np.testing.assert_array_equal(a, b, err_msg=name)
    fr = np.asarray(states_s.exec_slot)
    # the injected requests committed
    assert fr[0, 0] == 2 and fr[0, 1] == 1 and fr[0, G - 1] == 1


def test_spmd_8dev_2replica_mesh():
    """8 devices -> (g=4, r=2) mesh: 2-replica groups, majority 2."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=2, n_replicas=2)
    mesh = make_mesh(n_replicas=2, n_group_shards=4)
    fn = make_step(cfg, mesh)
    states = build_states(cfg)
    states, total = drive(fn, states, cfg, 10)
    fr = np.asarray(states.exec_slot)
    assert (fr == fr[0]).all() and fr.min() >= 6
