"""The serving path's jitted programs, compiled at real sizes for a
DESCRIBED TPU v5e (no chip attached, nothing runs): what the chip's
compiler refuses — a program that does not fit 16 GiB, a sharding it
cannot partition — fails here, at no chip time.

This is the only file that describes the chip.  The topology is
described inside a module-scoped fixture, never at import: only one
process may load the TPU's library, and every xdist worker imports every
test file.  A compile that passes here is not a chip run.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from gigapaxos_tpu.ops.engine import (
    EngineConfig,
    blob_vec_len,
    init_stack,
    init_state,
    set_peer_rows,
    update_vec_len,
)
from gigapaxos_tpu.ops.lifecycle import (
    create_groups,
    jump_rows,
    restore_paused_rows,
)
from gigapaxos_tpu.parallel.mesh import GROUP_AXIS
from gigapaxos_tpu.parallel.spmd import make_step

HBM_BYTES = 16 * 2 ** 30  # one TPU v5e chip
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all",
               "collective-permute", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _state_shapes(cfg, sharding):
    """One replica's EngineState as shapes on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda: init_state(cfg)),
    )


def _stack_shapes(cfg, sharding):
    """The gathered stack as shapes on ``sharding``, and its bytes."""
    stack = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        jax.eval_shape(lambda: init_stack(cfg)),
    )
    return stack, 4 * cfg.n_replicas * blob_vec_len(cfg)


def _dispatch_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("G,W,K,R", [
    (65_536, 16, 8, 3),       # the deployed default (what chip_smoke serves)
    (1_048_576, 32, 16, 3),   # the headline shape
    (65_536, 16, 8, 5),       # five replicas a name (the cell g1k-r5-lat)
])
def test_packed_host_step_compiles_and_fits_one_chip(one_chip, G, W, K, R):
    """The step the manager dispatches (state, gathered stack and heat
    donated; the tick's news as one fixed-shape update): one replica's
    dispatch must stay under one chip's 16 GiB, and the stack comes back
    in the buffers it went in."""
    cfg = EngineConfig(G, W, K, R)
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip
    )
    state = _state_shapes(cfg, one_chip)
    stack, stack_bytes = _stack_shapes(cfg, one_chip)
    step = make_step(cfg, donate=True, io="packed_host")
    args = (
        sds((update_vec_len(cfg),), jnp.int32), sds((R,), jnp.bool_),
        sds((G, K), jnp.int32), sds((G,), jnp.bool_),
        sds((), jnp.int32), sds((G,), jnp.int32),
        sds((blob_vec_len(cfg),), jnp.int32),
    )
    compiled = step.lower(state, stack, *args).compile()
    need = _dispatch_bytes(compiled)
    assert 0 < need < HBM_BYTES, need
    # state, stack, heat and the published vector are donated: their
    # buffers are aliased into the results — the stack's share is its
    # whole size, and the fresh blob comes back where the published
    # vector went in
    aliased = compiled.memory_analysis().alias_size_in_bytes
    state_bytes = sum(
        4 * int(np.prod(x.shape)) for x in jax.tree.leaves(state))
    assert aliased >= state_bytes + stack_bytes + 4 * blob_vec_len(cfg), (
        aliased, stack_bytes)


@pytest.mark.parametrize("G,W,K", [(65_536, 16, 8), (1_048_576, 32, 16)])
def test_whole_row_program_compiles_and_aliases_the_stack(one_chip, G, W, K):
    """``set_peer_rows``: a peer's whole vector over its row of the
    donated stack, in place."""
    cfg = EngineConfig(G, W, K, 3)
    stack, stack_bytes = _stack_shapes(cfg, one_chip)
    compiled = set_peer_rows.lower(
        stack,
        jax.ShapeDtypeStruct((blob_vec_len(cfg),), jnp.int32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip), cfg=cfg,
    ).compile()
    assert _dispatch_bytes(compiled) < HBM_BYTES
    # at least: the [R, G] leaves are laid out with R padded to a tile
    assert compiled.memory_analysis().alias_size_in_bytes >= stack_bytes


def test_lifecycle_scatters_compile_at_deployed_rows(one_chip):
    """create_groups + restore_paused_rows (the batched create / unpause
    scatters) over a 65,536-row state, 1,000 rows at a time."""
    G, W, N = 65_536, 16, 1000
    cfg = EngineConfig(G, W, 8, 3)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                             sharding=one_chip)
    state = _state_shapes(cfg, one_chip)

    def create_then_restore(state, idx, mask, coord0, tag, exec_slot, bal,
                            app_hash, n_execd, *windows):
        state = create_groups(state, idx, mask, coord0, my_id=1,
                              version=0, tag=tag)
        return restore_paused_rows(state, idx, exec_slot, bal, app_hash,
                                   n_execd, *windows)

    compiled = jax.jit(create_then_restore, donate_argnums=(0,)).lower(
        state, *[sds((N,))] * 8, *[sds((N, W))] * 5
    ).compile()
    assert "scatter" in compiled.as_text()
    assert _dispatch_bytes(compiled) < HBM_BYTES


def test_state_pull_jump_compiles_at_deployed_rows(one_chip):
    """jump_rows (a straggler's state pull: a node back after a while)
    over a 65,536-row state, ``PaxosManager.JUMP_CHUNK`` rows a call —
    the shape ``warm_engine`` compiles at boot."""
    from gigapaxos_tpu.manager import PaxosManager

    cfg = EngineConfig(65_536, 16, 8, 3)
    row = jax.ShapeDtypeStruct((PaxosManager.JUMP_CHUNK,), jnp.int32,
                               sharding=one_chip)
    compiled = jump_rows.lower(
        _state_shapes(cfg, one_chip), *[row] * 6).compile()
    assert "scatter" in compiled.as_text()
    assert _dispatch_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("n", [1, 64])
def test_row_gather_compiles_at_deployed_rows(one_chip, n):
    """take_rows (a pause record's, a donor snapshot's and the lifecycle
    checks' read of their rows) over a 65,536-row state at the two
    shapes ``warm_engine`` compiles at boot: one row, and
    ``PaxosManager.PAUSE_CHUNK``; what comes down is ``[n, 5 + 5W]``."""
    from gigapaxos_tpu.manager import PaxosManager
    from gigapaxos_tpu.ops.lifecycle import ROW_LEAVES, ROW_PLANES, take_rows

    assert n in (1, PaxosManager.PAUSE_CHUNK)
    cfg = EngineConfig(65_536, 16, 8, 3)
    rows = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = take_rows.lower(_state_shapes(cfg, one_chip), rows).compile()
    words = len(ROW_LEAVES) + len(ROW_PLANES) * cfg.window
    assert compiled.output_shardings is not None
    out, = jax.tree_util.tree_leaves(jax.eval_shape(
        take_rows, _state_shapes(cfg, one_chip), rows))
    assert out.shape == (n, words) and out.dtype == jnp.int32
    assert _dispatch_bytes(compiled) < HBM_BYTES


def test_group_sharded_has_no_collectives_on_four_chips(topo):
    """The ``('g',)``-sharded step on the four described devices
    (``chip_smoke.py --chips 4``): groups are independent, so the
    partitioned program holds no cross-device collective, and each
    device holds a quarter of the unsharded dispatch."""
    G, W, K, R = 1_048_576, 16, 8, 3
    cfg = EngineConfig(G, W, K, R)
    mesh = Mesh(np.array(topo.devices), (GROUP_AXIS,))
    assert mesh.size == 4
    on = lambda *spec: NamedSharding(mesh, P(*spec))
    by_rank = {2: on(None, GROUP_AXIS), 3: on(None, GROUP_AXIS, None)}
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (R,) + x.shape, x.dtype, sharding=by_rank[x.ndim + 1]
        ),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    compiled = make_step(cfg, mesh).lower(
        state,
        jax.ShapeDtypeStruct((R, G, K), jnp.int32, sharding=by_rank[3]),
        jax.ShapeDtypeStruct((R, G), jnp.bool_, sharding=by_rank[2]),
    ).compile()
    text = compiled.as_text()
    found = {c: len(re.findall(c, text)) for c in COLLECTIVES}
    assert not any(found.values()), found
    # memory_analysis() is per device: G/4 groups each
    assert _dispatch_bytes(compiled) < HBM_BYTES // 4
