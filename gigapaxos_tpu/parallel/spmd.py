"""The unified, mesh-parameterized consensus step.

ONE factory — :func:`make_step` — builds every execution shape of the pure
:func:`gigapaxos_tpu.ops.engine.step`:

* the **mesh is data**, not a code path: ``None`` runs on one device; a
  ``(g, r)`` mesh shards groups over 'g' and replicas over 'r' (the
  acceptor-per-chip deployment — the cross-replica blob exchange becomes
  an all_gather over 'r' that XLA inserts from the sharding constraints);
  a 1-D ``('g',)`` mesh shards only groups, keeping all R replica rows
  device-local so the step has **zero cross-device collectives** (the
  weak-scaling headline shape).  All three are the same traced program
  under different ``NamedSharding``/``PartitionSpec`` constraints, so the
  engine's all-int32 arithmetic is bit-identical across partitionings.

* a host call is ONE consensus round: within a call the peers' rows are
  what the host last gathered, so a second round in the same call would
  hear no new accept and no new decision from anybody else (a commit is
  counted in exchanges, not in steps).

Two I/O flavors:

* ``io="stacked"`` — the SPMD/bench face and the tests' reference:
  states are the stacked ``[R, G, ...]`` global layout, requests
  ``[R, G, K]``, outputs :class:`StepOutputs` of ``[R, ...]`` leaves.
  Every replica advances and the blob exchange is read from the states
  the call was given.

* ``io="packed_host"`` — the deployed-runtime face: one replica's state,
  the gathered STACK (every peer's blob as a device-resident ``Blob`` of
  ``[R, ...]`` leaves, rows minor — ``ops/engine.py:init_stack`` —
  donated and handed back), the tick's news as one
  fixed-shape update (``ops/engine.py:scatter_update``: the rows the
  peers' ``d`` frames named, scattered into the stack before it is
  read), the ``[G, K]`` request ring, a donated ``[G]`` int32 activity
  accumulator, and as the trailing argument the PUBLISHED vector: the
  packed blob the host last received from this node's step, donated
  too.  Returns ``(state', stack', out_vec [M], blob_vec, heat',
  digest [L], news)`` — ``heat'`` is the accumulator plus
  ``n_committed + n_admitted`` of the step (the host pulls it at
  the stats cadence, never per tick), ``digest`` what the
  host's post-step reads (``ops/engine.py:make_digest``: the [G] output
  leaves, the busy rows of the [G, W] planes with their accept lanes of
  the new state, a work-in-flight flag), so that ``out_vec`` can stay
  on the device; ``news`` the rows in which ``blob_vec`` differs from
  the published vector (``ops/engine.py:make_news``), so that
  ``blob_vec`` stays there too: it is the next dispatch's published
  vector, and the host pulls it only when more rows changed than the
  news holds.
  Row ``my_id`` of the stack is never sent up: the step takes it
  from the state it steps (that is the row a host would have
  gathered, after a lifecycle operation included).

Global array convention for SPMD: every state leaf gets a leading replica
axis -> ``[R, G, ...]``; a ``(g, r)`` mesh constrains ``P('r', 'g')``, a
``('g',)`` mesh ``P(None, 'g')`` (replica axis device-local).
"""

from __future__ import annotations

import functools
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.engine import (
    EngineConfig,
    EngineState,
    make_blob,
    make_digest,
    make_news,
    pack_blob,
    scatter_update,
    stack_blob,
    step,
    step_counted,
    with_my_row,
)
from .mesh import GROUP_AXIS, REPLICA_AXIS


def stack_states(states: List[EngineState]) -> EngineState:
    """Stack per-replica states into the [R, ...] global layout."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


def build_replica_states(cfg: EngineConfig, coord0=None) -> EngineState:
    """Stacked [R, ...] states with all groups created full-membership.

    The shared state builder for the bench, the driver entry points, and
    tests; ``coord0`` defaults to round-robin by group index."""
    import numpy as np

    from ..ops.engine import init_state
    from ..ops.lifecycle import create_groups

    G, R = cfg.n_groups, cfg.n_replicas
    idx = np.arange(G)
    masks = np.full(G, (1 << R) - 1)
    coord0 = (idx % R).astype(np.int32) if coord0 is None else coord0
    return stack_states([
        create_groups(init_state(cfg), idx, masks, coord0, my_id=rid)
        for rid in range(R)
    ])


# ---------------------------------------------------------------------------
# mesh-as-data: sharding constraints instead of per-mesh code paths
# ---------------------------------------------------------------------------


def _mesh_spec(mesh: Mesh, *lead) -> P:
    """PartitionSpec over the leading axes, keeping only names the mesh
    actually has — a ``(g, r)`` mesh yields ``P('r', 'g')`` where a
    ``('g',)`` mesh yields ``P(None, 'g')`` from the same request."""
    return P(*[
        a if (a is not None and a in mesh.axis_names) else None for a in lead
    ])


def _constrain(mesh: Optional[Mesh], tree, *lead):
    """Pin every leaf's leading dims to the mesh (no-op off-mesh).  This
    is the whole mesh parameterization: the traced program is identical;
    only the GSPMD partitioning (and hence the auto-inserted collectives,
    e.g. the 'r' all_gather of the compact blob exchange) changes."""
    if mesh is None:
        return tree
    sh = NamedSharding(mesh, _mesh_spec(mesh, *lead))
    return jax.tree.map(
        lambda x: lax.with_sharding_constraint(x, sh), tree
    )


# ---------------------------------------------------------------------------
# the factory
# ---------------------------------------------------------------------------


def _build_stacked(cfg: EngineConfig, mesh: Optional[Mesh], donate: bool):
    R = cfg.n_replicas

    def _exchange_step(states, req_vid, want_coord, h):
        # run under the ARRAY group count: padded [R, Gp, ...] states
        # (group-sharded deployments, pad_group_states) step with the
        # engine's internal index planes sized Gp; inert pad rows stay
        # frozen (member_mask 0 -> non-member -> no-op)
        run_cfg = cfg._replace(n_groups=int(states.bal.shape[1]))
        # the exchange payload is the COMPACT blob (4 [G] + 4 [G, W]
        # int32 leaves vs the state's 12 + 7): on a replica-sharded mesh
        # the in_axes=None consumption below is what XLA turns into the
        # all_gather over 'r' — ~42% fewer ICI bytes than pre-compact
        blobs = jax.vmap(make_blob)(states)
        my_ids = jnp.arange(R, dtype=jnp.int32)

        def _one(state, gathered, heard_row, req, want, my_id):
            return step(state, gathered, heard_row, req, want, my_id,
                        run_cfg)

        return jax.vmap(_one, in_axes=(0, None, 0, 0, 0, 0))(
            states, blobs, h, req_vid, want_coord, my_ids
        )

    def _heard(heard):
        # a replica always hears itself — the diagonal is forced (ref
        # fault model: testing/TESTPaxosConfig.java:563-580)
        return jnp.ones((R, R), bool) if heard is None else (
            jnp.asarray(heard, bool) | jnp.eye(R, dtype=bool)
        )

    @partial(jax.jit, donate_argnums=(0,) if donate else ())
    def run(states, req_vid, want_coord, heard=None):
        h = _heard(heard)
        states = _constrain(mesh, states, REPLICA_AXIS, GROUP_AXIS)
        new_states, outs = _exchange_step(
            states, req_vid, want_coord, h
        )
        return (
            _constrain(mesh, new_states, REPLICA_AXIS, GROUP_AXIS),
            _constrain(mesh, outs, REPLICA_AXIS, GROUP_AXIS),
        )

    return run


def _build_packed(cfg: EngineConfig, mesh: Optional[Mesh], donate: bool):
    # one scatter of the tick's news, one step, three downloads
    def run_heat(state, stack, upd, heard, req_ring, want_coord, my_id,
                 heat_acc, published):
        state = _constrain(mesh, state, GROUP_AXIS)
        stack = with_my_row(
            scatter_update(stack, upd, cfg), state, my_id)
        new_state, out, quorum_sums = step_counted(
            state, stack_blob(stack), heard, req_ring, want_coord,
            my_id, cfg=cfg,
        )
        heat_acc = _constrain(
            mesh, heat_acc + out.n_committed + out.n_admitted,
            GROUP_AXIS,
        )
        blob = make_blob(new_state)
        return (
            _constrain(mesh, new_state, GROUP_AXIS), stack,
            jnp.concatenate([jnp.ravel(leaf) for leaf in out]),
            pack_blob(blob), heat_acc,
            make_digest(out, new_state, cfg, quorum_sums),
            make_news(blob, published, cfg),
        )

    # the gathered stack, the accumulator and the published vector ride
    # the dispatch like state leaves (donated alongside them); the
    # accumulator is pulled host-side only at the stats cadence, the
    # stack never, the fresh vector only when its news does not fit
    return jax.jit(run_heat, donate_argnums=(0, 1, 7, 8) if donate else ())


@functools.lru_cache(maxsize=None)
def _make_step_cached(cfg, mesh, donate, io):
    from ..obs.device import StepSentinel

    if io == "stacked":
        fn = _build_stacked(cfg, mesh, donate)
    elif io == "packed_host":
        fn = _build_packed(cfg, mesh, donate)
    else:
        raise ValueError(f"unknown io flavor: {io!r}")
    # every factory instance leaves through the retrace/compile sentinel
    # (obs/device.py): each XLA compile is recorded, and a recompile
    # after warmup is surfaced as engine_retraces instead of vanishing
    # into a silently 100x-slower tick
    mesh_tag = "x".join(
        f"{k}{v}" for k, v in mesh.shape.items()
    ) if mesh is not None else "none"
    label = (
        f"make_step[{io} donate={donate} "
        f"mesh={mesh_tag} G={cfg.n_groups} "
        f"R={cfg.n_replicas} W={cfg.window} K={cfg.req_lanes}]"
    )
    return StepSentinel(fn, label=label)


def make_step(cfg: EngineConfig, mesh: Optional[Mesh] = None, *,
              donate: bool = True, io: str = "stacked"):
    """Build THE consensus step: mesh-parameterized, one round a call.

    Parameters
    ----------
    cfg : EngineConfig (static — one compile per config)
    mesh : None for single-device; a ``(g, r)`` or ``('g',)``
        :class:`jax.sharding.Mesh` to pin the GSPMD partitioning (the
        program is the same; only the auto-partitioning changes, so
        results are bit-identical across meshes — all-int32 arithmetic).
    donate : alias the caller's old state buffers into the new state
        (halves state HBM — the G=2M capacity lever); pass ``False``
        when input states must stay valid across calls.
    io : ``"stacked"`` ([R, ...] SPMD/bench face) or ``"packed_host"``
        (one replica + the device-resident gathered stack — the deployed
        runtime's face; see the module docstring for signatures).

    Instances are memoized: the same (cfg, mesh, donate, io)
    returns the same callable, so jit caches are shared across
    managers.  Every instance is wrapped in a
    :class:`gigapaxos_tpu.obs.device.StepSentinel`, so compiles and
    retraces are recorded process-wide.
    """
    return _make_step_cached(cfg, mesh, bool(donate), str(io))


# ---------------------------------------------------------------------------
# input placement helpers (unchanged layouts)
# ---------------------------------------------------------------------------


def replicate_inputs(mesh: Mesh, states: EngineState, req_vid, want_coord):
    """Device_put global inputs with the canonical (g, r) shardings."""
    sh = lambda spec: NamedSharding(mesh, spec)
    states = jax.tree.map(
        lambda x: jax.device_put(x, sh(P(REPLICA_AXIS, GROUP_AXIS))), states
    )
    req_vid = jax.device_put(req_vid, sh(P(REPLICA_AXIS, GROUP_AXIS, None)))
    want_coord = jax.device_put(want_coord, sh(P(REPLICA_AXIS, GROUP_AXIS)))
    return states, req_vid, want_coord


def padded_group_count(n_groups: int, n_shards: int) -> int:
    """Smallest shard-divisible G' >= n_groups (ceil to a multiple)."""
    return -(-n_groups // n_shards) * n_shards


def pad_group_states(cfg: EngineConfig, states: EngineState,
                     n_shards: int) -> EngineState:
    """Pad stacked [R, G, ...] states to a shard-divisible G with INERT
    rows (member_mask 0): the step freezes non-member rows, so padding
    changes no real group's transition and the padded tail stays at its
    init values bit-for-bit."""
    from ..ops.engine import init_state

    Gp = padded_group_count(cfg.n_groups, n_shards)
    if Gp == cfg.n_groups:
        return states
    pad_cfg = cfg._replace(n_groups=Gp - cfg.n_groups)
    pad = stack_states([init_state(pad_cfg) for _ in range(cfg.n_replicas)])
    return jax.tree.map(
        lambda a, b: jnp.concatenate([a, b], axis=1), states, pad
    )


def pad_group_inputs(cfg: EngineConfig, n_shards: int, req_vid, want_coord):
    """Pad [R, G, K] requests (NULL) and [R, G] election pulses (False)
    to the shard-divisible G."""
    from ..ops.engine import NULL as _NULL

    Gp = padded_group_count(cfg.n_groups, n_shards)
    G = cfg.n_groups
    if Gp == G:
        return jnp.asarray(req_vid), jnp.asarray(want_coord)
    R, K = cfg.n_replicas, cfg.req_lanes
    req = jnp.concatenate([
        jnp.asarray(req_vid),
        jnp.full((R, Gp - G, K), _NULL, jnp.int32),
    ], axis=1)
    want = jnp.concatenate([
        jnp.asarray(want_coord),
        jnp.zeros((R, Gp - G), bool),
    ], axis=1)
    return req, want


def strip_group_pad(tree, n_groups: int):
    """Slice the padded G axis (axis 1) back to the real group count —
    host-side readback only; keep the persistent arrays padded."""
    return jax.tree.map(lambda x: x[:, :n_groups], tree)


def shard_group_inputs(mesh: Mesh, cfg: EngineConfig, states: EngineState,
                       req_vid, want_coord):
    """Pad to the mesh's shard count and device_put with the group-sharded
    layout: states/want ``P(None, 'g')``, requests ``P(None, 'g', None)``.
    Returns (states, req_vid, want_coord) ready for the group-sharded
    step."""
    n_shards = mesh.shape[GROUP_AXIS]
    states = pad_group_states(cfg, states, n_shards)
    req_vid, want_coord = pad_group_inputs(cfg, n_shards, req_vid, want_coord)
    sh = lambda spec: NamedSharding(mesh, spec)
    states = jax.tree.map(
        lambda x: jax.device_put(x, sh(P(None, GROUP_AXIS))), states
    )
    req_vid = jax.device_put(req_vid, sh(P(None, GROUP_AXIS, None)))
    want_coord = jax.device_put(want_coord, sh(P(None, GROUP_AXIS)))
    return states, req_vid, want_coord
