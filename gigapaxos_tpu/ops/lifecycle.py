"""Group lifecycle array ops: batched create / kill / pause-extract / restore.

The reference creates one ``PaxosInstanceStateMachine`` object per group
(``PaxosManager.createPaxosInstance``, ``PaxosManager.java:611-810``) and
pauses idle ones to disk via ``HotRestoreInfo`` (``paxosutil/
HotRestoreInfo.java:31-60``, ``PaxosManager.java:2264-2392``).  Here a group
is a *row* of the engine arrays, so create/kill/pause are batched scatter /
gather updates on :class:`~gigapaxos_tpu.ops.engine.EngineState`.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .ballot import NULL, encode_ballot
from .engine import ACTIVE, IDLE, EngineState


def _popcount32(x: jnp.ndarray) -> jnp.ndarray:
    """Popcount over the full 32-bit replica-id space (ballot.py COORD_BITS=5
    supports ids 0..31; arithmetic >> keeps bit 31 correct for int32)."""
    c = jnp.zeros_like(x)
    for b in range(32):
        c = c + ((x >> b) & 1)
    return c


def initial_coordinator(idx: np.ndarray, member_mask: np.ndarray) -> np.ndarray:
    """Deterministic initial coordinator: round-robin by group index over the
    member set (the ``roundRobinCoordinator`` hash-offset rule,
    ``PaxosInstanceStateMachine.java:2123`` — spreads leadership).
    Pure numpy (host-side, used at create time by every replica identically).
    """
    idx = np.asarray(idx)
    member_mask = np.asarray(member_mask)
    out = np.zeros_like(idx)
    for row, (g, mask) in enumerate(zip(idx, member_mask)):
        members = [r for r in range(32) if (int(mask) >> r) & 1]
        out[row] = members[int(g) % len(members)] if members else 0
    return out


# One dispatch per call, not one per touched leaf: op by op, a single-row
# create is ~100 Python-level dispatches under the manager's state lock,
# and at the deployed row count three actives creating names in one
# process starve their own tick threads (whence pings and blobs) past the
# failure-detection timeout.  ``my_id``/``version``/``tag`` are traced,
# so there is one compile per (state shape, N).
@jax.jit
def create_groups(
    state: EngineState,
    idx: jnp.ndarray,          # [N] group indices to (re)create
    member_mask: jnp.ndarray,  # [N] replica-id bitmasks
    coord0: jnp.ndarray,       # [N] initial coordinator replica id
    my_id: int,
    version: jnp.ndarray | int = 0,
    tag: jnp.ndarray | int = 0,
) -> EngineState:
    """Batched group creation.  All replicas run this identically, so the
    initial ballot (0, coord0) is implicitly promised everywhere — the
    initial coordinator starts ACTIVE with no prepare phase, matching the
    reference's initial-ballot shortcut."""
    idx = jnp.asarray(idx, jnp.int32)
    member_mask = jnp.asarray(member_mask, jnp.int32)
    coord0 = jnp.asarray(coord0, jnp.int32)
    n = idx.shape[0]
    version = jnp.broadcast_to(jnp.asarray(version, jnp.int32), (n,))
    tag = jnp.broadcast_to(jnp.asarray(tag, jnp.int32), (n,))
    bal0 = encode_ballot(jnp.zeros((n,), jnp.int32), coord0)
    i_am_coord = coord0 == my_id
    W = state.acc_bal.shape[1]
    nullw = jnp.full((n, W), NULL, jnp.int32)
    zeros = jnp.zeros((n,), jnp.int32)
    return state._replace(
        member_mask=state.member_mask.at[idx].set(member_mask),
        majority=state.majority.at[idx].set(_popcount32(member_mask) // 2 + 1),
        version=state.version.at[idx].set(version),
        stopped=state.stopped.at[idx].set(0),
        tag=state.tag.at[idx].set(tag),
        bal=state.bal.at[idx].set(bal0),
        exec_slot=state.exec_slot.at[idx].set(0),
        acc_bal=state.acc_bal.at[idx].set(nullw),
        acc_vid=state.acc_vid.at[idx].set(nullw),
        acc_slot=state.acc_slot.at[idx].set(nullw),
        dec_vid=state.dec_vid.at[idx].set(nullw),
        dec_slot=state.dec_slot.at[idx].set(nullw),
        app_hash=state.app_hash.at[idx].set(0),
        n_execd=state.n_execd.at[idx].set(0),
        c_phase=state.c_phase.at[idx].set(
            jnp.where(i_am_coord, ACTIVE, IDLE).astype(jnp.int32)
        ),
        c_bal=state.c_bal.at[idx].set(jnp.where(i_am_coord, bal0, NULL)),
        c_next_slot=state.c_next_slot.at[idx].set(zeros),
        c_prop_vid=state.c_prop_vid.at[idx].set(nullw),
        c_prop_slot=state.c_prop_slot.at[idx].set(nullw),
    )


@jax.jit
def kill_groups(state: EngineState, idx: jnp.ndarray) -> EngineState:
    """Batched kill: rows become inert (the Cremator analog,
    ``PaxosManager.java:2142-2205``)."""
    idx = jnp.asarray(idx, jnp.int32)
    n = idx.shape[0]
    big = jnp.full((n,), 2 ** 30, jnp.int32)
    return state._replace(
        member_mask=state.member_mask.at[idx].set(0),
        majority=state.majority.at[idx].set(big),
        stopped=state.stopped.at[idx].set(0),
        tag=state.tag.at[idx].set(0),
        bal=state.bal.at[idx].set(NULL),
        c_phase=state.c_phase.at[idx].set(IDLE),
        c_bal=state.c_bal.at[idx].set(NULL),
    )


@jax.jit
def jump_rows(
    state: EngineState,
    idx: jnp.ndarray,       # [N] rows to jump
    exec_slot: jnp.ndarray, # [N] donor's executed frontier
    bal: jnp.ndarray,       # [N] donor's promised ballot
    app_hash: jnp.ndarray,  # [N] donor's device hash chain at that frontier
    n_execd: jnp.ndarray,   # [N]
    stopped: jnp.ndarray,   # [N]
) -> EngineState:
    """Checkpoint-transfer jump (``PaxosAcceptor.jumpSlot``,
    ``PaxosAcceptor.java:538`` / ``handleCheckpoint``,
    ``PaxosInstanceStateMachine.java:1744``): a straggler adopts a
    donor's frontier.  Window lanes clear only BELOW the new frontier
    (those slots are decided and obsolete); lanes at/above it keep —
    they may hold this replica's live accepted votes, and forgetting a
    vote could double-vote a slot.  The partial clear makes the jump
    safe at ANY gap size, not only past the whole ring (the small-gap
    case matters: a member stranded one slot behind a majority that
    paused+resumed can ONLY heal by jumping — the decisions it needs
    left every ring; chaos-soak find).  One program a row count
    (jitted: as eager scatters each leaf compiled where a straggler's
    first pull landed, in traffic); ``manager._apply_state_reply`` jumps
    ``JUMP_CHUNK`` rows a call, a row repeated to fill (the same values
    written twice change nothing), and ``warm_engine`` compiles that
    shape at boot."""
    idx = jnp.asarray(idx, jnp.int32)
    n = idx.shape[0]
    W = state.acc_bal.shape[1]
    nullw = jnp.full((n, W), NULL, jnp.int32)
    new_exec = jnp.asarray(exec_slot, jnp.int32)
    acc_keep = (state.acc_slot[idx] != NULL) & (
        state.acc_slot[idx] >= new_exec[:, None]
    )
    dec_keep = (state.dec_slot[idx] != NULL) & (
        state.dec_slot[idx] >= new_exec[:, None]
    )
    keepw = lambda keep, leaf: jnp.where(keep, leaf[idx], nullw)
    return state._replace(
        bal=state.bal.at[idx].set(jnp.maximum(state.bal[idx], jnp.asarray(bal, jnp.int32))),
        exec_slot=state.exec_slot.at[idx].set(new_exec),
        acc_bal=state.acc_bal.at[idx].set(keepw(acc_keep, state.acc_bal)),
        acc_vid=state.acc_vid.at[idx].set(keepw(acc_keep, state.acc_vid)),
        acc_slot=state.acc_slot.at[idx].set(keepw(acc_keep, state.acc_slot)),
        dec_vid=state.dec_vid.at[idx].set(keepw(dec_keep, state.dec_vid)),
        dec_slot=state.dec_slot.at[idx].set(keepw(dec_keep, state.dec_slot)),
        app_hash=state.app_hash.at[idx].set(jnp.asarray(app_hash, jnp.int32)),
        n_execd=state.n_execd.at[idx].set(jnp.asarray(n_execd, jnp.int32)),
        stopped=state.stopped.at[idx].set(jnp.asarray(stopped, jnp.int32)),
        c_phase=state.c_phase.at[idx].set(IDLE),
        c_bal=state.c_bal.at[idx].set(NULL),
        c_next_slot=state.c_next_slot.at[idx].set(jnp.asarray(exec_slot, jnp.int32)),
        c_prop_vid=state.c_prop_vid.at[idx].set(nullw),
        c_prop_slot=state.c_prop_slot.at[idx].set(nullw),
    )


@jax.jit
def restore_paused_rows(
    state: EngineState,
    idx: jnp.ndarray,        # [N] rows JUST created by create_groups
    exec_slot: jnp.ndarray,  # [N] record frontier
    bal: jnp.ndarray,        # [N] host-computed max(initial ballot, record)
    app_hash: jnp.ndarray,   # [N]
    n_execd: jnp.ndarray,    # [N]
    acc_bal: jnp.ndarray,    # [N, W] window remnants (NULL where empty)
    acc_vid: jnp.ndarray,    # [N, W]
    acc_slot: jnp.ndarray,   # [N, W]
    dec_vid: jnp.ndarray,    # [N, W]
    dec_slot: jnp.ndarray,   # [N, W]
) -> EngineState:
    """Batched unpause: scatter N pause records' consensus remnants over
    freshly created rows — ONE ``.at[idx].set`` per touched leaf instead
    of a per-name host round-trip of every leaf (the density campaign's
    wake-burst path; the old per-name install copied the WHOLE state to
    host and back per resumed name).  The rows must come straight from
    :func:`create_groups` (window lanes NULL, ballot at the initial
    (0, coord0)); the caller computes ``bal`` host-side as the max of
    that initial ballot and the record's promise, which is exactly the
    per-name restore's ``max(bal0, rec.bal)``.  One program (jitted: a
    wake in traffic must compile nothing, and ten eager scatters each
    compiled where they first ran), one compile per (state shape, N):
    the manager calls it with N = 1 and N = ``RESUME_CHUNK`` only, rows
    repeated to fill a chunk, and warms both."""
    idx = jnp.asarray(idx, jnp.int32)
    as32 = lambda a: jnp.asarray(a, jnp.int32)
    return state._replace(
        exec_slot=state.exec_slot.at[idx].set(as32(exec_slot)),
        bal=state.bal.at[idx].set(as32(bal)),
        app_hash=state.app_hash.at[idx].set(as32(app_hash)),
        n_execd=state.n_execd.at[idx].set(as32(n_execd)),
        c_next_slot=state.c_next_slot.at[idx].set(as32(exec_slot)),
        acc_bal=state.acc_bal.at[idx].set(as32(acc_bal)),
        acc_vid=state.acc_vid.at[idx].set(as32(acc_vid)),
        acc_slot=state.acc_slot.at[idx].set(as32(acc_slot)),
        dec_vid=state.dec_vid.at[idx].set(as32(dec_vid)),
        dec_slot=state.dec_slot.at[idx].set(as32(dec_slot)),
    )


def extract_rows(state: EngineState, idx) -> Tuple:
    """Gather full rows for pause-to-disk (HotRestoreInfo analog)."""
    idx = jnp.asarray(idx, jnp.int32)
    return tuple(leaf[idx] for leaf in state)


def restore_rows(state: EngineState, idx, rows: Tuple) -> EngineState:
    """Scatter previously extracted rows back (unpause)."""
    idx = jnp.asarray(idx, jnp.int32)
    return EngineState(*(leaf.at[idx].set(row) for leaf, row in zip(state, rows)))
