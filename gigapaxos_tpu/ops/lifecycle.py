"""Group lifecycle array ops: batched create / kill / pause-extract / restore.

The reference creates one ``PaxosInstanceStateMachine`` object per group
(``PaxosManager.createPaxosInstance``, ``PaxosManager.java:611-810``) and
pauses idle ones to disk via ``HotRestoreInfo`` (``paxosutil/
HotRestoreInfo.java:31-60``, ``PaxosManager.java:2264-2392``).  Here a group
is a *row* of the engine arrays, so create/kill/pause are batched scatter /
gather updates on :class:`~gigapaxos_tpu.ops.engine.EngineState`.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from .ballot import NULL, encode_ballot
from .engine import ACTIVE, IDLE, EngineState


# What a pause record, a donor's snapshot and the lifecycle checks read
# of a ROW: the [G] leaves first, one word each, then the [G, W] planes.
ROW_LEAVES = ("stopped", "bal", "exec_slot", "app_hash", "n_execd")
ROW_PLANES = ("acc_bal", "acc_vid", "acc_slot", "dec_vid", "dec_slot")


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


# The manager keeps host copies of state leaves and writes a lifecycle
# program's rows into them instead of pulling the leaves again
# (manager.py:_replace_state_locked).  Beside each program, what it
# writes at ``idx`` as the host knows it from the program's ARGUMENTS:
# leaf -> a word, ``[N]`` words or ``[N, W]`` lanes — or None where the
# value depends on what the device held.  A leaf a program writes and
# its ``*_wrote`` leaves out is a stale copy on the host
# (tests/test_host_leaf_carry.py holds every leaf to the device's).
def create_wrote(member_mask, coord0, my_id, version=0, tag=0) -> Dict:
    """What :func:`create_groups` writes at ``idx``."""
    member_mask = np.asarray(member_mask, np.int32)
    coord0 = np.asarray(coord0, np.int32)
    bal0 = encode_ballot(np.zeros_like(coord0), coord0)
    mine = coord0 == my_id
    members = sum((member_mask >> b) & 1 for b in range(32))
    wrote = dict.fromkeys(ROW_PLANES + ("c_prop_vid", "c_prop_slot"), NULL)
    wrote.update(
        member_mask=member_mask, majority=members // 2 + 1,
        version=version, stopped=0, tag=tag, bal=bal0, exec_slot=0,
        app_hash=0, n_execd=0, c_phase=np.where(mine, ACTIVE, IDLE),
        c_bal=np.where(mine, bal0, NULL), c_next_slot=0,
    )
    return wrote


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


# What :func:`kill_groups` writes at ``idx``.
KILL_WROTE = dict(member_mask=0, majority=2 ** 30, stopped=0, tag=0,
                  bal=NULL, c_phase=IDLE, c_bal=NULL)


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


def jump_wrote(exec_slot, bal, app_hash, n_execd, stopped,
               bal_before=None) -> Dict:
    """What :func:`jump_rows` writes at ``idx``.  The ballot is the
    larger of the donor's and the row's own, so ``bal_before`` is the
    host's copy of ``bal[idx]`` (None: it has none); which window lanes
    it keeps only the device can say."""
    wrote = dict.fromkeys(ROW_PLANES)
    wrote.update(
        bal=None if bal_before is None else np.maximum(bal_before, bal),
        exec_slot=exec_slot, app_hash=app_hash, n_execd=n_execd,
        stopped=stopped, c_phase=IDLE, c_bal=NULL, c_next_slot=exec_slot,
        c_prop_vid=NULL, c_prop_slot=NULL,
    )
    return wrote


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


def restore_wrote(exec_slot, bal, app_hash, n_execd, acc_bal, acc_vid,
                  acc_slot, dec_vid, dec_slot) -> Dict:
    """What :func:`restore_paused_rows` writes at ``idx``: its arguments."""
    return dict(
        exec_slot=exec_slot, bal=bal, app_hash=app_hash, n_execd=n_execd,
        c_next_slot=exec_slot, acc_bal=acc_bal, acc_vid=acc_vid,
        acc_slot=acc_slot, dec_vid=dec_vid, dec_slot=dec_slot,
    )


@jax.jit
def take_rows(state: EngineState, idx: jnp.ndarray) -> jnp.ndarray:
    """``[N, len(ROW_LEAVES) + len(ROW_PLANES) * W]``: the words of
    ``ROW_LEAVES`` and then the lanes of ``ROW_PLANES`` of rows ``idx``,
    as ONE device value — a reader of N rows pulls N * (5 + 5W) words,
    not ten whole leaves (:func:`split_rows` is its host side).  One
    compile per (state shape, N): the manager asks for N = 1 and
    N = ``PAUSE_CHUNK`` only, and warms both."""
    idx = jnp.asarray(idx, jnp.int32)
    return jnp.concatenate(
        [getattr(state, leaf)[idx][:, None] for leaf in ROW_LEAVES]
        + [getattr(state, leaf)[idx] for leaf in ROW_PLANES], axis=1)


def split_rows(words: np.ndarray, window: int) -> Dict[str, np.ndarray]:
    """:func:`take_rows`' result on the host, as leaf -> ``[N]`` words or
    ``[N, W]`` lanes."""
    out = {leaf: words[:, i] for i, leaf in enumerate(ROW_LEAVES)}
    base = len(ROW_LEAVES)
    for i, leaf in enumerate(ROW_PLANES):
        out[leaf] = words[:, base + i * window:base + (i + 1) * window]
    return out
