"""The batched vectorized Paxos engine — the heart of the framework.

This replaces the reference's object-per-group event machines
(``PaxosInstanceStateMachine.java:117`` dispatching per-packet at 486-550,
``PaxosAcceptor.java:59``, ``PaxosCoordinatorState.java:57``) with a single
pure jitted transition over struct-of-array state for *all* G groups at once:

  * Acceptor state (``PaxosAcceptor.java:82-103``: ``_slot``, ``ballotNum``,
    ``ballotCoord``, accepted/committed maps) becomes int32 arrays ``[G]``
    plus fixed ``[G, W]`` slot-ring windows (W = in-flight slot cap, the
    ``SYNC_THRESHOLD``/out-of-order analog).
  * Coordinator state (``PaxosCoordinatorState.java:68-143``: ballot,
    prepare waitfor, myProposals slot map) becomes ``[G]`` phase/ballot
    arrays plus a ``[G, W]`` proposal ring.
  * Message passing (the reference's per-group NIO unicast/multicast of
    PREPARE/ACCEPT/ACCEPT_REPLY/DECISION packets) becomes ONE exchange per
    step of each replica's packed **state blob** — on real hardware an
    ``all_gather`` over the 'replica' mesh axis (ICI); in host-simulation a
    list of blobs with a ``heard`` mask for fault injection.

Protocol formulation ("state-exchange Paxos"): each replica publishes an
atomic snapshot (promised ballot, accepted window, learned decisions,
coordinator proposals, prepare intent).  Every replica can then *locally*:

  * promise: fold the max gathered prepare/proposal ballot into its own
    (``PaxosAcceptor.handlePrepare``/``acceptAndUpdateBallot`` analog);
  * accept: adopt the highest-ballot proposal per window lane
    (phase-2a/2b collapse: publishing the accepted window IS the
    accept-reply);
  * learn: a slot is decided when >= majority of gathered windows show the
    same (slot, ballot) accepted — every replica is a learner, so no
    separate DECISION/COMMIT message is needed (the gathered windows double
    as ``BatchedAcceptReply``+``BatchedCommit``);
  * elect: prepare quorum = count of gathered promises at my ballot;
    carryover = max-ballot accepted pvalue per lane among promisers' atomic
    (ballot, window) snapshots — the ``handlePrepareReply`` carryover rule
    (``PaxosInstanceStateMachine.java:945-975``).

Safety notes (why time-skewed snapshots are sound): every (slot, ballot,
value) shown in a window was genuinely accepted at some time; "a majority
ever accepted (b, v) for slot s" is exactly the Paxos chosen-value
condition, and the phase-1 carryover rule preserves it for higher ballots.
Within one ballot only that ballot's unique coordinator proposes, so a
majority at equal ballots implies equal values.

Ring convention: window lane ``j`` always holds slot ``s`` with
``s % W == j``.  All rings (accepted, decided, proposals) share it, so
windows align lane-for-lane across replicas and the whole step is
element-wise + [R]-axis reductions — no scatters, no dynamic shapes.

Compact exchange format: the published blob does NOT ship absolute
``[G, W]`` slot planes or per-lane ballots.  The ring convention makes a
lane's absolute slot reconstructible from the sender's ``exec_slot``
anchor plus a small ring-epoch ("wrap") delta, and an accepted lane's
ballot is reconstructible from the sender's promised ``bal`` minus a
small delta (acceptance happens AT the promise ballot, so the delta is 0
in steady state).  All three wrap deltas (5 bits each, biased, 0=NULL)
and the accepted-ballot delta (16 bits, 0=NULL) bit-pack into ONE int32
``lane_meta`` plane, and the two coordinator-intent scalars
(``prep_bal``/``prop_bal`` — mutually exclusive by phase) pack into one
``coord`` word.  Net: 4 ``[G]`` + 4 ``[G, W]`` int32 leaves instead of
5 + 7 — 42% fewer exchange bytes at W=32 (528 B/group vs 916), which is
directly HBM for the gathered rows, ICI bytes for the all_gather, and
socket bytes for the loopback ``D`` wire frame.

Representability bound: a wrap delta spans ±15 ring epochs around the
sender's frontier (±480 slots at W=32).  Ring CONTENT is inherently
within ~1 epoch of the sender's frontier (lanes are overwritten as the
ring wraps), so in-range lanes lose nothing; the lanes that saturate are
(a) stale accepted residue far below a sender that caught up by jumping,
and (b) far-ahead decisions a laggard mirrored from an ahead peer.  Both
encode as NULL, and both are liveness aids only: (a) is covered for
safety by the election floor rule (a promiser's own ``exec_slot`` rides
in the blob and floors new proposals, so a hidden accepted value below it
can never be contradicted), and any receiver lagging that far heals via
the host sync/checkpoint-jump protocols, not the rings.  The accepted-
ballot delta saturates once ``bal - acc_bal`` exceeds 2^16 in ENCODED
ballot space — ~2^11 ballot-number bumps, since a packed ballot steps by
2^COORD_BITS (ballot.py) — on a still undecided lane; the same NULL-out
applies.

TPU lowering note: the step deliberately contains NO gathers — no
``argmax``+``take_along_axis`` row selection.  Measured on a v5e chip,
each such gather inside the fused step cost ~50-100ms at G=1M (vs ~10ms
for the rest of the step combined).  Every row/lane select is instead a
masked max, which is sound by Paxos value-uniqueness: rows agreeing on
(slot, ballot) necessarily hold the same value (one coordinator per
ballot proposes one value per slot), so "pick any matching row" ==
"masked max over matching rows".  Likewise the majority-rank frontier
uses an O(R^2) rank count instead of a sort, and ``% W`` is a bitmask
(W is required to be a power of two).

Transient note: the cross-replica reductions (accept-winner select,
learn, decision-ring merge, carryover) run as a ``lax.fori_loop`` fold
over the R peer axis with ``[G, W]`` carries, decoding one peer row at a
time — the step never materializes a ``[R, G, W]`` masked intermediate.
The execute rotation and admission placement likewise run as static
unrolls over W/K offsets with ``[G, W]`` temporaries instead of
``[G, W, W]`` / ``[G, K, W]`` one-hots.  At G=1M/W=32 this cuts peak
step transients from ~8 GB (R- and W-fanned intermediates) to a small
multiple of one ``[G, W]`` plane (~128 MB each).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .ballot import NULL, ballot_num, encode_ballot

# Coordinator phases (``PaxosCoordinator`` null / PaxosCoordinatorState
# preparing-vs-active distinction, ``PaxosCoordinatorState.java:68-143``).
IDLE = 0
PREPARING = 1
ACTIVE = 2

# Value-id space: NULL (-1) = empty lane; NOOP_VID (0) = hole-filling no-op
# (not folded into app state); real request vids are > 0.  Bit 30 marks an
# epoch-final stop request (``RequestPacket.stop``).
NOOP_VID = 0
STOP_BIT = 1 << 30

# numpy scalar, NOT jnp: a module-scope jnp constant initializes the JAX
# backend at import time — importing this module must not touch a device
# (a process that only routes, or a parent that starts engine-owning
# children, would take the chip from the process that needs it)
_BIG = np.int32(2 ** 30)

# ---- compact lane_meta bit layout (one int32 per lane) --------------------
# [ 0:16) accepted-ballot delta field: 0 = lane empty/unrepresentable,
#         else (sender_bal - acc_bal) + 1  (delta <= DELTA_MAX)
# [16:21) accepted-slot wrap field   \  0 = NULL, else ring-epoch delta
# [21:26) decided-slot wrap field     } vs the sender's exec_slot anchor,
# [26:31) proposal-slot wrap field   /  biased by WRAP_BIAS
# [31]    always 0 (meta stays non-negative)
WRAP_MAX = 15                 # wrap delta in [-WRAP_MAX, WRAP_MAX]
WRAP_BIAS = 16                # stored = delta + bias; 0 reserved for NULL
_WRAP_MASK = 31
DELTA_MAX = 0xFFFE            # max representable (bal - acc_bal)
_META_DELTA_MASK = 0xFFFF
_ACC_SHIFT = 16
_DEC_SHIFT = 21
_PROP_SHIFT = 26


class EngineConfig(NamedTuple):
    """Static engine shape (all python ints — closed over by jit).

    ``window`` must be a power of two: lane residue (slot % W) compiles to
    a bitmask, which matters on TPU where integer modulo is ~10x an AND.
    ``req_lanes`` must not exceed ``window``: K admission candidates are
    consecutive slots, whose ring lanes are distinct only while K <= W.
    """

    n_groups: int          # G: group capacity (PINSTANCES_CAPACITY analog)
    window: int = 16       # W: in-flight slots per group (ring size)
    req_lanes: int = 8     # K: new client requests admitted per group per step
    n_replicas: int = 3    # R: replica-axis size (mesh dim / gather width)


class EngineState(NamedTuple):
    """Per-replica engine state; every leaf int32 of shape [G] or [G, W]."""

    # --- group metadata ---
    member_mask: jnp.ndarray   # [G] bitmask of replica ids in the group (0 = inert)
    majority: jnp.ndarray      # [G] popcount(member_mask)//2 + 1
    version: jnp.ndarray       # [G] epoch number (reconfiguration)
    stopped: jnp.ndarray       # [G] 1 after an epoch-final stop executed
    tag: jnp.ndarray           # [G] instance identity (hash of name:epoch).
    #   Rows are REUSED across instances (paxosID+version keying is by row
    #   here, by string in the reference) — a stale holdout still running
    #   the previous tenant of a row would otherwise merge its acceptor /
    #   decision columns into the new tenant's consensus (a decided stop
    #   of name A executing inside name B's RSM — chaos-soak find).  The
    #   blob ships the tag and step() ignores peers whose tag differs.
    # --- acceptor (ref: PaxosAcceptor.java:82-103) ---
    bal: jnp.ndarray           # [G] promised ballot (packed)
    exec_slot: jnp.ndarray     # [G] first un-executed slot (frontier)
    acc_bal: jnp.ndarray       # [G, W] accepted ballot per lane
    acc_vid: jnp.ndarray       # [G, W] accepted value id
    acc_slot: jnp.ndarray      # [G, W] absolute slot of the lane (NULL empty)
    # --- learner ---
    dec_vid: jnp.ndarray       # [G, W] learned decision value
    dec_slot: jnp.ndarray      # [G, W] learned decision slot (NULL empty)
    app_hash: jnp.ndarray      # [G] device-side hash-chain of executed vids
    n_execd: jnp.ndarray       # [G] total executed (== exec_slot minus noops... stats)
    # --- coordinator (ref: PaxosCoordinatorState.java:68-143) ---
    c_phase: jnp.ndarray       # [G] IDLE / PREPARING / ACTIVE
    c_bal: jnp.ndarray         # [G] my coordinator ballot
    c_next_slot: jnp.ndarray   # [G] next proposal slot to assign
    c_prop_vid: jnp.ndarray    # [G, W] my outstanding proposals (value)
    c_prop_slot: jnp.ndarray   # [G, W] my outstanding proposals (slot)


class Blob(NamedTuple):
    """What one replica publishes per step (the all_gather payload) —
    the COMPACT exchange format (see the module docstring).  All leaves
    int32; narrow fields bit-pack inside ``lane_meta``/``coord``, so the
    packed wire vector stays a plain int32 ravel."""

    tag: jnp.ndarray         # [G] sender's instance tag (cross-instance guard)
    bal: jnp.ndarray         # [G] promised ballot (also the acc_bal anchor)
    exec_slot: jnp.ndarray   # [G] frontier (also the slot-wrap anchor)
    coord: jnp.ndarray       # [G] packed coordinator intent: NULL when IDLE,
    #   c_bal when PREPARING, c_bal|INT32_MIN when ACTIVE (the sign bit is
    #   free: valid ballots are non-negative, ballot.py)
    acc_vid: jnp.ndarray     # [G, W] accepted value (NULL when lane dropped)
    dec_vid: jnp.ndarray     # [G, W] decided value (NULL when lane dropped)
    prop_vid: jnp.ndarray    # [G, W] proposal value (NULL unless ACTIVE)
    lane_meta: jnp.ndarray   # [G, W] packed wrap deltas + accepted-bal delta


class ExpandedBlob(NamedTuple):
    """A compact blob decoded back to absolute planes (tests/debugging —
    the step itself decodes peer rows one at a time inside its fold)."""

    tag: jnp.ndarray
    bal: jnp.ndarray
    exec_slot: jnp.ndarray
    acc_bal: jnp.ndarray
    acc_vid: jnp.ndarray
    acc_slot: jnp.ndarray
    dec_vid: jnp.ndarray
    dec_slot: jnp.ndarray
    prep_bal: jnp.ndarray
    prop_bal: jnp.ndarray
    prop_vid: jnp.ndarray
    prop_slot: jnp.ndarray


class StepOutputs(NamedTuple):
    """Per-step results surfaced to the host."""

    n_committed: jnp.ndarray   # [G] slots newly executed this step
    exec_base: jnp.ndarray     # [G] frontier before this step's advance
    exec_vid: jnp.ndarray      # [G, W] executed vids in slot order (NULL pad)
    n_admitted: jnp.ndarray    # [G] client reqs consumed from req_vid lanes
    maj_exec: jnp.ndarray      # [G] majority-rank execute frontier (GC mark)
    app_hash: jnp.ndarray      # [G] post-step app hash (RSM invariant probe)
    acc_new: jnp.ndarray       # [G, W] lanes newly accepted this step — the
    #   journal's log-before-send delta (AbstractPaxosLogger.logAndMessage
    #   rule: these rows must be durable before the blob is published)
    bal_new: jnp.ndarray       # [G] 1 where the promised ballot rose this
    #   step — must also be durable before the blob is published, even when
    #   no accept carries it (the reference logs promise-upgrading prepare
    #   replies before sending, PaxosInstanceStateMachine.handlePrepare);
    #   otherwise a crashed acceptor forgets a bare promise and can accept
    #   an older-ballot proposal it had promised against
    preempted_vid: jnp.ndarray  # [G, W] my proposals that lost their slot to
    #   another value (host re-proposes them; NULL elsewhere)


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def init_state(cfg: EngineConfig) -> EngineState:
    """All groups inert (member_mask 0) — the MultiArrayMap-of-capacity analog."""
    G, W = cfg.n_groups, cfg.window
    g = lambda fill: jnp.full((G,), fill, jnp.int32)
    gw = lambda fill: jnp.full((G, W), fill, jnp.int32)
    return EngineState(
        member_mask=g(0), majority=g(_BIG), version=g(0), stopped=g(0),
        tag=g(0),
        bal=g(NULL), exec_slot=g(0),
        acc_bal=gw(NULL), acc_vid=gw(NULL), acc_slot=gw(NULL),
        dec_vid=gw(NULL), dec_slot=gw(NULL),
        app_hash=g(0), n_execd=g(0),
        c_phase=g(IDLE), c_bal=g(NULL), c_next_slot=g(0),
        c_prop_vid=gw(NULL), c_prop_slot=gw(NULL),
    )


def make_blob(state: EngineState) -> Blob:
    """Atomic COMPACT snapshot of what peers need; masked by coordinator
    phase, anchored to this replica's ``exec_slot``/``bal``.  A lane whose
    slot falls outside the ±WRAP_MAX ring-epoch window (or whose accepted
    ballot trails ``bal`` by more than DELTA_MAX) publishes as NULL — see
    the module docstring for why that is safe."""
    W = state.acc_bal.shape[-1]
    if W & (W - 1):
        raise ValueError(f"window must be a power of two, got {W}")
    kbits = W.bit_length() - 1
    ebase = (state.exec_slot >> kbits)[..., None]

    def wrap_enc(slot):
        c = (slot >> kbits) - ebase
        ok = (slot != NULL) & (c >= -WRAP_MAX) & (c <= WRAP_MAX)
        return ok, jnp.where(ok, c + WRAP_BIAS, 0)

    acc_in, acc_w = wrap_enc(state.acc_slot)
    delta = state.bal[..., None] - state.acc_bal
    acc_ok = acc_in & (state.acc_bal != NULL) & (delta >= 0) & (delta <= DELTA_MAX)
    acc_w = jnp.where(acc_ok, acc_w, 0)
    acc_d = jnp.where(acc_ok, delta + 1, 0)
    dec_ok, dec_w = wrap_enc(state.dec_slot)
    preparing = state.c_phase == PREPARING
    active = state.c_phase == ACTIVE
    prop_ok, prop_w = wrap_enc(
        jnp.where(active[..., None], state.c_prop_slot, NULL)
    )
    meta = (
        acc_d
        | (acc_w << _ACC_SHIFT)
        | (dec_w << _DEC_SHIFT)
        | (prop_w << _PROP_SHIFT)
    )
    coord = jnp.where(
        preparing, state.c_bal,
        jnp.where(active, state.c_bal | jnp.int32(-(2 ** 31)), NULL),
    )
    return Blob(
        tag=state.tag,
        bal=state.bal,
        exec_slot=state.exec_slot,
        coord=coord,
        acc_vid=jnp.where(acc_ok, state.acc_vid, NULL),
        dec_vid=jnp.where(dec_ok, state.dec_vid, NULL),
        prop_vid=jnp.where(prop_ok, state.c_prop_vid, NULL),
        lane_meta=meta,
    )


def _decode_coord(coord):
    """coord word -> (prep_bal, prop_bal), NULL where not applicable."""
    prep_bal = jnp.where(coord >= 0, coord, NULL)
    is_active = (coord < 0) & (coord != NULL)
    prop_bal = jnp.where(is_active, coord & jnp.int32(0x7FFFFFFF), NULL)
    return prep_bal, prop_bal


def _decode_lanes(meta, bal, exec_slot, lanes, kbits):
    """One sender's lane planes from its meta + [.. ] anchors.

    Returns (acc_bal, acc_slot, dec_slot, prop_slot), each ``[..., W]``
    with NULL for empty/dropped lanes.  Works for a single row ([G, W])
    and for whole batched blobs ([R, G, W]) alike."""
    d = meta & _META_DELTA_MASK
    aw = (meta >> _ACC_SHIFT) & _WRAP_MASK
    dw = (meta >> _DEC_SHIFT) & _WRAP_MASK
    pw = (meta >> _PROP_SHIFT) & _WRAP_MASK
    ebase = (exec_slot >> kbits)[..., None]

    def wrap_dec(w):
        s = ((ebase + (w - WRAP_BIAS)) << kbits) | lanes
        return jnp.where(w != 0, s, NULL)

    acc_bal = jnp.where(d != 0, bal[..., None] - (d - 1), NULL)
    return acc_bal, wrap_dec(aw), wrap_dec(dw), wrap_dec(pw)


def expand_blob(blob: Blob) -> ExpandedBlob:
    """Decode a compact blob (single [G, ...] or batched [R, G, ...]) back
    to the absolute-plane view.  ``compact -> expand`` is the identity on
    every representable lane (the codec round-trip property test)."""
    W = blob.lane_meta.shape[-1]
    kbits = W.bit_length() - 1
    lanes = jnp.arange(W, dtype=jnp.int32)
    acc_bal, acc_slot, dec_slot, prop_slot = _decode_lanes(
        blob.lane_meta, blob.bal, blob.exec_slot, lanes, kbits
    )
    prep_bal, prop_bal = _decode_coord(blob.coord)
    return ExpandedBlob(
        tag=blob.tag, bal=blob.bal, exec_slot=blob.exec_slot,
        acc_bal=acc_bal, acc_vid=blob.acc_vid, acc_slot=acc_slot,
        dec_vid=blob.dec_vid, dec_slot=dec_slot,
        prep_bal=prep_bal, prop_bal=prop_bal,
        prop_vid=blob.prop_vid, prop_slot=prop_slot,
    )


def _mix(h, vid):
    """Deterministic app-hash fold (int32 wraparound is defined in XLA)."""
    return (h * jnp.int32(31) + vid) ^ (vid << 7)


def step_counted(
    state: EngineState,
    g: Blob,                 # gathered COMPACT blobs, every leaf with leading [R] axis
    heard: jnp.ndarray,      # [R] bool — which peers' blobs are live
    req_vid: jnp.ndarray,    # [G, K] new request value-ids (left-packed, NULL pad)
    want_coord: jnp.ndarray, # [G] bool — host FD election trigger
    my_id,                   # python int or traced scalar (replica-axis index)
    cfg: EngineConfig,
):
    """One vectorized consensus step for all G groups. Pure function.

    Returns (state', StepOutputs, quorum_sums).  The caller journals the
    accepted-window delta of state' *before* publishing blob(state') — that
    preserves the reference's log-before-send rule
    (``AbstractPaxosLogger.logAndMessage``, ``AbstractPaxosLogger.java:157``).

    ``quorum_sums`` is two int32 sums over this replica's own rows: the lanes
    it FIRST saw decided by its own count of matching accepts in this
    step (a slot its decision ring takes from the count and did not hold:
    one it learned from a peer's ring before its count got there is not
    among them, and a slot is counted once), and the sum of that count
    (``n_match``: the live members, itself among them, whose accepted
    (slot, ballot) is the lane's) over them.  Their quotient says how many
    accepts a decision waited for: the majority where it went ahead
    without the slowest replicas, the group's size where it did not.
    """
    G, W, K, R = cfg.n_groups, cfg.window, cfg.req_lanes, cfg.n_replicas
    if W <= 0 or W & (W - 1):
        # hard error (not an assert): under python -O a silent bitmask with
        # a non-power-of-two W would map slots to wrong ring lanes
        raise ValueError(f"window must be a power of two, got {W}")
    if K > W:
        # K consecutive admission candidates must map to distinct ring
        # lanes; beyond W they collide and placements would overwrite
        raise ValueError(f"req_lanes ({K}) must not exceed window ({W})")
    kbits = W.bit_length() - 1
    my_id = _i32(my_id)
    rids = jnp.arange(R, dtype=jnp.int32)
    lanes = jnp.arange(W, dtype=jnp.int32)
    lane_of = lambda s: s & jnp.int32(W - 1)  # slot -> ring lane (W = 2^k)

    # [R, G] — which gathered rows are valid senders for each group:
    # heard and a member of the group (per-group replica subsets,
    # ``groupMembers[]`` analog, PaxosInstanceStateMachine.java:176-188).
    in_group = ((state.member_mask[None, :] >> rids[:, None]) & 1) == 1
    # instance guard: a peer row speaking for a DIFFERENT tenant of this
    # row index (stale holdout after row reuse, or a not-yet-caught-up
    # joiner) is not part of this instance's consensus
    same_inst = g.tag == state.tag[None, :]               # [R, G]
    live = heard[:, None] & in_group & same_inst          # [R, G]

    inert = state.member_mask == 0
    maj = state.majority
    # Am I a member of each group?  A replica holds rows for groups it does
    # not belong to (the [G] arrays are capacity, not membership); it must
    # neither mutate nor act on those rows (the reference simply has no
    # PaxosInstanceStateMachine object for such groups).
    i_member = ((state.member_mask >> my_id) & 1) == 1

    # ---- 1. promise update (handlePrepare / acceptAndUpdateBallot) ----
    # (named_scope blocks annotate the HLO/profiler view of the step
    # with the consensus phase each op belongs to — trace-time only,
    # zero runtime cost; scripts/… profile captures read them back)
    with jax.named_scope("gp.promise"):
        prep_bal_g, prop_bal_g = _decode_coord(g.coord)   # [R, G]
        in_prep = jnp.where(live, prep_bal_g, NULL)
        in_prop = jnp.where(live, prop_bal_g, NULL)
        max_prop = in_prop.max(axis=0)                    # [G]
        new_bal = jnp.maximum(
            state.bal, jnp.maximum(in_prep.max(axis=0), max_prop)
        )

    exec2 = state.exec_slot[:, None]

    # ---- 2+3. the peer fold: accept-winner select, learn, decision-ring
    # merge — ONE sequential pass over the R gathered rows with [G, W]
    # carries (see the transient note in the module docstring).  Each
    # iteration decodes exactly one peer's compact lane planes.
    #
    # Ballots encode the coordinator id, so at most ONE live row publishes
    # max_prop — folding a masked max over winning rows IS that row's
    # window (no argmax+gather; see the TPU lowering note).
    win_row = (in_prop == max_prop[None, :]) & (max_prop[None, :] != NULL)

    def _row(x, r):
        return lax.dynamic_index_in_dim(x, r, 0, keepdims=False)

    def _decode_row(r):
        return _decode_lanes(
            _row(g.lane_meta, r), _row(g.bal, r), _row(g.exec_slot, r),
            lanes, kbits,
        )

    nullw = jnp.full((G, W), NULL, jnp.int32)

    def fold_peers(r, carry):
        (p_slot, p_vid, s_c, b_c, det_vid, n_match, c1_s, c1_v) = carry
        a_bal, a_slot, d_slot, pr_slot = _decode_row(r)
        a_vid = _row(g.acc_vid, r)
        d_vid = _row(g.dec_vid, r)
        pr_vid = _row(g.prop_vid, r)
        live_r = _row(live, r)[:, None]                   # [G, 1]
        # accept winner: adopt the max-prop row's proposal window
        w_r = _row(win_row, r)[:, None]
        p_slot = jnp.maximum(p_slot, jnp.where(w_r, pr_slot, NULL))
        p_vid = jnp.maximum(p_vid, jnp.where(w_r, pr_vid, NULL))
        # learn: running lexicographic (slot, ballot) max per lane with a
        # count of rows matching the current max — equal (slot, ballot)
        # implies equal value (one coordinator per ballot), so keeping the
        # first-seen vid == the reference's masked-max over matching rows
        ok = live_r & (a_slot != NULL)
        s_r = jnp.where(ok, a_slot, NULL)
        b_r = jnp.where(ok, a_bal, NULL)
        better = ok & ((s_r > s_c) | ((s_r == s_c) & (b_r > b_c)))
        same = ok & (s_r == s_c) & (b_r == b_c)
        n_match = jnp.where(better, 1, n_match + same.astype(jnp.int32))
        s_c = jnp.where(better, s_r, s_c)
        b_c = jnp.where(better, b_r, b_c)
        det_vid = jnp.where(better, a_vid, det_vid)
        # decision-ring merge: keep the SMALLEST needed decided slot >= my
        # frontier (rows at the min slot decided the SAME slot => same value)
        okd = live_r & (d_slot != NULL) & (d_slot >= exec2)
        lower = okd & (d_slot < c1_s)
        c1_s = jnp.where(lower, d_slot, c1_s)
        c1_v = jnp.where(lower, d_vid, c1_v)
        return (p_slot, p_vid, s_c, b_c, det_vid, n_match, c1_s, c1_v)

    with jax.named_scope("gp.peer_fold"):
        (p_slot, p_vid, s_c, b_c, det_vid, n_match, c1_s, c1_v) = \
            lax.fori_loop(
                0, R, fold_peers,
                (
                    nullw, nullw,                          # accept winner
                    nullw, nullw, nullw,
                    jnp.zeros((G, W), jnp.int32),          # learn
                    jnp.full((G, W), _BIG, jnp.int32),
                    nullw,                                 # decision merge
                ),
            )
    detected = (n_match >= maj[:, None]) & (s_c != NULL)

    # ---- 2. accept (handleAccept, PaxosAcceptor.acceptAndUpdateBallot) ----
    # Highest-ballot proposer wins; its ballot must equal the new promise.
    with jax.named_scope("gp.accept"):
        acc_ok = (
            (max_prop == new_bal) & (max_prop != NULL)
            & (state.stopped == 0)
        )
        # no ring-residue check needed: compact decode reconstructs
        # every slot as (epoch << kbits) | lane, so residue matches its
        # lane by construction
        in_win = (p_slot >= exec2) & (p_slot < exec2 + W) & (p_vid != NULL)
        do_acc = acc_ok[:, None] & in_win
        acc_bal = jnp.where(do_acc, max_prop[:, None], state.acc_bal)
        acc_vid = jnp.where(do_acc, p_vid, state.acc_vid)
        acc_slot = jnp.where(do_acc, p_slot, state.acc_slot)
        # True journal delta: an unchanged in-flight proposal re-fires
        # do_acc every step until it decides — only a changed lane needs
        # durability.
        acc_changed = do_acc & (
            (acc_bal != state.acc_bal) | (acc_vid != state.acc_vid)
            | (acc_slot != state.acc_slot)
        )

    # ---- 3. learn (the BatchedAcceptReply->DECISION collapse) ----
    # Decision candidates per lane: keep the SMALLEST undecided-needed slot
    # >= my frontier (so a lane never skips past an unexecuted decision).
    def cand(slot, vid, valid):
        ok = valid & (slot != NULL) & (slot >= exec2)
        return jnp.where(ok, slot, _BIG), vid

    with jax.named_scope("gp.learn"):
        c0_s, c0_v = cand(state.dec_slot, state.dec_vid, True)
        c2_s, c2_v = cand(s_c, det_vid, detected)

        best = jnp.minimum(jnp.minimum(c0_s, c1_s), c2_s)
        have = best < _BIG
        dec_vid = jnp.where(
            have,
            jnp.where(
                best == c0_s, c0_v,
                jnp.where(best == c1_s, c1_v, c2_v),
            ),
            state.dec_vid,
        )
        dec_slot = jnp.where(have, best, state.dec_slot)
        # the count's own news: c0_s == c2_s is a decision already held
        first = have & (c2_s == best) & (c2_s < c0_s) & i_member[:, None]
        quorum_sums = jnp.stack([
            first.sum(dtype=jnp.int32),
            jnp.where(first, n_match, 0).sum(dtype=jnp.int32),
        ])

    # ---- 4. execute: advance the in-order frontier (EEC analog,
    # PaxosInstanceStateMachine.extractExecuteAndCheckpoint:1511-1593) ----
    # A lane holds frontier+o exactly when its decided slot equals it —
    # checked per offset with [G, W] temporaries (a static W unroll; the
    # [G, W, W] one-hot this replaces was a 4 GB transient at G=1M/W=32).
    with jax.named_scope("gp.execute"):
        h = state.app_hash
        n_execd = state.n_execd
        stop_seen = jnp.zeros((G,), bool)
        run_prev = jnp.ones((G,), bool)
        n_adv = jnp.zeros((G,), jnp.int32)
        run_cols = []
        vid_cols = []
        for o in range(W):  # static unroll; W small
            slot_o = state.exec_slot + o
            eq = dec_slot == slot_o[:, None]              # [G, W]
            hit = eq.any(axis=1)
            vid_o = jnp.where(eq, dec_vid, NULL).max(axis=1)  # [G]
            take = run_prev & hit
            real = take & (vid_o > 0)
            h = jnp.where(real, _mix(h, vid_o), h)
            n_execd = n_execd + real.astype(jnp.int32)
            stop_seen = stop_seen | (take & ((vid_o & STOP_BIT) != 0))
            n_adv = n_adv + take.astype(jnp.int32)
            run_cols.append(take)
            vid_cols.append(vid_o)
            run_prev = take
        exec_new = state.exec_slot + n_adv
        run = jnp.stack(run_cols, axis=1)                 # [G, W] bool
        d_vid_at = jnp.stack(vid_cols, axis=1)            # [G, W]
        stopped = jnp.maximum(
            state.stopped, stop_seen.astype(jnp.int32)
        )

    # Majority-rank execute frontier: the slot that >= majority of replicas
    # have executed past (the medianCheckpointedSlot GC watermark analog,
    # PValuePacket.medianCheckpointedSlot / nodeSlotNumbers piggybacking).
    # k-th largest via O(R^2) rank count (no sort/gather): v is the maj-th
    # largest iff #{rows >= v} >= maj, and the largest such v is exact.
    with jax.named_scope("gp.maj_frontier"):
        ge = jnp.where(live, g.exec_slot, NULL)
        rank = (ge[:, None, :] <= ge[None, :, :]).sum(axis=1)  # [R, G]
        maj_exec = jnp.where(rank >= maj[None, :], ge, NULL).max(axis=0)
        maj_exec = jnp.maximum(maj_exec, jnp.int32(0))

    # ---- 5. coordinator ----
    me_coord = state.c_bal
    phase = state.c_phase
    # Preempted by a strictly higher ballot in the system (-> resign,
    # handlePrepareReply preemption, PaxosInstanceStateMachine.java:955-965).
    preempt = (phase != IDLE) & (new_bal > me_coord)
    phase = jnp.where(preempt, IDLE, phase)

    # Election start (checkRunForCoordinator, :1962-2072): host FD says go,
    # OR the promise ballot names ME as coordinator while I hold no
    # coordinator state — the "I'm ballot-coordinator but not running"
    # eligibility clause (:1992-2006).  This happens after crash recovery:
    # replayed accepts restore the promise ballot, but coordinator state is
    # volatile (HotRestore-only in the reference too), so without this rule
    # the group wedges — the failure detector sees the named coordinator
    # alive and never fires.
    from .ballot import COORD_MASK

    orphaned = ((new_bal & COORD_MASK) == my_id) & (new_bal != NULL)
    start = (want_coord | orphaned) & (phase == IDLE) & (~inert) & (stopped == 0)
    start_bal = encode_ballot(ballot_num(new_bal) + 1, my_id)
    c_bal = jnp.where(start, start_bal, me_coord)
    phase = jnp.where(start, PREPARING, phase)
    # Self-promise to my own prepare.
    new_bal = jnp.where(phase == PREPARING, jnp.maximum(new_bal, c_bal), new_bal)

    # Prepare quorum: peers whose published promise equals my ballot, +1 self.
    not_me = rids != my_id
    promised = (g.bal == c_bal[None, :]) & live & not_me[:, None]
    n_promise = promised.sum(axis=0) + 1
    quorum = (phase == PREPARING) & (n_promise >= maj)

    # Carryover (the one genuinely sparse flow in the reference — a
    # lane-wise lexicographic (slot, ballot) max over promisers' atomic
    # snapshots (newest slot wins the lane; ballot breaks ties), folded one
    # peer row at a time like the learn pass; my own post-accept window
    # joins as the self-promise row after the fold).
    def fold_carryover(r, carry):
        co_slot, co_bal, co_vid = carry
        a_bal, a_slot, _d, _p = _decode_row(r)
        a_vid = _row(g.acc_vid, r)
        ok = _row(promised, r)[:, None] & (a_slot != NULL) & (a_slot >= exec2)
        better = ok & ((a_slot > co_slot) | ((a_slot == co_slot) & (a_bal > co_bal)))
        co_slot = jnp.where(better, a_slot, co_slot)
        co_bal = jnp.where(better, a_bal, co_bal)
        co_vid = jnp.where(better, a_vid, co_vid)
        return co_slot, co_bal, co_vid

    with jax.named_scope("gp.carryover"):
        co_slot, co_bal, co_vid = lax.fori_loop(
            0, R, fold_carryover, (nullw, nullw, nullw)
        )
    my_ok = (acc_slot != NULL) & (acc_slot >= exec2)
    mine = my_ok & ((acc_slot > co_slot) | ((acc_slot == co_slot) & (acc_bal > co_bal)))
    co_slot = jnp.where(mine, acc_slot, co_slot)
    co_bal = jnp.where(mine, acc_bal, co_bal)
    co_vid = jnp.where(mine, acc_vid, co_vid)
    co_has = co_slot != NULL

    won = quorum
    phase = jnp.where(won, ACTIVE, phase)
    # Safety bound for NEW proposals after an election: a promiser whose
    # execute frontier passed slot s has executed a decision for s that may
    # no longer appear in any window (its lane was reused).  So never invent
    # proposals (hole no-ops / fresh requests) below the promise set's max
    # frontier; those slots are learned via decision rings or sync instead.
    # (Carryover re-proposals below it are safe: synod rules guarantee the
    # carried value equals any chosen value.)
    prom_exec = jnp.where(promised, g.exec_slot, NULL).max(axis=0)  # [G]
    floor = jnp.maximum(exec_new, prom_exec)

    # Adopt carryovers into my proposal ring on victory.
    won2 = won[:, None]
    c_prop_vid = jnp.where(won2, jnp.where(co_has, co_vid, NULL), state.c_prop_vid)
    c_prop_slot = jnp.where(won2, jnp.where(co_has, co_slot, NULL), state.c_prop_slot)
    max_co_slot = co_slot.max(axis=1)                             # [G] (NULL if none)
    next_on_win = jnp.maximum(floor, max_co_slot + 1)
    c_next = jnp.where(won, next_on_win, state.c_next_slot)

    # Hole-filling no-ops: undecided slots in [floor, next) with no carryover
    # must be proposed as no-ops to unblock the frontier.
    exp_slot = exec_new[:, None] + lane_of(lanes[None, :] - exec_new[:, None])
    hole = (
        won2 & (exp_slot >= floor[:, None]) & (exp_slot < c_next[:, None])
        & (c_prop_slot != exp_slot) & (dec_slot != exp_slot)
    )
    c_prop_vid = jnp.where(hole, NOOP_VID, c_prop_vid)
    c_prop_slot = jnp.where(hole, exp_slot, c_prop_slot)

    # Retire proposals once their decision is learned (waitfor retirement,
    # PaxosCoordinatorState myProposals) or they fell below the frontier.
    # A retired lane whose decided value differs from my proposal was
    # PREEMPTED (another ballot chose a different value there) — surface
    # those vids so the host can re-propose them at a fresh slot (the
    # reference's PREEMPTED packet -> re-propose path, PValuePacket
    # PREEMPTED / PaxosInstanceStateMachine.java:955-965).
    is_active = phase == ACTIVE
    dec_at_prop = dec_slot == c_prop_slot                 # lane-aligned
    retire = (c_prop_slot != NULL) & (dec_at_prop | (c_prop_slot < exec2))
    preempted_vid = jnp.where(
        retire & (dec_vid != c_prop_vid) & (c_prop_vid > 0),  # >0: no NOOPs
        c_prop_vid, NULL,
    )
    c_prop_vid = jnp.where(retire, NULL, c_prop_vid)
    c_prop_slot = jnp.where(retire, NULL, c_prop_slot)

    # Stop-request ordering (proposeStop semantics, PaxosManager.java:1269-
    # 1390): once a stop is proposed or decided, admit nothing more.
    stopping = ((c_prop_vid != NULL) & ((c_prop_vid & STOP_BIT) != 0)).any(axis=1)
    dec_stop = (
        (dec_slot != NULL) & (dec_slot >= exec2) & ((dec_vid & STOP_BIT) != 0)
    ).any(axis=1)
    may_admit = is_active & (stopped == 0) & (~stopping) & (~dec_stop)
    # ...and within this step's batch, nothing after a stop lane.
    req_stop = (req_vid != NULL) & ((req_vid & STOP_BIT) != 0)
    no_stop_before = jnp.cumprod(1 - req_stop.astype(jnp.int32), axis=1)
    no_stop_before = jnp.concatenate(
        [jnp.ones((G, 1), jnp.int32), no_stop_before[:, :-1]], axis=1
    )

    # Admit new client requests: consecutive slots from c_next, bounded by
    # the majority window (don't outrun a majority's rings) and free lanes.
    # c_next must never lag the frontier (a recovered snapshot can be a few
    # slots behind the replayed decisions — proposing at an already-decided
    # slot would silently lose the request).  Placement runs as a static K
    # unroll with [G, W] temporaries; consecutive candidates map to
    # DISTINCT lanes (K <= W enforced above), so the sequential placement
    # equals the reference's all-at-once one-hot scatter.
    with jax.named_scope("gp.admission"):
        c_next = jnp.where(
            is_active, jnp.maximum(c_next, exec_new), c_next
        )
        bound = maj_exec + W
        adm_prev = jnp.ones((G,), bool)
        n_admit = jnp.zeros((G,), jnp.int32)
        for k in range(K):  # static unroll; K small
            cand_slot = c_next + k                        # [G]
            oh = lane_of(cand_slot)[:, None] == lanes[None, :]  # [G, W]
            lane_busy = (oh & (c_prop_slot != NULL)).any(axis=1)
            dec_at_cand = jnp.where(oh, dec_slot, NULL).max(axis=1)
            can = (
                may_admit & (no_stop_before[:, k] > 0)
                & (req_vid[:, k] != NULL) & (cand_slot < bound)
                & (~lane_busy)
                & (dec_at_cand != cand_slot)  # never re-propose a
                                              # decided slot
            )
            adm = adm_prev & can           # contiguous admission prefix
            place = oh & adm[:, None]
            c_prop_vid = jnp.where(
                place, req_vid[:, k][:, None], c_prop_vid
            )
            c_prop_slot = jnp.where(
                place, cand_slot[:, None], c_prop_slot
            )
            n_admit = n_admit + adm.astype(jnp.int32)
            adm_prev = adm
        c_next = c_next + n_admit

    new_state = EngineState(
        member_mask=state.member_mask, majority=state.majority,
        version=state.version, stopped=stopped, tag=state.tag,
        bal=new_bal, exec_slot=exec_new,
        acc_bal=acc_bal, acc_vid=acc_vid, acc_slot=acc_slot,
        dec_vid=dec_vid, dec_slot=dec_slot,
        app_hash=h, n_execd=n_execd,
        c_phase=phase, c_bal=c_bal, c_next_slot=c_next,
        c_prop_vid=c_prop_vid, c_prop_slot=c_prop_slot,
    )
    # Non-member rows stay frozen (and report nothing).
    m1 = i_member
    m2 = i_member[:, None]
    keep = lambda new, old: jnp.where(m1 if new.ndim == 1 else m2, new, old)
    new_state = EngineState(*(keep(n, o) for n, o in zip(new_state, state)))
    outputs = StepOutputs(
        n_committed=jnp.where(m1, n_adv, 0),
        exec_base=state.exec_slot,
        exec_vid=jnp.where(m2 & run, d_vid_at, NULL),
        n_admitted=jnp.where(m1, n_admit, 0),
        maj_exec=jnp.where(m1, maj_exec, 0),
        app_hash=new_state.app_hash,
        acc_new=(m2 & acc_changed).astype(jnp.int32),
        bal_new=(new_state.bal != state.bal).astype(jnp.int32),
        preempted_vid=jnp.where(m2, preempted_vid, NULL),
    )
    return new_state, outputs, quorum_sums


def step(state: EngineState, g: Blob, heard, req_vid, want_coord, my_id,
         cfg: EngineConfig):
    """:func:`step_counted` less its quorum sums: (state', StepOutputs)."""
    return step_counted(state, g, heard, req_vid, want_coord, my_id, cfg)[:2]


# ---------------------------------------------------------------------------
# Packed host-exchange interface.
#
# The deployed (socket/loopback) runtime moves every blob leaf host<->device
# each tick.  Doing that as ~50 per-leaf jnp.asarray / device_put / asarray
# dispatches costs far more than the engine step itself at loopback scale
# (it was ~70% of a node's tick on a 1-core host).  These helpers move each
# direction as ONE int32 vector: the gathered peer blobs upload as a single
# [R, N] array (sliced back into Blob leaves INSIDE the jitted step, where
# the slices fuse for free), and the step's outputs + fresh publish blob
# come back as single vectors split into numpy views on the host.
#
# The vector layout intentionally equals the ``D`` wire frame body
# (Blob._fields order, C-order ravel): a received frame's payload IS the
# packed row, byte-for-byte, so the transport needs no re-packing either.
# ---------------------------------------------------------------------------

def _leaf_shapes(fields, cfg: EngineConfig):
    G, W = cfg.n_groups, cfg.window
    return [
        (name, (G,) if name in _G_LEAVES else (G, W)) for name in fields
    ]


# [G]-shaped leaves across Blob and StepOutputs (everything else is [G, W])
_G_LEAVES = frozenset((
    "tag", "bal", "exec_slot", "coord",
    "n_committed", "exec_base", "n_admitted", "maj_exec", "app_hash",
    "bal_new",
))


import functools


@functools.lru_cache(maxsize=None)
def blob_vec_len(cfg: EngineConfig) -> int:
    # memoized: recomputing the shape walk on every received frame would
    # tax the exact hot path the packed codec exists to relieve
    return sum(
        int(np.prod(s)) for _n, s in _leaf_shapes(Blob._fields, cfg)
    )


@functools.lru_cache(maxsize=None)
def out_vec_len(cfg: EngineConfig) -> int:
    return sum(
        int(np.prod(s)) for _n, s in _leaf_shapes(StepOutputs._fields, cfg)
    )


def legacy_blob_vec_len(cfg: EngineConfig) -> int:
    """Int32 words of the pre-compact all-int32 blob layout (5 ``[G]`` +
    7 ``[G, W]`` planes) — the footprint probe's reduction baseline."""
    return 5 * cfg.n_groups + 7 * cfg.n_groups * cfg.window


def pack_blob(blob: Blob) -> jnp.ndarray:
    """[N] device vector in Blob._fields order (== wire frame body)."""
    return jnp.concatenate([jnp.ravel(leaf) for leaf in blob])


def _unpack(vec, fields, cfg: EngineConfig, cls, batched: bool):
    leaves = []
    off = 0
    for name, shape in _leaf_shapes(fields, cfg):
        n = int(np.prod(shape))
        chunk = vec[..., off:off + n]
        off += n
        full = (vec.shape[0],) + shape if batched else shape
        leaves.append(chunk.reshape(full))
    return cls(*leaves)


def unpack_gathered(gvec: jnp.ndarray, cfg: EngineConfig) -> Blob:
    """[R, N] packed peer blobs -> Blob of [R, ...] leaves (inside jit)."""
    return _unpack(gvec, Blob._fields, cfg, Blob, batched=True)


# ---------------------------------------------------------------------------
# The gathered stack: every peer's blob as ONE device-resident Blob of
# [R, ...] leaves, which the packed step is handed donated and hands back.
# Its lane leaves are held [R, W, G], ROWS MINOR: that is how the chip lays
# out a [G, W] plane (the step reads them with no relayout — the parent's
# unpacking of an uploaded [R, N] matrix was 0.9 of its 2.18 ms), and in
# that layout a row's W words are scattered one by one, in place; a
# scatter of whole [W] windows would relayout every plane there and back
# (+1.2 ms; PERF.md, PR 30).
#
# A tick sends up only what its frames brought: an UPDATE of fixed shape
# (``update_vec_len``) — ``update_rows`` flat indices ``peer * G + row``,
# ascending and unique, then those rows' words as a packed vector of that
# many rows in the wire layout (``net/codec.py:_row_blocks`` cuts it the
# same way).  An index at or past ``R * G`` pads the update and is dropped.
# A peer whose news does not fit sends its whole vector (``set_peer_rows``).
# ---------------------------------------------------------------------------

def update_rows(cfg: EngineConfig) -> int:
    """C, the rows one update holds: from the shape, as the digest's."""
    return digest_rows(cfg)


@functools.lru_cache(maxsize=None)
def update_vec_len(cfg: EngineConfig) -> int:
    C = update_rows(cfg)
    return C + blob_vec_len(cfg._replace(n_groups=C))


def _swap_lanes(blob: Blob) -> Blob:
    """Lane leaves [..., G, W] <-> [..., W, G] (on the chip a bitcast)."""
    return Blob(*[
        leaf if name in _G_LEAVES else jnp.swapaxes(leaf, -1, -2)
        for name, leaf in zip(Blob._fields, blob)
    ])


def init_stack(cfg: EngineConfig) -> Blob:
    """The stack before any peer was heard: zeros, which no step reads
    (``heard`` masks a row until its peer's first whole vector lands)."""
    R = cfg.n_replicas
    return _swap_lanes(Blob(*[
        jnp.zeros((R,) + shape, jnp.int32)
        for _name, shape in _leaf_shapes(Blob._fields, cfg)
    ]))


def stack_blob(stack: Blob) -> Blob:
    """The stack as the step reads it: [R, G] and [R, G, W] leaves."""
    return _swap_lanes(stack)


_SCATTER_CHUNK = 256  # rows scattered per pass of scatter_update's loop


def scatter_update(stack: Blob, upd: jnp.ndarray, cfg: EngineConfig) -> Blob:
    """Inside jit: the update's rows written into the stack.  The
    device's work follows the rows that are there: a chunk of them a
    pass, as many passes as hold them (0.24 ms a pass at 65,536 rows)."""
    G, W, R, C = cfg.n_groups, cfg.window, cfg.n_replicas, update_rows(cfg)
    S = min(_SCATTER_CHUNK, C)
    idx = upd[:C]
    rows = _unpack(upd[C:], Blob._fields, cfg._replace(n_groups=C), Blob,
                   batched=False)
    n = (idx < R * G).sum(dtype=jnp.int32)
    lanes = jnp.arange(W, dtype=jnp.int32)

    def scatter_chunk(i, stack):
        # a chunk that would run past C is read S rows back from the
        # end (dynamic_slice clamps): rows written twice, to one value
        at = lax.dynamic_slice(idx, (i * S,), (S,))
        peer, row = at // G, at % G  # padding: peer >= R, dropped
        plane = (peer[:, None] * W + lanes[None]).ravel()   # of [R * W, G]
        col = jnp.repeat(row, W)
        out = []
        for leaf, new in zip(stack, rows):
            new = lax.dynamic_slice_in_dim(new, i * S, S)
            if leaf.ndim == 2:
                out.append(leaf.at[peer, row].set(
                    new, mode="drop", unique_indices=True,
                    indices_are_sorted=True))
            else:
                out.append(leaf.reshape(R * W, G).at[plane, col].set(
                    new.ravel(), mode="drop", unique_indices=True,
                ).reshape(R, W, G))
        return Blob(*out)

    return lax.fori_loop(0, (n + S - 1) // S, scatter_chunk, stack)


def _set_row(stack: Blob, blob: Blob, r) -> Blob:
    """Row ``r`` of the stack := one replica's blob ([G], [G, W])."""
    return Blob(*[
        lax.dynamic_update_index_in_dim(leaf, row, r, 0)
        for leaf, row in zip(stack, _swap_lanes(blob))
    ])


@functools.partial(jax.jit, static_argnames="cfg", donate_argnums=0)
def set_peer_rows(stack: Blob, vec: jnp.ndarray, peer, *,
                  cfg: EngineConfig) -> Blob:
    """The whole-row program: one peer's packed [N] vector over its row
    of the (donated) stack — a new connection's ``D`` frame, the answer
    to a resync, news of more rows than an update holds."""
    return _set_row(
        stack, _unpack(vec, Blob._fields, cfg, Blob, batched=False), peer)


def with_my_row(stack: Blob, state: EngineState, my_id) -> Blob:
    """Inside jit: the stack with row ``my_id`` taken from the state."""
    return _set_row(stack, make_blob(state), my_id)


@functools.partial(jax.jit, static_argnames="cfg")
def gathered_matrix(state: EngineState, stack: Blob, upd: jnp.ndarray,
                    my_id, *, cfg: EngineConfig) -> jnp.ndarray:
    """What the next step reads of (state, stack, update), as the packed
    [R, N] matrix a host would have assembled; donates nothing (tests,
    and a look at a node by hand)."""
    g = with_my_row(scatter_update(stack, upd, cfg), state, my_id)
    return jnp.concatenate(
        [leaf.reshape(cfg.n_replicas, -1) for leaf in stack_blob(g)], axis=1
    )


def split_out_vec(vec: np.ndarray, cfg: EngineConfig) -> StepOutputs:
    """Host-side: one transferred [M] vector -> StepOutputs of np views."""
    return _unpack(
        np.asarray(vec), StepOutputs._fields, cfg, StepOutputs, batched=False
    )


def split_blob_vec(vec: np.ndarray, cfg: EngineConfig) -> Blob:
    return _unpack(
        np.asarray(vec), Blob._fields, cfg, Blob, batched=False
    )


# ---------------------------------------------------------------------------
# The step digest: what the host's post-step reads, reduced on the device
# to the rows that have something in them.  The six [G] output leaves come
# whole (the watermark and the state-pull detectors read every row); of the
# [G, W] planes only the BUSY rows come — those with a commit, a newly
# accepted lane or a preempted proposal — each with its lanes of the NEW
# state's accept columns (the journal's log-before-send rows), plus one
# flag: does any row still hold consensus work, and the step's two
# quorum sums (``step_counted``).  A dispatch with more busy rows than the
# digest holds reports its count, and the host pulls the whole planes for
# that one (``digest_from_planes``).
#
# Vector layout (int32): the six [G] leaves in StepOutputs order, n_busy,
# live, the two quorum sums, rows [M], then the six [M, W] planes in
# StepDigest order.
# ---------------------------------------------------------------------------

class StepDigest(NamedTuple):
    """One step's results as the host reads them: the [G] leaves of
    :class:`StepOutputs` whole, ``rows`` the busy rows in ascending order,
    and every plane [len(rows), W] — row ``k`` of a plane is row
    ``rows[k]`` of the [G, W] plane it was gathered from."""

    n_committed: np.ndarray    # [G]
    exec_base: np.ndarray      # [G]
    n_admitted: np.ndarray     # [G]
    maj_exec: np.ndarray       # [G]
    app_hash: np.ndarray       # [G]
    bal_new: np.ndarray        # [G]
    live: bool                 # the new state holds work in flight
    rows: np.ndarray           # [n] busy rows, ascending
    exec_vid: np.ndarray       # [n, W] of StepOutputs
    acc_new: np.ndarray        # [n, W] of StepOutputs
    preempted_vid: np.ndarray  # [n, W] of StepOutputs
    acc_slot: np.ndarray       # [n, W] of the NEW state
    acc_bal: np.ndarray        # [n, W] of the NEW state
    acc_vid: np.ndarray        # [n, W] of the NEW state


_DIGEST_G_LEAVES = StepDigest._fields[:6]
_DIGEST_HEAD = 4  # n_busy, live, decisions detected, accepts at detection
_DIGEST_OUT_PLANES = ("exec_vid", "acc_new", "preempted_vid")
_DIGEST_STATE_PLANES = ("acc_slot", "acc_bal", "acc_vid")


def digest_rows(cfg: EngineConfig) -> int:
    """M, the busy rows one digest holds: a sixteenth of the rows, at
    least 1,024, at most all of them (4,096 of 65,536; 64 of 64)."""
    return min(cfg.n_groups, max(1024, cfg.n_groups // 16))


@functools.lru_cache(maxsize=None)
def digest_vec_len(cfg: EngineConfig) -> int:
    M = digest_rows(cfg)
    return 6 * cfg.n_groups + _DIGEST_HEAD + M + 6 * M * cfg.window


def work_in_flight(state: EngineState) -> jnp.ndarray:
    """Scalar bool: some row holds consensus work that the next peer
    blob can advance — an accepted lane at or past the execute frontier,
    or an outstanding coordinator proposal."""
    lanes = (
        (state.acc_slot != NULL) & (state.acc_vid != NULL)
        & (state.acc_slot >= state.exec_slot[:, None])
    )
    return lanes.any() | (state.c_prop_vid != NULL).any()


def _busy_rows(out) -> "jnp.ndarray | np.ndarray":
    """[G] bool: the rows whose [G, W] output planes are not all empty."""
    return (
        (out.n_committed > 0)
        | (out.acc_new != 0).any(-1)
        | (out.preempted_vid != NULL).any(-1)
    )


_DIGEST_CHUNK = 256  # rows gathered per pass of make_digest's loop


def make_digest(out: StepOutputs, state: EngineState, cfg: EngineConfig,
                quorum_sums: jnp.ndarray) -> jnp.ndarray:
    """Inside jit: the digest vector of one step's ``out`` and
    ``quorum_sums`` (``step_counted``'s third result) against the
    dispatch's NEW ``state``.  The device's work follows the busy
    rows: one sort of [G] keys names them, and their lanes are gathered
    a chunk of rows at a time, as many chunks as hold them (rows of the
    planes past the last chunk stay 0; the host reads none of them)."""
    G, W, M = cfg.n_groups, cfg.window, digest_rows(cfg)
    C = min(_DIGEST_CHUNK, M)
    busy = _busy_rows(out)
    n_busy = busy.sum(dtype=jnp.int32)
    # the first M busy rows, ascending; G past the last of them
    rows = jnp.sort(jnp.where(busy, jnp.arange(G, dtype=jnp.int32), G))[:M]
    at = jnp.minimum(rows, G - 1)
    sources = [getattr(out, f) for f in _DIGEST_OUT_PLANES] \
        + [getattr(state, f) for f in _DIGEST_STATE_PLANES]

    def gather_chunk(i, planes):
        # a chunk that would run past M is read and written C rows back
        # from the end: dynamic_slice clamps both the same way
        chunk = lax.dynamic_slice(at, (i * C,), (C,))
        return lax.dynamic_update_slice(
            planes, jnp.stack([src[chunk] for src in sources]), (0, i * C, 0)
        )

    planes = lax.fori_loop(
        0, (jnp.minimum(n_busy, M) + C - 1) // C, gather_chunk,
        jnp.zeros((6, M, W), jnp.int32),
    )
    head = jnp.concatenate([
        jnp.stack([n_busy, work_in_flight(state).astype(jnp.int32)]),
        quorum_sums,
    ])
    return jnp.concatenate(
        [getattr(out, f) for f in _DIGEST_G_LEAVES]
        + [head, rows, jnp.ravel(planes)]
    )


def split_digest_vec(vec: np.ndarray, cfg: EngineConfig):
    """Host-side: one transferred digest vector -> (:class:`StepDigest`
    of np views, n_busy, the step's quorum sums as two ints: decisions
    first detected, accepts counted at their detection).  Where ``n_busy``
    exceeds the digest's rows the planes hold only the first of them: the
    caller pulls the whole planes instead (``digest_from_planes``)."""
    G, W, M = cfg.n_groups, cfg.window, digest_rows(cfg)
    vec = np.asarray(vec)
    g_leaves = vec[:6 * G].reshape(6, G)
    n_busy, live, decisions, accepts = (
        int(x) for x in vec[6 * G:6 * G + _DIGEST_HEAD])
    n = min(n_busy, M)
    off = 6 * G + _DIGEST_HEAD
    rows = vec[off:off + n]
    planes = vec[off + M:].reshape(6, M, W)[:, :n]
    return (StepDigest(*g_leaves, bool(live), rows, *planes), n_busy,
            (decisions, accepts))


def digest_from_planes(out: StepOutputs, acc_slot: np.ndarray,
                       acc_bal: np.ndarray, acc_vid: np.ndarray,
                       live: bool) -> StepDigest:
    """Host-side: the digest of whole pulled planes, however many rows
    are busy — the path of a dispatch that overflowed the device's
    digest, and what the tests hold the device's digest against."""
    rows = np.flatnonzero(_busy_rows(out)).astype(np.int32)
    return StepDigest(
        *[getattr(out, f) for f in _DIGEST_G_LEAVES], live, rows,
        *[getattr(out, f)[rows] for f in _DIGEST_OUT_PLANES],
        acc_slot[rows], acc_bal[rows], acc_vid[rows],
    )


# ---------------------------------------------------------------------------
# The blob news: the rows in which the fresh publish vector differs from
# the one the host last received from this node's step — what the sender
# used to find by comparing two 17.8 MB vectors for every peer, every
# tick.  The ``published`` vector lives on the device beside the stack
# (donated to the step, which hands the fresh one back in its place), so
# it equals the host's mirror by construction, whatever rewrote a row
# between two steps (a create, a kill, a restore, a state replaced).
#
# Vector layout (int32): n_changed, then an update's (``update_vec_len``):
# C row indices ascending, G past the last, then those rows' words as a
# packed vector of C rows in the wire layout.  More than C changed rows:
# the count says so, and the host pulls the whole vector for that one.
# ---------------------------------------------------------------------------

def make_news(blob: Blob, published: jnp.ndarray,
              cfg: EngineConfig) -> jnp.ndarray:
    """Inside jit: the news vector of the fresh ``blob`` against the
    packed ``published`` vector.  As :func:`make_digest`: one sort of [G]
    keys names the rows, and their words are gathered a chunk of rows at
    a time, as many chunks as hold them."""
    G, C = cfg.n_groups, update_rows(cfg)
    S = min(_DIGEST_CHUNK, C)
    old = _unpack(published, Blob._fields, cfg, Blob, batched=False)
    changed = jnp.zeros((G,), bool)
    for new, was in zip(blob, old):
        diff = new != was
        changed |= diff if diff.ndim == 1 else diff.any(-1)
    n = changed.sum(dtype=jnp.int32)
    rows = jnp.sort(jnp.where(changed, jnp.arange(G, dtype=jnp.int32), G))[:C]
    at = jnp.minimum(rows, G - 1)

    def gather_chunk(i, leaves):
        chunk = lax.dynamic_slice(at, (i * S,), (S,))
        return tuple(
            lax.dynamic_update_slice_in_dim(leaf, src[chunk], i * S, 0)
            for leaf, src in zip(leaves, blob)
        )

    leaves = lax.fori_loop(
        0, (jnp.minimum(n, C) + S - 1) // S, gather_chunk,
        tuple(jnp.zeros((C,) + src.shape[1:], jnp.int32) for src in blob),
    )
    return jnp.concatenate(
        [n[None], rows] + [jnp.ravel(leaf) for leaf in leaves])


def split_news_vec(vec: np.ndarray, cfg: EngineConfig):
    """Host-side: one transferred news vector -> (n_changed, the changed
    rows ascending, the packed vector of ``update_rows`` rows whose first
    ``len(rows)`` hold their words).  Where ``n_changed`` exceeds what
    the vector holds, the rows are only the first of them: the caller
    pulls the whole publish vector instead."""
    C = update_rows(cfg)
    vec = np.asarray(vec)
    n = int(vec[0])
    return n, vec[1:1 + min(n, C)], vec[1 + C:]
