"""Compact exchange format: codec round-trip property + safety parity.

The blob a replica publishes no longer ships absolute ``[G, W]`` slot and
ballot planes — slots are exec-anchored wrap deltas and the accepted
ballot is a delta off the promised ballot, bit-packed into ``lane_meta``
(``ops/engine.py`` module docstring).  Two properties pin the format:

* **Round trip** — ``expand_blob(make_blob(state))`` equals the legacy
  absolute-plane blob on every representable lane, and NULLs exactly the
  lanes the format declares unrepresentable (outside the ±WRAP_MAX ring
  epoch window / ballot delta beyond DELTA_MAX), over random valid states
  including NULL lanes, wrap boundaries, and all coordinator phases.
* **Safety parity** — there is ONE format (no dual path), so the whole
  existing engine/spmd invariant suite already runs through it; here a
  long-run cluster crosses the wrap-bias window many times and re-asserts
  the RSM invariant + committed-order property at high slot numbers.
"""

import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.ops.ballot import NULL
from gigapaxos_tpu.ops.engine import (
    ACTIVE,
    DELTA_MAX,
    IDLE,
    PREPARING,
    WRAP_MAX,
    EngineConfig,
    EngineState,
    blob_vec_len,
    expand_blob,
    init_state,
    legacy_blob_vec_len,
    make_blob,
    pack_blob,
)
from gigapaxos_tpu.testing.sim import SimCluster

G, W, K, R = 6, 8, 4, 3
CFG = EngineConfig(n_groups=G, window=W, req_lanes=K, n_replicas=R)
KBITS = W.bit_length() - 1


def random_state(rng: np.random.Generator) -> EngineState:
    """A structurally valid EngineState: ring-residue slots scattered
    around the frontier (some beyond the ±WRAP_MAX window), ballots with
    deltas straddling DELTA_MAX, NULL lanes, and mixed phases."""
    lanes = np.arange(W, dtype=np.int64)
    # keep slots non-negative even at epoch delta -(WRAP_MAX+5)
    exec_slot = rng.integers((WRAP_MAX + 8) * W, 10_000, size=G)
    ebase = exec_slot >> KBITS
    bal = rng.integers(DELTA_MAX + 10, 2 ** 24, size=G)

    def lane_slots(p_null: float) -> np.ndarray:
        """[G, W] ring-residue slots at epoch deltas in [-20, 20]."""
        eps = rng.integers(-(WRAP_MAX + 5), WRAP_MAX + 6, size=(G, W))
        s = ((ebase[:, None] + eps) << KBITS) | lanes[None, :]
        return np.where(rng.random((G, W)) < p_null, NULL, s)

    acc_slot = lane_slots(0.3)
    # ballot deltas 0..DELTA_MAX+big: some saturate, a few NULL
    acc_bal = bal[:, None] - rng.integers(0, DELTA_MAX + 100, size=(G, W))
    acc_bal = np.where(rng.random((G, W)) < 0.1, NULL, acc_bal)
    c_phase = rng.integers(0, 3, size=G)  # IDLE / PREPARING / ACTIVE

    st = init_state(CFG)
    i32 = lambda a: jnp.asarray(a, jnp.int32)
    return st._replace(
        tag=i32(rng.integers(1, 1000, size=G)),
        bal=i32(bal),
        exec_slot=i32(exec_slot),
        acc_bal=i32(acc_bal),
        acc_vid=i32(rng.integers(1, 2 ** 20, size=(G, W))),
        acc_slot=i32(acc_slot),
        dec_vid=i32(rng.integers(1, 2 ** 20, size=(G, W))),
        dec_slot=i32(lane_slots(0.3)),
        c_phase=i32(c_phase),
        c_bal=i32(rng.integers(0, 2 ** 24, size=G)),
        c_prop_vid=i32(rng.integers(1, 2 ** 20, size=(G, W))),
        c_prop_slot=i32(lane_slots(0.3)),
    )


def legacy_blob_planes(st: EngineState) -> dict:
    """What the pre-compact all-int32 blob shipped (absolute planes,
    phase-masked) — the round-trip oracle."""
    preparing = np.asarray(st.c_phase) == PREPARING
    active = np.asarray(st.c_phase) == ACTIVE
    act2 = active[:, None]
    return {
        "acc_bal": np.asarray(st.acc_bal),
        "acc_vid": np.asarray(st.acc_vid),
        "acc_slot": np.asarray(st.acc_slot),
        "dec_vid": np.asarray(st.dec_vid),
        "dec_slot": np.asarray(st.dec_slot),
        "prep_bal": np.where(preparing, st.c_bal, NULL),
        "prop_bal": np.where(active, st.c_bal, NULL),
        "prop_vid": np.where(act2, st.c_prop_vid, NULL),
        "prop_slot": np.where(act2, st.c_prop_slot, NULL),
    }


def representable(slot, exec_slot) -> np.ndarray:
    e = np.asarray(exec_slot)[:, None] >> KBITS
    d = (np.asarray(slot) >> KBITS) - e
    return (np.asarray(slot) != NULL) & (d >= -WRAP_MAX) & (d <= WRAP_MAX)


def test_roundtrip_random_states():
    rng = np.random.default_rng(7)
    for _ in range(20):
        st = random_state(rng)
        ex = expand_blob(make_blob(st))
        ref = legacy_blob_planes(st)

        np.testing.assert_array_equal(ex.tag, st.tag)
        np.testing.assert_array_equal(ex.bal, st.bal)
        np.testing.assert_array_equal(ex.exec_slot, st.exec_slot)
        np.testing.assert_array_equal(ex.prep_bal, ref["prep_bal"])
        np.testing.assert_array_equal(ex.prop_bal, ref["prop_bal"])

        # accepted lanes: slot in window AND ballot delta in [0, DELTA_MAX]
        delta = np.asarray(st.bal)[:, None] - ref["acc_bal"]
        a_ok = (
            representable(ref["acc_slot"], st.exec_slot)
            & (ref["acc_bal"] != NULL) & (delta >= 0) & (delta <= DELTA_MAX)
        )
        for got, want in (
            (ex.acc_slot, ref["acc_slot"]),
            (ex.acc_bal, ref["acc_bal"]),
            (ex.acc_vid, ref["acc_vid"]),
        ):
            np.testing.assert_array_equal(
                np.asarray(got), np.where(a_ok, want, NULL)
            )

        d_ok = representable(ref["dec_slot"], st.exec_slot)
        np.testing.assert_array_equal(
            np.asarray(ex.dec_slot), np.where(d_ok, ref["dec_slot"], NULL)
        )
        np.testing.assert_array_equal(
            np.asarray(ex.dec_vid), np.where(d_ok, ref["dec_vid"], NULL)
        )

        p_ok = representable(ref["prop_slot"], st.exec_slot)
        np.testing.assert_array_equal(
            np.asarray(ex.prop_slot), np.where(p_ok, ref["prop_slot"], NULL)
        )
        np.testing.assert_array_equal(
            np.asarray(ex.prop_vid), np.where(p_ok, ref["prop_vid"], NULL)
        )


def test_wrap_and_delta_boundaries():
    """Exactly-representable extremes survive; one past each NULLs."""
    st = init_state(CFG)
    exec_slot = (WRAP_MAX + 2) * 2 * W  # epoch base with room both ways
    ebase = exec_slot >> KBITS
    cases = [  # (epoch delta, bal delta, survives?)
        (0, 0, True),
        (WRAP_MAX, 0, True),
        (-WRAP_MAX, 0, True),
        (WRAP_MAX + 1, 0, False),
        (-(WRAP_MAX + 1), 0, False),
        (0, DELTA_MAX, True),
        (0, DELTA_MAX + 1, False),
    ]
    bal = DELTA_MAX + 7
    for eps, bd, survives in cases:
        lane = 3
        slot = ((ebase + eps) << KBITS) | lane
        s = st._replace(
            tag=st.tag.at[:].set(1),
            bal=st.bal.at[0].set(bal),
            exec_slot=st.exec_slot.at[0].set(exec_slot),
            acc_slot=st.acc_slot.at[0, lane].set(slot),
            acc_bal=st.acc_bal.at[0, lane].set(bal - bd),
            acc_vid=st.acc_vid.at[0, lane].set(42),
            dec_slot=st.dec_slot.at[0, lane].set(slot),
            dec_vid=st.dec_vid.at[0, lane].set(43),
        )
        ex = expand_blob(make_blob(s))
        if survives:
            assert int(ex.acc_slot[0, lane]) == slot, (eps, bd)
            assert int(ex.acc_bal[0, lane]) == bal - bd, (eps, bd)
            assert int(ex.acc_vid[0, lane]) == 42, (eps, bd)
            assert int(ex.dec_slot[0, lane]) == slot, (eps, bd)
        else:
            assert int(ex.acc_slot[0, lane]) == NULL, (eps, bd)
            assert int(ex.acc_bal[0, lane]) == NULL, (eps, bd)
            assert int(ex.acc_vid[0, lane]) == NULL, (eps, bd)
            if abs(eps) > WRAP_MAX:
                assert int(ex.dec_slot[0, lane]) == NULL, (eps, bd)


def test_coord_word_phases():
    st = init_state(CFG)
    st = st._replace(
        c_phase=jnp.asarray([IDLE, PREPARING, ACTIVE, IDLE, PREPARING,
                             ACTIVE], jnp.int32),
        c_bal=jnp.asarray([5, 6, 7, 8, 9, 10], jnp.int32),
    )
    ex = expand_blob(make_blob(st))
    np.testing.assert_array_equal(
        np.asarray(ex.prep_bal), [NULL, 6, NULL, NULL, 9, NULL]
    )
    np.testing.assert_array_equal(
        np.asarray(ex.prop_bal), [NULL, NULL, 7, NULL, NULL, 10]
    )


def test_wire_frame_roundtrip_and_version_skew():
    from gigapaxos_tpu.net.codec import (
        decode_blob,
        decode_blob_vec,
        encode_blob,
        encode_blob_vec,
    )

    st = random_state(np.random.default_rng(3))
    blob = make_blob(st)
    sender, tick, back = decode_blob(encode_blob(1, 9, blob), CFG)
    assert (sender, tick) == (1, 9)
    for a, b in zip(blob, back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    vec = np.asarray(pack_blob(blob))
    assert vec.shape == (blob_vec_len(CFG),)
    s2, t2, v2 = decode_blob_vec(encode_blob_vec(2, 11, vec), CFG)
    assert (s2, t2) == (2, 11)
    np.testing.assert_array_equal(v2, vec)

    # a stale-schema frame (pre-compact 'C' / pre-tag 'B') must be refused
    # loudly, never parsed misaligned
    stale = b"C" + encode_blob_vec(2, 11, vec)[1:]
    with pytest.raises(ValueError, match="schema"):
        decode_blob_vec(stale, CFG)
    with pytest.raises(ValueError, match="schema"):
        decode_blob(b"B" + encode_blob(1, 9, blob)[1:], CFG)

    # the row-delta frame is a kind of its own ('d': rows of a 'D'
    # vector): each decoder refuses the other's frames by kind, and a
    # delta of another schema is refused like a full frame of one
    from gigapaxos_tpu.net.codec import decode_blob_delta, encode_blob_frame

    vec2 = vec.copy()
    vec2[1] += 1  # row 1 of the first [G] leaf
    delta, rows = encode_blob_frame(2, CFG, (12, vec2), (11, vec))
    assert delta[:1] == b"d" and rows == 1
    s3, t3, base_tick, idx, _leaves = decode_blob_delta(delta, CFG)
    assert (s3, t3, base_tick, idx.tolist()) == (2, 12, 11, [1])
    with pytest.raises(ValueError, match="schema"):
        decode_blob_vec(delta, CFG)
    with pytest.raises(ValueError, match="schema"):
        decode_blob_delta(encode_blob_vec(2, 11, vec), CFG)
    with pytest.raises(ValueError, match="schema"):
        decode_blob_delta(b"e" + delta[1:], CFG)


@pytest.mark.parametrize("kind", [b"B", b"C", b"e", b"E"])
def test_server_drops_blob_frames_of_another_schema_by_kind(kind):
    """The ingress speaks 'D' (whole vector), 'd' (row delta) and 'J':
    a blob of any other kind byte is dropped before it is parsed, warned
    about once, and leaves nothing held of that sender."""
    from gigapaxos_tpu.net.codec import encode_blob_frame, encode_blob_vec
    from tests.test_blob_delta import receiver

    srv, _sent = receiver(CFG)
    try:
        vec = np.asarray(pack_blob(make_blob(
            random_state(np.random.default_rng(4)))))
        full = encode_blob_vec(0, 5, vec)
        delta, _rows = encode_blob_frame(0, CFG, (6, vec.copy()), (5, vec))
        for frame in (full, delta, full):
            srv._on_message(kind + frame[1:], ("127.0.0.1", 0),
                            lambda b: None)
        assert 0 not in srv._peer_blobs
        assert srv.manager.metrics.get("blob_frames_received") == 0
        assert srv.manager.metrics.get("blob_base_mismatch") == 0
        assert srv._schema_skew_warned == {kind.decode()}
        # and the two kinds it speaks get through
        for frame in (full, delta):
            srv._on_message(frame, ("127.0.0.1", 0), lambda b: None)
        assert srv._peer_blob_tick[0] == 6
        assert srv.manager.metrics.get("blob_frames_received") == 2
    finally:
        srv.manager.close()


def test_footprint_reduction_at_headline_shape():
    """The acceptance-criterion assert: compact blob bytes/replica at the
    headline bench shape are >= 40% below the all-int32 layout (pure
    arithmetic — runs on CPU, no TPU needed)."""
    cfg = EngineConfig(
        n_groups=1_048_576, window=32, req_lanes=16, n_replicas=3
    )
    compact = 4 * blob_vec_len(cfg)
    legacy = 4 * legacy_blob_vec_len(cfg)
    assert compact <= 0.60 * legacy, (compact, legacy)


def test_footprint_probe_script_runs():
    """CI hook for the budget: the probe prints one JSON line whose
    reduction field clears the 40% floor."""
    import json

    root = Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(root / "scripts" / "footprint_probe.py")],
        capture_output=True, text=True, timeout=120, check=True,
    )
    rec = json.loads(out.stdout.strip())
    assert rec["blob_reduction_pct"] >= 40.0, rec
    assert rec["blob_bytes_per_replica"] == 4 * blob_vec_len(
        EngineConfig(n_groups=1_048_576, window=32, req_lanes=16,
                     n_replicas=3)
    )


@pytest.mark.slow
def test_safety_parity_across_many_ring_wraps():
    """Long-run cluster: commit far past the ±WRAP_MAX epoch window so
    live traffic exercises wrap deltas at every bias repeatedly, then
    re-assert the RSM invariant and exact committed order — the compact
    path must be invisible at the safety level."""
    c = SimCluster(CFG)
    c.create_all_groups()
    vid = 1
    sent = []
    # (WRAP_MAX * 4) epochs of slots through group 0
    target = WRAP_MAX * 4 * W
    while True:
        arr = np.full((G, K), NULL, np.int32)
        vids = list(range(vid, vid + K))
        arr[0, :] = vids
        out = c.step_all(reqs={c.coordinator_of(0): arr})
        n = int(np.asarray(out[c.coordinator_of(0)].n_admitted)[0])
        sent.extend(vids[:n])
        vid += K
        if len(sent) >= target:
            break
    c.run(8)
    fr = c.exec_frontiers()
    assert (fr[:, 0] == fr[0, 0]).all(), fr
    assert int(fr[0, 0]) >= target
    c.assert_rsm_invariant()
    committed = [c.checker.chosen[(0, s)] for s in range(int(fr[0, 0]))]
    assert committed == sent[: len(committed)]
