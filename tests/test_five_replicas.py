"""Five replicas a name (PR 43): what a group of five does that a group
of three never shows, on the stepped harness (``testing/cluster.py``,
``n_replicas=5``) against the sequential model of the adder, and one
step's majority arithmetic against a plain numpy restatement.

(a) writes commit and read back equal on all five; (b) with TWO of five
silent writes still commit and the three agree, with THREE silent nothing
new is acknowledged, and when they return every acknowledged write is on
all five exactly once; (c) a coordinator that dies with an accept on
exactly ONE peer: the election among the other four carries the value,
the same on every replica, and what was acknowledged stays; (d)
``maj_exec`` and the learn pass of one step on seeded random inputs
against sort-and-count, at R = 3, 4 and 5; (e) the step's two quorum sums
(``decisions_detected`` / ``accepts_at_detection``): a slot accepted by
exactly three of five reads 3, by all five reads 5, and is counted once.
"""

import numpy as np
import pytest

from gigapaxos_tpu.models.apps import StatefulAdderApp
from gigapaxos_tpu.ops.ballot import NULL, encode_ballot
from gigapaxos_tpu.ops.engine import (
    EngineConfig,
    init_state,
    make_blob,
    step_counted,
)
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster

CFG = EngineConfig(n_groups=16, window=8, req_lanes=4, n_replicas=5)
NAMES = ["n%d" % i for i in range(6)]


def cut_off(silent, R=5):
    """The delivery matrix in which the ``silent`` replicas neither hear
    nor are heard (they keep stepping, alone)."""
    d = np.full((R, R), DELIVER)
    for s in silent:
        d[s, :] = d[:, s] = DROP
        d[s, s] = DELIVER
    return d


class Model:
    """The sequential adder: per name the sum of acknowledged deltas,
    and every answer the running total at its turn."""

    def __init__(self, cluster):
        self.c, self.sent, self.acked, self.next_id = cluster, {}, {}, 1000

    def write(self, name, delta, entry):
        rid = self.next_id = self.next_id + 1
        self.sent[rid] = (name, delta)
        self.c.managers[entry].propose(
            name, str(delta), request_id=rid,
            callback=lambda r, resp: self.acked.setdefault(r, resp))
        return rid

    def totals(self):
        out = {}
        for rid in self.acked:
            name, delta = self.sent[rid]
            out[name] = out.get(name, 0) + delta
        return out

    def assert_answers_are_running_totals(self):
        """Each name's answers, sorted, are the partial sums of its
        deltas in SOME order: no delta twice, none lost."""
        by_name = {}
        for rid, resp in self.acked.items():
            name, delta = self.sent[rid]
            by_name.setdefault(name, []).append((int(resp), delta))
        for name, pairs in by_name.items():
            pairs.sort()
            assert pairs[-1][0] == sum(d for _, d in pairs), (name, pairs)
            assert len({t for t, _ in pairs}) == len(pairs), (name, pairs)


@pytest.fixture
def cluster():
    c = ManagerCluster(CFG, StatefulAdderApp)
    for name in NAMES:
        c.create(name)
    c.run(6)  # every name elects its coordinator
    yield c
    c.close()


def app_totals(c, replicas=range(5)):
    return [{n: c.managers[r].app.totals.get(n, 0) for n in NAMES}
            for r in replicas]


def test_writes_commit_and_read_back_equal_on_all_five(cluster):
    c, rng = cluster, np.random.default_rng(43)
    model = Model(c)
    for k in range(40):  # entry round-robin: four of five are forwarded
        name = NAMES[int(rng.integers(len(NAMES)))]
        model.write(name, int(rng.integers(1, 100)), entry=k % 5)
        if k % 4 == 3:
            c.run(2)
    c.run(20)
    assert len(model.acked) == 40
    want = {n: model.totals().get(n, 0) for n in NAMES}
    assert app_totals(c) == [want] * 5
    model.assert_answers_are_running_totals()
    # a majority of five is three, on every row that holds a name
    m = c.managers[0]
    rows = [m.names[n] for n in NAMES]
    assert set(np.asarray(m.state.majority)[rows]) == {3}
    assert not (np.diff(c.app_exec()[:, rows], axis=0) != 0).any()


def test_two_silent_commit_three_silent_do_not(cluster):
    c = cluster
    model = Model(c)
    lead = {n: c.managers[0].coordinator_of_row(c.managers[0].names[n])
            for n in NAMES}
    # --- two of five silent: a name whose coordinator is among the
    # three that hear each other still commits, on those three
    silent = [3, 4]
    alive = [0, 1, 2]
    served = [n for n in NAMES if lead[n] in alive]
    assert served, lead
    first = [model.write(n, 10 + i, entry=alive[i % 3])
             for i, n in enumerate(served)]
    c.run(14, delivery=cut_off(silent))
    assert set(first) <= set(model.acked)
    want = {n: model.totals().get(n, 0) for n in NAMES}
    assert app_totals(c, alive) == [want] * 3
    assert all(t[n] == 0 for t in app_totals(c, silent) for n in served)
    # --- a third goes silent: two of five decide nothing (names whose
    # coordinator still hears the entry: a forward into the silence would
    # be lost, and this harness has no client to send it again)
    acked_before = dict(model.acked)
    silent, alive = [2, 3, 4], [0, 1]
    still_led = [n for n in served if lead[n] in alive]
    assert still_led, lead
    second = [model.write(n, 100 + i, entry=alive[i % 2])
              for i, n in enumerate(still_led)]
    c.run(14, delivery=cut_off(silent))
    assert model.acked == acked_before
    assert app_totals(c, [0, 1, 2]) == [want] * 3
    # --- they return: everything acknowledged is on all five, once
    c.run(40)
    assert set(first) | set(second) <= set(model.acked)
    want = {n: model.totals().get(n, 0) for n in NAMES}
    assert app_totals(c) == [want] * 5
    model.assert_answers_are_running_totals()


def test_a_value_accepted_by_one_peer_survives_its_coordinator(cluster):
    """The coordinator proposes, ONE peer hears it and accepts, and the
    coordinator is never heard again.  Two accepts of five decide
    nothing; the election among the other four has the accepting peer
    among its promisers, so the new coordinator carries the value into
    its own ballot (three replicas never show this: there one peer's
    accept IS the majority).  Decided once, the same everywhere."""
    c = cluster
    model = Model(c)
    name = NAMES[0]
    row = c.managers[0].names[name]
    old = c.managers[0].coordinator_of_row(row)
    others = [r for r in range(5) if r != old]
    peer, new = others[0], others[1]
    before = model.write(name, 5, entry=old)   # acknowledged: must stay
    c.run(8)
    assert before in model.acked
    rid = model.write(name, 7, entry=old)
    # only `peer` hears the coordinator, and nobody hears `peer`
    d = np.full((5, 5), DROP)
    np.fill_diagonal(d, DELIVER)
    d[peer, old] = DELIVER
    c.step_all(delivery=d)   # staged and accepted by the coordinator
    c.step_all(delivery=d)   # accepted by the one peer
    acc = [np.asarray(m.state.acc_vid)[row] for m in c.managers]
    vid = int(acc[old][acc[old] > 0].max())
    holders = [r for r in range(5) if (acc[r] == vid).any()]
    assert holders == sorted([old, peer]), holders
    assert rid not in model.acked
    # the coordinator is gone for good; the four elect `new`
    gone = cut_off([old])
    want = np.zeros(CFG.n_groups, bool)
    want[row] = True
    c.managers[new].note_election(want)  # as a node's tick does: the
    #   wave's account counts what the winner carries
    c.step_all(delivery=gone, want_coord={new: want})
    c.run(12, delivery=gone)
    assert all(c.managers[r].coordinator_of_row(row) == new for r in others)
    assert c.managers[new].metrics.snapshot()["counters"][
        "pvalues_carried_over"] == 1
    # carried and decided among the four: 5 + 7 on each of them, once
    assert [t[name] for t in app_totals(c, others)] == [12] * 4
    # the dead one returns and catches up to the same
    c.run(30)
    assert [t[name] for t in app_totals(c)] == [12] * 5
    assert model.acked[before] == "5"
    # whoever holds the callback answers with the total at its turn
    assert model.acked.get(rid, "12") == "12"


# ---------------------------------------------------------------------------
# (d), (e): one step against plain numpy
# ---------------------------------------------------------------------------
def random_round(R, seed, G=32, W=8):
    """R replicas' states of one name a row, all members of every row:
    random frontiers, and per lane a random subset of the replicas
    holding an accept of one of two ballots for the lane's slot in the
    window above the LOWEST frontier.  Returns (cfg, states, planes as
    numpy: exec [R, G], acc_slot / acc_bal / acc_vid [R, G, W])."""
    rng = np.random.default_rng(seed)
    cfg = EngineConfig(n_groups=G, window=W, req_lanes=4, n_replicas=R)
    exec_slot = rng.integers(0, 3 * W, (R, G)).astype(np.int32)
    base = exec_slot.min(axis=0)                              # [G]
    lanes = np.arange(W)
    # the slot with residue `lane` in [base, base + W)
    slot = base[:, None] + ((lanes[None, :] - base[:, None]) % W)
    bals = np.array([encode_ballot(1, 0), encode_ballot(2, 1)], np.int32)
    which = rng.integers(0, 2, (R, G, W))
    holds = rng.random((R, G, W)) < 0.55
    acc_slot = np.where(holds, slot[None], NULL).astype(np.int32)
    acc_bal = np.where(holds, bals[which], NULL).astype(np.int32)
    # one value a (slot, ballot): the ballot's coordinator proposed it
    acc_vid = np.where(holds, 1000 * (which + 1) + slot[None] % 997 + 1,
                       NULL).astype(np.int32)
    states = []
    for r in range(R):
        st = init_state(cfg)._replace(
            member_mask=np.full(G, (1 << R) - 1, np.int32),
            majority=np.full(G, R // 2 + 1, np.int32),
            tag=np.full(G, 7, np.int32),
            bal=np.full(G, bals.max(), np.int32),
            exec_slot=exec_slot[r], acc_slot=acc_slot[r],
            acc_bal=acc_bal[r], acc_vid=acc_vid[r])
        states.append(st)
    return cfg, states, (exec_slot, acc_slot, acc_bal, acc_vid)


def gathered(states):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[make_blob(s) for s in states])


def plain_majority(planes, heard, me, maj):
    """The restatement: per row the maj-th largest frontier among the
    replicas heard (sort, take it); per lane the lexicographically
    largest (slot, ballot) accepted among them, the count of replicas
    holding exactly it, and whether that count is a majority of the
    GROUP and the slot at or past MY frontier."""
    exec_slot, acc_slot, acc_bal, acc_vid = planes
    live = np.flatnonzero(heard)
    R, G, W = acc_slot.shape
    top = np.sort(exec_slot[live], axis=0)[::-1]
    maj_exec = top[maj - 1] if len(live) >= maj else np.zeros(G, np.int32)
    dec_slot = np.full((G, W), NULL, np.int32)
    dec_vid = np.full((G, W), NULL, np.int32)
    decisions = accepts = 0
    for g in range(G):
        for w in range(W):
            held = [(int(acc_slot[r, g, w]), int(acc_bal[r, g, w]),
                     int(acc_vid[r, g, w])) for r in live
                    if acc_slot[r, g, w] != NULL]
            if not held:
                continue
            best = max(held)[:2]
            n = sum(1 for h in held if h[:2] == best)
            if n >= maj and best[0] >= exec_slot[me, g]:
                dec_slot[g, w] = best[0]
                dec_vid[g, w] = max(h[2] for h in held if h[:2] == best)
                decisions += 1
                accepts += n
    return maj_exec, dec_slot, dec_vid, (decisions, accepts)


@pytest.mark.parametrize("R, seed", [(3, 1), (3, 2), (4, 3), (4, 4),
                                     (5, 5), (5, 6), (5, 7)])
def test_one_steps_majorities_against_sort_and_count(R, seed):
    """``maj_exec`` (the majority-rank frontier, an O(R^2) rank count on
    the device) and the learn pass's ``n_match >= majority`` against
    sorting and counting; majority 2 of 3, 3 of 4, 3 of 5.  One replica
    in the larger groups goes unheard, so the quorum is taken among
    those that are."""
    import jax.numpy as jnp

    cfg, states, planes = random_round(R, seed)
    maj, me = R // 2 + 1, seed % R
    heard = np.ones(R, bool)
    if R > 3:
        heard[(me + 1) % R] = False
    new, out, counted = step_counted(
        states[me], gathered(states), jnp.asarray(heard),
        jnp.full((cfg.n_groups, cfg.req_lanes), NULL, jnp.int32),
        jnp.zeros(cfg.n_groups, bool), me, cfg)
    maj_exec, dec_slot, dec_vid, sums = plain_majority(
        planes, heard, me, maj)
    assert np.array_equal(np.asarray(out.maj_exec), maj_exec)
    # the decision ring takes the count's slot (it held nothing, and no
    # peer's ring did); a lane executed in this very step keeps it too
    assert np.array_equal(np.asarray(new.dec_slot), dec_slot)
    assert np.array_equal(np.asarray(new.dec_vid), dec_vid)
    assert tuple(int(x) for x in counted) == sums and sums[0] > 0
    assert maj * sums[0] <= sums[1] <= int(heard.sum()) * sums[0]


@pytest.mark.parametrize("holders, reads", [((0, 2, 4), 3),
                                            ((0, 1, 2, 3), 4),
                                            ((0, 1, 2, 3, 4), 5)])
def test_the_quorum_sums_count_a_slot_once_with_its_accepts(holders, reads):
    """One slot of one row accepted by exactly ``holders`` of five: the
    step that first sees it reads (1, len(holders)); the next step, with
    the slot in the decision ring (executed or not), reads (0, 0); two
    holders of five are no majority and read nothing."""
    import jax.numpy as jnp

    R, G, W = 5, 4, 8
    cfg = EngineConfig(n_groups=G, window=W, req_lanes=4, n_replicas=R)
    bal = encode_ballot(1, 0)

    def state(holds, exec_at=0):
        st = init_state(cfg)
        plane = lambda v: np.where(
            (np.arange(G)[:, None] == 1) & (np.arange(W)[None] == 2) & holds,
            v, NULL).astype(np.int32)
        return st._replace(
            member_mask=np.full(G, 31, np.int32),
            majority=np.full(G, 3, np.int32), tag=np.full(G, 9, np.int32),
            bal=np.full(G, bal, np.int32),
            exec_slot=np.full(G, exec_at, np.int32),
            acc_slot=plane(2), acc_bal=plane(bal), acc_vid=plane(77))

    def run(states, me):
        return step_counted(
            states[me], gathered(states), jnp.ones(R, bool),
            jnp.full((G, cfg.req_lanes), NULL, jnp.int32),
            jnp.zeros(G, bool), me, cfg)

    states = [state(r in holders) for r in range(R)]
    me = 1  # a member that may not hold the accept itself: it counts too
    new, _out, counted = run(states, me)
    assert tuple(int(x) for x in counted) == (1, reads)
    assert int(np.asarray(new.dec_slot)[1, 2]) == 2
    # slots 0 and 1 are undecided, so slot 2 waits in the ring: the same
    # blobs again count nothing
    states[me] = new
    _new, _out, again = run(states, me)
    assert tuple(int(x) for x in again) == (0, 0)
    # a bare minority decides nothing and counts nothing
    few = [state(r in holders[:2]) for r in range(R)]
    new, _out, counted = run(few, me)
    assert tuple(int(x) for x in counted) == (0, 0)
    assert (np.asarray(new.dec_slot) == NULL).all()
    # a non-member of the row counts nothing of it
    out_of_it = [s._replace(member_mask=np.full(G, 31 & ~(1 << 3), np.int32))
                 for s in states]
    _new, _out, counted = run(out_of_it, 3)
    assert tuple(int(x) for x in counted) == (0, 0)


def test_the_manager_adds_the_sums_to_its_two_counters(cluster):
    """Registered at 0; after traffic every replica has seen decisions,
    each at three accepts of five at the least and five at the most."""
    c = cluster
    fresh = ManagerCluster(CFG, StatefulAdderApp)
    zero = fresh.managers[0].metrics.snapshot()["counters"]
    fresh.close()
    assert zero["decisions_detected"] == 0 == zero["accepts_at_detection"]
    model = Model(c)
    for k in range(10):
        model.write(NAMES[k % len(NAMES)], 1, entry=k % 5)
    c.run(16)
    assert len(model.acked) == 10
    for m in c.managers:
        got = m.metrics.snapshot()["counters"]
        n, a = got["decisions_detected"], got["accepts_at_detection"]
        assert n >= 1 and 3 * n <= a <= 5 * n, (m.my_id, n, a)
