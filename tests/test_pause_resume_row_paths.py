"""A pause frees rows and a batched resume restores them under the row
paths of PRs 26, 28 and 30: the freed and the restored rows must reach
the peers' device stacks (``ops/engine.py:scatter_update``), the delta
frames' bases (what a receiver holds of each sender) and the step's
digest exactly as they would in a cluster that never slept.

Two stepped manager clusters run the same seeded writes, pipelined as a
node serves (``step_dispatch`` / ``step_complete``).  In one of them
three of six names are paused on every replica, sit out some rounds with
their rows free, and come back through ONE ``resume_group_batch`` each.
Afterwards, bit for bit: every acknowledgement; and, once every lane of
the window has been written again (a restored row carries no executed
lane, a row that never slept keeps its old ones until they are
overwritten), every digest a step handed its host, the whole publish
vectors, what each receiver holds of each sender, and each device stack
against the vectors it was fed."""

import numpy as np
import pytest

from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig, split_blob_vec
from gigapaxos_tpu.testing.cluster import ManagerCluster

CFG = EngineConfig(n_groups=64, window=8, req_lanes=4, n_replicas=3)
NAMES = [f"p{i}" for i in range(6)]
SLEEPERS = NAMES[:3]


class Twin:
    def __init__(self):
        self.c = ManagerCluster(CFG, StatefulAdderApp)
        self.c.pipelined = True
        self.rows = {n: self.c.create(n) for n in NAMES}
        self.acks = []
        self.digests = []       # every digest a step handed its host
        for m in self.c.managers:
            whole = m._complete_locked

            def spy(pend, digest_np, *news, _whole=whole):
                self.digests.append(np.array(digest_np))
                return _whole(pend, digest_np, *news)

            m._complete_locked = spy

    def write(self, rng, names, rounds):
        """Seeded deltas, each at its name's coordinator: the ids a
        manager mints then depend on the order of these calls alone."""
        m0 = self.c.managers[0]
        for _ in range(rounds):
            for name in names:
                entry = m0.coordinator_of_row(self.rows[name])
                self.c.managers[entry].propose(
                    name, str(int(rng.integers(1, 1000))),
                    callback=lambda rid, resp, n=name: self.acks.append(
                        (n, resp)))
            self.c.run(8)

    def close(self):
        self.c.close()


@pytest.fixture
def twins():
    slept, awake = Twin(), Twin()
    yield slept, awake
    slept.close()
    awake.close()


def live_leaves(vec, rows):
    """The publish vector's leaves, the named rows only."""
    return [np.asarray(leaf)[rows] for leaf in split_blob_vec(vec, CFG)]


def test_pause_and_batched_resume_leave_the_row_paths_as_if_never_slept(twins):
    slept, awake = twins
    for t in twins:
        t.write(np.random.default_rng(7), NAMES, 3)
    assert slept.acks == awake.acks and len(slept.acks) == 18

    # ---- the sleep: rows freed on every replica, then rounds without them
    for m in slept.c.managers:
        for name in SLEEPERS:
            assert m.pause_group(name, 0) == "ok"
        assert all(n not in m.names for n in SLEEPERS)
    slept.c.republish()
    for t in twins:
        t.write(np.random.default_rng(8), NAMES[3:], 2)
    freed = [slept.rows[n] for n in SLEEPERS]
    for i, m in enumerate(slept.c.managers):
        # the freed rows reached each peer's device stack as freed
        stack = m.gathered_host()
        for j in range(3):
            for leaf_s, leaf_v in zip(live_leaves(stack[j], freed),
                                      live_leaves(slept.c.vecs[j], freed)):
                assert np.array_equal(leaf_s, leaf_v), (i, j)

    # ---- the wake: one fused restore a replica, same rows
    for m in slept.c.managers:
        out = m.resume_group_batch([
            (n, 0, [0, 1, 2], slept.rows[n], False) for n in SLEEPERS])
        assert out == {n: True for n in SLEEPERS}
        assert m.metrics.get("names_woken_batched") == 3
    slept.c.republish()
    for t in twins:                         # every lane written again
        t.write(np.random.default_rng(9), NAMES, CFG.window)
    n_before = len(slept.digests)
    assert n_before == len(awake.digests)
    for t in twins:
        t.write(np.random.default_rng(10), NAMES, 2)
        t.c.run(6)                          # idle rounds: the vectors settle

    # every acknowledgement: the running sums came from the restored state
    assert slept.acks == awake.acks \
        and len(slept.acks) == 18 + 6 + 6 * (CFG.window + 2)
    for i in range(3):
        ms, ma = slept.c.managers[i], awake.c.managers[i]
        assert ms.app.totals == ma.app.totals
        # the publish vectors, whole: frontiers, ballots, what is
        # accepted and decided in every row
        assert np.array_equal(slept.c.vecs[i], awake.c.vecs[i]), i
        for j in range(3):
            if i == j:
                continue
            # the delta frames' base: what receiver i holds of sender j
            # is what j published — in both clusters, and the same
            assert np.array_equal(slept.c._held[i][j], slept.c.vecs[j])
            assert np.array_equal(awake.c._held[i][j], awake.c.vecs[j])
        # the device stack against the vectors it was fed, whole
        stack_s, stack_a = ms.gathered_host(), ma.gathered_host()
        for j in range(3):
            assert np.array_equal(stack_s[j], slept.c.vecs[j]), (i, j)
            assert np.array_equal(stack_a[j], awake.c.vecs[j]), (i, j)
    # the digests of every step since, one for one
    since = slept.digests[n_before:]
    assert len(since) == len(awake.digests[n_before:]) > 30
    for k, (a, b) in enumerate(zip(since, awake.digests[n_before:])):
        assert np.array_equal(a, b), k
    assert all(m.metrics.get("gather_updates_whole") <= 6
               for m in slept.c.managers)
