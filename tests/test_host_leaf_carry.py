"""A lifecycle operation patches the host's copies, it does not throw
them away (PR 46): the manager's leaf cache, its index of member rows
and the failure detector's standing election mask follow a create, a
kill, a pause, a restore and a jump BY ROWS — and must equal what the
device holds bit for bit after every one of them, because an election
mask or an admission check read from a stale ``member_mask`` is a safety
fault, not a slowdown.

(a) random sequences of lifecycle operations and steps on stepped
    256-row clusters at R = 3 and R = 5, every host copy held to the
    device's leaf after every operation;
(b) counts: what a restore, a liveness change and a pause cost in whole
    leaves pulled and in [G] passes of the detector, with the old
    whole-leaf record reader kept here as the reference;
(c) readers on other threads during lifecycle calls.
"""

import sys
import threading

import numpy as np
import pytest

from gigapaxos_tpu.failure_detection import FailureDetector
from gigapaxos_tpu.models import StatefulAdderApp
from gigapaxos_tpu.ops.engine import EngineConfig, EngineState
from gigapaxos_tpu.ops.lifecycle import ROW_LEAVES, ROW_PLANES
from gigapaxos_tpu.packets.paxos_packets import StatePacket
from gigapaxos_tpu.testing.cluster import ManagerCluster

LEAVES = EngineState._fields
INF = float("inf")


def device(m, leaf):
    return np.asarray(getattr(m.state, leaf))


def assert_host_is_device(m, fd, where):
    """Every cached leaf, the member-row index and the detector's
    standing answer against the device's leaves."""
    R = m.cfg.n_replicas
    with m._state_lock:
        m._await_step_locked()
        cache = m._np_cache_locked()
        assert m._np_cache_state is m.state
        for leaf, arr in cache.items():
            np.testing.assert_array_equal(
                arr, device(m, leaf), err_msg=f"{where}: {leaf}")
        for row, words in m._row_words.items():  # rows read as rows
            for leaf, word in words.items():
                np.testing.assert_array_equal(
                    word, device(m, leaf)[row],
                    err_msg=f"{where}: row {row} {leaf}")
        blocks = m._member_rows_locked()
        mask = device(m, "member_mask")
        assert all(0 < rows.size <= m.ROWS_A_PASS for rows, _b in blocks)
        rows = np.concatenate([r for r, _b in blocks] or [np.zeros(0, int)])
        np.testing.assert_array_equal(
            rows, np.flatnonzero(mask), err_msg=f"{where}: member rows")
        bits = np.concatenate(
            [b for _r, b in blocks] or [np.zeros((R, 0), bool)], axis=1)
        np.testing.assert_array_equal(
            bits, m._member_bits(mask[rows]), err_msg=f"{where}: members")
        bal, mask_h, changed = m.election_inputs()
        want = fd.want_coord(bal, mask_h, R, changed)
    cold = FailureDetector(m.my_id, range(R), timeout_s=INF)
    cold.last_heard = dict(fd.last_heard)
    np.testing.assert_array_equal(
        want, cold.want_coord(device(m, "bal"), mask, R),
        err_msg=f"{where}: want_coord")


def whole_leaf_record(m, name, epoch, row):
    """The pause record as the reader before PR 46 made it: every leaf
    whole from the device, the row's words taken on the host."""
    leaf = lambda k: device(m, k)[row]
    exec_now = int(leaf("exec_slot"))
    acc, dec = [], []
    for lane in range(m.cfg.window):
        if int(leaf("acc_slot")[lane]) >= exec_now:
            acc.append([int(leaf("acc_slot")[lane]),
                        int(leaf("acc_bal")[lane]),
                        int(leaf("acc_vid")[lane])])
        if int(leaf("dec_slot")[lane]) >= exec_now:
            dec.append([int(leaf("dec_slot")[lane]),
                        int(leaf("dec_vid")[lane])])
    return {
        "name": name, "epoch": epoch, "exec": exec_now,
        "bal": int(leaf("bal")), "app_hash": int(leaf("app_hash")),
        "n_execd": int(leaf("n_execd")),
        "app_state": m.app.checkpoint(name),
        "app_exec": int(m.app_exec_slot[row]), "acc": acc, "dec": dec,
        "dedup": m._executed.of_name(name),
        "members": m.get_replica_group(name),
    }


class Drive:
    """Random lifecycle operations, the same on every replica of a
    stepped cluster (a node's peers run them too), and steps."""

    def __init__(self, R, seed, rows_a_pass):
        self.cfg = EngineConfig(n_groups=256, window=8, req_lanes=4,
                                n_replicas=R)
        self.c = ManagerCluster(self.cfg, StatefulAdderApp)
        self.rng = np.random.default_rng(seed)
        self.live = {}      # name -> (epoch, members, row)
        self.asleep = {}    # name -> (epoch, members)
        self.n = 0
        for m in self.c.managers:
            m.ROWS_A_PASS = rows_a_pass  # 256 rows must still split blocks
            m.FRONTIER_STALLED_TICKS = 2  # op_jump's stalled rows

    def prime(self):
        """Leaves into the caches, so the operation has copies to keep
        right: every leaf now and then, a few otherwise."""
        for m in self.c.managers:
            some = LEAVES if self.rng.random() < 0.3 else self.rng.choice(
                LEAVES, 4, replace=False)
            for leaf in some:
                m._np(str(leaf))
            for name in sorted(self.live)[:2]:
                m.is_stopped(name)  # rows' own words, held while they stand

    def members(self):
        R = self.cfg.n_replicas
        k = int(self.rng.integers(2, R + 1))
        return sorted(self.rng.choice(R, k, replace=False).tolist())

    def op_create(self):
        name = f"n{self.n}"
        self.n += 1
        members = self.members()
        row = self.c.managers[0].default_row_for(name)
        epoch = int(self.rng.integers(0, 3))
        for m in self.c.managers:
            assert m.create_paxos_instance(
                name, members, version=epoch, row=row)
        self.live[name] = (epoch, members, row)

    def op_create_batch(self):
        names = [f"n{self.n + i}" for i in range(int(self.rng.integers(2, 9)))]
        self.n += len(names)
        members = self.members()
        for m in self.c.managers:
            assert m.create_paxos_batch(names, members) == len(names)
        for name in names:
            self.live[name] = (0, members, self.c.managers[0].names[name])

    def pick(self, table):
        return str(self.rng.choice(sorted(table))) if table else None

    def op_kill(self):
        name = self.pick(self.live)
        if name:
            for m in self.c.managers:
                assert m.kill(name)
            del self.live[name]

    def op_pause(self):
        name = self.pick(self.live)
        if name:
            epoch, members, row = self.live[name]
            force = bool(self.rng.random() < 0.5)
            for m in self.c.managers:
                ref = whole_leaf_record(m, name, epoch, row)
                if m.pause_group(name, epoch, force=force) != "ok":
                    # busy: everybody sleeps, by force
                    assert m.pause_group(name, epoch, force=True) == "ok"
                self.same_record(m, name, epoch, ref)
            del self.live[name]
            self.asleep[name] = (epoch, members)

    @staticmethod
    def same_record(m, name, epoch, ref):
        """The record a pause made from its row's words against the
        whole-leaf reader's."""
        rec = dict(m.paused[(name, epoch)])
        for k in ("held_vids", "held_scopes"):
            rec.pop(k, None)
        assert rec == ref

    def op_pause_batch(self):
        names = sorted(self.live)[:int(self.rng.integers(2, 6))]
        items = [(n, self.live[n][0]) for n in names]
        for m in self.c.managers:
            refs = {n: whole_leaf_record(m, n, e, self.live[n][2])
                    for n, e in items}
            out = m.pause_group_batch(items)
            for name, epoch in items:
                if out[(name, epoch)] != "ok":
                    assert m.pause_group(name, epoch, force=True) == "ok"
                self.same_record(m, name, epoch, refs[name])
        for name, epoch in items:
            self.asleep[name] = (epoch, self.live.pop(name)[1])

    def op_resume(self):
        name = self.pick(self.asleep)
        if name:
            epoch, members = self.asleep.pop(name)
            row = self.c.managers[0].default_row_for(name)
            for m in self.c.managers:
                assert m.resume_group(name, epoch, members, row, False)
            self.live[name] = (epoch, members, row)

    def op_resume_batch(self):
        names = sorted(self.asleep)[:int(self.rng.integers(2, 12))]
        if not names:
            return
        claimed, items = set(), []
        m0 = self.c.managers[0]
        for name in names:
            epoch, members = self.asleep.pop(name)
            row = m0.default_row_for(name)
            while row in claimed or row in m0.row_name:
                row = (row + 1) % self.cfg.n_groups
            claimed.add(row)
            items.append((name, epoch, members, row, False))
            self.live[name] = (epoch, members, row)
        for m in self.c.managers:
            assert all(m.resume_group_batch(items).values())

    def op_jump(self):
        """A donor's snapshot past one replica's frontier: that replica
        jumps its row (``jump_rows``) — clear past its window, or one
        slot on a row whose frontier has stalled with accepts in
        flight, whose lanes at and past the new frontier the jump keeps
        (which lanes, only the device can say)."""
        name = self.pick(self.live)
        if name:
            epoch, members, row = self.live[name]
            m = self.c.managers[int(self.rng.choice(members))]
            near = m._tick_no > m.FRONTIER_STALLED_TICKS \
                and bool(self.rng.random() < 0.6)
            if near:
                for _ in range(4):  # accepted in two rounds, not decided
                    self.c.submit(name, "1", entry=m.coordinator_of_row(row))
                self.c.run(2)
                for leaf in LEAVES:
                    m._np(leaf)
                m._stall_since[row] = 0
                m._stall_slot[row] = int(device(m, "exec_slot")[row])
            ahead = int(device(m, "exec_slot")[row]) + (
                1 if near else self.cfg.window + int(self.rng.integers(0, 5)))
            m.on_host_message("state_reply", {"states": [StatePacket(
                paxos_id=name, version=epoch,
                ballot_num=int(self.rng.integers(0, 4)),
                ballot_coord=int(self.rng.choice(members)),
                slot=ahead, row=row, app_hash=int(self.rng.integers(1, 99)),
                n_execd=ahead, stopped=0, state=None,
            ).to_json()], "response_cache": {}})
            assert int(device(m, "exec_slot")[row]) == ahead

    def op_step(self):
        for name in sorted(self.live)[:int(self.rng.integers(0, 6))]:
            _e, members, _r = self.live[name]
            self.c.submit(name, "1", entry=members[0])
        self.c.run(int(self.rng.integers(1, 4)))

    def op_liveness(self):
        """A peer falls silent for a detector, or is heard again: the
        next rounds run elections, whose ballot rises are rows too."""
        i = int(self.rng.integers(0, self.cfg.n_replicas))
        fd = self.c._fds[i]
        peer = int(self.rng.choice(
            [r for r in range(self.cfg.n_replicas) if r != i]))
        fd.last_heard[peer] = None if fd.last_heard[peer] is not None \
            else 0.0

    def run(self, n_ops):
        ops = [self.op_create, self.op_create, self.op_create_batch,
               self.op_kill, self.op_pause, self.op_pause_batch,
               self.op_resume, self.op_resume_batch, self.op_jump,
               self.op_step, self.op_step, self.op_liveness]
        for k in range(n_ops):
            op = ops[int(self.rng.integers(0, len(ops)))]
            self.prime()
            op()
            self.c.republish()
            for i, m in enumerate(self.c.managers):
                assert_host_is_device(m, self.c._fds[i],
                                      f"op {k} {op.__name__} node {i}")


@pytest.mark.parametrize("R,seed,rows_a_pass", [(3, 11, 500), (3, 12, 8),
                                                (5, 13, 500), (5, 14, 8)])
def test_host_copies_equal_the_device_after_every_operation(
        R, seed, rows_a_pass):
    d = Drive(R, seed, rows_a_pass)
    try:
        d.run(70)
        # the run did carry and patch, and met every kind of operation
        mx = d.c.managers[0].metrics
        assert mx.get("host_leaf_carried") > 100
        assert mx.get("host_leaf_patched_rows") > 100
    finally:
        d.c.close()


# ---- (b) counts ---------------------------------------------------------
CFG = EngineConfig(n_groups=256, window=8, req_lanes=4, n_replicas=3)
NAMES = [f"c{i}" for i in range(6)]


@pytest.fixture
def cluster():
    c = ManagerCluster(CFG, StatefulAdderApp)
    for i, fd in enumerate(c._fds):
        fd.metrics = c.managers[i].metrics
    rows = {n: c.create(n) for n in NAMES}
    for name in NAMES:
        c.submit(name, "5", entry=c.managers[0].coordinator_of_row(rows[name]))
    c.run(8)
    yield c, rows
    c.close()


def counts(m):
    keys = ("host_leaf_pulls", "host_leaf_pull_bytes", "want_coord_full",
            "want_coord_patched_rows", "lifecycle_row_reads")
    return {k: m.metrics.get(k) or 0 for k in keys}


def delta(m, before):
    return {k: v - before[k] for k, v in counts(m).items()}


def test_a_restore_between_two_ticks_pulls_no_leaf_and_patches_one_row(
        cluster):
    c, rows = cluster
    name, row = NAMES[2], rows[NAMES[2]]
    for m in c.managers:
        assert m.pause_group(name, 0) == "ok"
    c.republish()
    c.run(2)
    before = [counts(m) for m in c.managers]
    for m in c.managers:
        assert m.resume_group(name, 0, [0, 1, 2], row, False)
    c.republish()
    c.run(1)  # the tick after: its gather, its dispatch, its post-step
    for m, b in zip(c.managers, before):
        d = delta(m, b)
        assert d["host_leaf_pulls"] == 0 and d["host_leaf_pull_bytes"] == 0
        assert d["want_coord_full"] == 0
        assert d["want_coord_patched_rows"] == 1
        assert d["lifecycle_row_reads"] == 0
    # and the restored name serves
    got = []
    c.submit(name, "7", entry=c.managers[0].coordinator_of_row(row),
             callback=lambda rid, resp: got.append(resp))
    c.run(8)
    assert got == ["12"]


def test_a_liveness_change_still_makes_a_full_pass(cluster):
    c, rows = cluster
    c.run(1)
    m, fd = c.managers[0], c._fds[0]
    theirs = [n for n in NAMES if m.coordinator_of_row(rows[n]) == 2]
    assert theirs
    before = counts(m)
    c.run(2)
    assert delta(m, before)["want_coord_full"] == 0  # the answer stands
    fd.last_heard[2] = None  # a peer falls silent: node 0 is next in line
    c.run(1)
    d = delta(m, before)
    assert d["want_coord_full"] == 1 and d["host_leaf_pulls"] == 0
    c.run(4)  # the elections' ballot rises are rows, not passes
    d = delta(m, before)
    assert d["want_coord_full"] == 1
    assert d["want_coord_patched_rows"] >= len(theirs)
    assert all(node.coordinator_of_row(rows[n]) == 0
               for n in theirs for node in c.managers)


def test_a_pause_reads_its_row_not_eight_leaves(cluster):
    c, rows = cluster
    name, row = NAMES[4], rows[NAMES[4]]
    m = c.managers[0]
    reference = whole_leaf_record(m, name, 0, row)
    before = counts(m)
    assert m.pause_group(name, 0) == "ok"
    d = delta(m, before)
    row_bytes = 4 * (len(ROW_LEAVES) + len(ROW_PLANES) * CFG.window)
    pulled = d["host_leaf_pull_bytes"] + d["lifecycle_row_reads"] * row_bytes
    assert d["lifecycle_row_reads"] == 1 and 0 < pulled < 4096
    assert d["host_leaf_pulls"] == 0
    assert m.paused[(name, 0)] == reference
    assert reference["exec"] == 1 and reference["n_execd"] == 1
    # a forced pause in traffic carries its window remnants the same way
    other, orow = NAMES[5], rows[NAMES[5]]
    c.submit(other, "3", entry=m.coordinator_of_row(orow))
    c.run(2)
    for node in c.managers:
        ref = whole_leaf_record(node, other, 0, orow)
        assert node.pause_group(other, 0, force=True) == "ok"
        Drive.same_record(node, other, 0, ref)


def test_a_batched_pause_gathers_its_rows_once(cluster):
    c, rows = cluster
    m = c.managers[1]
    refs = {n: whole_leaf_record(m, n, 0, rows[n]) for n in NAMES[:4]}
    before = counts(m)
    out = m.pause_group_batch([(n, 0) for n in NAMES[:4]])
    assert set(out.values()) == {"ok"}
    d = delta(m, before)
    assert d["lifecycle_row_reads"] == 4 and d["host_leaf_pulls"] == 0
    for n in NAMES[:4]:
        assert m.paused[(n, 0)] == refs[n]


# ---- (c) readers on other threads ------------------------------------------
def test_readers_during_lifecycle_calls_never_see_another_states_cache():
    """Transport threads read leaves through ``_np`` while lifecycle
    calls and ticks replace the state: whatever they read under the lock
    is the CURRENT state's leaf (the race ``_np``'s docstring names: an
    old state's array stored under the new state's cache)."""
    c = ManagerCluster(CFG, StatefulAdderApp)
    m = c.managers[0]
    stop = threading.Event()
    seen = {"reads": 0}
    errors = []

    def reader(leaf, locked):
        while not stop.is_set():
            try:
                if locked:
                    with m._state_lock:
                        m._await_step_locked()
                        arr = m._np(leaf).copy()
                        dev = device(m, leaf)
                    if not np.array_equal(arr, dev):
                        errors.append((leaf, np.flatnonzero(arr != dev)))
                        return
                else:
                    m._np(leaf)  # an unlocked caller must poison nothing
                seen["reads"] += 1
            except Exception as exc:  # noqa: BLE001 - the test reports it
                errors.append((leaf, repr(exc)))
                return

    threads = [threading.Thread(target=reader, args=a, daemon=True)
               for a in (("member_mask", True), ("bal", True),
                         ("version", False), ("exec_slot", True),
                         ("stopped", False), ("tag", True))]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)  # hand the interpreter over often
    for t in threads:
        t.start()
    try:
        rng = np.random.default_rng(5)
        for k in range(40):
            name = f"t{k}"
            row = c.create(name)
            c.submit(name, "1", entry=m.coordinator_of_row(row))
            c.run(2)
            if rng.random() < 0.5:
                for node in c.managers:
                    assert node.pause_group(name, 0, force=True) == "ok"
                c.republish()
                c.run(1)
                for node in c.managers:
                    assert node.resume_group(name, 0, [0, 1, 2], row, False)
                c.republish()
            else:
                for node in c.managers:
                    assert node.kill(name)
                c.republish()
            c.run(1)
            assert not errors, errors
    finally:
        stop.set()
        for t in threads:
            t.join(10)
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert seen["reads"] > 0
    for i, node in enumerate(c.managers):
        assert_host_is_device(node, c._fds[i], f"end node {i}")
    c.close()


def test_a_ballot_raised_back_to_the_published_one_is_not_missed(cluster):
    """``bal`` / ``exec_slot`` in the cache after a completion are the
    publish mirror's own pair, which follows the steps' NEWS (the rows
    in which a step's blob differs from the one PUBLISHED before it).
    A lifecycle operation writes its rows into the pair in place — so a
    row re-created under its initial ballot that the next step raises
    back to exactly the ballot last published, nothing else of the row
    differing from what was published either (a name never written
    to), makes no news, and the host would keep the initial ballot: the
    completion reads the rows lifecycle operations wrote again from
    what was published."""
    c, _rows = cluster
    name = "never-written"
    row = c.create(name)
    m = c.managers[0]
    runner = (m.coordinator_of_row(row) + 1) % 3
    want = np.zeros(CFG.n_groups, bool)
    want[row] = True
    c.step_all(want_coord={runner: want})  # an election: the ballot rises
    c.run(4)
    raised = int(device(m, "bal")[row])
    assert all(int(device(n, "bal")[row]) == raised for n in c.managers)
    assert m.coordinator_of_row(row) == runner
    # node 0 alone loses the row and creates it anew, at ballot (0, coord0)
    assert m.kill(name)
    assert m.create_paxos_instance(name, [0, 1, 2], row=row)
    assert int(device(m, "bal")[row]) < raised
    assert int(m._np("bal")[row]) == int(device(m, "bal")[row])
    c.vecs[0] = m.blob_vec()
    c.run(2)  # it hears the peers' ballot and promises it again
    assert int(device(m, "bal")[row]) == raised
    for i, node in enumerate(c.managers):
        assert_host_is_device(node, c._fds[i], f"node {i}")
