"""The post-step reads the rows that hold a name (``manager.py``:
``_member_rows_locked``): the payload-retention watermark, the state-pull
detectors and the tick's sums run over the member rows, not over every
engine row.  The dense ``[G]`` forms left the program and live here as
the reference: after EVERY step of a seeded run — admits, decisions,
dropped links, a node cut off past the jump horizon, payloads withheld,
stalled frontiers, and between steps names created, killed, paused,
restored, moved to their next epoch and jumped on rows that were freed
and taken again — ``_min_exec``, ``_stall_since`` and ``_stall_slot``
are the dense pass's element for element and the ``state_request``s
queued are the same.  And a served tick makes no pass over ``[G]`` for
its index (one a lifecycle operation), nor one that gives up the
interpreter lock: the member rows are read ``ROWS_A_PASS`` at a time."""

import sys
import threading
import time

from collections import Counter

import numpy as np
import pytest

from gigapaxos_tpu.manager import PaxosManager
from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.testing.cluster import DELIVER, DROP, ManagerCluster
from gigapaxos_tpu.utils.config import Config

ALL = [0, 1, 2]


# ---- the dense forms, as the program had them --------------------------
def dense_min_exec(m, last, seen):
    """The payload-retention watermark over every engine row."""
    mask = m._np("member_mask")
    R = m.cfg.n_replicas
    rids = np.arange(R)
    in_group = ((mask[None, :] >> rids[:, None]) & 1) == 1
    cursors = np.stack([
        m.peer_app_exec.get(r, m._zero_cursors)
        if r != m.my_id else m.app_exec_slot
        for r in range(R)
    ])
    horizon = last.maj_exec.astype(np.int64) - m.jump_horizon
    eligible = in_group & (cursors >= horizon[None, :])
    cur_masked = np.where(eligible, cursors, np.iinfo(np.int64).max)
    seen["beyond_horizon"] += int((in_group & ~eligible).any())
    seen["unheard"] += int(len(m.peer_app_exec) < R - 1)
    return np.where(
        eligible.any(axis=0), cur_masked.min(axis=0), m._min_exec
    )


def dense_detectors(m, out_np, seen):
    """-> (``_stall_since``, ``_stall_slot``, need [G] bool) as the four
    detectors over every engine row leave them."""
    W = m.cfg.window
    exec_np = (
        out_np.exec_base.astype(np.int64)
        + out_np.n_committed.astype(np.int64)
    )
    behind_dev = (out_np.maj_exec - exec_np) > W
    behind_app = (exec_np - m.app_exec_slot) > m.jump_horizon
    need = behind_dev | behind_app
    seen["behind_dev"] += int(behind_dev.any())
    seen["behind_app"] += int(behind_app.any())
    for g, (t0, _slot) in m._payload_blocked.items():
        if m._tick_no - t0 > m.PAYLOAD_BLOCKED_TICKS:
            need[g] = True
            seen["blocked"] += 1
    mask_np = m._np("member_mask")
    peak = np.maximum(
        exec_np.astype(np.int64), out_np.maj_exec.astype(np.int64)
    )
    for r, arr in m.peer_app_exec.items():
        in_grp = ((mask_np >> r) & 1) == 1
        peak = np.maximum(peak, np.where(in_grp, arr, 0))
    behind = peak > exec_np
    rearm = behind & (m._stall_slot != exec_np)
    since = np.where(
        rearm, m._tick_no, np.where(behind, m._stall_since, -1)
    )
    slot = np.where(behind, exec_np, -1)
    stalled = (
        behind & (since >= 0)
        & (m._tick_no - since > m.FRONTIER_STALLED_TICKS)
    )
    seen["stalled"] += int(stalled.any())
    need |= stalled
    for g in m._needs_state:
        need[g] = True
        seen["needs_state"] += 1
    if m.hydrating_rows:
        seen["hydrating"] += int(
            need[np.fromiter(m.hydrating_rows, np.int64)].any())
        need[np.fromiter(m.hydrating_rows, np.int64)] = False
    return since, slot, need


def dense_requests(m, need):
    """The ``state_request``s the tick queues for ``need``: the donor
    rotation and the interval, against a copy of the manager's table."""
    versions = m._np("version")
    masks = m._np("member_mask")
    last_req = dict(m._last_state_req)
    by_dst = {}
    for g in np.nonzero(need)[0]:
        g = int(g)
        name = m.row_name.get(g)
        if name is None or m.names.get(name) != g:
            continue
        if m._tick_no - last_req.get(g, -(10 ** 9)) < m.STATE_REQ_INTERVAL:
            continue
        members = [r for r in range(32)
                   if (int(masks[g]) >> r) & 1 and r != m.my_id]
        if not members:
            continue
        dst = members[(m._tick_no // m.STATE_REQ_INTERVAL) % len(members)]
        by_dst.setdefault(dst, []).append(
            {"row": g, "name": name, "version": int(versions[g])})
    return [(dst, {"rows": rows, "from": m.my_id})
            for dst, rows in by_dst.items()]


def _hold_to_dense(m, seen):
    """Every post-step of ``m`` against the dense pass on the same
    inputs: the watermark before anything executes, the detectors where
    the program runs them."""
    post, detect = m._post_step_locked, m._maybe_request_state

    def post_step(out):
        want = dense_min_exec(m, out, seen)
        dec = int(out.n_committed.sum())
        adm = int(out.n_admitted.sum())
        before = (m.metrics.get("decisions_executed"),
                  m.metrics.get("requests_admitted"))
        result = post(out)
        assert np.array_equal(m._min_exec, want), (m.my_id, m._tick_no)
        assert (m.metrics.get("decisions_executed") - before[0],
                m.metrics.get("requests_admitted") - before[1]) == (dec, adm)
        seen["steps"] += 1
        seen["decided"] += dec
        return result

    def maybe_request_state(out_np):
        since, slot, need = dense_detectors(m, out_np, seen)
        want = dense_requests(m, need)
        n0 = len(m.forward_out)
        detect(out_np)
        at = (m.my_id, m._tick_no)
        assert np.array_equal(m._stall_since, since), at
        assert np.array_equal(m._stall_slot, slot), at
        got = [(dst, body) for dst, kind, body in m.forward_out[n0:]
               if kind == "state_request"]
        assert got == want, at
        assert len(got) == len(m.forward_out) - n0
        seen["requests"] += sum(len(body["rows"]) for _d, body in got)

    m._post_step_locked = post_step
    m._maybe_request_state = maybe_request_state


def _isolate(dead):
    d = np.full((3, 3), DELIVER)
    d[dead, :] = DROP
    d[:, dead] = DROP
    d[dead, dead] = DELIVER
    return d


class _Run:
    """The seeded traffic and the lifecycle operations between steps."""

    def __init__(self, c, rng, names):
        self.c, self.rng = c, rng
        self.live = list(names)
        self.asleep, self.stopping, self.old = [], [], []
        self.freed, self.done = [], []
        self.rid = 1 << 56
        self.n_lifecycle = 0
        self.reused = set()

    def write(self, name, entry=None):
        self.rid += 1
        entry = int(self.rng.integers(0, 3)) if entry is None else entry
        self.c.managers[entry].propose(
            name, f"v{self.rid & 0xffff}", request_id=self.rid,
            callback=lambda r, x: self.done.append(r))

    def round(self, per_round=2, names=None, delivery=None, entry=None,
              drops=0.0, withhold=None):
        names = self.live if names is None else names
        for _ in range(int(self.rng.integers(0, per_round + 1))):
            self.write(names[int(self.rng.integers(0, len(names)))], entry)
        if delivery is None:
            delivery = np.where(
                self.rng.random((3, 3)) < drops, DROP, DELIVER)
            np.fill_diagonal(delivery, DELIVER)
        if withhold is not None:  # this node hears no payloads, no cursors
            self.c.inboxes[withhold] = [
                kb for kb in self.c.inboxes[withhold] if kb[0] != "payloads"]
        self.c.step_all(delivery=delivery)

    def free_row(self):
        """A row no manager holds: one a kill freed, where there is one."""
        m0 = self.c.managers[0]
        while self.freed:
            row = self.freed.pop()
            if all(row not in m.row_name for m in self.c.managers):
                self.reused.add(row)
                return row
        return m0.default_row_for("probe")

    def lifecycle(self, op, step):
        c, ms = self.c, self.c.managers
        G = c.cfg.n_groups
        if op == 0 and len(ms[0].row_name) < G:  # a create
            nm, row = f"late{step}", self.free_row()
            for m in ms:
                assert m.create_paxos_instance(nm, ALL, row=row)
            self.live.append(nm)
        elif op == 1 and len(self.live) > 6:  # a kill
            nm = self.live.pop(int(self.rng.integers(0, len(self.live))))
            self.freed.append(ms[0].names[nm])
            for m in ms:
                assert m.kill(nm)
        elif op == 2 and len(self.live) > 6:  # a pause, of an idle name
            nm = self.live.pop(0)
            row = ms[0].names[nm]
            for _ in range(10):
                self.round()
            if all(m.pause_group(nm, 0) == "ok" for m in ms):
                self.asleep.append(nm)
                self.freed.append(row)
            else:  # busy somewhere: forced, as a sweep's second try is
                for m in ms:
                    assert m.pause_group(nm, 0, force=True) == "ok"
                self.asleep.append(nm)
        elif op == 3 and self.asleep and len(ms[0].row_name) < G:
            nm, row = self.asleep.pop(0), self.free_row()  # its restore
            for m in ms:
                assert m.resume_group_batch(
                    [(nm, 0, ALL, row, False)]) == {nm: True}
            self.live.append(nm)
        elif op == 4 and len(self.live) > 6 and not self.stopping:
            nm = self.live.pop(0)  # an epoch change: the stop ...
            c.submit(nm, "", entry=0, stop=True)
            self.stopping.append(nm)
        elif op == 5 and self.stopping and len(ms[0].row_name) < G:
            nm = self.stopping[0]  # ... and the next epoch's row
            if not all(int(m._np("stopped")[m.names[nm]]) for m in ms):
                return
            self.stopping.pop(0)
            row = self.free_row()
            final = ms[0].app.checkpoint(nm)
            for m in ms:
                assert m.create_paxos_instance(
                    nm, ALL, initial_state=final, version=1, row=row)
            self.live.append(nm)
            self.old.append(nm)
        elif op == 6 and self.old:  # the old epoch's row dropped
            nm = self.old.pop(0)
            self.freed.append(ms[0].old_epochs[(nm, 0)])
            for m in ms:
                assert m.kill_epoch(nm, 0)
        else:
            return
        self.n_lifecycle += 1
        c.republish()


@pytest.mark.parametrize("G,n_names,block,seed", [
    (64, 64, 500, 3600065),    # every row a member, one block
    (64, 64, 16, 3600068),     # ... in four blocks
    (4096, 30, 8, 3604097),    # 30 members of 4,096 rows, in four blocks
    (4096, 30, 500, 3604100),  # ... in one
])
def test_member_rows_equal_the_dense_pass_after_every_step(
        G, n_names, block, seed, monkeypatch):
    monkeypatch.setattr(PaxosManager, "ROWS_A_PASS", block)
    Config.set("BATCHING_ENABLED", "false")  # a slot a request
    # the triggers at a tenth of their ticks, for both forms alike
    monkeypatch.setattr(PaxosManager, "FRONTIER_STALLED_TICKS", 6)
    monkeypatch.setattr(PaxosManager, "PAYLOAD_BLOCKED_TICKS", 6)
    monkeypatch.setattr(PaxosManager, "STATE_REQ_INTERVAL", 4)
    cfg = EngineConfig(n_groups=G, window=8, req_lanes=4, n_replicas=3)
    rng = np.random.default_rng(seed)
    c = ManagerCluster(cfg, HashChainApp)
    seen = Counter()
    try:
        names = [f"pr{i}" for i in range(n_names)]
        for m in c.managers:
            assert m.create_paxos_batch(names, ALL) == n_names
            _hold_to_dense(m, seen)
        c.republish()
        m0, m1, m2 = c.managers
        assert m0.names == m1.names == m2.names
        if n_names == G:  # every row a member
            assert sorted(m0.names.values()) == list(range(G))
        run = _Run(c, rng, names)

        # (1) writes over dropped links, a lifecycle operation every
        # third round
        for step in range(126):
            run.round(drops=0.15)
            if step % 3 == 2:
                run.lifecycle((step // 3) % 7, step)
        assert run.n_lifecycle > 20 and run.reused

        # (2) node 2 cut off while three names run past the jump
        # horizon; a name created meanwhile, whose cursors node 2 never
        # hears; a row that awaits its state and one not yet hydrated
        led = [nm for nm in run.live
               if m0.coordinator_of_row(m0.names[nm]) != 2]
        gone, hot = led[0], led[1:3]
        row = m0.names[gone]  # freed now and taken again: it will jump
        for m in c.managers:
            assert m.kill(gone)
        run.live.remove(gone)
        c.republish()
        run.round()
        for m in c.managers:
            assert m.create_paxos_instance("again", ALL, row=row)
        c.republish()
        run.live.append("again")
        if m0.coordinator_of_row(row) != 2:
            hot.append("again")
        cut = _isolate(2)
        flagged = [m0.names[nm] for nm in run.live[-2:]]
        for k in range(30):
            for nm in hot:
                for _ in range(3):
                    run.write(nm, entry=k % 2)
            run.round(per_round=0, delivery=cut)
            if k == 4:
                run.lifecycle(0, 1000)
                m1._needs_state.add(flagged[0])
                m0._needs_state.add(flagged[1])  # and would ask, but
                m0.hydrating_rows.add(flagged[1])
            if k == 20:
                m0.hydrating_rows.discard(flagged[1])
        behind = [int(np.asarray(m0.state.exec_slot)[m0.names[nm]])
                  - int(np.asarray(m2.state.exec_slot)[m2.names[nm]])
                  for nm in hot]
        assert min(behind) > m0.jump_horizon + cfg.window, behind

        # (3) back: it pulls state and jumps, on a row that was freed
        # and taken again among them
        before = seen["requests"]
        for _ in range(40):
            run.round(drops=0.05)
        assert seen["requests"] > before
        for nm in hot:
            row = m0.names[nm]
            assert int(np.asarray(m2.state.exec_slot)[row]) \
                >= int(np.asarray(m0.state.exec_slot)[row]) - cfg.window

        # (4) node 1 hears blobs and no payloads (nor cursors, late):
        # its cursor parks on a decided slot until the pull fires
        before = seen["blocked"]
        for _ in range(16):
            run.round(per_round=3, entry=0, withhold=1)
        assert seen["blocked"] > before
        for _ in range(20):
            run.round()

        # (5) a peer's cursor heard ahead of a frontier that stands
        # still: the stall timer arms, fires, and the row asks
        rows = [m0.names[nm] for nm in run.live[:2]]
        idle = [nm for nm in run.live[2:]]
        before = seen["stalled"]
        m0.peer_app_exec[1][rows] += 3
        for _ in range(12):
            run.round(names=idle)
        assert seen["stalled"] > before
        assert (m0._stall_since[rows] >= 0).all()

        assert seen["steps"] > 3 * 280 and seen["decided"] > 400
        for key in ("beyond_horizon", "unheard", "behind_dev", "blocked",
                    "stalled", "needs_state", "hydrating", "requests"):
            assert seen[key] > 0, (key, seen)
        assert len(run.done) > 200
        for m in c.managers:
            # no row without a member is ever armed
            free = np.asarray(m.state.member_mask) == 0
            assert (m._stall_since[free] == -1).all()
            assert (m._stall_slot[free] == -1).all()
    finally:
        c.close()


# ---- one [G] pass a lifecycle operation, none a tick -------------------
def test_a_served_tick_builds_no_index_and_scans_the_member_rows(
        monkeypatch):
    """30 names on 4,096 rows, served (``step_dispatch`` /
    ``step_complete``): the index over ``[G]`` is built once, on no tick
    and — since PR 46 writes a lifecycle operation's rows into it — for
    no kill either, and the counters read 30 of 4,096 a tick."""
    cfg = EngineConfig(n_groups=4096, window=8, req_lanes=4, n_replicas=3)
    built = []
    orig = PaxosManager._index_member_rows
    monkeypatch.setattr(
        PaxosManager, "_index_member_rows",
        lambda self, mask: built.append(self.my_id) or orig(self, mask))
    c = ManagerCluster(cfg, HashChainApp)
    c.pipelined = True
    try:
        names = [f"sv{i}" for i in range(30)]
        for m in c.managers:
            assert m.create_paxos_batch(names, ALL) == 30
        c.republish()
        c.run(2)
        assert sorted(built) == [0, 1, 2]  # the one batch of creates
        done = []

        def counters():
            return [(m.metrics.get("post_step_rows_scanned"),
                     m.metrics.get("post_step_rows_total"))
                    for m in c.managers]

        for k in range(30):
            c.submit(names[k % 30], f"v{k}", entry=k % 3,
                     callback=lambda r, x: done.append(x))
            before = counters()
            c.step_all()
            assert [(a - a0, b - b0) for (a, b), (a0, b0)
                    in zip(counters(), before)] == [(30, 4096)] * 3
        c.run(8)
        assert len(done) == 30 and len(built) == 3
        before = counters()
        for m in c.managers:
            assert m.kill(names[0])
        c.republish()
        c.run(3)
        assert sorted(built) == [0, 1, 2]  # the freed row left the index
        assert [(a - a0, b - b0) for (a, b), (a0, b0)
                in zip(counters(), before)] == [(3 * 29, 3 * 4096)] * 3
    finally:
        c.close()


# ---- no pass of a tick gives up the interpreter lock -------------------
def _turns_taken_during_post_steps(monkeypatch, block):
    """1,100 names on 4,096 rows, one replica a name, ticked 43 times
    under writes while a second thread asks for the interpreter lock:
    how often it got it inside a post-step.  With a switch interval of
    an hour the ticking thread is never made to hand the lock over, so
    the other runs only where a call lets go of it."""
    monkeypatch.setattr(PaxosManager, "ROWS_A_PASS", block)
    cfg = EngineConfig(n_groups=4096, window=8, req_lanes=4, n_replicas=3)
    m = PaxosManager(0, HashChainApp(), cfg)
    names = [f"gl{i}" for i in range(1100)]
    assert m.create_paxos_batch(names, [0]) == 1100
    for r in (1, 2):  # cursors heard of both peers, as on a served node
        m.peer_app_exec[r] = np.zeros(cfg.n_groups, np.int64)
    heard = np.array([True, False, False])
    turns, inside, stop = [0], [0], threading.Event()

    def other():
        while not stop.is_set():
            turns[0] += 1
            time.sleep(0)

    post = m._post_step_locked

    def post_step(out):
        before = turns[0]
        try:
            return post(out)
        finally:
            inside[0] += turns[0] - before

    m._post_step_locked = post_step
    done = []
    for _ in range(3):
        m.tick_host(None, heard)
    was = sys.getswitchinterval()
    thread = threading.Thread(target=other, daemon=True)
    try:
        sys.setswitchinterval(3600.0)
        thread.start()
        for k in range(40):
            m.propose(names[k], "v", callback=lambda r, x: done.append(r))
            m.tick_host(None, heard)
        for _ in range(3):
            m.tick_host(None, heard)
    finally:
        sys.setswitchinterval(was)
        stop.set()
        thread.join(timeout=10)
        m.close()
    assert not thread.is_alive() and len(done) == 40
    assert turns[0] > 0  # it ran: the device wait lets go of the lock
    return inside[0]


def test_no_pass_over_a_block_gives_up_the_interpreter_lock(monkeypatch):
    """numpy keeps the lock through a loop of at most 500 elements: read
    500 rows at a time, 43 post-steps over 1,100 member rows hardly ever
    hand it over; read in one block of 1,100 they do, several times a
    tick."""
    assert PaxosManager.ROWS_A_PASS == 500
    # (~170 turns in one block and 0-1 in three, on the sandbox's CPU)
    assert _turns_taken_during_post_steps(monkeypatch, 500) <= 5
    assert _turns_taken_during_post_steps(monkeypatch, 4096) > 40
