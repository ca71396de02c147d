"""Pause/residency: the 1M-idle-groups memory story (ref:
``PaxosManager.java:2264-2392,2786-2881`` — Deactivator sweep, pause to
disk, message-triggered unpause).  TPU re-design: rows must stay aligned
across replicas, so pause/resume is RC-coordinated — pause frees the row
on every active; a touch reactivates at a freshly probed row through the
start-epoch machinery, same epoch."""

import numpy as np
import pytest

from gigapaxos_tpu.models.apps import HashChainApp
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.reconfiguration import RCState
from gigapaxos_tpu.testing.rc_cluster import ReconfigurableCluster


def make_cluster(n_rows=16, **kw):
    ar_cfg = EngineConfig(n_groups=n_rows, window=8, req_lanes=4, n_replicas=3)
    rc_cfg = EngineConfig(n_groups=8, window=8, req_lanes=4, n_replicas=3)
    return ReconfigurableCluster(ar_cfg, rc_cfg, HashChainApp, **kw)


def create(c, name, max_steps=120):
    c.client_request("create_service", {"name": name, "actives": [0, 1, 2]})
    ack = c.wait_for("create_ack", max_steps=max_steps)
    assert ack and ack["ok"], (name, ack)
    return ack


def run_requests(c, name, values, entry=0, max_steps=80):
    done = {}
    for v in values:
        c.ars.managers[entry].propose(
            name, v, callback=lambda rid, r: done.setdefault(rid, r)
        )
    for _ in range(max_steps):
        if len(done) == len(values):
            return done
        c.step()
    raise AssertionError(f"{len(done)}/{len(values)} executed for {name}")


def pause(c, name, max_steps=80):
    """Drive a pause to PAUSED via the suggest path."""
    rec0 = c.reconfigurators[0].rc_app.get_record(name)
    c.active_replicas[0].send(
        ("RC", 0), "suggest_pause",
        {"name": name, "epoch": rec0.epoch, "from": 0},
    )
    for _ in range(max_steps):
        c.step()
        rec = c.reconfigurators[0].rc_app.get_record(name)
        if rec is not None and rec.state is RCState.PAUSED:
            return
    raise AssertionError(
        f"pause of {name} did not complete: "
        f"{c.reconfigurators[0].rc_app.get_record(name)}"
    )


def reactivate(c, name, max_steps=120):
    """Touch via request_actives until the record is READY again."""
    for _ in range(max_steps):
        c.client_request("request_actives", {"name": name})
        c.step()
        rec = c.reconfigurators[0].rc_app.get_record(name)
        if rec is not None and rec.state is RCState.READY and rec.row >= 0:
            c.drain_client()
            return rec
        c.drain_client()
    raise AssertionError(f"reactivation of {name} wedged")


def test_pause_frees_rows_and_reactivation_preserves_state():
    c = make_cluster()
    try:
        create(c, "svc")
        run_requests(c, "svc", [f"r{i}" for i in range(6)])
        h_before = c.ars.managers[0].app.state["svc"]
        n_before = c.ars.managers[0].app.n_executed["svc"]
        old_row = c.ars.managers[0].names["svc"]

        pause(c, "svc")
        for m in c.ars.managers:
            assert m.names.get("svc") is None, "row not freed"
            assert ("svc", 0) in m.paused
        rec = reactivate(c, "svc")
        assert rec.epoch == 0, "resume must not bump the epoch"
        # run more requests; the hash chain continues from pre-pause state
        run_requests(c, "svc", ["after1", "after2"], entry=1, max_steps=160)
        a0 = c.ars.managers[0].app
        assert a0.n_executed["svc"] == n_before + 2
        for m in c.ars.managers[1:]:
            assert m.app.state["svc"] == a0.state["svc"]
        assert a0.state["svc"] != h_before  # chain advanced, not reset
    finally:
        c.close()


def test_paging_beyond_row_capacity():
    """More names than engine rows, served by paging idle ones out (the
    VERDICT item-4 'row capacity < #names' criterion).  4 rows; 3 resident
    names + 2 paused names = 5 > 4."""
    c = make_cluster(n_rows=4)
    try:
        for n in ("a", "b", "c"):
            create(c, n)
            run_requests(c, n, [f"{n}0", f"{n}1"])
        pause(c, "a")
        pause(c, "b")
        # two rows free now: two more names fit
        for n in ("d", "e"):
            create(c, n, max_steps=200)
            run_requests(c, n, [f"{n}0"], max_steps=160)
        # 5 names exist on 4 rows; touch a paused one — it pages back in
        reactivate(c, "a")
        run_requests(c, "a", ["a2"], max_steps=160)
        a0 = c.ars.managers[0].app
        assert a0.n_executed["a"] == 3  # 2 pre-pause + 1 post-resume
    finally:
        c.close()


def test_pause_survives_restart(tmp_path):
    """A paused group's snapshot is durable: restart every node, then
    reactivate — state continues from the pre-pause chain."""
    ar_dirs = [str(tmp_path / f"ar{i}") for i in range(3)]
    rc_dirs = [str(tmp_path / f"rc{i}") for i in range(3)]
    c = make_cluster(ar_log_dirs=ar_dirs, rc_log_dirs=rc_dirs)
    try:
        create(c, "dur")
        run_requests(c, "dur", ["x", "y", "z"])
        h = c.ars.managers[0].app.state["dur"]
        pause(c, "dur")
    finally:
        c.close()

    c2 = make_cluster(ar_log_dirs=ar_dirs, rc_log_dirs=rc_dirs)
    try:
        for m in c2.ars.managers:
            assert ("dur", 0) in m.paused, "pause record lost on restart"
        rec = c2.reconfigurators[0].rc_app.get_record("dur")
        assert rec is not None and rec.state is RCState.PAUSED
        reactivate(c2, "dur")
        run_requests(c2, "dur", ["w"], max_steps=200)
        a0 = c2.ars.managers[0].app
        assert a0.n_executed["dur"] == 4
        assert a0.state["dur"] != h  # advanced from the restored chain
        for m in c2.ars.managers[1:]:
            assert m.app.state["dur"] == a0.state["dur"]
    finally:
        c2.close()


def test_rc_cluster_restart_mid_migration(tmp_path):
    """VERDICT r2 weak #4: restart the RECONFIGURATORS from their journals
    mid-migration — the paxos-replicated record recovers in WAIT_* state
    and the re-drive completes the stranded migration."""
    ar_dirs = [str(tmp_path / f"ar{i}") for i in range(3)]
    rc_dirs = [str(tmp_path / f"rc{i}") for i in range(3)]
    c = make_cluster(ar_log_dirs=ar_dirs, rc_log_dirs=rc_dirs)
    try:
        create(c, "mid")
        run_requests(c, "mid", ["a", "b"])
        # start a migration and cut the world down before it completes:
        # drop all start/stop traffic so the record strands in WAIT_*
        c.msg_filter = lambda dst, kind, body: kind not in (
            "stop_epoch", "start_epoch", "ack_stop_epoch", "ack_start_epoch",
        )
        c.client_request("reconfigure", {"name": "mid", "new_actives": [1, 2]})
        for _ in range(30):
            c.step()
        rec = c.reconfigurators[0].rc_app.get_record("mid")
        assert rec is not None and rec.state is not RCState.READY, (
            "migration unexpectedly completed before the restart"
        )
        stranded_state = rec.state
    finally:
        c.close()

    c2 = make_cluster(ar_log_dirs=ar_dirs, rc_log_dirs=rc_dirs)
    try:
        for rc in c2.reconfigurators:
            rc.REDRIVE_EVERY = 4
        rec = c2.reconfigurators[0].rc_app.get_record("mid")
        assert rec is not None, "record lost across RC restart"
        assert rec.state == stranded_state
        # the re-drive completes the migration without any client help
        import time as _time

        deadline = _time.time() + 60
        while _time.time() < deadline:
            c2.step()
            rec = c2.reconfigurators[0].rc_app.get_record("mid")
            if rec.state is RCState.READY and sorted(rec.actives) == [1, 2]:
                break
        assert rec.state is RCState.READY, rec.to_json()
        assert sorted(rec.actives) == [1, 2]
        run_requests(c2, "mid", ["after"], entry=1, max_steps=200)
        a1 = c2.ars.managers[1].app
        # a, b, the epoch-final stop, and the post-migration request
        assert a1.n_executed["mid"] == 4
    finally:
        c2.close()


def test_frozen_coordinator_heals_via_pause_probe():
    """Chaos-soak find: a pause round that aborts after SOME members
    froze leaves them holding pause records while the RC record stays
    READY.  A frozen ballot COORDINATOR wedges the whole group (it still
    answers pings and stays in the member mask, so no election fires).
    The frozen member's periodic pause-probe must get a committed resume
    from the RC and rejoin, unwedging consensus."""
    c = make_cluster()
    try:
        # no organic idle-pausing in this test (slow-compile wall time
        # can exceed the 60s sweep period and pause the group for real;
        # the healed member's own fast sweep would instantly re-pause it)
        for ar in c.active_replicas:
            ar.pause_option = False
        create(c, "fz")
        run_requests(c, "fz", ["w1", "w2"])
        m0 = c.ars.managers[0]
        row = m0.names["fz"]
        coord = m0.coordinator_of_row(row)
        epoch = m0.current_epoch("fz")
        # simulate the aborted pause round: ONLY the coordinator froze
        mc = c.ars.managers[coord]
        assert mc.pause_group("fz", epoch, force=True) == "ok"
        assert "fz" not in mc.names and ("fz", epoch) in mc.paused
        # fast probe cadence ONLY on the frozen member (a fast sweep on
        # the LIVE members would also fire genuine idle-pause suggestions
        # and pause the whole group mid-test)
        c.active_replicas[coord].deactivation_period_s = 0.1
        # traffic from a live member: wedged until the probe heals the
        # coordinator back in.  RETRANSMITTED like a real client — a
        # pre-heal forward to the frozen coordinator is consumed there
        # (not hosting -> dropped), and only the retransmit after the
        # heal can commit (exactly-once holds via the shared request id)
        entry = (coord + 1) % 3
        done = {}
        rid0 = 1 << 54
        import time as _t

        deadline = _t.time() + 60
        last_send = 0.0
        while _t.time() < deadline and not done:
            if _t.time() - last_send > 1.0:
                last_send = _t.time()
                c.ars.managers[entry].propose(
                    "fz", "x", request_id=rid0,
                    callback=lambda rid, r: done.setdefault(rid, r),
                )
            c.step()
        assert done, "frozen-coordinator group never unwedged"
        assert "fz" in mc.names  # the coordinator rejoined in place
        assert ("fz", epoch) not in mc.paused
    finally:
        c.close()


def test_orphaned_pause_record_dropped_by_probe():
    """A pause record for a DELETED name must be GC'd by the probe
    instead of lingering forever."""
    c = make_cluster()
    try:
        for ar in c.active_replicas:
            ar.pause_option = False
        create(c, "gone")
        run_requests(c, "gone", ["v"])
        epoch = c.ars.managers[0].current_epoch("gone")
        mc = c.ars.managers[1]
        assert mc.pause_group("gone", epoch, force=True) == "ok"
        # delete the name while member 1 holds a frozen copy
        c.client_request("delete_service", {"name": "gone"})
        ack = c.wait_for("delete_ack", max_steps=400)
        assert ack and ack.get("ok"), ack
        c.active_replicas[1].deactivation_period_s = 0.1
        import time as _t

        deadline = _t.time() + 60
        while _t.time() < deadline and ("gone", epoch) in mc.paused:
            c.step()
        assert ("gone", epoch) not in mc.paused, "orphan record never GC'd"
    finally:
        c.close()


def test_stranded_pending_row_heals_via_pending_probe():
    """Chaos-soak find: a member stranded at a LOSING probe row (its
    late-start retransmits expired) refuses every proposal forever, and
    the commit round that would heal it already completed on the other
    members.  The member's pending-row probe must get a committed resume
    at the winning row."""
    c = make_cluster()
    try:
        for ar in c.active_replicas:
            ar.pause_option = False
        create(c, "pr")
        run_requests(c, "pr", ["a", "b"])
        rec = c.reconfigurators[0].rc_app.get_record("pr")
        win_row = rec.row
        m1 = c.ars.managers[1]
        # strand member 1 at a losing pending row for the same epoch
        assert m1.kill("pr")
        lose_row = (win_row + 5) % 16
        assert m1.create_paxos_instance(
            "pr", [0, 1, 2], row=lose_row, version=rec.epoch, pending=True
        )
        assert m1.names["pr"] == lose_row and lose_row in m1.pending_rows
        c.active_replicas[1].deactivation_period_s = 0.1
        import time as _t

        deadline = _t.time() + 60
        while _t.time() < deadline and m1.names.get("pr") != win_row:
            c.step()
        assert m1.names.get("pr") == win_row, (
            "pending-row straggler never re-homed",
            m1.names.get("pr"), win_row,
        )
        assert win_row not in m1.pending_rows
        run_requests(c, "pr", ["c"], entry=1, max_steps=160)
    finally:
        c.close()


def test_stranded_winning_row_confirm_heals_via_pending_probe():
    """The sibling shape: the member holds the WINNING row but its
    epoch_commit confirm was lost and the commit round completed without
    needing it — the probe re-sends the confirm directly."""
    c = make_cluster()
    try:
        for ar in c.active_replicas:
            ar.pause_option = False
        create(c, "pw")
        run_requests(c, "pw", ["a"])
        rec = c.reconfigurators[0].rc_app.get_record("pw")
        m1 = c.ars.managers[1]
        row = m1.names["pw"]
        assert row == rec.row
        # simulate the lost confirm: re-gate the row
        m1.pending_rows.add(row)
        c.active_replicas[1].deactivation_period_s = 0.1
        import time as _t

        deadline = _t.time() + 60
        while _t.time() < deadline and row in m1.pending_rows:
            c.step()
        assert row not in m1.pending_rows, "lost confirm never re-sent"
    finally:
        c.close()


def test_deactivator_sweep_pauses_a_name_that_sat_idle():
    """The sweep itself, as a deployed active runs it from its tick: a
    name idle past DEACTIVATION_PERIOD_S is suggested for pause, capped
    at PAUSE_BATCH_SIZE per period (the cap's config read raised a
    NameError in every deployed active after its first 60 s, PR 19-22)."""
    import time

    c = make_cluster()
    create(c, "idle")
    run_requests(c, "idle", ["a", "b"])
    ar = c.active_replicas[0]
    assert ar.pause_option and ar.pause_batch_size > 0
    ar.deactivation_period_s = 0.05  # the period is also the idle bound
    time.sleep(0.1)
    ar._maybe_sweep()
    for _ in range(80):
        c.step()
        rec = c.reconfigurators[0].rc_app.get_record("idle")
        if rec is not None and rec.state is RCState.PAUSED:
            break
    assert rec.state is RCState.PAUSED, rec
    assert all(m.names.get("idle") is None for m in c.ars.managers)
