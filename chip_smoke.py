"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` needs one TPU chip and runs, in this one
process:

* *engine parity* — three replica states through the ``packed_host`` step
  the manager dispatches, at the deployed default shape, once on the TPU
  and once on this host's CPU backend, same seeded trace, blobs exchanged
  directly.  The engine is int32 throughout, so every state leaf and
  every output must match bit for bit.  This is what tells an engine
  fault from a server fault when the next phase fails.
* *served* — the six names of ``scenarios/loopback_3ar_3rc.properties``
  booted in this process the way ``reconfigurable_node.main`` boots them,
  engine at the deployed defaults; names created and written through
  ``ReconfigurableAppClient`` over the binary client frames; every
  acknowledged write read back from the app of each of the three actives.
  After the first round of writes active 1 goes dark for ten seconds (the
  ``crash`` admin op, upstream's emulated crash) and the second round is
  written into its absence: detection, election, the forwards it took
  with it, its return, resync and catch-up run on the chip, the read-back
  is from the returned node too, and nothing may compile after boot.

``python chip_smoke.py --chips 4`` needs four chips and runs only the
``('g',)``-sharded step against the unsharded step on one of the chips.

Each phase prints one JSON line; the last line of standard output is
``{"ok": true, "device": {...}}``.  There is no CPU branch: without a TPU
the script exits non-zero and prints no result.  The phases are plain
functions of their sizes so that ``tests/test_chip_smoke.py`` can call
them on the CPU at tiny sizes.
"""

import argparse
import json
import os
import resource
import sys
import threading
import time

import numpy as np

SEED = 22
ROOT = os.path.dirname(os.path.abspath(__file__))
SCENARIO = os.path.join(ROOT, "scenarios", "loopback_3ar_3rc.properties")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def peak_bytes(device):
    stats = device.memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else None


def compile_summary(sentinel) -> dict:
    """A StepSentinel's counts, and the seconds of each recorded compile
    (trace + lower + compile or cache read + one execution)."""
    return {
        "compiles": sentinel.n_compiles,
        "retraces": sentinel.n_retraces,
        "wall_s": [e["wall_s"] for e in sentinel.events()],
    }


def log(msg: str) -> None:
    """Progress, to standard error: the result lines own standard output."""
    print(f"[chip_smoke {time.strftime('%H:%M:%S')}] {msg}",
          file=sys.stderr, flush=True)


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def make_trace(cfg, n_steps: int, seed: int):
    """Seeded per-step inputs: ``req [G, K]`` offered at every replica
    (only a group's active coordinator admits), two election pulses
    ``want [R, G]`` and two steps with dropped links ``heard [R, R]``."""
    from gigapaxos_tpu.ops.ballot import NULL

    G, K, R = cfg.n_groups, cfg.req_lanes, cfg.n_replicas
    rng = np.random.default_rng(seed)
    lane = np.arange(K)
    for t in range(n_steps):
        n_req = rng.integers(0, K + 1, size=G)
        vids = 1 + ((t * G + np.arange(G))[:, None] * K + lane) % (1 << 29)
        req = np.where(lane[None, :] < n_req[:, None], vids, NULL)
        want = np.zeros((R, G), bool)
        if t % 6 == 5:
            want[t % R] = rng.random(G) < 0.02
        heard = np.ones((R, R), bool)
        if t % 8 in (6, 7):
            heard[0, 1 % R] = heard[R - 1, 0] = False
            np.fill_diagonal(heard, True)
        yield req.astype(np.int32), want, heard


# ---------------------------------------------------------------------------
# phase: engine parity
# ---------------------------------------------------------------------------
class _ReplicaArm:
    """R replica states on one device, each with its gathered stack,
    stepped through the packed step; a replica's blob reaches the others
    as a node's frames bring it (``net/gather.py``): whole the first
    time and where more rows changed than an update holds, else as the
    rows that changed, scattered by the step."""

    def __init__(self, cfg, device, step_fn):
        import jax
        import jax.numpy as jnp

        from gigapaxos_tpu.net.gather import GatherNews, empty_update_vec
        from gigapaxos_tpu.ops.engine import (
            init_stack, init_state, make_blob, pack_blob)
        from gigapaxos_tpu.ops.lifecycle import create_groups

        G, R = cfg.n_groups, cfg.n_replicas
        self.cfg, self.step_fn = cfg, step_fn
        # on the default device the arguments are left uncommitted, as
        # the manager leaves them: a committed argument is another jit
        # signature, and the served phase would then compile again
        self.put = jnp.asarray if device == jax.devices()[0] else (
            lambda x: jax.device_put(x, device)
        )
        idx = np.arange(G)
        masks = np.full(G, (1 << R) - 1)
        coord0 = (idx % R).astype(np.int32)
        self.states = [
            jax.tree.map(self.put, create_groups(
                init_state(cfg), idx, masks, coord0, my_id=r
            ))
            for r in range(R)
        ]
        pack = jax.jit(lambda s: pack_blob(make_blob(s)))
        self.blobs = [pack(s) for s in self.states]
        self.stacks = [jax.tree.map(self.put, init_stack(cfg))
                       for _ in range(R)]
        self.held = [None] * R  # the vectors every stack holds
        self.heard_news = GatherNews(cfg)
        self.no_rows = empty_update_vec(cfg)
        self.scattered = self.whole = 0
        self.heat = [self.put(jnp.zeros((G,), jnp.int32)) for _ in range(R)]
        self.digests = [None] * R  # the last step's, per replica
        self.news = [None] * R    # and its blob's news

    def step(self, req, want, heard):
        import jax.numpy as jnp

        from gigapaxos_tpu.ops.engine import set_peer_rows

        put, cfg = self.put, self.cfg
        R = cfg.n_replicas
        # every replica is told of every other (``heard`` masks what it
        # may use): one update serves all R stacks, its row for a
        # replica's own id overwritten by the step from that state
        self.held = [
            self.heard_news.hear(j, np.asarray(blob), self.held[j])
            for j, blob in enumerate(self.blobs)
        ]
        update = self.heard_news.drain(dict(enumerate(self.held)))
        self.whole += len(update.whole)
        self.scattered += update.n_scattered
        upd = put(self.no_rows if update.rows is None else update.rows)
        ring = put(req)
        outs, blobs = [], []
        for r in range(R):
            for peer, vec in update.whole:
                self.stacks[r] = set_peer_rows(
                    self.stacks[r], put(vec), put(np.int32(peer)), cfg=cfg)
            # the blob of the last step goes in as the published vector
            # (donated on the arm that donates) and the fresh one comes
            # back with its news: the rows that differ, lifecycle
            # operations since the last step included
            (self.states[r], self.stacks[r], out, blob, self.heat[r],
             digest, news) = self.step_fn(
                self.states[r], self.stacks[r], upd, put(heard[r]), ring,
                put(want[r]), put(np.int32(r)), self.heat[r],
                self.blobs[r],
            )
            outs.append(out)
            blobs.append(blob)
            self.digests[r] = digest
            self.news[r] = news
        self.blobs = blobs
        return outs

    def check_news(self, t: int) -> int:
        """Each replica's news of step ``t`` against the host's compare
        of the fresh vector with the one held of it before (``held`` is
        what the last step published): the same rows, the same words.
        -> the rows the step named."""
        from gigapaxos_tpu.net.codec import changed_rows, rows_of
        from gigapaxos_tpu.net.mirror import news_blocks
        from gigapaxos_tpu.ops.engine import split_news_vec, update_rows

        cfg, named = self.cfg, 0
        for r, (news, blob) in enumerate(zip(self.news, self.blobs)):
            n, rows, body = split_news_vec(np.asarray(news), cfg)
            fresh = np.asarray(blob)
            want = changed_rows(fresh, self.held[r], cfg)
            if n != want.size:
                raise AssertionError(
                    f"step {t} r{r}: news names {n} rows, {want.size} differ")
            if n <= update_rows(cfg):
                _assert_same(f"step {t} r{r}.news.rows", rows, want)
                for got, exp in zip(news_blocks(body, n, cfg),
                                    rows_of(fresh, want, cfg)):
                    _assert_same(f"step {t} r{r}.news.words", got, exp)
            named += n
        return named

    def pause(self, rows):
        """What ``manager.pause_group`` does to the device, on every
        replica: the rows' records read off the state (frontier, ballot,
        hash, the lanes at or past the frontier), the rows freed."""
        from gigapaxos_tpu.ops.ballot import NULL
        from gigapaxos_tpu.ops.lifecycle import kill_groups

        self.records = []
        for r, state in enumerate(self.states):
            rec = {k: np.asarray(getattr(state, k))[rows] for k in (
                "exec_slot", "bal", "app_hash", "n_execd", "acc_bal",
                "acc_vid", "acc_slot", "dec_vid", "dec_slot")}
            for plane in ("acc", "dec"):
                gone = rec[plane + "_slot"] < rec["exec_slot"][:, None]
                for leaf in ("_bal", "_vid", "_slot"):
                    if plane + leaf in rec:
                        rec[plane + leaf] = np.where(
                            gone, NULL, rec[plane + leaf]).astype(np.int32)
            self.records.append(rec)
            self.states[r] = kill_groups(state, rows)

    def resume(self, rows):
        """What ``manager.resume_group_batch`` does to the device: ONE
        ``create_groups`` and ONE ``restore_paused_rows`` for all of
        ``rows``, on every replica."""
        from gigapaxos_tpu.ops.ballot import encode_ballot
        from gigapaxos_tpu.ops.lifecycle import (
            create_groups, restore_paused_rows)

        R = self.cfg.n_replicas
        masks = np.full(len(rows), (1 << R) - 1, np.int32)
        coord0 = (rows % R).astype(np.int32)
        zeros = np.zeros(len(rows), np.int32)
        for r, rec in enumerate(self.records):
            created = create_groups(self.states[r], rows, masks, coord0,
                                    my_id=r, version=zeros, tag=zeros)
            bal = np.maximum(np.asarray(encode_ballot(zeros, coord0)),
                             rec["bal"]).astype(np.int32)
            self.states[r] = restore_paused_rows(
                created, rows, rec["exec_slot"], bal, rec["app_hash"],
                rec["n_execd"], rec["acc_bal"], rec["acc_vid"],
                rec["acc_slot"], rec["dec_vid"], rec["dec_slot"])

    def leaves(self):
        """Everything the phase compares at the end, as (name, array)."""
        for r, (state, blob, heat) in enumerate(
            zip(self.states, self.blobs, self.heat)
        ):
            for name, leaf in state._asdict().items():
                yield f"r{r}.state.{name}", leaf
            yield f"r{r}.blob", blob
            for name, leaf in self.stacks[r]._asdict().items():
                yield f"r{r}.stack.{name}", leaf
            yield f"r{r}.heat", heat
            yield f"r{r}.digest", self.digests[r]
            yield f"r{r}.news", self.news[r]


def _assert_same(name: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(
            f"{name}: {got.dtype}{got.shape} vs {want.dtype}{want.shape}"
        )
    if not np.array_equal(got, want):
        bad = np.flatnonzero(got.ravel() != want.ravel())
        raise AssertionError(
            f"{name}: {bad.size} of {got.size} words differ, first at "
            f"{int(bad[0])}: {int(got.ravel()[bad[0]])} vs "
            f"{int(want.ravel()[bad[0]])}"
        )


def phase_engine_parity(n_groups: int, window: int, req_lanes: int,
                        n_replicas: int, n_steps: int, seed: int,
                        device, reference_device) -> dict:
    """The manager's donated ``packed_host`` step on ``device`` against
    the same traced program (its non-donating instance, a jit of its
    own) on ``reference_device``; raises on the first word that
    differs."""
    from gigapaxos_tpu.ops.engine import EngineConfig, split_out_vec
    from gigapaxos_tpu.parallel.spmd import make_step

    cfg = EngineConfig(n_groups, window, req_lanes, n_replicas)
    step_fn = make_step(cfg, donate=True, io="packed_host")
    ref_fn = make_step(cfg, donate=False, io="packed_host")
    arm = _ReplicaArm(cfg, device, step_fn)
    ref = _ReplicaArm(cfg, reference_device, ref_fn)
    decided = admitted = news_rows = 0
    first_call_s = steady_s = 0.0
    # residency in the trace: eight rows are paused a third of the way
    # in (freed on every replica, their records kept) and come back two
    # thirds in through one batched restore; both arms do the same, each
    # from its own device's state, and every step between is compared
    slept = (np.arange(8, dtype=np.int32) * 37 + 5) % n_groups
    pause_at, resume_at = n_steps // 3, (2 * n_steps) // 3
    for t, (req, want, heard) in enumerate(make_trace(cfg, n_steps, seed)):
        if t in (pause_at, resume_at):
            for side in (arm, ref):
                (side.pause if t == pause_at else side.resume)(slept)
        t0 = time.perf_counter()
        outs = arm.step(req, want, heard)
        outs_np = [np.asarray(o) for o in outs]  # waits for the device
        dt = time.perf_counter() - t0
        if t == 0:
            first_call_s = dt
        else:
            steady_s += dt
        for r, (got, exp) in enumerate(zip(outs_np, ref.step(req, want,
                                                             heard))):
            _assert_same(f"step {t} r{r}.out", got, exp)
        for r, (got, exp) in enumerate(zip(arm.news, ref.news)):
            _assert_same(f"step {t} r{r}.news", got, exp)
        news_rows += arm.check_news(t)
        out0 = split_out_vec(outs_np[0], cfg)
        decided += int(out0.n_committed.sum())
        admitted += sum(
            int(split_out_vec(o, cfg).n_admitted.sum()) for o in outs_np
        )
    n_leaves = 0
    for (name, got), (_, exp) in zip(arm.leaves(), ref.leaves()):
        _assert_same(name, got, exp)
        n_leaves += 1
    if not decided:
        raise AssertionError("the trace decided nothing: no parity shown")
    return {
        "phase": "engine_parity",
        "shape": {"G": n_groups, "W": window, "K": req_lanes,
                  "R": n_replicas},
        "steps": n_steps,
        "device": str(device),
        "reference": str(reference_device),
        "bit_exact": True,
        "leaves_compared": n_leaves,
        "decided": decided,
        "admitted": admitted,
        "gather_updates": {"scattered": arm.scattered, "whole": arm.whole},
        "blob_news_rows": news_rows,
        "residency": {"rows": int(slept.size), "paused_at_step": pause_at,
                      "resumed_at_step": resume_at,
                      "executed_while_awake": int(
                          arm.records[0]["n_execd"].sum())},
        "first_call_s": first_call_s,  # compile + one dispatch of R steps
        "steady_s_per_round": steady_s / max(1, n_steps - 1),
        "compile": compile_summary(step_fn),
        "peak_bytes_in_use": peak_bytes(device),
    }


# ---------------------------------------------------------------------------
# phase: served
# ---------------------------------------------------------------------------
def _stats(ports) -> list:
    """Each active's answer to the ``stats`` admin op."""
    from gigapaxos_tpu.clients import PaxosClientAsync

    cli = PaxosClientAsync([("127.0.0.1", p) for p in ports])
    try:
        answers = [
            cli.admin_sync(node, {"op": "stats"}, timeout=60)
            for node in range(len(ports))
        ]
    finally:
        cli.close()
    for node, st in enumerate(answers):
        if not st or not st.get("ok"):
            raise AssertionError(f"stats of active {node} failed: {st}")
    return answers


def _describe(ars) -> list:
    """What each active looked like, from its own counters: ticks,
    elections, preempted and revived proposals, frames dropped."""
    out = []
    for s in ars:
        counters = s.manager.metrics.snapshot()["counters"]
        out.append({
            "tick": s._tick,
            "inflight": len(s.manager.inflight),
            "frames_dropped": s.transport.n_dropped,
            **{k: counters.get(k, 0) for k in (
                "decisions_executed", "coordinator_flips", "preempts",
                "requests_reproposed",
            )},
        })
    return out


def _write_round(client, targets, items, timeout_s: float):
    """Send one write per (name, delta) over the binary client frames and
    wait until every one is acknowledged, retransmitting the unanswered
    under the SAME request id (the servers execute an id once).  Returns
    ({name: response}, retransmissions)."""
    lock = threading.Lock()
    done = threading.Event()
    resp, rid_name, errors = {}, {}, []

    def cb(rid, response, error):
        with lock:
            if error:
                errors.append((rid_name.get(rid), error))
            else:
                resp[rid_name[rid]] = response
            if errors or len(resp) == len(items):
                done.set()

    by_addr = {}
    for name, delta in items:
        by_addr.setdefault(targets[name], []).append((name, str(delta)))
    with lock:  # callbacks may fire before the ids are recorded
        for addr, batch in by_addr.items():
            rids = client.send_prepared_batch(addr, batch, cb)
            rid_name.update(zip(rids, (n for n, _ in batch)))
    name_rid = {n: r for r, n in rid_name.items()}
    deltas = dict(items)
    resent = 0
    deadline = time.time() + timeout_s
    while not done.wait(10.0):
        if time.time() > deadline:
            raise AssertionError(
                f"{len(items) - len(resp)} of {len(items)} writes "
                f"unacknowledged after {timeout_s:.0f}s"
            )
        with lock:
            missing = [n for n in deltas if n not in resp]
        log(f"served: {len(missing)} of {len(items)} writes unanswered, "
            "sending them again")
        for name in missing:
            client.send_prepared(
                targets[name], name, str(deltas[name]), cb,
                request_id=name_rid[name],
            )
        resent += len(missing)
    if errors:
        raise AssertionError(f"writes refused: {errors[:5]}")
    return resp, resent


def _compiles(ars) -> list:
    """Per active: every compile its manager's programs have made (the
    step, the lifecycle scatters, the whole-row program)."""
    return [sum(c["compiles"] + c["retraces"]
                for c in s.manager.engine_compile_stats().values())
            for s in ars]


def phase_served(n_names: int, writes_per_name: int, engine_rows: int,
                 window: int, seed: int, expect_platform: str,
                 timeout_s: float = 600.0, crash_s: float = 10.0,
                 fd_timeout_s: float = 6.0) -> dict:
    """Boot the scenario's six names in this process, create ``n_names``
    names on all three actives, write ``writes_per_name`` deltas to each,
    and read the per-name sum of acknowledged deltas back from the app
    of every active.  After the first round active 1 goes dark for
    ``crash_s`` seconds (the ``crash`` admin op): the second round is
    written into its absence — the writes it led wait for the election,
    those that enter at it for its return — and the read-back at the end
    is from the returned node too, with no program compiled since boot."""
    import jax

    from gigapaxos_tpu.clients.reconfigurable_client import (
        ReconfigurableAppClient,
    )
    from gigapaxos_tpu.reconfigurable_node import boot_nodes
    from gigapaxos_tpu.testing.ports import free_ports
    from gigapaxos_tpu.utils.config import Config

    Config.clear()
    Config.load_file(SCENARIO)
    ar_names = sorted(Config.node_addresses("active"))
    rc_names = sorted(Config.node_addresses("reconfigurator"))
    ports = free_ports(len(ar_names) + len(rc_names))
    for name, port in zip(ar_names, ports):
        Config.set(f"active.{name}", f"127.0.0.1:{port}")
    for name, port in zip(rc_names, ports[len(ar_names):]):
        Config.set(f"reconfigurator.{name}", f"127.0.0.1:{port}")
    Config.set("APPLICATION", "gigapaxos_tpu.models.apps.StatefulAdderApp")
    Config.set("ENGINE_ROWS", str(engine_rows))
    Config.set("SLOT_WINDOW", str(window))
    # residency is pinned for the run: an active pauses a name that sat
    # idle for DEACTIVATION_PERIOD_S (60 s by default), which checkpoints
    # its state out of the app — and the read-back below is from the app
    Config.set("DEACTIVATION_PERIOD_S", str(2 * timeout_s))
    Config.set("ALLOW_CRASH_EMULATION", "true")
    Config.set("FAILURE_DETECTION_TIMEOUT_S", str(fd_timeout_s))

    rng = np.random.default_rng(seed)
    names = [f"smoke{i:05d}" for i in range(n_names)]
    expected = dict.fromkeys(names, 0)
    nodes, client = [], None
    try:
        t0 = time.perf_counter()
        nodes = boot_nodes(ar_names + rc_names)
        boot_s = time.perf_counter() - t0
        log(f"served: six names booted in {boot_s:.1f}s")
        ars = [n.servers[0] for n in nodes[:len(ar_names)]]
        compiles_at_boot = [
            s.manager._dispatch_step.n_compiles for s in ars
        ]
        programs_at_boot = _compiles(ars)
        client = ReconfigurableAppClient.from_properties()

        t0 = time.perf_counter()
        # one attempt per batch: a retransmitted batch rotates to another
        # reconfigurator, which forwards its names one by one
        acks = client.create_names(names, timeout=timeout_s,
                                   retransmit_every=timeout_s)
        create_s = time.perf_counter() - t0
        log(f"served: {n_names} creates answered in {create_s:.1f}s")
        all_actives = list(range(len(ar_names)))
        bad = {
            n: acks.get(n) for n in names
            if not (acks.get(n) or {}).get("ok")
            or sorted(acks[n].get("actives", ())) != all_actives
        }
        if bad:
            raise AssertionError(
                f"{len(bad)} of {n_names} creates failed: "
                f"{list(bad.items())[:3]}"
            )

        # entry replicas round-robin over the actives: two of three
        # writes enter at a non-coordinator and are forwarded
        targets = {
            n: tuple(client.actives[i % len(ar_names)])
            for i, n in enumerate(names)
        }
        resent = 0
        crash = None
        t0 = time.perf_counter()
        for round_no in range(writes_per_name):
            if round_no == 1 and crash_s > 0:
                answer = client.admin_sync(
                    1, {"op": "crash", "for_s": crash_s}, timeout=30)
                if not (answer or {}).get("ok"):
                    raise AssertionError(f"crash op refused: {answer}")
                crash = {"active": 1, "for_s": crash_s,
                         "at_s": time.perf_counter() - t0}
                log(f"served: active 1 dark for {crash_s:.0f}s")
            deltas = rng.integers(1, 1000, size=n_names)
            try:
                resp, again = _write_round(
                    client, targets, list(zip(names, map(int, deltas))),
                    timeout_s,
                )
            except AssertionError:
                log(f"served: the actives when the round failed: "
                    f"{_describe(ars)}")
                raise
            resent += again
            log(f"served: a round of {n_names} writes acknowledged "
                f"({again} retransmitted), "
                f"{time.perf_counter() - t0:.1f}s so far, ticks "
                f"{[s._tick for s in ars]}")
            for n, d in zip(names, deltas):
                expected[n] += int(d)
                if resp[n] != str(expected[n]):
                    raise AssertionError(
                        f"{n}: acknowledged with {resp[n]!r}, the sum of "
                        f"acknowledged deltas is {expected[n]}"
                    )
        requests_s = time.perf_counter() - t0

        # the guarantee: an acknowledged write is read back from ALL
        # three actives (the laggards execute a few ticks behind)
        deadline = time.time() + 60
        while True:
            wrong = [
                (i, n, s.manager.app.totals.get(n), expected[n])
                for i, s in enumerate(ars) for n in names
                if s.manager.app.totals.get(n) != expected[n]
            ]
            if not wrong or time.time() > deadline:
                break
            time.sleep(0.2)
        if wrong:
            raise AssertionError(
                f"{len(wrong)} (active, name) sums differ from the "
                f"acknowledged writes: {wrong[:5]}"
            )

        if crash is not None:
            snaps = [s.manager.metrics.snapshot() for s in ars]
            back = snaps[1]["counters"]
            crash.update({
                "frames_dropped": back.get("frames_dropped_while_crashed", 0),
                "rows_caught_up": back.get("rows_caught_up", 0),
                "catchup_s": snaps[1]["hists"].get(
                    "phase_catchup_s", {}).get("sum"),
                **{key: [snap["counters"].get(key, 0) for snap in snaps]
                   for key in ("coordinator_flips",
                               "executions_skipped_duplicate",
                               "requests_reforwarded")},
            })
            if not crash["frames_dropped"]:
                raise AssertionError(f"active 1 was never dark: {crash}")
        if _compiles(ars) != programs_at_boot:
            raise AssertionError(
                f"a program compiled after boot: {programs_at_boot} -> "
                f"{_compiles(ars)}: "
                f"{[s.manager.engine_compile_stats() for s in ars]}")
        codec = None
        meshes, tick_means = [], []
        for i, (s, st) in enumerate(zip(ars, _stats(ports[:len(ars)]))):
            hists = st["engine"]["hists"]
            tick_means.append({
                k: hists[k]["sum"] / hists[k]["count"]
                for k in ("engine_step_s", "phase_publish_s",
                          "phase_execute_s", "pipeline_overlap_s")
                if hists.get(k, {}).get("count")
            })
            mesh = st["engine"]["mesh"]
            if mesh["platform"] != expect_platform:
                raise AssertionError(
                    f"active {i}: engine arrays on {mesh}, expected "
                    f"platform {expect_platform!r}"
                )
            meshes.append(mesh)
            codec = st["serving"]["codec"]["impl"]
            sent = s.manager._dispatch_step
            if sent.n_retraces or sent.n_compiles != compiles_at_boot[i]:
                raise AssertionError(
                    f"active {i}: a compile after warm-up: {sent.stats()}"
                )
        sent = ars[0].manager._dispatch_step
        return {
            "phase": "served",
            "names": n_names,
            "writes_per_name": writes_per_name,
            "writes_acknowledged": n_names * writes_per_name,
            "retransmissions": resent,
            "crash": crash,
            "read_back_from_actives": len(ars),
            "engine": {"rows": engine_rows, "W": window,
                       "K": ars[0].cfg.req_lanes, "R": len(ars)},
            "boot_s": boot_s,  # six names, warm-up compiles included
            "create_s": create_s,
            "requests_s": requests_s,
            "actives": _describe(ars),
            # host-clock means per dispatch, from each active's stats op
            "tick_mean_s": tick_means,
            "mesh": meshes,
            "codec_impl": codec,
            "compile": compile_summary(sent),
            "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
            "host_peak_rss_bytes": resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024,
        }
    finally:
        if client is not None:
            client.close()
        for n in nodes:
            n.stop()
        Config.clear()


# ---------------------------------------------------------------------------
# phase: four chips
# ---------------------------------------------------------------------------
def _dispatch_bytes(step_fn, cfg) -> int:
    """Device bytes one dispatch of the unsharded stacked step needs, by
    the compiler's own account (AOT: nothing runs)."""
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.ops.engine import init_state

    R, G, K = cfg.n_replicas, cfg.n_groups, cfg.req_lanes
    states = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((R,) + x.shape, x.dtype),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    ma = step_fn.fn.lower(
        states, jax.ShapeDtypeStruct((R, G, K), jnp.int32),
        jax.ShapeDtypeStruct((R, G), jnp.bool_),
        jax.ShapeDtypeStruct((R, R), jnp.bool_),
    ).compile().memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        + ma.temp_size_in_bytes - ma.alias_size_in_bytes
    )


def phase_four_chips(n_groups: int, window: int, req_lanes: int,
                     n_replicas: int, n_steps: int, seed: int,
                     n_devices: int = 4) -> dict:
    """``make_step(cfg, make_group_mesh(n))`` against
    ``make_step(cfg)`` on the first of the devices, same seeded
    trace, every output of every step and every final state leaf
    compared on the device, bit for bit.  ``n_groups`` is halved until
    the unsharded arm fits the device by the compiler's account."""
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.parallel.mesh import (
        GROUP_AXIS,
        describe_state_mesh,
        make_group_mesh,
    )
    from gigapaxos_tpu.parallel.spmd import (
        build_replica_states,
        make_step,
        shard_group_inputs,
    )

    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise AssertionError(f"need {n_devices} devices, found {len(devs)}")
    stats = devs[0].memory_stats()
    limit = int(stats["bytes_limit"]) if stats else None
    requested = n_groups
    while True:
        cfg = EngineConfig(n_groups, window, req_lanes, n_replicas)
        single = make_step(cfg)
        need = _dispatch_bytes(single, cfg)
        # the comparison also keeps a copy of the sharded arm's state
        # and outputs on this device: count the dispatch twice
        if limit is None or 2 * need <= limit:
            break
        n_groups //= 2
    mesh = make_group_mesh(n_devices, devices=devs)
    sharded = make_step(cfg, mesh)

    R, G, K = cfg.n_replicas, cfg.n_groups, cfg.req_lanes
    one = jax.sharding.SingleDeviceSharding(devs[0])
    s_one = jax.device_put(build_replica_states(cfg), one)
    no_req = np.full((R, G, K), -1, np.int32)
    s_mesh, _, _ = shard_group_inputs(
        mesh, cfg, build_replica_states(cfg), no_req, np.zeros((R, G), bool)
    )
    shards = s_mesh.bal.addressable_shards
    shard_devices = {s.device for s in shards}
    desc = describe_state_mesh(s_mesh.bal)
    if len(shard_devices) != n_devices or desc["n_devices"] != n_devices \
            or any(s.data.shape != (R, G // n_devices) for s in shards):
        raise AssertionError(
            f"state not spread over {n_devices} devices: {desc}, shards "
            f"{[(str(s.device), s.data.shape) for s in shards]}"
        )

    on_mesh = lambda *spec: jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*spec)
    )
    same = jax.jit(lambda a, b: jnp.stack([
        jnp.array_equal(x, y)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    ]))

    def check(what, got, exp):
        eq = np.asarray(same(jax.device_put(got, one), exp))
        if not eq.all():
            fields = type(exp)._fields
            raise AssertionError(
                f"{what}: sharded and unsharded differ in "
                f"{[f for f, ok in zip(fields, eq) if not ok]}"
            )

    decided = 0
    first_s = [0.0, 0.0]
    steady_s = [0.0, 0.0]
    for t, (req, want, heard) in enumerate(make_trace(cfg, n_steps, seed)):
        req = np.broadcast_to(req[None], (R, G, K))
        req_m = jax.device_put(req, on_mesh(None, GROUP_AXIS, None))
        want_m = jax.device_put(want, on_mesh(None, GROUP_AXIS))
        t0 = time.perf_counter()
        s_mesh, out_m = sharded(s_mesh, req_m, want_m, heard)
        jax.block_until_ready(out_m)
        t1 = time.perf_counter()
        s_one, out_1 = single(
            s_one, jax.device_put(req, one), jax.device_put(want, one),
            heard,
        )
        jax.block_until_ready(out_1)
        t2 = time.perf_counter()
        acc = first_s if t == 0 else steady_s
        acc[0] += t1 - t0
        acc[1] += t2 - t1
        check(f"step {t} outputs", out_m, out_1)
        decided += int(out_1.n_committed[0].sum())
    check("final state", s_mesh, s_one)
    if not decided:
        raise AssertionError("the trace decided nothing: no parity shown")
    n = max(1, n_steps - 1)
    return {
        "phase": "four_chips",
        "shape": {"G": G, "W": window, "K": K, "R": R},
        "groups_requested": requested,
        "unsharded_dispatch_bytes": need,
        "device_bytes_limit": limit,
        "steps": n_steps,
        "bit_exact": True,
        "decided": decided,
        "mesh": desc,
        "shard_devices": sorted(str(d) for d in shard_devices),
        "sharded_first_call_s": first_s[0],
        "unsharded_first_call_s": first_s[1],
        "sharded_step_s": steady_s[0] / n,
        "unsharded_step_s": steady_s[1] / n,
        "compile": {"sharded": compile_summary(sharded),
                    "unsharded": compile_summary(single)},
        "peak_bytes_in_use": [peak_bytes(d) for d in devs],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the group-sharded step against the "
                         "unsharded one (needs four chips)")
    args = ap.parse_args()

    import jax

    from gigapaxos_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    device = device_info()
    if device["platform"] != "tpu":
        print(f"chip_smoke needs a TPU, JAX found {device}", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"--chips {args.chips} needs that many, JAX found {device}",
              file=sys.stderr)
        return 1
    emit({"phase": "start", "device": device,
          "compile_cache": cache_dir or os.environ.get(
              "JAX_COMPILATION_CACHE_DIR")})
    if args.chips == 4:
        emit(phase_four_chips(1 << 20, 16, 8, 3, n_steps=12, seed=SEED))
    else:
        emit(phase_engine_parity(
            65536, 16, 8, 3, n_steps=20, seed=SEED,
            device=jax.devices()[0],
            reference_device=jax.devices("cpu")[0],
        ))
        emit(phase_served(
            n_names=1000, writes_per_name=3, engine_rows=65536, window=16,
            seed=SEED, expect_platform="tpu",
        ))
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
