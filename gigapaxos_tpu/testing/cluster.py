"""Loopback manager cluster: N full PaxosManagers (engine + logger + app +
callbacks) in one process, exchanging packed blob vectors (whole the
first time, then the rows that changed, as a node's `D` and `d` frames
carry them) and host-channel payloads with controllable delivery —
the manager-level analog of :mod:`.sim` and of the
reference's N-nodes-in-one-JVM integration mode (``TESTPaxosNode.java:44``,
``PaxosManager.java:108-111``).  Each replica steps by one of the manager's
two ways to run a tick, so a stepped test runs the program a node runs."""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

import numpy as np

from ..manager import PaxosManager
from ..net.gather import GatherNews
from ..ops.engine import EngineConfig

DELIVER, DROP = 0, 1


class ManagerCluster:
    # False: each replica's tick is ``tick_host`` (the serial reference);
    # True: ``step_dispatch`` then ``step_complete``, what a node serves
    pipelined = False

    def __init__(
        self,
        cfg: EngineConfig,
        make_app: Callable[[], object],
        log_dirs: Optional[List[str]] = None,
        sync_journal: Optional[bool] = None,
        checkpoint_every: Optional[int] = None,
    ):
        R = cfg.n_replicas
        self.cfg = cfg
        self._make_app = make_app
        self._log_dirs = log_dirs
        self._sync_journal = sync_journal
        self._checkpoint_every = checkpoint_every
        self.managers: List[PaxosManager] = [
            PaxosManager(
                rid,
                make_app(),
                cfg,
                log_dir=(log_dirs[rid] if log_dirs else None),
                sync_journal=sync_journal,
                checkpoint_every=checkpoint_every,
            )
            for rid in range(R)
        ]
        # what each replica last published; and per receiver what a
        # node keeps of its peers (server.py): the vector each peer's
        # deliveries add up to (None before the first) and the news of
        # them that no dispatch has taken yet
        self.republish()
        self._held: List[List[Optional[np.ndarray]]] = [
            [None] * R for _ in range(R)
        ]
        self._news = [GatherNews(cfg) for _ in range(R)]
        # host-channel inboxes: (kind, body) per receiver
        self.inboxes: List[List] = [[] for _ in range(R)]
        # default election drive (the deployed server's FailureDetector)
        # with an INFINITE timeout: stepped clusters exchange no pings, so
        # a finite timeout would make every node look dead after a few
        # wall-clock seconds and storm elections.  With everyone forever
        # "up", the mask fires ONLY for groups whose ballot coordinator is
        # not a member (elastic-membership leftovers, the chaos-soak
        # 20260730 wedge) — explicit want_coord args override.
        from ..failure_detection import FailureDetector

        self._fds = [
            FailureDetector(r, range(R), timeout_s=float("inf"))
            for r in range(R)
        ]
        # same reasoning as the infinite FD timeout above: stepped
        # clusters run on LOGICAL time, but the client-callback GC is
        # wall-clock — on a loaded box (cold jax compiles, CI
        # contention) a single tick can outlive the 8s client TTL and
        # silently reap every callback a test is counting
        for m in self.managers:
            m.outstanding.timeout_s = float("inf")

    # ---- lifecycle across the cluster ---------------------------------
    def republish(self) -> None:
        """Take every replica's publish vector from its CURRENT state:
        after a lifecycle op made outside :meth:`step_all`, the vectors
        of the last round no longer describe the rows it rewrote."""
        self.vecs = [m.blob_vec() for m in self.managers]

    def create(self, name: str, members: Optional[List[int]] = None,
               initial_state: Optional[str] = None) -> int:
        members = list(range(self.cfg.n_replicas)) if members is None else members
        row = self.managers[members[0]].default_row_for(name)
        for m in self.managers:
            m.create_paxos_instance(
                name, members, initial_state=initial_state, row=row
            )
        self.republish()
        return row

    def restart(self, rid: int, hydrate: bool = True) -> PaxosManager:
        """Crash-restart member ``rid``: close it and boot a FRESH
        PaxosManager from the same ``log_dir`` — journal replay +
        checkpoints are the only state that survives (queued vids,
        outstanding callbacks, and anything unlogged die with the old
        process, exactly as a real crash).  Requires ``log_dirs`` (a
        restart without durability is just amnesia).  ``hydrate=True``
        drains the lazy-hydration backlog synchronously so the member
        serves immediately; pass False to exercise the hydration gates
        themselves."""
        if not self._log_dirs:
            raise RuntimeError("restart needs log_dirs (durable members)")
        self.managers[rid].close()
        m = PaxosManager(
            rid,
            self._make_app(),
            self.cfg,
            log_dir=self._log_dirs[rid],
            sync_journal=self._sync_journal,
            checkpoint_every=self._checkpoint_every,
        )
        m.outstanding.timeout_s = float("inf")
        self.managers[rid] = m
        if hydrate:
            m.hydrate_all()
        self.vecs[rid] = m.blob_vec()
        self.inboxes[rid] = []
        # a fresh manager's stack holds nothing: every peer whole again
        self._held[rid] = [None] * self.cfg.n_replicas
        self._news[rid] = GatherNews(self.cfg)
        return m


    # ---- client entry ---------------------------------------------------
    def submit(self, name: str, value: str, entry: int = 0,
               callback=None, stop: bool = False) -> Optional[int]:
        return self.managers[entry].propose(
            name, value, callback=callback, stop=stop
        )

    # ---- the cluster tick ----------------------------------------------
    def step_all(self, delivery: Optional[np.ndarray] = None,
                 want_coord: Optional[Dict[int, np.ndarray]] = None) -> None:
        R = self.cfg.n_replicas
        if delivery is None:
            delivery = np.full((R, R), DELIVER)
        want_coord = want_coord or {}

        # deliver host-channel messages that arrived last round
        for i in range(R):
            inbox, self.inboxes[i] = self.inboxes[i], []
            for kind, body in inbox:
                self.managers[i].on_host_message(kind, body)

        # every replica of a round steps against the vectors the
        # PREVIOUS round published; an unheard peer's row of its stack
        # keeps what it held, masked by ``heard``
        new_vecs = list(self.vecs)
        deltas = []
        for i, m in enumerate(self.managers):
            heard = np.zeros(R, bool)
            for j in range(R):
                heard[j] = i == j or delivery[i, j] == DELIVER
                if heard[j] and i != j:  # whole, then what changed
                    self._held[i][j] = self._news[i].hear(
                        j, self.vecs[j], self._held[i][j])
            update = self._news[i].drain(self._held[i])
            want = want_coord.get(i)
            if want is None:
                bal, mask, changed = m.election_inputs()
                want = self._fds[i].want_coord(bal, mask, R, changed)
            if self.pipelined:
                pend = m.step_dispatch(update, heard, want)
                _tick, _state, delta = m.step_complete(pend)
            else:
                _tick, _state, delta = m.tick_host(update, heard, want)
            # what a node's peers are sent is cut from its mirror, which
            # the step's news has just patched; this round's stays as it
            # is while the later replicas of the round step
            new_vecs[i] = m.mirror.vec.copy()
            deltas.append(delta)
        self.vecs = new_vecs

        # route host-channel traffic over live links for NEXT round
        for i in range(R):
            delta = deltas[i]
            ae = delta.get("app_exec")
            if delta["arena"] or (ae and ae[1]):
                # cursor-only deltas matter too (the deployed server
                # forwards them the same way): the periodic app-cursor
                # baseline refresh is how a resumed member's frontier
                # becomes visible to stranded peers' stall detectors
                for j in range(R):
                    if j != i and delivery[j, i] == DELIVER:
                        self.inboxes[j].append(("payloads", delta))
            mgr = self.managers[i]
            fwd = mgr.drain_forward_out()
            for dst, kind, body in fwd:
                if dst == i:
                    mgr.on_host_message(kind, body)
                elif dst == -1:  # broadcast (e.g. payload pulls)
                    for j in range(R):
                        if j != i and delivery[j, i] == DELIVER:
                            self.inboxes[j].append((kind, body))
                elif 0 <= dst < R and delivery[dst, i] == DELIVER:
                    self.inboxes[dst].append((kind, body))

    def run(self, n_steps: int, **kw) -> None:
        for _ in range(n_steps):
            self.step_all(**kw)

    # ---- inspection -----------------------------------------------------
    def frontiers(self) -> np.ndarray:
        return np.stack(
            [np.asarray(m.state.exec_slot) for m in self.managers]
        )

    def app_exec(self) -> np.ndarray:
        return np.stack([m.app_exec_slot for m in self.managers])

    def close(self) -> None:
        for m in self.managers:
            m.close()
