"""The closed loop of ``closed.py`` while one active goes dark and comes
back (upstream's emulated crash, ``TESTPaxosConfig.crash``): a service
that runs three replicas so that one may fail — a rolling restart, a
frozen VM, a switch that drops one node for a quarter of a minute — with
every log's one writer writing on.

Parameters, beside those of ``closed.py`` (a traffic file):

``crash_active``  index of the active that goes dark
``crash_at_s``    seconds after :meth:`start` at which :meth:`poll` sends
                  it ONE ``{"op": "crash", "for_s": down_s}`` admin op,
                  from a thread of its own (the loop is not held up while
                  the answer is on its way)
``down_s``        how long it stays dark: it takes nothing in, sends
                  nothing out and does not tick; it keeps its memory
``failover_s``    a request unanswered this long after its LAST send goes
                  out again, under the same id, to the NEXT active in
                  index order — whoever that is — and its client stays
                  there: every later request of that client enters at the
                  active it moved to.  This is the loop's only resend, so
                  ``retransmit_s`` must say the same

:meth:`start` first asks every active for ``{"op": "crash", "for_s": 0}``
(a probe: answered ``ok``, nothing happens) through the client library's
``admin_sync``, and raises on any other answer: a program without the op
(it answers ``unknown_op``) or a node that does not allow it ends the run
in set-up.

A traffic dict that carries ``budget`` is the harness's warm-up round and
goes to ``closed.ClosedLoop`` unchanged.  At the end of the drain
(:meth:`fail_outstanding`) one JSON line on standard error says what the
crash cost: when the op was sent and answered, how many requests moved and
how often, the acknowledgements second by second from :meth:`start`, and
per name the longest time between two acknowledgements.
"""

import collections
import json
import math
import sys
import threading
import time

from generators import closed  # benchmark/ is on the loader's path
from generators.closed_wake import _nearest_rank

ADMIN_TIMEOUT_S = 10.0


class ClosedCrashLoop(closed.ClosedLoop):
    def __init__(self, client, names, targets, traffic, seed,
                 clock=time.perf_counter):
        super().__init__(client, names, targets, traffic, seed, clock=clock)
        self.targets = list(targets)
        self.crash_active = int(traffic["crash_active"])
        self.crash_at_s = float(traffic["crash_at_s"])
        self.down_s = float(traffic["down_s"])
        self.failover_s = float(traffic["failover_s"])
        if self.failover_s != self.retransmit_s:
            raise ValueError("failover_s is the loop's only resend: "
                             "retransmit_s has to say the same")
        if not 0 <= self.crash_active < len(targets):
            raise ValueError(f"crash_active {self.crash_active}")
        # per client: how many actives past its name's own it has moved
        self.shift = [0] * self.in_flight
        self.moves = collections.Counter()  # request id -> times it moved
        self.crash = None  # {"sent_s", "answered_s", "answer"} once sent
        self._crash_thread = None

    # -- where a request goes ---------------------------------------------
    def _send(self, req):
        entry = (req.name + self.shift[req.slot]) % len(self.targets)
        self.client.send_prepared(
            self.targets[entry], self.names[req.name],
            f"{req.delta:0{closed.PAYLOAD_DIGITS}d}", self._on_reply,
            request_id=req.rid,
        )

    # -- the crash ----------------------------------------------------------
    def _admin(self, active, for_s):
        return self.client.admin_sync(
            active, {"op": "crash", "for_s": for_s},
            timeout=ADMIN_TIMEOUT_S) or {}

    def start(self):
        for i in range(len(self.targets)):
            answer = self._admin(i, 0)
            if not answer.get("ok"):
                raise RuntimeError(
                    f"active {i} answers the crash probe with {answer!r}: "
                    "the program under test has no emulated crash, or its "
                    "configuration does not allow it (ALLOW_CRASH_EMULATION)")
        super().start()

    def _send_crash(self):
        answer = self._admin(self.crash_active, self.down_s)
        with self.lock:
            self.crash["answered_s"] = self.clock() - self.t_start
            self.crash["answer"] = answer
        print(f"[closed_crash] active {self.crash_active} dark for "
              f"{self.down_s:.1f}s from {self.crash['sent_s']:.2f}s: "
              f"{answer!r}", file=sys.stderr, flush=True)

    # -- the harness's thread -------------------------------------------------
    def poll(self):
        """``closed.py``'s poll, but what went unanswered for
        ``failover_s`` goes to the next active and its client with it;
        and the crash, once its time has come."""
        self._start_due()
        now = self.clock()
        if self.crash is None and self.issuing \
                and now - self.t_start >= self.crash_at_s:
            self.crash = {"sent_s": now - self.t_start, "answered_s": None,
                          "answer": None}
            self._crash_thread = threading.Thread(
                target=self._send_crash, name="closed-crash-op", daemon=True)
            self._crash_thread.start()
        again = []
        with self.lock:
            for req in list(self.pending.values()):
                if now - req.t_first >= self.fail_after_s:
                    self._fail_locked(req)
                elif now - req.t_sent >= self.failover_s:
                    req.t_sent = now
                    req.sends += 1
                    self.shift[req.slot] += 1
                    self.moves[req.rid] += 1
                    again.append(req)
        for req in again:
            self._send(req)

    def fail_outstanding(self):
        super().fail_outstanding()
        if self._crash_thread is not None:
            self._crash_thread.join(ADMIN_TIMEOUT_S + 1.0)
        print(json.dumps({"crash": self.summary()}), file=sys.stderr,
              flush=True)

    def summary(self):
        t0 = self.t_start
        by_s = collections.Counter()
        last = {}      # name -> time of its last acknowledgement
        longest = {}   # name -> its longest time between two
        for r in sorted((r for r in self.reqs if r.t_ack is not None),
                        key=lambda r: r.t_ack):
            by_s[math.floor(r.t_ack - t0)] += 1
            if r.name in last:
                longest[r.name] = max(longest.get(r.name, 0.0),
                                      r.t_ack - last[r.name])
            last[r.name] = r.t_ack
        gaps = sorted(1000.0 * g for g in longest.values())
        moved = [n for n in self.moves.values() if n]
        out = dict(self.crash or {"sent_s": None, "answered_s": None,
                                  "answer": None})
        out.update({
            "active": self.crash_active, "down_s": self.down_s,
            "failover_s": self.failover_s,
            "requests_moved": len(moved),
            "moves": sum(moved),
            "moved_twice_or_more": sum(1 for n in moved if n > 1),
            "clients_by_moves": sorted(
                collections.Counter(self.shift).items()),
            "failed": len(self.failed),
            "acked_by_s": [by_s[s] for s in range(max(by_s, default=-1) + 1)],
            "longest_gap_ms": {"names": len(gaps),
                               "p50": _nearest_rank(gaps, 0.50),
                               "p95": _nearest_rank(gaps, 0.95),
                               "max": gaps[-1] if gaps else None},
        })
        return out


def Loop(client, names, targets, traffic, seed):
    if traffic.get("budget") is not None:
        return closed.ClosedLoop(client, names, targets, traffic, seed)
    return ClosedCrashLoop(client, names, targets, traffic, seed)
