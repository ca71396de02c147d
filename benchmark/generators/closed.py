"""The closed loop: a fixed number of logical clients, each with one
request in flight; a client's next request goes out when its last was
acknowledged.  A slow system is therefore offered less, no backlog can
grow, and no rate has to be found.

Parameters (a traffic file, ``benchmark/traffic/<mix>.json``):

``in_flight``       logical clients, each one request in flight
``key_dist``        ``"slot"``: client i writes name i mod names, always;
                    ``"uniform"``: each request's name is drawn uniformly
                    from the seed
``per_name_order``  true: a name never has two requests in flight (with
                    ``uniform`` a busy name is drawn again), so each
                    acknowledgement's value is that name's running sum
``entry``           ``"round_robin_by_name"``: name i enters at active
                    i mod actives, always (two of three are forwarded)
``retransmit_s``    an unanswered request is sent again, same id
``fail_after_s``    unanswered this long after its first send: failed,
                    and its client stops (the traffic is chosen so that
                    none does)
``ramp_s``          optional: the clients start one after the other, spread
                    evenly over this long, not all at once (clients of a
                    service are not synchronised; started together they
                    finish together, in waves a window cuts unevenly)
``budget``          optional: requests per client, then it stops (the
                    harness's warm-up round is ``budget`` 1)

The loop owns no thread.  The next request of a client is sent from the
client library's reply callback (its one loop thread); the harness calls
:meth:`poll` from its own thread for retransmissions and failures.  What
it needs of the client is ``mint_id() -> id`` and ``send_prepared(addr,
name, value, callback, request_id=id)`` with ``callback(id, response,
error)``.
"""

import threading
import time

import numpy as np

PAYLOAD_DIGITS = 10  # upstream's 10-byte request baggage; int() parses it


class Req:
    """One request, first send to acknowledgement."""

    __slots__ = ("name", "delta", "rid", "slot", "t_first", "t_sent",
                 "t_ack", "response", "sends", "failed")

    def __init__(self, name, delta, slot, now):
        self.name, self.delta, self.slot = name, delta, slot
        self.rid = None
        self.t_first = self.t_sent = now
        self.t_ack = None
        self.response = None
        self.sends = 1
        self.failed = False


class ClosedLoop:
    def __init__(self, client, names, targets, traffic, seed,
                 clock=time.perf_counter):
        if traffic["key_dist"] not in ("slot", "uniform"):
            raise ValueError(f"key_dist {traffic['key_dist']!r}")
        if traffic["entry"] != "round_robin_by_name":
            raise ValueError(f"entry {traffic['entry']!r}")
        self.client, self.names, self.clock = client, names, clock
        self.addr = [targets[i % len(targets)] for i in range(len(names))]
        self.in_flight = int(traffic["in_flight"])
        self.uniform = traffic["key_dist"] == "uniform"
        self.ordered = bool(traffic["per_name_order"])
        if self.ordered and not self.uniform \
                and self.in_flight > len(names):
            raise ValueError("per_name_order with more clients than names")
        self.retransmit_s = float(traffic["retransmit_s"])
        self.fail_after_s = float(traffic["fail_after_s"])
        self.budget = traffic.get("budget")
        self.ramp_s = float(traffic.get("ramp_s", 0.0))
        self.started = 0        # clients that have sent their first request
        self.t_start = None
        self.rng = np.random.default_rng(seed)
        self._draws = iter(())
        self.lock = threading.Lock()
        self.issuing = False
        self.reqs = []          # every request, in order of first send
        self.pending = {}       # id -> Req, unanswered and not failed
        self.failed = {}        # id -> Req, given up
        self.busy = set()       # names with a request in flight
        self.sent = [0] * self.in_flight
        self.errors = []        # (name, error) replies: refusals

    # -- drawing from the seed ------------------------------------------
    def _draw(self):
        """(name index, delta), in blocks: one rng call per request would
        be most of the loop's cost."""
        try:
            return next(self._draws)
        except StopIteration:
            n = 4096
            self._draws = iter(zip(
                self.rng.integers(0, len(self.names), size=n).tolist(),
                self.rng.integers(1, 1000, size=n).tolist(),
            ))
            return next(self._draws)

    # -- issuing --------------------------------------------------------
    def start(self):
        self.t_start = self.clock()
        self.issuing = True
        self._start_due()

    def _start_due(self):
        """First requests of the clients whose turn has come."""
        if self.started == self.in_flight:
            return
        elapsed = self.clock() - self.t_start
        due = self.in_flight if elapsed >= self.ramp_s else \
            1 + int(self.in_flight * elapsed / self.ramp_s)
        with self.lock:
            if not self.issuing:
                return
            batch = [self._next_locked(s) for s in range(self.started, due)]
            self.started = max(self.started, due)
        for req in batch:
            self._send(req)

    def _next_locked(self, slot):
        name, delta = self._draw()
        if not self.uniform:
            name = slot % len(self.names)
        elif self.ordered:
            while name in self.busy:
                name, _ = self._draw()
        req = Req(name, delta, slot, self.clock())
        # the id is minted and recorded before the send: a reply cannot
        # overtake the bookkeeping
        req.rid = self.client.mint_id()
        self.pending[req.rid] = req
        self.busy.add(name)
        self.sent[slot] += 1
        self.reqs.append(req)
        return req

    def _send(self, req):
        self.client.send_prepared(
            self.addr[req.name], self.names[req.name],
            f"{req.delta:0{PAYLOAD_DIGITS}d}", self._on_reply,
            request_id=req.rid,
        )

    def _on_reply(self, rid, response, error):
        now = self.clock()
        nxt = None
        with self.lock:
            req = self.pending.pop(rid, None)
            if req is None:
                # answered twice (a retransmission's reply), or after it
                # was given up: a late answer says the write did execute
                late = self.failed.get(rid)
                if late is not None and not error and late.t_ack is None:
                    late.t_ack, late.response = now, response
                return
            if error:
                self.errors.append((self.names[req.name], error))
                self._fail_locked(req)
                return
            req.t_ack, req.response = now, response
            self.busy.discard(req.name)
            if self.issuing and (self.budget is None
                                 or self.sent[req.slot] < self.budget):
                nxt = self._next_locked(req.slot)
        if nxt is not None:
            self._send(nxt)

    # -- the harness's thread -------------------------------------------
    def poll(self):
        """Send again what went unanswered for ``retransmit_s``; fail what
        is unanswered ``fail_after_s`` after its first send; start the
        clients whose turn in the ramp has come."""
        self._start_due()
        now = self.clock()
        again = []
        with self.lock:
            for rid, req in list(self.pending.items()):
                if now - req.t_first >= self.fail_after_s:
                    self._fail_locked(req)
                elif now - req.t_sent >= self.retransmit_s:
                    req.t_sent = now
                    req.sends += 1
                    again.append(req)
        for req in again:
            self._send(req)

    def _fail_locked(self, req):
        self.pending.pop(req.rid, None)
        req.failed = True
        self.failed[req.rid] = req

    def stop(self):
        with self.lock:
            self.issuing = False

    def outstanding(self):
        """Requests unanswered, and clients still to start."""
        with self.lock:
            waiting = self.in_flight - self.started if self.issuing else 0
            return len(self.pending) + waiting

    def fail_outstanding(self):
        """The end of the drain: whatever is unanswered has failed."""
        with self.lock:
            for req in list(self.pending.values()):
                self._fail_locked(req)
Loop = ClosedLoop
