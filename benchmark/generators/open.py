"""The open loop: requests arrive on a schedule fixed by the seed and are
sent whether or not earlier ones were answered.  A slow system is offered
the same load as a fast one, so a backlog can grow and latency includes
the wait behind it; a request's clock starts when it was DUE, not when
the injector got to it (a late injector is part of what a user waits for).

Parameters (a traffic file, ``benchmark/traffic/<mix>.json``):

``rate_per_s``      mean arrivals a second; the gaps between arrivals are
                    exponential, drawn from the seed (a Poisson process)
``key_dist``        ``"uniform"``: each request's name is drawn uniformly
                    from the seed
``per_name_order``  false: a name may have several requests in flight, so
                    an acknowledgement's value is checked against bounds
                    and against its name's other acknowledgements, not
                    against a running sum
``entry``           ``"round_robin_by_name"``: name i enters at active
                    i mod actives, always (two of three are forwarded)
``retransmit_s``    an unanswered request is sent again, same id
``fail_after_s``    unanswered this long after it was due: failed

The injector wakes every ``QUANTUM_S`` and sends what fell due
(``probe.py``'s quantum injector: the cost of the harness stays flat as
the rate rises).

A traffic dict that carries ``budget`` is the harness's warm-up round (one
write to every name, each client waiting for its reply): that is the
closed loop's business and goes to ``closed.ClosedLoop`` unchanged.

The loop owns one thread, the injector.  Replies arrive on the client
library's loop thread; the harness calls :meth:`poll` from its own thread
for retransmissions and failures.
"""

import threading
import time

import numpy as np

from generators import closed  # benchmark/ is on the loader's path

Req, PAYLOAD_DIGITS = closed.Req, closed.PAYLOAD_DIGITS
BLOCK = 4096        # arrivals drawn per call of the generator
QUANTUM_S = 0.004   # the injector's sleep between two looks at the schedule


class OpenLoop:
    def __init__(self, client, names, targets, traffic, seed,
                 clock=time.perf_counter, sleep=time.sleep):
        if traffic["key_dist"] != "uniform":
            raise ValueError(f"key_dist {traffic['key_dist']!r}")
        if traffic["entry"] != "round_robin_by_name":
            raise ValueError(f"entry {traffic['entry']!r}")
        if traffic["per_name_order"]:
            raise ValueError("an open loop keeps no per-name order")
        self.client, self.names = client, names
        self.clock, self.sleep = clock, sleep
        self.addr = [targets[i % len(targets)] for i in range(len(names))]
        self.rate = float(traffic["rate_per_s"])
        self.retransmit_s = float(traffic["retransmit_s"])
        self.fail_after_s = float(traffic["fail_after_s"])
        self.rng = np.random.default_rng(seed)
        self._block = iter(())
        self._last_due = 0.0    # offset of the last arrival drawn
        self._next = None       # (offset, name index, delta) not yet sent
        self.t_start = None
        self.lock = threading.Lock()
        self.issuing = False
        self.thread = None
        self.reqs = []          # every request, in order of first send
        self.pending = {}       # id -> Req, unanswered and not failed
        self.failed = {}        # id -> Req, given up
        self.errors = []        # (name, error) replies: refusals

    # -- the schedule, from the seed --------------------------------------
    def _arrival(self):
        """The next (offset from the start, name index, delta)."""
        try:
            return next(self._block)
        except StopIteration:
            due = self._last_due + np.cumsum(
                self.rng.exponential(1.0 / self.rate, size=BLOCK))
            self._last_due = float(due[-1])
            self._block = iter(zip(
                due.tolist(),
                self.rng.integers(0, len(self.names), size=BLOCK).tolist(),
                self.rng.integers(1, 1000, size=BLOCK).tolist(),
            ))
            return next(self._block)

    # -- issuing ----------------------------------------------------------
    def start(self, thread=True):
        self.t_start = self.clock()
        self.issuing = True
        if thread:
            self.thread = threading.Thread(
                target=self._inject, name="open-loop-injector", daemon=True)
            self.thread.start()

    def _inject(self):
        while self.issuing:
            self.send_due()
            self.sleep(QUANTUM_S)

    def send_due(self):
        """Send everything that fell due by now; returns how many."""
        now = self.clock()
        elapsed = now - self.t_start
        batch = []
        with self.lock:
            if not self.issuing:
                return 0
            while True:
                if self._next is None:
                    self._next = self._arrival()
                offset, name, delta = self._next
                if offset > elapsed:
                    break
                self._next = None
                req = Req(name, delta, len(self.reqs), self.t_start + offset)
                req.t_sent = now
                # the id is minted and recorded before the send: a reply
                # cannot overtake the bookkeeping
                req.rid = self.client.mint_id()
                self.pending[req.rid] = req
                self.reqs.append(req)
                batch.append(req)
        for req in batch:
            self._send(req)
        return len(batch)

    def _send(self, req):
        self.client.send_prepared(
            self.addr[req.name], self.names[req.name],
            f"{req.delta:0{PAYLOAD_DIGITS}d}", self._on_reply,
            request_id=req.rid,
        )

    def _on_reply(self, rid, response, error):
        now = self.clock()
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

    # -- the harness's thread ---------------------------------------------
    def poll(self):
        """Send again what went unanswered for ``retransmit_s``; fail what
        is unanswered ``fail_after_s`` after it was due."""
        now = self.clock()
        again = []
        with self.lock:
            for req in list(self.pending.values()):
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
        if self.thread is not None:
            self.thread.join(timeout=5.0)

    def outstanding(self):
        with self.lock:
            return len(self.pending)

    def fail_outstanding(self):
        """The end of the drain: whatever is unanswered has failed."""
        with self.lock:
            for req in list(self.pending.values()):
                self._fail_locked(req)


def Loop(client, names, targets, traffic, seed):
    if traffic.get("budget") is not None:
        return closed.ClosedLoop(client, names, targets, traffic, seed)
    return OpenLoop(client, names, targets, traffic, seed)
