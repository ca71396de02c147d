"""The closed loop of ``closed.py`` over a hot set that drifts through a
cold tail: most writes go to names used moments ago, and a steady trickle
is the FIRST write to a name that has sat idle long enough for the
service to page it out (upstream's Deactivator) — a name service whose
written set wanders, as the GNS's does.

Parameters, beside those of ``closed.py`` (a traffic file):

``hot_names``      size of the hot set: the last ``hot_names`` names that
                   entered it; at the start names 0 .. ``hot_names`` - 1
``wake_every``     request number k of the loop (in order of first send,
                   counted from the end of the settling wait) with
                   k mod ``wake_every`` = 0 goes to the NEXT COLD NAME in
                   index order (``hot_names``, ``hot_names`` + 1, ...,
                   round again past the last), which thereby enters the
                   hot set and pushes its oldest name out; every other
                   request draws its name uniformly from the hot set
                   (with ``per_name_order`` a busy name is drawn again)
``settle_max_s``   :meth:`start` starts the clients — on the hot set
                   alone, which keeps it awake — and returns once EVERY
                   active reports ``names`` - ``hot_names`` names asleep
                   (the ``stats`` admin op's ``residency`` block, asked
                   every ``SETTLE_POLL_S``), serving the retransmissions
                   itself meanwhile; not reached in this long, it raises
                   and the run ends in set-up

The service sweeps for idle names once a period (the ``stats`` op's
``layer.sweep`` block says how long the period is and how long ago the
last sweep was).  Once everyone sleeps, :meth:`start` waits on until
``SWEEP_PHASE_S`` after a sweep before it lets the first wake go: a name
woken in the first seconds is let go by the hot set some seconds later
and has sat idle for a period only ~36 s after its wake, so whether a
window of 40 s that begins 10 s after :meth:`start` returned holds ANY
pause would otherwise hang on how long the first sweep's pause rounds
happened to take.  From that phase the next sweep but one falls about
33 s into the window and finds the names woken in the first ~7 s.

So one request in ``wake_every`` is the first write to a name that has
slept since the warm-up round, whatever the throughput, and the names the
hot set let go fall asleep again behind it.  What the loop needs of the
client beside ``closed.py``'s needs is ``admin_sync(active, {"op":
"stats"}) -> {"residency": {"paused_names": n}}``; a client library
without it cannot run this loop (the constructor says so).

A traffic dict that carries ``budget`` is the harness's warm-up round and
goes to ``closed.ClosedLoop`` unchanged.  At the end of the drain
(:meth:`fail_outstanding`) one JSON line on standard error says what the
wakes cost: issued, acknowledged, their latency beside the resident
names', second by second from the end of the settling wait, and who slept
at the end of the settling wait and when the loop was stopped.
"""

import collections
import json
import math
import sys
import time

from generators import closed  # benchmark/ is on the loader's path

SETTLE_POLL_S = 2.0   # between two looks at who sleeps
STATS_TIMEOUT_S = 10.0
SWEEP_PHASE_S = 17.0  # the first wake goes out this long after a sweep


def _nearest_rank(sorted_xs, q):
    if not sorted_xs:
        return None
    return sorted_xs[max(0, math.ceil(q * len(sorted_xs)) - 1)]


class ClosedWakeLoop(closed.ClosedLoop):
    def __init__(self, client, names, targets, traffic, seed,
                 clock=time.perf_counter, sleep=time.sleep):
        super().__init__(client, names, targets,
                         {**traffic, "key_dist": "uniform"}, seed,
                         clock=clock)
        self.sleep = sleep
        self.n_actives = len(targets)
        self.hot_n = int(traffic["hot_names"])
        self.wake_every = int(traffic["wake_every"])
        self.settle_max_s = float(traffic["settle_max_s"])
        if not 0 < self.hot_n < len(names):
            raise ValueError(f"hot_names {self.hot_n} of {len(names)} names")
        if self.ordered and self.in_flight > self.hot_n:
            raise ValueError("per_name_order with more clients than hot names")
        self.hot = collections.deque(range(self.hot_n))  # oldest first
        self.next_cold = self.hot_n
        self.k = None           # requests first sent since the settling wait
        self.wakes = []         # the requests that went to a cold name
        self.t_settled = None
        self.asleep_at_settle = self.asleep_at_stop = None

    # -- drawing from the seed ------------------------------------------
    def _draw(self):
        """(position in the hot set, delta), in blocks."""
        try:
            return next(self._draws)
        except StopIteration:
            n = 4096
            self._draws = iter(zip(
                self.rng.integers(0, self.hot_n, size=n).tolist(),
                self.rng.integers(1, 1000, size=n).tolist(),
            ))
            return next(self._draws)

    def _cold_locked(self):
        """The next cold name in index order; it enters the hot set."""
        name = self.next_cold
        while name in self.busy or name in self.hot:  # only once round
            name = (name + 1) % len(self.names)
        self.next_cold = (name + 1) % len(self.names)
        self.hot.append(name)
        self.hot.popleft()
        return name

    def _next_locked(self, slot):
        pos, delta = self._draw()
        wake = self.k is not None and self.k % self.wake_every == 0
        if wake:
            name = self._cold_locked()
        else:
            name = self.hot[pos]
            while self.ordered and name in self.busy:
                name = self.hot[self._draw()[0]]
        if self.k is not None:
            self.k += 1
        req = closed.Req(name, delta, slot, self.clock())
        req.rid = self.client.mint_id()
        self.pending[req.rid] = req
        self.busy.add(name)
        self.sent[slot] += 1
        self.reqs.append(req)
        if wake:
            self.wakes.append(req)
        return req

    # -- who sleeps ---------------------------------------------------------
    def _stats(self, active):
        return self.client.admin_sync(
            active, {"op": "stats"}, timeout=STATS_TIMEOUT_S) or {}

    def asleep(self):
        """Per active: the names it reports asleep, or None (no answer)."""
        return [(self._stats(i).get("residency") or {}).get("paused_names")
                for i in range(self.n_actives)]

    def _wait_for_sweep_phase(self):
        """Until ``SWEEP_PHASE_S`` after a sweep of active 0's (the three
        were built within a second of each other); returns the seconds
        waited.  A service that does not say where its sweep stands is
        not waited for."""
        sweep = (self._stats(0).get("layer") or {}).get("sweep") or {}
        period, since = sweep.get("period_s"), sweep.get("since_s")
        if not period or since is None or period <= SWEEP_PHASE_S:
            return 0.0
        t_go = self.clock() + (SWEEP_PHASE_S - since) % period
        while self.clock() < t_go:
            self.sleep(min(SETTLE_POLL_S, max(0.0, t_go - self.clock())))
            self.poll()
        return (SWEEP_PHASE_S - since) % period

    def start(self):
        super().start()
        want = len(self.names) - self.hot_n
        t0 = self.clock()
        while True:
            asleep = self.asleep()
            if all(a is not None and a >= want for a in asleep):
                break
            if self.clock() - t0 > self.settle_max_s:
                self.stop()
                raise RuntimeError(
                    f"after {self.settle_max_s:.0f}s the actives report "
                    f"{asleep} names asleep, not {want} each: no idle name "
                    "is paused here, or the pause rounds are slower than that")
            print(f"[closed_wake] {self.clock() - t0:5.1f}s: {asleep} asleep",
                  file=sys.stderr, flush=True)
            self.sleep(SETTLE_POLL_S)
            self.poll()
        t_asleep = self.clock()
        waited = self._wait_for_sweep_phase()
        with self.lock:
            self.k = 0
            self.t_settled = self.clock()
            self.asleep_at_settle = asleep
        print(f"[closed_wake] {asleep} asleep after {t_asleep - t0:.1f}s, "
              f"{waited:.1f}s more to {SWEEP_PHASE_S:.0f}s after a sweep",
              file=sys.stderr, flush=True)

    def stop(self):
        super().stop()
        if self.t_settled is not None and self.asleep_at_stop is None:
            self.asleep_at_stop = self.asleep()

    def fail_outstanding(self):
        super().fail_outstanding()
        print(json.dumps({"wakes": self.summary()}), file=sys.stderr,
              flush=True)

    def summary(self):
        woke = {id(r) for r in self.wakes}
        t0 = self.t_settled
        lat = {True: [], False: []}
        by_s = collections.Counter()
        for r in self.reqs:
            if t0 is None or r.t_ack is None or r.t_first < t0:
                continue
            lat[id(r) in woke].append(1000.0 * (r.t_ack - r.t_first))
            if id(r) in woke:
                by_s[math.floor(r.t_ack - t0)] += 1
        out = {
            "issued": len(self.wakes),
            "acked": len(lat[True]),
            "failed": sum(1 for r in self.wakes if r.failed),
            "sent_again": sum(r.sends - 1 for r in self.wakes),
            "asleep_at_settle": self.asleep_at_settle,
            "asleep_at_stop": self.asleep_at_stop,
            "acked_by_s": [by_s[s] for s in range(max(by_s, default=-1) + 1)],
        }
        for key, xs in (("wake_ms", sorted(lat[True])),
                        ("resident_ms", sorted(lat[False]))):
            out[key] = {"n": len(xs), "p50": _nearest_rank(xs, 0.50),
                        "p95": _nearest_rank(xs, 0.95),
                        "max": xs[-1] if xs else None}
        return out


def Loop(client, names, targets, traffic, seed):
    if not hasattr(client, "admin_sync"):
        raise RuntimeError(
            "closed_wake needs the client library's admin_sync (the stats "
            "admin op says who sleeps): the program under test has none")
    if traffic.get("budget") is not None:
        return closed.ClosedLoop(client, names, targets, traffic, seed)
    return ClosedWakeLoop(client, names, targets, traffic, seed)
