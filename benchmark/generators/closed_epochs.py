"""The closed loop of ``closed.py`` with names changing epoch beside it:
while the logical clients write, in-place reconfigurations
``client.reconfigure(name, [0, 1, 2])`` go out on a fixed schedule, as a
placement layer re-homes names while their writers keep writing
(upstream's ``TESTReconfigurationClient`` test04 with
``RECONFIGURE_IN_PLACE``).

Parameters, beside those of ``closed.py`` (a traffic file):

``reconfigure_per_s``        one reconfiguration every 1/this seconds from
                             the start of the loop, whatever became of the
                             earlier ones; names in a permutation of the
                             configuration's drawn from the seed, then
                             round again

Each call is made once, by one of a pool of threads as large as the calls
that can be unanswered at a time (the rate times ``TIMEOUT_S``), and has
failed when it is unanswered after ``TIMEOUT_S``.  It is a refusal — appended to ``errors``, which
the harness holds to 0 — when it is unanswered, answered not ``ok``, or
answered with an epoch that is not the name's previous epoch plus one.
The FIRST call is the cell's own precondition: if its acknowledgement's
epoch did not rise, the program under test changes no epoch in place (it
has no ``RECONFIGURE_IN_PLACE``), and :meth:`poll` raises — the run ends
in set-up instead of measuring a closed loop with nothing beside it.

No reconfiguration is issued when the dict has ``budget`` (the harness's
warm-up round).  At the end of the drain (:meth:`fail_outstanding`) one
JSON line on standard error counts what was issued and acknowledged,
second by second from the start of the loop.
"""

import json
import math
import queue
import sys
import threading
import time

import numpy as np

from generators import closed  # benchmark/ is on the loader's path

SEED_OFFSET = 0x5EED  # the permutation's stream, apart from the writes'
TIMEOUT_S = 15.0      # a reconfiguration unanswered this long has failed


class Reconf:
    """One reconfiguration, due time to acknowledgement."""

    __slots__ = ("name", "t_due", "t_issued", "t_done", "ack", "error")

    def __init__(self, name, t_due):
        self.name, self.t_due = name, t_due
        self.t_issued = self.t_done = self.ack = self.error = None


class ClosedEpochsLoop(closed.ClosedLoop):
    def __init__(self, client, names, targets, traffic, seed,
                 clock=time.perf_counter, sleep=time.sleep):
        super().__init__(client, names, targets, traffic, seed, clock=clock)
        self.sleep = sleep
        self.per_s = float(traffic["reconfigure_per_s"]) \
            if self.budget is None else 0.0
        self.n_threads = max(4, math.ceil(self.per_s * TIMEOUT_S))
        self.everyone = list(range(len(targets)))
        self.order = np.random.default_rng(seed + SEED_OFFSET).permutation(
            len(names)).tolist()
        self.epoch = [0] * len(names)   # every name was created at epoch 0
        self.reconfs = []               # every reconfiguration, by due time
        self.calls_in_flight = 0        # due and not yet answered
        self.fatal = None               # the first call changed no epoch
        self.jobs = queue.Queue()
        self.threads = []

    # -- the schedule ------------------------------------------------------
    def start(self):
        super().start()
        if self.per_s <= 0:
            return
        self.threads = [threading.Thread(
            target=self._schedule, name="epochs-schedule", daemon=True)]
        self.threads += [threading.Thread(
            target=self._work, name=f"epochs-{i}", daemon=True)
            for i in range(self.n_threads)]
        for t in self.threads:
            t.start()

    def _schedule(self):
        k = 0
        while self.issuing:
            due = self.t_start + (k + 1) / self.per_s
            now = self.clock()
            if now < due:
                self.sleep(min(0.05, due - now))
                continue
            self.issue(self.order[k % len(self.order)], due)
            k += 1

    def issue(self, name, due):
        rc = Reconf(name, due)
        with self.lock:
            if not self.issuing:
                return None
            self.reconfs.append(rc)
            self.calls_in_flight += 1
        self.jobs.put(rc)
        return rc

    def _work(self):
        while True:
            rc = self.jobs.get()
            if rc is None:
                return
            self.call(rc)

    def call(self, rc):
        """One blocking call of the client library, and the verdict."""
        rc.t_issued = self.clock()
        try:
            rc.ack = self.client.reconfigure(
                self.names[rc.name], self.everyone, timeout=TIMEOUT_S)
        except Exception as e:  # the library's own failure is a refusal too
            rc.ack, rc.error = None, repr(e)
        rc.t_done = self.clock()
        with self.lock:
            self.calls_in_flight -= 1
            before = self.epoch[rc.name]
            ack = rc.ack
            if ack is None:
                rc.error = rc.error or "unanswered"
            elif not ack.get("ok"):
                rc.error = f"refused: {ack.get('reason')}"
            elif ack.get("epoch") != before + 1:
                rc.error = f"epoch {ack.get('epoch')} after {before}"
                if rc is self.reconfs[0] and ack.get("epoch") == 0:
                    self.fatal = (
                        f"the first reconfiguration of "
                        f"{self.names[rc.name]!r} was acknowledged at epoch "
                        "0, as the name was created: the program changes no "
                        "epoch in place (RECONFIGURE_IN_PLACE)")
            else:
                self.epoch[rc.name] = before + 1
            if rc.error:
                self.errors.append((self.names[rc.name],
                                    "reconfigure: " + rc.error))

    # -- the harness's thread ----------------------------------------------
    def poll(self):
        if self.fatal:
            raise RuntimeError(self.fatal)
        super().poll()

    def stop(self):
        super().stop()
        for _ in self.threads[1:]:
            self.jobs.put(None)

    def outstanding(self):
        n = super().outstanding()
        with self.lock:
            return n + self.calls_in_flight

    def fail_outstanding(self):
        super().fail_outstanding()
        if self.per_s > 0:
            print(json.dumps({"reconfigurations": self.summary()}),
                  file=sys.stderr, flush=True)

    def summary(self):
        """Counts by the second of the loop in which a reconfiguration was
        acknowledged ``ok`` with its epoch risen by one (the window is
        seconds 10 to 50 of the loop), the calls' latency from due time,
        and how late the pool got to them."""
        with self.lock:
            done = [rc for rc in self.reconfs if rc.t_done is not None]
            good = [rc for rc in done if not rc.error]
            lat = sorted(rc.t_done - rc.t_due for rc in good)
            late = sorted(rc.t_issued - rc.t_due for rc in done)
            per_s = {}
            for rc in good:
                s = int(rc.t_done - self.t_start)
                per_s[s] = per_s.get(s, 0) + 1

            def pct(xs, q):
                return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else None
            return {
                "per_s": self.per_s, "issued": len(self.reconfs),
                "ok": len(good), "errors": len(done) - len(good),
                "unfinished": len(self.reconfs) - len(done),
                "ok_within_10s": sum(1 for x in lat if x <= 10.0),
                "latency_s": {"p50": pct(lat, 0.5), "p95": pct(lat, 0.95),
                              "max": lat[-1] if lat else None},
                "issued_late_s": {"p95": pct(late, 0.95),
                                  "max": late[-1] if late else None},
                "ok_by_second": [per_s.get(s, 0)
                                 for s in range(max(per_s, default=-1) + 1)],
            }


Loop = ClosedEpochsLoop
