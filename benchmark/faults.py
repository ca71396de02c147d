"""Broken guarantees, for the runs that ``correct`` has to fail: the
control on the chip (``control.py``) and the tests (``tests/``).  Each
is a context manager that breaks the adder app underneath the timed path
— where an answer is produced — for one write: the ``nth`` executed on
``name``, counted per replica (every replica executes a name's writes in
the same order, so it is the same write everywhere).

``dropped_write``    acknowledged, applied nowhere: the configuration's
                     "an acknowledged write is read back" is broken
``double_execute``   applied twice on every replica: "executes exactly once"
``replica_behind``   applied on two replicas of three: "on all three
                     replicas"; the acknowledged values stay right when the
                     lagging replica is not the write's entry replica, so
                     only the read-back from every active can show it
"""

import contextlib
import threading


@contextlib.contextmanager
def _patched_execute(name, nth, times_for):
    """``times_for(i)``: how often the chosen write is applied on the
    i-th app (0, 1, 2) in the order the replicas first execute ``name``."""
    from gigapaxos_tpu.models.apps import StatefulAdderApp

    sound = StatefulAdderApp.execute
    lock = threading.Lock()
    apps, seen = [], {}

    def execute(self, request, do_not_reply_to_client=False):
        if request.get_service_name() != name:
            return sound(self, request, do_not_reply_to_client)
        with lock:
            if id(self) not in seen:
                seen[id(self)] = 0
                apps.append(id(self))
            seen[id(self)] += 1
            chosen = seen[id(self)] == nth
            times = times_for(apps.index(id(self))) if chosen else 1
        if times == 0:
            # answer as if applied: the reply carries the sum it would be
            self.totals[name] = self.totals.get(name, 0)
            delta = int(request.request_value)
            if hasattr(request, "response_value"):
                request.response_value = str(self.totals[name] + delta)
            return True
        for _ in range(times - 1):
            self.totals[name] = self.totals.get(name, 0) \
                + int(request.request_value)
        return sound(self, request, do_not_reply_to_client)

    StatefulAdderApp.execute = execute
    try:
        yield
    finally:
        StatefulAdderApp.execute = sound


def dropped_write(name, nth=3):
    return _patched_execute(name, nth, lambda i: 0)


def double_execute(name, nth=3):
    return _patched_execute(name, nth, lambda i: 2)


def replica_behind(name, nth=3, behind=2):
    return _patched_execute(name, nth, lambda i: 0 if i == behind else 1)


FAULTS = {"dropped_write": dropped_write, "double_execute": double_execute,
          "replica_behind": replica_behind}
