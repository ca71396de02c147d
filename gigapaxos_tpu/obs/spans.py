"""The one span primitive: every timed phase of a node goes through it.

``with span(registry, "step.dispatch", cpu=True, node=0, tick=41): ...``

* observes the wall time into the node's :class:`MetricsRegistry` as the
  histogram ``phase_step_dispatch_s`` (dots become underscores) — the
  ``phase_*_s`` family the stats op, ``/metrics`` and the benchmark read;
* with ``cpu=True`` (the tick thread's long spans) also observes the
  calling thread's CPU time as ``phase_step_dispatch_cpu_s``: wall minus CPU is
  the time that thread was NOT running — waiting for the interpreter
  lock, ``_state_lock``, the device or a socket.  The two reads of the
  thread's CPU clock are a span's whole cost where it matters: 11 us on
  the chip's host, 150 us with the interpreter lock contended, against
  3 us for the rest (PERF.md, PR 25) — right for a tick's phases of a
  millisecond or more, wrong for its short ones and for anything that
  runs a hundred times a second;
* wraps the body in ``jax.profiler.TraceAnnotation("gp.step.dispatch",
  node=0, tick=41)``, so that while a profiler session is open the span
  lies in the ``/host:CPU`` plane of the same ``.xplane.pb`` as the
  device's ``XLA Ops``, on the profiler's clock.  The annotation is
  always constructed; the open session is the only switch.

The rule for anyone adding a span: NO SPAN MAY ENCLOSE A WHOLE TICK.  The
benchmark's reducer names each idle gap of the device by the host event
that overlaps it most, so an enclosing span would take every gap and
name none.  The tick's envelope is a histogram only (``tick_s``);
spans tile it, and children nest under ``post_step`` alone.
"""

from __future__ import annotations

import functools
import time

from jax.profiler import TraceAnnotation


@functools.lru_cache(maxsize=None)
def _names(phase: str):
    stem = "phase_" + phase.replace(".", "_")
    return "gp." + phase, stem + "_s", stem + "_cpu_s"


def observe_interval(registry, phase: str, seconds: float) -> None:
    """A phase that begins with one message and ends with another, on
    whatever threads carry them (a reconfigurator's intent to its
    COMPLETE, an active's stop_epoch to the stop's execution): no
    ``with`` block can hold it, so it has no host event — only its
    histogram, named as :class:`span` names one."""
    if registry is not None:
        registry.observe(_names(phase)[1], seconds)


class span:
    """Context manager; see the module docstring.  ``registry`` may be
    None (a transport outside any node): the annotation alone is made.
    ``record=False`` keeps the annotation and skips the histograms (a
    phase that is only counted when it had work)."""

    __slots__ = ("_registry", "_names", "_cpu", "_record", "_ann",
                 "_t0", "_c0")

    def __init__(self, registry, phase: str, cpu: bool = False,
                 record: bool = True, **args):
        self._registry = registry
        self._names = _names(phase)
        self._cpu = cpu
        self._record = record
        self._ann = TraceAnnotation(self._names[0], **args)

    def __enter__(self) -> "span":
        self._ann.__enter__()
        if self._cpu:
            self._c0 = time.thread_time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        wall_s = time.perf_counter() - self._t0
        cpu_s = time.thread_time() - self._c0 if self._cpu else 0.0
        self._ann.__exit__(exc_type, exc, tb)
        reg = self._registry
        if reg is not None and self._record:
            reg.observe(self._names[1], wall_s)
            if self._cpu:
                reg.observe(self._names[2], cpu_s)
