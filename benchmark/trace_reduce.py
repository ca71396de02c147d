"""From the profiler's trace (``*.xplane.pb``) to the numbers the
benchmark reports: the device's busy time (the union of the intervals in
which an operation ran), the device time of each program, the operations
that took most time, and the longest idle gaps by what the host was
doing.  Reads the file with nothing but JAX (``ProfileData``).

What the planes look like on a TPU v5e (looked at by hand, PR 24): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per run of a compiled program, named ``jit_<fn>(<fingerprint>)``)
and ``XLA Ops`` (one event per operation inside it); host threads are
lines of the plane ``/host:CPU``.
"""

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
MIN_GAP_S = 100e-6  # shorter gaps are the device's own, between operations


def short_op(name):
    """``%fusion.192 = (s32[...]) fusion(...)`` -> ``fusion.192``: an
    operation's event carries its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def merge(intervals):
    """Union of (start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def read_planes(path):
    """{plane: {line: [(name, start_s, end_s)]}}"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
    return planes


def _label_gaps(gaps, host_events):
    """Seconds of idle gaps by the host event that covers most of each."""
    host_events.sort(key=lambda ev: ev[1])
    starts = [ev[1] for ev in host_events]
    longest = max((e - s for _, s, e in host_events), default=0.0)
    by_label = defaultdict(float)
    for g0, g1 in gaps:
        best, best_overlap = "host: nothing traced", 0.0
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_right(starts, g1)
        for name, s, e in host_events[lo:hi]:
            overlap = min(e, g1) - max(s, g0)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        by_label[best] += g1 - g0
    return sorted(([k, v] for k, v in by_label.items()),
                  key=lambda kv: -kv[1])


def reduce_planes(planes):
    devices = {n: p for n, p in planes.items() if DEVICE_PLANE.match(n)}
    every = [ev for p in planes.values() for evs in p.values() for ev in evs]
    window_s = (max(e for _, _, e in every) - min(s for _, s, _ in every)) \
        if every else 0.0
    busy, programs, ops, gaps = [], defaultdict(lambda: [0, 0.0]), \
        defaultdict(float), []
    for plane in devices.values():
        op_events = plane.get(OPS_LINE) or [
            ev for name, evs in plane.items() if name != MODULES_LINE
            for ev in evs]
        merged = merge((s, e) for _, s, e in op_events)
        busy.append(sum(e - s for s, e in merged))
        gaps += [(a[1], b[0]) for a, b in zip(merged, merged[1:])
                 if b[0] - a[1] >= MIN_GAP_S]
        for name, s, e in op_events:
            ops[short_op(name)] += e - s
        for name, s, e in plane.get(MODULES_LINE, ()):
            programs[name][0] += 1
            programs[name][1] += e - s
    host_events = [ev for evs in planes.get(HOST_PLANE, {}).values()
                   for ev in evs]
    n = max(1, len(devices))
    return {
        "planes": {n_: sorted(p) for n_, p in planes.items()},
        "devices": len(devices),
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "programs": {k: {"events": v[0], "seconds": v[1] / n}
                     for k, v in programs.items()},
        "top_ops": sorted(([k, v / n] for k, v in ops.items()),
                          key=lambda kv: -kv[1]),
        "idle_gaps": [[k, v / n] for k, v in _label_gaps(gaps, host_events)],
    }


def program_time(reduced, pattern):
    """The program whose name matches ``pattern`` and took most device
    time: ``{"name", "events", "seconds"}``, or None."""
    rx = re.compile(pattern)
    hits = [(v["seconds"], k, v) for k, v in reduced["programs"].items()
            if rx.search(k)]
    if not hits:
        return None
    _, name, v = max(hits)
    return {"name": name, **v}


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir):
    return reduce_planes(read_planes(find_xplane(trace_dir)))


def by_hand(planes, out):
    """What a trace holds, plane by plane and line by line: the first
    thing to read before trusting the reduction."""
    import json

    for pname, lines in planes.items():
        for lname, evs in lines.items():
            names = defaultdict(lambda: [0, 0.0])
            for n_, s, e in evs:
                names[n_][0] += 1
                names[n_][1] += e - s
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:8]
            print(pname, "|", lname, "|", len(evs), "events |", top, file=out)
    print(json.dumps(reduce_planes(planes), indent=1)[:8000], file=out)


def cut(planes, start_s, seconds):
    """The events that start inside a slice of the trace, as plain lists:
    small enough to keep as a recorded trace for the tests."""
    t0 = min(s for p in planes.values() for evs in p.values()
             for _, s, _ in evs) + start_s
    return {
        pname: {lname: [[n, s - t0, e - t0] for n, s, e in evs
                        if t0 <= s < t0 + seconds]
                for lname, evs in lines.items()}
        for pname, lines in planes.items()
    }


if __name__ == "__main__":
    import sys

    by_hand(read_planes(sys.argv[1]), sys.stdout)
