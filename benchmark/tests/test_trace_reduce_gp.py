"""The program's own host spans in the reduction: a recorded slice of a
real trace (TPU v5e, ``g1k-sat``, 0.4 s cut out of a traced run by
``trace_reduce.cut``, PR 25) in which ``gigapaxos_tpu`` wrote ``gp.*``
events into the ``/host:CPU`` plane beside the device's ``XLA Ops`` —
and the case that shows why no span of the program encloses a tick."""

import gzip
import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def planes():
    with gzip.open(os.path.join(HERE, "recorded_trace_v5e_gp.json.gz"),
                   "rt") as f:
        raw = json.load(f)
    return {p: {line: [tuple(ev) for ev in evs]
                for line, evs in lines.items()}
            for p, lines in raw.items()}


def test_the_recorded_trace_holds_gp_spans_beside_the_devices_ops(planes):
    host = planes[trace_reduce.HOST_PLANE]
    names = {n for evs in host.values() for n, _, _ in evs}
    # the tick thread's spans and the transport loops', by name alone:
    # node and tick are stats of the event, not part of its name
    assert {"gp.tick.gather", "gp.step.dispatch", "gp.step.device_wait",
            "gp.post_step", "gp.publish", "gp.blob.send",
            "gp.blob.decode"} <= names
    assert not any("#" in n or "node=" in n for n in names
                   if n.startswith("gp."))
    device = [p for p in planes if trace_reduce.DEVICE_PLANE.match(p)]
    assert len(device) == 1 and planes[device[0]][trace_reduce.OPS_LINE]


def test_idle_gaps_carry_the_programs_names(planes):
    r = trace_reduce.reduce_planes(planes)
    labels = [name for name, _ in r["idle_gaps"]]
    assert labels[0].startswith("gp.")
    assert "host: nothing traced" not in labels[:3]
    by_gp = sum(s for name, s in r["idle_gaps"] if name.startswith("gp."))
    assert by_gp > 0.9 * sum(s for _, s in r["idle_gaps"])
    # the device's own numbers are what they were without the spans
    step = trace_reduce.program_time(r, r"^jit_run(_heat)?\b")
    assert 1000.0 * step["seconds"] / step["events"] == pytest.approx(
        1.845, abs=0.01)


def test_a_span_around_the_tick_would_take_every_gap(planes):
    """Each gap goes to the host event that overlaps it most, ties to
    the earlier start: an event that covers everything wins them all."""
    every = [ev for lines in planes.values() for evs in lines.values()
             for ev in evs]
    lo, hi = min(s for _, s, _ in every), max(e for _, _, e in every)
    host = dict(planes[trace_reduce.HOST_PLANE])
    host["enclosing"] = [("gp.tick", lo, hi)]
    r = trace_reduce.reduce_planes({**planes, trace_reduce.HOST_PLANE: host})
    assert [name for name, _ in r["idle_gaps"]] == ["gp.tick"]
