"""The reduction from the profiler's trace to numbers: on a recorded
slice of a real trace (TPU v5e, ``g1k-sat``, 0.25 s cut out of a traced
run by ``trace_reduce.cut``, PR 24) and on planes made by hand."""

import gzip
import json
import os

import pytest

import run
import trace_reduce
from step_bytes import step_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
PATTERN = json.load(open(os.path.join(
    os.path.dirname(HERE), "layer_metrics", "step.device_ms.sat.json")))["pattern"]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_trace_v5e.json.gz"), "rt") as f:
        planes = json.load(f)
    return trace_reduce.reduce_planes({
        p: {line: [tuple(ev) for ev in evs] for line, evs in lines.items()}
        for p, lines in planes.items()})


def test_recorded_trace_programs_and_busy_time(recorded):
    assert recorded["devices"] == 1
    # four runs of the 65,536-row step, 1.84 ms each; the reconfigurators'
    # 64-row step has the same name and is not in this slice, the
    # convert_element_type program is 1 us a run
    step = trace_reduce.program_time(recorded, PATTERN)
    assert step["name"].startswith("jit_run_heat(") and step["events"] == 4
    assert step["seconds"] == pytest.approx(0.007378, abs=1e-6)
    assert trace_reduce.program_time(recorded, "^jit_nothing") is None
    # the operations' union is the programs' time less the gaps inside them
    assert recorded["busy_s"] == pytest.approx(0.0073717, abs=1e-6)
    assert recorded["busy_s"] <= sum(
        p["seconds"] for p in recorded["programs"].values())
    assert recorded["window_s"] == pytest.approx(0.2375, abs=1e-3)
    assert recorded["top_ops"][0][0] == "fusion.192"
    assert all(len(name) <= 80 for name, _ in recorded["top_ops"])
    # what the host was doing while the device sat idle
    assert recorded["idle_gaps"][0][0] == "np.asarray(jax.Array)"
    assert sum(s for _, s in recorded["idle_gaps"]) < recorded["window_s"]


def test_recorded_trace_through_the_metric_readers(recorded):
    ctx = {"trace": recorded, "peaks": {"hbm_bytes_per_s": 819e9},
           "step_bytes": step_bytes(65536, 16, 8, 3), "before": [], "after": [],
           "device": {"memory_peak_bytes": 5}}
    specs = [json.load(open(os.path.join(
        os.path.dirname(HERE), "layer_metrics", name + ".json")))
        for name in ("step.device_ms.sat", "step_roofline.sat",
                     "device.idle_share.sat", "device.peak_hbm_bytes.sat")]
    got = {k: v["value"] for k, v in run.layer_metrics(specs, ctx).items()}
    assert got["step.device_ms.sat"] == pytest.approx(1.8445, abs=1e-3)
    # 153,354,252 bytes at 819 GB/s is 0.1872 ms
    assert got["step_roofline.sat"] == pytest.approx(
        100 * 0.18724 / 1.8445, abs=0.01)
    assert got["device.idle_share.sat"] == pytest.approx(
        100 * (1 - 0.0073717 / recorded["window_s"]), abs=1e-3)
    assert got["device.peak_hbm_bytes.sat"] == 5.0
    assert run.layer_metrics(specs[:3], {**ctx, "trace": None}) == {}


def test_planes_made_by_hand():
    ops = trace_reduce.OPS_LINE
    mods = trace_reduce.MODULES_LINE
    planes = {
        "/device:TPU:0": {
            ops: [("%a = f()", 0.0, 1.0), ("%b = g()", 0.5, 1.5),  # overlap
                  ("%a = f()", 3.0, 4.0)],
            mods: [("jit_run_heat(1)", 0.0, 1.5), ("jit_run_heat(1)", 3.0, 4.0),
                   ("jit_run_heat(2)", 1.6, 1.7)],
        },
        "/device:TPU:1": {ops: [("%a = f()", 0.0, 0.5)], mods: []},
        "/host:CPU": {"python3": [("waiting", 1.4, 3.1), ("short", 2.0, 2.1)],
                      "other": [("elsewhere", 9.0, 10.0)]},
        "#Chip0 Misc": {},
    }
    r = trace_reduce.reduce_planes(planes)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((2.5 + 0.5) / 2)   # mean of the chips
    assert r["window_s"] == pytest.approx(10.0)
    assert r["top_ops"] == [["a", pytest.approx(2.5 / 2)],
                            ["b", pytest.approx(1.0 / 2)]]
    step = trace_reduce.program_time(r, r"^jit_run(_heat)?\b")
    assert step == {"name": "jit_run_heat(1)", "events": 2,
                    "seconds": pytest.approx(2.5 / 2)}
    assert r["idle_gaps"] == [["waiting", pytest.approx(1.5 / 2)]]
    assert trace_reduce.merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [
        [0, 2.5], [3, 4]]
    empty = trace_reduce.reduce_planes({"/host:CPU": {"t": [("x", 0.0, 1.0)]}})
    assert empty["devices"] == 0 and empty["busy_s"] == 0.0
