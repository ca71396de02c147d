"""The arithmetic between the program's counters and the numbers
reported: snapshot differences, the readers, percentiles, the window."""

import json
import os
import re

import pytest

import run
from generators.closed import Req
from step_bytes import step_bytes

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def snap(ticks, flips, blob, step_sum, step_n):
    return {"counters": {"ticks": ticks, "coordinator_flips": flips,
                         "blob_bytes_sent": blob},
            "hists": {"engine_step_s": {"sum": step_sum, "count": step_n}}}


BEFORE = [snap(100, 5, 1000, 2.0, 100), snap(110, 0, 2000, 3.0, 100),
          snap(90, 1, 0, 1.0, 50)]
AFTER = [snap(200, 7, 5000, 4.0, 200), snap(310, 0, 4000, 3.0, 100),
         snap(140, 1, 3000, 2.0, 100)]
CTX = {"before": BEFORE, "after": AFTER, "window_s": 10.0, "acks": 90,
       "trace": None}


def test_differences_of_two_snapshots():
    assert run.counter_delta(BEFORE, AFTER, "ticks") == [100, 200, 50]
    assert run.counter_delta(BEFORE, AFTER, "absent") == [0, 0, 0]
    assert run.hist_delta(BEFORE, AFTER, "engine_step_s") == [
        (2.0, 100), (0.0, 0), (1.0, 50)]
    assert run.hist_delta(BEFORE, AFTER, "absent") == [(0.0, 0)] * 3


@pytest.mark.parametrize("spec, expected", [
    ({"reader": "stats_counter", "counter": "coordinator_flips",
      "per": "total"}, 2.0),
    ({"reader": "stats_counter", "counter": "blob_bytes_sent",
      "per": "commit"}, 9000 / 90),
    ({"reader": "stats_counter", "counter": "blob_bytes_sent",
      "per": "second"}, 900.0),
    # the window over each active's ticks, mean of the three
    ({"reader": "stats_counter", "counter": "ticks", "per": "ms_per_count"},
     (100.0 + 50.0 + 200.0) / 3),
    # mean per observation, mean of the actives that observed anything
    ({"reader": "stats_hist", "hist": "engine_step_s", "scale": 1000.0},
     1000.0 * (2.0 / 100 + 1.0 / 50) / 2),
    ({"reader": "stats_hist", "hist": "absent"}, None),
    ({"reader": "trace", "pattern": "x", "stat": "ms_per_event"}, None),
])
def test_readers(spec, expected):
    spec = {"name": "m", "unit": "u", **spec}
    got = run.layer_metrics([spec], CTX)
    if expected is None:
        assert got == {}          # nothing to read: left out of the line
    else:
        assert got == {"m": {"value": pytest.approx(expected), "unit": "u"}}


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert run.percentile(xs, 0.5) == 50 and run.percentile(xs, 0.95) == 95
    assert run.percentile([7], 0.95) == 7 and run.percentile([], 0.5) is None
    assert run.percentile(list(range(1, 11)), 0.95) == 10


def test_end_to_end_counts_the_window_and_all_of_it():
    reqs = []
    for k, (t_first, t_ack) in enumerate(
            [(0.0, 9.9), (9.0, 10.0), (10.0, 10.5), (19.0, 19.9),
             (19.5, 20.0), (12.0, None)]):
        r = Req(0, 1, 0, t_first)
        r.t_ack = t_ack
        reqs.append(r)
    failed = Req(0, 1, 0, -15.0)       # given up at 15.0, inside the window
    failed.failed = True
    reqs.append(failed)
    e2e, n, lat = run.end_to_end(reqs, 10.0, 20.0, 30.0, 42.0, 4.0, 1000)
    assert lat == sorted(lat) and len(lat) == 4
    assert n == 3                      # acknowledged in [10, 20)
    assert e2e["committed_rps"] == (0.3, "req/s")
    # 1000, 500, 900 ms and the failure, slower than any
    assert e2e["commit_p50_ms"][0] == pytest.approx(900.0)
    assert e2e["commit_p95_ms"][0] == pytest.approx(30000.0)
    assert e2e["create_names_per_s"] == (250.0, "names/s")
    assert e2e["setup_s"] == (42.0, "s")


def test_step_bytes_against_a_hand_count():
    """65,536 rows, window 16, 8 lanes, 3 replicas; words of 4 bytes.
    state: 12 [G] + 7 [G,16] = 124 words a row, read once, written once
    gathered blobs: 3 x (4 [G] + 4 [G,16] = 68 words a row), read
    request ring: 8 words a row, read; want: 1; heat: 1 read, 1 written
    outputs: 6 [G] + 3 [G,16] = 54 words a row, written
    fresh blob: 68 words a row, written;  heard: 3 words in all"""
    per_row = 2 * 124 + 3 * 68 + 8 + 1 + 2 + 54 + 68
    assert per_row == 585
    assert step_bytes(65536, 16, 8, 3) == 4 * (65536 * per_row + 3) \
        == 153354252
    # two substeps: the state, the ring and the outputs once more
    assert step_bytes(65536, 16, 8, 3, 2) - step_bytes(65536, 16, 8, 3) \
        == 4 * 65536 * (2 * 124 + 8 + 54)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_units_and_files_agree_with_benchmark_json():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert bench["paths"] == ["benchmark"]
    configs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for c in configs.values():
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["name"] == c["name"] and body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and len(w["why"]) <= 200
        mix = json.load(open(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json")))
        assert os.path.exists(os.path.join(
            BENCH, "generators", mix["loop"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    files = {f[:-5] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
             if f.endswith(".json")}
    assert files == {m["name"] for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        spec = json.load(open(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".json")))
        assert {k: spec[k] for k in ("name", "unit", "better", "source",
                                     "layer", "moves")} == \
            {k: m[k] for k in ("name", "unit", "better", "source",
                               "layer", "moves")}
        assert spec["reader"] in run.READERS
    for path, _, names in os.walk(BENCH):
        if "__pycache__" in path:
            continue
        for f in names:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


PER_LAYER_MAX = 128     # the driver's contract for BENCHMARK.json


def _body(spec):
    """What a metric IS: its file less what names and describes it and
    where it is read.  ``moves`` is part of it: the same reader under
    ``.sat`` and ``.lat`` is two metrics, judged by two end-to-end ones."""
    return json.dumps({k: v for k, v in spec.items()
                       if k not in ("name", "cells", "what", "twin_of")},
                      sort_keys=True)


def per_layer_faults(bench, specs):
    """``per_layer`` was full at 128 (PR 37) because every new cell brought
    a suffixed copy of each common metric.  A metric is ONE entry for a
    reader and the end-to-end metric it moves, with the list of the cells
    that report it.  A PR that may edit no file and so has to bring a copy
    for its new cell says so in the copy's file (``"twin_of"``: the entry
    whose list the next ``benchmark`` PR lengthens by the copy's cells)."""
    faults = []
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    twins = sorted(n for n, s in specs.items() if "twin_of" in s)
    n = len(bench["per_layer"])
    if n > PER_LAYER_MAX:
        faults.append(
            f"per_layer holds {n} entries, {n - PER_LAYER_MAX} over the "
            f"{PER_LAYER_MAX} the driver admits; merging the declared twins "
            f"frees {len(twins)}: {twins}")
    heads, read_in = {}, {}
    for m in bench["per_layer"]:
        name, spec = m["name"], specs[m["name"]]
        body = _body(spec)
        if spec["cells"] != m["workloads"]:
            faults.append(f"{name}: workloads {m['workloads']} is not its "
                          f"file's cells {spec['cells']}")
        for cell in spec["cells"]:
            if cell not in e2e[spec["moves"]].get("workloads", cells):
                faults.append(f"{name}: {cell} does not report "
                              f"{spec['moves']}")
            other = read_in.setdefault((body, cell), name)
            if other != name:
                faults.append(f"{cell} reads {other} again as {name}")
        if "twin_of" not in spec:
            other = heads.setdefault(body, name)
            if other != name:
                faults.append(
                    f"{name} is {other} again: one entry, with both lists "
                    f"(or \"twin_of\": \"{other}\" in the file of a PR "
                    "that may edit none)")
    for name in twins:
        if heads.get(_body(specs[name])) != specs[name]["twin_of"]:
            faults.append(f"{name} is no copy of {specs[name]['twin_of']}")
    return faults


def test_one_entry_a_reader_and_judged_metric():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    specs = {m["name"]: json.load(open(os.path.join(
        BENCH, "layer_metrics", m["name"] + ".json")))
        for m in bench["per_layer"]}
    assert per_layer_faults(bench, specs) == []


def _tiny_bench(*entries):
    """Two cells judged by ``rps`` and one by ``p50``; ``entries`` are
    (name, moves, cells, further keys of the file)."""
    specs = {name: {"name": name, "moves": moves, "cells": list(cells),
                    "reader": "stats_hist", "hist": "tick_s",
                    "what": name + " in words", **more}
             for name, moves, cells, more in entries}
    return {"workloads": [{"name": c} for c in ("a", "b", "c")],
            "end_to_end": [{"name": "rps", "workloads": ["a", "b"]},
                           {"name": "p50", "workloads": ["c"]},
                           {"name": "setup_s"}],
            "per_layer": [{"name": n, "workloads": list(s["cells"])}
                          for n, s in specs.items()]}, specs


@pytest.mark.parametrize("entries, fault", [
    # one reader under two judged metrics is two metrics
    ([("t.sat", "rps", "ab", {}), ("t.lat", "p50", "c", {})], None),
    # the copy a new cell used to bring: one entry with both cells instead
    ([("t.sat", "rps", "a", {}), ("t.new", "rps", "b", {})],
     "t.new is t.sat again"),
    # ... unless its file says whose copy it is (a PR that may edit no file)
    ([("t.sat", "rps", "a", {}), ("t.new", "rps", "b", {"twin_of": "t.sat"})],
     None),
    ([("t.sat", "rps", "a", {}), ("t.new", "rps", "b", {"twin_of": "t.lat"})],
     "t.new is no copy of t.lat"),
    ([("t.sat", "rps", "ab", {}), ("t.new", "rps", "b", {"twin_of": "t.sat"})],
     "b reads t.sat again as t.new"),
    ([("t.sat", "rps", "a", {}), ("t.n1", "rps", "b", {"twin_of": "t.sat"}),
      ("t.n2", "rps", "b", {"twin_of": "t.sat"})],
     "b reads t.n1 again as t.n2"),
    # every cell of an entry reports what the entry moves
    ([("t.sat", "rps", "ac", {})], "t.sat: c does not report rps"),
    ([(f"t{i}.sat", "rps", "a", {"hist": f"h{i}"})
      for i in range(PER_LAYER_MAX + 1)],
     "per_layer holds 129 entries, 1 over the 128"),
])
def test_the_rule_that_keeps_per_layer_from_filling_up(entries, fault):
    bench, specs = _tiny_bench(*entries)
    faults = per_layer_faults(bench, specs)
    if fault is None:
        assert faults == []
    else:
        assert len(faults) == 1 and faults[0].startswith(fault), faults


def test_workloads_is_the_files_cells():
    bench, specs = _tiny_bench(("t.sat", "rps", "ab", {}))
    bench["per_layer"][0]["workloads"] = ["a"]
    assert per_layer_faults(bench, specs)[0].startswith(
        "t.sat: workloads ['a'] is not its file's cells")
