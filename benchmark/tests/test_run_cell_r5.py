"""The cell ``g1k-r5-lat`` end to end on the CPU at a tiny size
(``test_run_cell_new_loops.py``'s way: 256 rows, 8 names; the steering
lives here, not in an option of ``run.py``): five actives boot, every name
is created on all five, a sound traced run is correct with every write
read back from FIVE apps and reports the cell's entries, a fault from
``faults.py`` under it comes out as not correct, and the entries stand
under ``test_metrics.py``'s rule.  It pins no total and no position in
``per_layer``: the next PR appends behind these."""

import json
import os

import faults
import run
from test_metrics import PER_LAYER_MAX, per_layer_faults
from test_run_cell_new_loops import lines, tiny  # noqa: F401  (fixture)

CELL = "g1k-r5-lat"
TWINS = {"device.idle_share.r5", "tick.busy_ms.r5", "tick.per_commit.r5",
         "tick.gather_ms.r5", "step.dispatch_ms.r5",
         "transport.blob_encode_ms.r5"}
HEADS = {"step.device_ms.r5", "step_roofline.r5",
         "quorum.accepts_at_decision.r5"}


def small_cell(tiny):
    cell, config, traffic, specs, e2e = run.load_cell(CELL)
    assert cell == {**cell, "config": "upstream-1k-groups-r5",
                    "traffic": "closed-9-clients", "chips": 1}
    assert e2e == ["commit_p50_ms", "commit_p95_ms", "create_names_per_s",
                   "setup_s"]
    assert len(config["actives"]) == config["replicas_per_name"] \
        == config["engine"]["replicas"] \
        == config["settings"]["DEFAULT_NUM_REPLICAS"] == 5
    # nine clients on eight ordered names never start
    return tiny(config), {**traffic, "in_flight": 8, "ramp_s": 0.5}, \
        specs, e2e


def test_the_file_is_upstream_1k_groups_but_for_the_five():
    """Everything the program sees but the number of actives and of
    replicas a name is ``g1k-lat``'s configuration, key for key."""
    _, r5, traffic5, _, _ = run.load_cell(CELL)
    _, r3, traffic3, _, _ = run.load_cell("g1k-lat")
    assert traffic5 == traffic3
    for key in ("names", "reconfigurators", "name_prefix", "payload_bytes",
                "journal"):
        assert r5[key] == r3[key], key
    assert r5["actives"] == ["AR0", "AR1", "AR2", "AR3", "AR4"]
    assert {**r5["settings"], "DEFAULT_NUM_REPLICAS": None} \
        == {**r3["settings"], "DEFAULT_NUM_REPLICAS": None}
    assert {**r5["engine"], "replicas": 3} == r3["engine"]
    assert sorted(r5["reduced"]) == sorted(r3["reduced"])
    assert len(r5["guarantees"]) == len(r3["guarantees"]) == 4
    assert "three" in r5["guarantees"][0] and "five" in r5["guarantees"][2]


def test_the_cell_in_a_sound_traced_run(tiny, capsys):
    config, traffic, specs, e2e = small_cell(tiny)
    result = run.run_cell(config, traffic, specs, e2e, seed=2**31 + 43,
                          seconds=4.0, trace=True, expect_platform="cpu")
    checks = {c["check"]: c for c in lines(capsys, "check")}
    assert result["correct"] is True and result["failed"] == 0, checks
    for what in ("ack_value_mismatches", "replica_total_mismatches",
                 "replicas_missing", "compiles_in_window", "refusals"):
        assert checks[what]["value"] == 0, checks[what]
    assert result["attempted"] > 8
    got = result["metrics"]
    # no program of the step's name on the CPU's planes
    assert set(got) == {s["name"] for s in specs} \
        - {"step.device_ms.r5", "step_roofline.r5"}
    assert len(specs) == len(TWINS | HEADS)
    # a decision is seen at three accepts of five at the least
    assert 3.0 <= got["quorum.accepts_at_decision.r5"]["value"] <= 5.0
    assert got["tick.per_commit.r5"]["value"] >= 3
    for name, m in got.items():
        if m["unit"] == "%":
            assert 0 <= m["value"] <= 100, (name, m)


def test_a_fault_under_five_replicas_is_not_correct(tiny, capsys):
    config, traffic, specs, e2e = small_cell(tiny)
    with faults.FAULTS["double_execute"](run.cell_names(config)[0]):
        result = run.run_cell(config, traffic, specs, e2e, seed=7,
                              seconds=1.5, trace=False,
                              expect_platform="cpu")
    assert result["correct"] is False
    failed = {c["check"] for c in lines(capsys, "check") if not c["ok"]}
    assert failed & {"ack_value_mismatches", "replica_total_mismatches"}


def test_the_entries_stand_under_the_rule():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    specs = {m["name"]: run.load_json(run.HERE, "layer_metrics",
                                      m["name"] + ".json")
             for m in bench["per_layer"]}
    assert per_layer_faults(bench, specs) == []
    assert len(bench["per_layer"]) <= PER_LAYER_MAX
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].endswith(".r5")}
    assert set(mine) == TWINS | HEADS
    for name, m in mine.items():
        assert m["workloads"] == [CELL] == specs[name]["cells"]
        assert m["moves"] == "commit_p50_ms"
    for name in TWINS:
        assert specs[name]["twin_of"] == name[:-3] + ".lat"
    assert all("twin_of" not in specs[n] for n in HEADS)
    for e in bench["end_to_end"]:
        if e["name"] in ("commit_p50_ms", "commit_p95_ms",
                         "create_names_per_s"):
            assert e["workloads"][-1] == CELL
