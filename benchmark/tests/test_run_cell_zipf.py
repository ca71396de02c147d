"""The cell ``g1k-zipf`` end to end on the CPU at a tiny size
(``test_run_cell_new_loops.py``'s way: 256 rows, 8 names; the steering
lives here, not in an option of ``run.py``): a sound traced run is correct
and reports the admission metrics and the declared twins, a fault from
``faults.py`` under the zipfian loop comes out as not correct, and the
seventeen entries stand under ``test_metrics.py``'s rule."""

import json
import os

import faults
import run
from test_metrics import per_layer_faults
from test_run_cell_new_loops import lines, tiny  # noqa: F401  (fixture)

OWN = {"admission.requests_per_proposal.zpf", "admission.coalesced_share.zpf",
       "admission.rows_per_dispatch.zpf", "admission.admitted_share.zpf",
       "admission.busiest_coordinator_share.zpf"}


def small_cell(tiny):
    cell, config, traffic, specs, e2e = run.load_cell("g1k-zipf")
    assert cell == {**cell, "config": "ycsb-zipf-1k-groups",
                    "traffic": "closed-500-zipf99", "chips": 1}
    assert e2e == ["committed_rps", "setup_s"]
    return tiny(config), {**traffic, "in_flight": 64, "ramp_s": 0.5}, \
        specs, e2e


def test_the_cell_in_a_sound_traced_run(tiny, capsys):
    config, traffic, specs, e2e = small_cell(tiny)
    result = run.run_cell(config, traffic, specs, e2e, seed=2**31 + 41,
                          seconds=4.0, trace=True, expect_platform="cpu")
    checks = {c["check"]: c for c in lines(capsys, "check")}
    assert result["correct"] is True and result["failed"] == 0, checks
    assert checks["ack_value_mismatches"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0
    assert result["attempted"] > 64
    got = result["metrics"]
    # no program of the step's name on the CPU's planes
    assert set(got) == {s["name"] for s in specs} \
        - {"step.device_ms.zpf", "step_roofline.zpf"}
    assert OWN <= set(got) and len(specs) == 17
    # 64 clients on 8 names, ~24 on the hottest: batches and lone vids
    assert got["admission.requests_per_proposal.zpf"]["value"] > 1
    assert 0 < got["admission.coalesced_share.zpf"]["value"] <= 100
    assert 1 <= got["admission.rows_per_dispatch.zpf"]["value"] <= 8
    assert 0 < got["admission.admitted_share.zpf"]["value"] <= 100
    assert 100 / 3 <= got["admission.busiest_coordinator_share.zpf"][
        "value"] <= 100
    for name, m in got.items():
        if m["unit"] == "%":
            assert 0 <= m["value"] <= 100, (name, m)


def test_the_loops_summary_line_and_a_fault_under_it(tiny, capsys):
    """``double_execute`` on the configuration's first name, whatever its
    rank under this seed's permutation: with 8 names every name is
    written within the window."""
    config, traffic, specs, e2e = small_cell(tiny)
    with faults.FAULTS["double_execute"](run.cell_names(config)[0]):
        result = run.run_cell(config, traffic, specs, e2e, seed=7,
                              seconds=1.5, trace=False,
                              expect_platform="cpu")
    assert result["correct"] is False
    out = capsys.readouterr()
    both = (out.out + out.err).splitlines()
    failed = {json.loads(l)["check"] for l in both
              if l.startswith('{"check') and not json.loads(l)["ok"]}
    assert failed & {"ack_value_mismatches", "replica_total_mismatches"}
    zipf = [json.loads(l) for l in both if l.startswith('{"zipf')][-1]["zipf"]
    assert zipf["requests"] == result["attempted"]
    assert zipf["names_drawn"] == 8 and zipf["hottest_share"] > 0.2


def test_the_seventeen_entries_stand_under_the_rule():
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    specs = {m["name"]: run.load_json(run.HERE, "layer_metrics",
                                      m["name"] + ".json")
             for m in bench["per_layer"]}
    assert per_layer_faults(bench, specs) == []
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".zpf")]
    assert len(mine) == 17 and len(bench["per_layer"]) == 115
    assert bench["per_layer"][-17:] == mine          # appended, in order
    twins = [m["name"] for m in mine if m["name"] not in OWN]
    for m in mine:
        assert m["workloads"] == ["g1k-zipf"] == specs[m["name"]]["cells"]
        assert m["moves"] == "committed_rps"
    for name in twins:
        assert specs[name]["twin_of"] == name[:-4] + ".sat"
    assert all(specs[n]["layer"] == "admission" and "twin_of" not in specs[n]
               for n in OWN)
