"""The harness end to end on the CPU at a tiny size with the two
generators PR 27 brought: the open loop, and the closed loop with epoch
changes beside it (``test_run_cell.py``'s way: 256 rows, 8 names; the
steering lives here, not in an option of ``run.py``)."""

import json
import re

import pytest

import faults
import run
import trace_reduce


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"hbm_bytes_per_s": 1e11}}))
    monkeypatch.setattr(run, "PEAKS_FILE", str(peaks))
    monkeypatch.setattr(run, "WARM_TRAFFIC_S", 1.5)
    monkeypatch.setattr(run, "WARM_ROUND_RAMP_S", 0.5)
    monkeypatch.setattr(run, "SETTLE_S", 0.5)
    monkeypatch.setattr(run, "TRACE_S", 1.0)
    monkeypatch.setattr(run, "READ_BACK_S", 2.0)
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))

    def small(config):
        return {**config, "names": 8,
                "settings": {**config["settings"], "ENGINE_ROWS": 256},
                "engine": {**config["engine"], "rows": 256}}
    return small


def lines(capsys, key):
    out = capsys.readouterr()
    return [json.loads(l) for l in (out.out + out.err).splitlines()
            if l.startswith('{"' + key)]


def test_names_change_epoch_in_a_sound_traced_run(tiny, capsys):
    _, config, traffic, specs, e2e = run.load_cell("g1k-reconf")
    assert config["settings"]["RECONFIGURE_IN_PLACE"] is True
    traffic = {**traffic, "in_flight": 8, "ramp_s": 0.5,
               "reconfigure_per_s": 4.0}
    result = run.run_cell(tiny(config), traffic, specs, e2e, seed=2**31 + 27,
                          seconds=4.0, trace=True, expect_platform="cpu")
    checks = {c["check"]: c for c in lines(capsys, "check")}
    assert result["correct"] is True and result["failed"] == 0, checks
    assert checks["refusals"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0
    got = result["metrics"]
    # no device program of the lifecycle's name on the CPU's planes
    assert set(got) == {s["name"] for s in specs} \
        - {"reconf.lifecycle_device_ms.rcf"}
    # three starts a change, four changes a second
    assert 6.0 < got["reconf.epoch_changes_per_s.rcf"]["value"] < 18.0
    for name in ("reconf.stop_ms.rcf", "reconf.start_ms.rcf",
                 "reconf.drop_ms.rcf", "reconf.unwritable_ms.rcf",
                 "reconf.await_step_ms.rcf"):
        assert got[name]["value"] >= 0
    assert got["reconf.carried_per_change.rcf"]["value"] >= 0


def test_a_program_without_the_flag_ends_in_set_up(tiny):
    """What the parent's tree does with the cell: the first
    reconfiguration is acknowledged at the epoch the name had, and the
    loop raises before the window."""
    _, config, traffic, specs, e2e = run.load_cell("g1k-reconf")
    config = tiny(config)
    config["settings"] = {k: v for k, v in config["settings"].items()
                          if k != "RECONFIGURE_IN_PLACE"}
    traffic = {**traffic, "in_flight": 8, "ramp_s": 0.5,
               "reconfigure_per_s": 4.0}
    with pytest.raises(RuntimeError, match="RECONFIGURE_IN_PLACE"):
        run.run_cell(config, traffic, specs, e2e, seed=5, seconds=2.0,
                     trace=False, expect_platform="cpu")


def test_a_broken_guarantee_under_epoch_changes_is_not_correct(tiny, capsys):
    _, config, traffic, specs, e2e = run.load_cell("g1k-reconf")
    config = tiny(config)
    traffic = {**traffic, "in_flight": 8, "ramp_s": 0.5,
               "reconfigure_per_s": 4.0}
    with faults.FAULTS["replica_behind"](run.cell_names(config)[0]):
        result = run.run_cell(config, traffic, specs, e2e, seed=7,
                              seconds=1.5, trace=False,
                              expect_platform="cpu")
    assert result["correct"] is False
    failed = {c["check"] for c in lines(capsys, "check") if not c["ok"]}
    assert "replica_total_mismatches" in failed


def test_the_open_loop_in_a_sound_run(tiny, capsys):
    """``g1k-open80`` is in no entry of ``BENCHMARK.json`` (PERF.md
    section 7 keeps it), so the cell is put together here: the
    configuration of ``g1k-sat`` under the open loop's traffic file."""
    _, config, _, _, _ = run.load_cell("g1k-sat")
    traffic = {**run.load_json(run.HERE, "traffic", "open-280.json"),
               "rate_per_s": 40.0}
    e2e = ["commit_p50_ms", "commit_p95_ms", "setup_s"]
    result = run.run_cell(tiny(config), traffic, [], e2e, seed=2**31 + 3,
                          seconds=3.0, trace=False, expect_platform="cpu")
    checks = {c["check"]: c for c in lines(capsys, "check")}
    assert result["correct"] is True and result["failed"] == 0, checks
    assert 100 < result["attempted"] < 260     # 40 a second for ~4.5 s
    assert set(result["metrics"]) == set(e2e)
    assert result["metrics"]["commit_p50_ms"]["value"] > 0
