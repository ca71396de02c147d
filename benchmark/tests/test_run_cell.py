"""The harness end to end on the CPU at a tiny size (256 rows, 8 names):
the steering — the platform, the sizes, a peaks table and a device plane
for the CPU — lives here, not in an option of ``run.py``.  The look for a
chip in ``run.main`` is skipped; everything after it is driven."""

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
    monkeypatch.setattr(run, "WARM_TRAFFIC_S", 0.5)
    monkeypatch.setattr(run, "WARM_ROUND_RAMP_S", 0.5)
    monkeypatch.setattr(run, "SETTLE_S", 0.5)
    monkeypatch.setattr(run, "TRACE_S", 1.0)
    monkeypatch.setattr(run, "READ_BACK_S", 2.0)
    # on the CPU backend the operations run on the host's threads
    monkeypatch.setattr(trace_reduce, "DEVICE_PLANE", re.compile(r"^/host:CPU$"))
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))

    def cell(workload):
        _, config, traffic, specs, e2e = run.load_cell(workload)
        config = {**config, "names": min(8, config["names"]),
                  "settings": {**config["settings"], "ENGINE_ROWS": 256},
                  "engine": {**config["engine"], "rows": 256}}
        traffic = {**traffic, "in_flight": min(
            traffic["in_flight"], 8 if traffic["per_name_order"] else 64)}
        return config, traffic, specs, e2e
    return cell


def test_a_sound_traced_run_is_correct_and_reports_its_metrics(tiny, tmp_path):
    config, traffic, specs, e2e = tiny("g1k-sat")
    result = run.run_cell(config, traffic, specs, e2e, seed=2**31 + 11,
                          seconds=3.0, trace=True, expect_platform="cpu")
    json.dumps(result)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    # each number compared beside its limit, as the result's last key
    assert list(result)[-1] == "compared" and len(result["compared"]) == 6
    assert all(n == {"value": 0, "limit": 0}
               for n in result["compared"].values())
    assert result["device"]["busy_s"] > 0
    assert 0 < result["device"]["window_s"] < 3.0
    got = result["metrics"]
    # no program of the step's name on the CPU's planes: those readers
    # find nothing and the line leaves them out; the rest is there
    absent = {"step.device_ms.sat", "step_roofline.sat",
              "device.peak_hbm_bytes.sat"}
    assert set(got) == {s["name"] for s in specs} - absent
    assert 0 < got["device.idle_share.sat"]["value"] < 100
    assert got["tick.ms.sat"]["value"] > 0
    assert result["breakdown"]["device_ops"]
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert not list(tmp_path.glob("gp_bench_*")), "journals or trace left"


@pytest.mark.parametrize("workload, fault", [
    ("g1k-sat", "dropped_write"),      # an answer altered where it is made
    ("g1k-sat", "replica_behind"),     # the control: "on all three replicas"
    ("g1-sat", "double_execute"),      # the control: "exactly once"
])
def test_a_broken_guarantee_comes_out_as_not_correct(tiny, workload, fault,
                                                    capsys):
    config, traffic, specs, e2e = tiny(workload)
    with faults.FAULTS[fault](run.cell_names(config)[0]):
        result = run.run_cell(config, traffic, specs, e2e, seed=7,
                              seconds=1.0, trace=False,
                              expect_platform="cpu")
    assert result["correct"] is False
    assert set(result["metrics"]) == set(e2e)
    assert list(result)[-1] == "compared"
    assert result["compared"]["replica_total_mismatches"]["value"] > 0
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    failed = {c["check"] for c in lines if "check" in c and not c["ok"]}
    assert "replica_total_mismatches" in failed
    if fault != "replica_behind":
        assert "ack_value_mismatches" in failed
    # a configuration of one name says which of its two modes the run was in
    info, = [c for c in lines if "boot_s" in c]
    assert info["entry_is_coordinator"] is None if config["names"] > 1 \
        else sorted(info["entry_is_coordinator"]) == [False, False, True]


def test_the_wrong_platform_comes_out_as_not_correct(tiny):
    config, traffic, specs, e2e = tiny("g1k-lat")
    result = run.run_cell(config, traffic, specs, e2e, seed=1, seconds=1.0,
                          trace=False, expect_platform="tpu")
    assert result["correct"] is False
    assert result["metrics"]["commit_p95_ms"]["value"] > 0


class _Reconfigurators:
    """``create_names`` of the client library: the first ``silent`` calls
    are never answered (an intent stranded by the start-up election)."""

    def __init__(self, silent):
        self.silent, self.calls = silent, []

    def create_names(self, names, timeout, retransmit_every):
        assert timeout < retransmit_every  # one send per batch id
        self.calls.append(list(names))
        if len(self.calls) <= self.silent:
            return {}
        return {n: {"ok": True, "actives": [0, 1, 2]} for n in names}


@pytest.mark.parametrize("silent", [0, 1, 2, run.CREATE_TRIES])
def test_an_unanswered_create_batch_goes_out_again_as_a_new_batch(
        silent, monkeypatch):
    names = [f"n{i:03d}" for i in range(250)]
    first, rest = names[:100], [names[100:200], names[200:]]
    rcs = _Reconfigurators(silent)
    acks, again = run._create(rcs, names)
    sends_of_first = min(silent + 1, run.CREATE_TRIES)
    assert rcs.calls == [first] * sends_of_first + rest
    assert again == 100 * silent
    # given up after CREATE_TRIES: the names are missing, the run raises
    assert set(acks) == set(names) - (
        set(first) if silent == run.CREATE_TRIES else set())
