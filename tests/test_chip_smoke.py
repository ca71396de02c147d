"""``chip_smoke.py``'s phases at tiny sizes on the CPU (the steering
lives here: the script has no CPU branch, no size option), the compile
cache helper, and the boot-time refusal of serving workers on a chip."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


def _load(script: str):
    spec = importlib.util.spec_from_file_location(
        script.removesuffix(".py"), ROOT / script
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke.py")


def test_engine_parity_phase_cpu_vs_cpu(smoke):
    cpu = jax.devices("cpu")
    line = smoke.phase_engine_parity(
        256, 16, 8, 3, n_steps=20, seed=7,
        device=cpu[0], reference_device=cpu[1],
    )
    json.dumps(line)  # every phase prints its line as JSON
    assert line["bit_exact"] and line["decided"] > 0
    # state, blob, heat, digest, the blob's news, and the eight leaves
    # of the stack
    assert line["leaves_compared"] == 3 * (19 + 4 + 8)
    # every step's news was held against the host's compare of the two
    # vectors, the rows paused and restored between steps among them
    assert line["blob_news_rows"] > 16
    assert line["gather_updates"]["whole"] >= 3
    assert line["compile"]["retraces"] == 0


def test_engine_parity_trace_pauses_rows_and_resumes_them_in_one_batch(smoke):
    """The parity trace holds a pause and a batched resume (PR 31): the
    rows are dead between the two and live, from their records, after."""
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.parallel.spmd import make_step

    cpu = jax.devices("cpu")
    line = smoke.phase_engine_parity(
        256, 16, 8, 3, n_steps=24, seed=11,
        device=cpu[0], reference_device=cpu[1],
    )
    res = line["residency"]
    assert res == {"rows": 8, "paused_at_step": 8, "resumed_at_step": 16,
                   "executed_while_awake": res["executed_while_awake"]}
    assert res["executed_while_awake"] > 0 and line["bit_exact"]
    # and the arm's own view: freed rows admit nothing, restored rows
    # carry on from their frontier
    cfg = EngineConfig(256, 16, 8, 3)
    arm = smoke._ReplicaArm(cfg, cpu[0], make_step(
        cfg, donate=False, io="packed_host"))
    rows = np.array([5, 42], np.int32)
    trace = list(smoke.make_trace(cfg, 12, 3))
    for req, want, heard in trace[:6]:
        arm.step(req, want, heard)
    before = np.asarray(arm.states[0].exec_slot)[rows].copy()
    assert before.min() > 0
    arm.pause(rows)
    for req, want, heard in trace[6:9]:
        arm.step(req, want, heard)
    assert (np.asarray(arm.states[0].member_mask)[rows] == 0).all()
    arm.resume(rows)
    assert (np.asarray(arm.states[0].exec_slot)[rows] == before).all()
    for req, want, heard in trace[9:]:
        arm.step(req, want, heard)
    assert (np.asarray(arm.states[0].exec_slot)[rows] > before).all()


def test_parity_comparison_names_the_first_differing_word(smoke):
    a = np.arange(12, dtype=np.int32).reshape(3, 4)
    smoke._assert_same("leaf", a, a.copy())
    b = a.copy()
    b[2, 1] += 1
    with pytest.raises(AssertionError, match=r"leaf: 1 of 12 .* first at 9"):
        smoke._assert_same("leaf", a, b)
    with pytest.raises(AssertionError, match="int64"):
        smoke._assert_same("leaf", a, a.astype(np.int64))


def test_trace_is_seeded_and_exercises_faults(smoke):
    from gigapaxos_tpu.ops.engine import EngineConfig

    cfg = EngineConfig(64, 16, 8, 3)
    one = list(smoke.make_trace(cfg, 16, seed=3))
    two = list(smoke.make_trace(cfg, 16, seed=3))
    other = list(smoke.make_trace(cfg, 16, seed=4))
    for (r1, w1, h1), (r2, w2, h2) in zip(one, two):
        assert (r1 == r2).all() and (w1 == w2).all() and (h1 == h2).all()
    assert any((r1 != r3).any() for (r1, _, _), (r3, _, _) in zip(one, other))
    assert any(w.any() for _, w, _ in one), "no election pulse"
    assert any(not h.all() for _, _, h in one), "no dropped link"
    assert all(h.diagonal().all() for _, _, h in one)


def test_served_phase_reads_every_write_back_from_three_actives(smoke):
    line = smoke.phase_served(
        n_names=8, writes_per_name=3, engine_rows=256, window=16, seed=7,
        expect_platform="cpu", timeout_s=120, crash_s=3.0, fd_timeout_s=1.0,
    )
    json.dumps(line)
    assert line["writes_acknowledged"] == 24
    # active 1 went dark after the first round and the last two rounds
    # were read back from it too, with nothing compiled since boot
    assert line["crash"]["active"] == 1 and line["crash"]["frames_dropped"]
    assert sum(line["crash"]["coordinator_flips"]) > 0
    assert line["read_back_from_actives"] == 3
    assert line["engine"] == {"rows": 256, "W": 16, "K": 8, "R": 3}
    assert [m["platform"] for m in line["mesh"]] == ["cpu"] * 3
    assert line["compile"]["retraces"] == 0
    assert line["codec_impl"] in ("gp_codec.so", "python-struct")


def test_served_phase_fails_when_the_arrays_sit_elsewhere(smoke):
    with pytest.raises(AssertionError, match="expected platform 'tpu'"):
        smoke.phase_served(
            n_names=2, writes_per_name=1, engine_rows=256, window=16,
            seed=7, expect_platform="tpu", timeout_s=120,
        )


def test_four_chip_phase_on_four_virtual_devices(smoke):
    line = smoke.phase_four_chips(1024, 16, 8, 3, n_steps=12, seed=7,
                                  n_devices=4)
    json.dumps(line)
    assert line["bit_exact"] and line["decided"] > 0
    assert line["mesh"]["n_devices"] == 4 and line["mesh"]["shape"] == {"g": 4}
    assert len(line["shard_devices"]) == 4
    assert line["shape"]["G"] == line["groups_requested"] == 1024


def _run(code_or_script, cwd, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT), **env)
    return subprocess.run(
        [sys.executable, *code_or_script], cwd=cwd, env=full,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_script_refuses_to_run_without_a_tpu(argv):
    r = _run([str(ROOT / "chip_smoke.py"), *argv], cwd=ROOT)
    assert r.returncode != 0, r.stdout
    assert '"ok"' not in r.stdout and "needs a TPU" in r.stderr


_WHERE = (
    "import jax\n"
    "from gigapaxos_tpu.utils.compile_cache import configure_compile_cache\n"
    "print(configure_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_compile_cache_left_to_the_environment_when_it_places_it(tmp_path):
    placed = str(tmp_path / "placed_from_outside")
    r = _run(["-c", _WHERE], cwd=tmp_path, JAX_COMPILATION_CACHE_DIR=placed)
    assert r.returncode == 0, r.stderr
    # the helper set nothing; JAX read the variable by itself
    assert r.stdout.split() == ["None", placed]


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(tmp_path):
    from gigapaxos_tpu.utils.compile_cache import DEFAULT_CACHE_DIR

    assert DEFAULT_CACHE_DIR == str(ROOT / ".jax_cache")
    seen = set()
    for cwd in (tmp_path, ROOT / "tests"):  # two directories, two processes
        r = _run(["-c", _WHERE], cwd=cwd)
        assert r.returncode == 0, r.stderr
        seen.add(tuple(r.stdout.split()))
    assert seen == {(DEFAULT_CACHE_DIR, DEFAULT_CACHE_DIR)}


def test_serving_workers_refused_off_the_cpu_backend(monkeypatch):
    from gigapaxos_tpu.models.apps import NoopPaxosApp
    from gigapaxos_tpu.reconfigurable_node import ReconfigurableNode
    from gigapaxos_tpu.utils.config import Config

    for i in range(3):
        Config.set(f"active.AR{i}", f"127.0.0.1:{21000 + i}")
        Config.set(f"reconfigurator.RC{i}", f"127.0.0.1:{22000 + i}")
    Config.set("SERVING_WORKERS", "2")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one chip cannot be shared"):
        ReconfigurableNode("AR0", NoopPaxosApp)


def test_bench_runs_on_the_cpu_only_when_asked_from_outside(
        monkeypatch, tmp_path):
    bench = _load("bench.py")
    # the cache placed from outside: the helper leaves this process alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.require_backend() == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as refused:
        bench.require_backend()
    assert refused.value.code == 1
