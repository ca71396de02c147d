"""Group-sharded SPMD mode (the zero-collective scale-out shape):
parity pins against the single-chip vmap step, padding/edge-shard
behavior for a non-divisible G, the mesh-shape sweep, the runtime mesh
descriptor behind the ``stats`` admin op, the footprint probe's
``--sharded`` budget assert, and the driver's ``dryrun_multichip``
one-line JSON artifact (the previously-blind multichip smoke)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapaxos_tpu.ops.ballot import NULL, ballot_coord
from gigapaxos_tpu.ops.engine import EngineConfig
from gigapaxos_tpu.parallel.mesh import (
    describe_state_mesh,
    make_group_mesh,
    pick_mesh_shape,
)
from gigapaxos_tpu.parallel.spmd import (
    build_replica_states,
    make_step,
    pad_group_states,
    padded_group_count,
    shard_group_inputs,
    strip_group_pad,
)

ROOT = Path(__file__).resolve().parents[1]


def _parity_schedule(cfg):
    """A 4-step schedule that exercises requests, an election pulse, and
    a dropped peer — returns [(req, want, heard), ...] host arrays."""
    R, G, K = cfg.n_replicas, cfg.n_groups, cfg.req_lanes
    steps = []
    # step 0: live requests at two coordinator rows
    req = np.full((R, G, K), NULL, np.int32)
    req[0, 0, :2] = [5, 6]
    req[1, 1 % G, 0] = 7
    steps.append((req, np.zeros((R, G), bool), None))
    # step 1: quiet
    steps.append((np.full((R, G, K), NULL, np.int32),
                  np.zeros((R, G), bool), None))
    # step 2: election pulse (replica 1 runs for every group) under a
    # dropped peer (replica R-1 unheard) — carryover through both modes
    heard = np.ones((R, R), bool)
    heard[:, R - 1] = False
    want = np.zeros((R, G), bool)
    want[1, :] = True
    steps.append((np.full((R, G, K), NULL, np.int32), want, heard))
    # step 3: full delivery again, more requests at every row (only the
    # active coordinator admits)
    req = np.full((R, G, K), NULL, np.int32)
    req[:, :, 0] = 9
    steps.append((req, np.zeros((R, G), bool), None))
    return steps


def _assert_parity(cfg, n_devices):
    mesh = make_group_mesh(n_devices)
    Gp = padded_group_count(cfg.n_groups, n_devices)
    vm = make_step(cfg)
    gs = make_step(cfg, mesh)

    states_v = build_replica_states(cfg)
    R, G, K = cfg.n_replicas, cfg.n_groups, cfg.req_lanes
    states_s, _r0, _w0 = shard_group_inputs(
        mesh, cfg, build_replica_states(cfg),
        np.full((R, G, K), NULL, np.int32), np.zeros((R, G), bool),
    )
    assert states_s.bal.shape == (R, Gp)

    for t, (req, want, heard) in enumerate(_parity_schedule(cfg)):
        states_v, out_v = vm(
            states_v, jnp.asarray(req), jnp.asarray(want),
            None if heard is None else jnp.asarray(heard),
        )
        req_p = np.concatenate(
            [req, np.full((R, Gp - G, K), NULL, np.int32)], axis=1
        )
        want_p = np.concatenate(
            [want, np.zeros((R, Gp - G), bool)], axis=1
        )
        states_s, out_s = gs(
            states_s, jnp.asarray(req_p), jnp.asarray(want_p),
            None if heard is None else jnp.asarray(heard),
        )
        # EVERY state leaf and EVERY StepOutputs field, every step
        su = strip_group_pad(states_s, G)
        ou = strip_group_pad(out_s, G)
        for name in states_v._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(states_v, name)),
                np.asarray(getattr(su, name)),
                err_msg=f"state.{name} @ step {t}",
            )
        for name in out_v._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out_v, name)),
                np.asarray(getattr(ou, name)),
                err_msg=f"out.{name} @ step {t}",
            )
    # commits actually flowed (the schedule is live, not a no-op parity)
    assert np.asarray(states_v.exec_slot).max() >= 1
    return states_s


def test_group_sharded_parity_8dev():
    """Bit-identical to the unsharded step over 4 steps on the 8-device
    virtual mesh — every leaf, every output field, every step."""
    cfg = EngineConfig(n_groups=16, window=8, req_lanes=4, n_replicas=3)
    states = _assert_parity(cfg, 8)
    sh = states.bal.sharding
    assert len(sh.device_set) == 8  # really spread over the mesh


def test_group_sharded_parity_nondivisible_g():
    """G=13 over 8 shards: the padded edge shard must not perturb any
    real group, and the inert pad tail stays bit-frozen."""
    cfg = EngineConfig(n_groups=13, window=8, req_lanes=4, n_replicas=3)
    states = _assert_parity(cfg, 8)
    Gp = padded_group_count(13, 8)
    assert Gp == 16
    tail = np.asarray(states.member_mask)[:, 13:]
    assert (tail == 0).all()
    assert (np.asarray(states.exec_slot)[:, 13:] == 0).all()


def test_group_sharded_commits_end_to_end():
    """Drive coordinator-routed traffic for 10 steps: commits flow on
    every group through the sharded step (not just parity on quiet
    schedules)."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=2, n_replicas=3)
    mesh = make_group_mesh(8)
    fn = make_step(cfg, mesh)
    R, G, K = 3, 8, 2
    states, _r, _w = shard_group_inputs(
        mesh, cfg, build_replica_states(cfg),
        np.full((R, G, K), NULL, np.int32), np.zeros((R, G), bool),
    )
    vid = 1
    for _ in range(10):
        req = np.full((R, G, K), NULL, np.int32)
        coord = ballot_coord(np.asarray(states.bal)[0])
        for g in range(G):
            req[int(coord[g]), g, 0] = vid
            vid += 1
        states, out = fn(
            states, jnp.asarray(req), jnp.zeros((R, G), bool)
        )
    fr = np.asarray(states.exec_slot)
    assert (fr == fr[0]).all() and fr.min() >= 6
    h = np.asarray(states.app_hash)
    assert (h == h[0]).all() and (h[0] != 0).all()


def test_pick_mesh_shape_sweep():
    """n_devices in {1, 2, 3, 4, 8}: replica axis prefers 3, then 2,
    then 1; group shards take the rest."""
    expect = {1: (1, 1), 2: (1, 2), 3: (1, 3), 4: (2, 2), 8: (4, 2)}
    for n, want in expect.items():
        assert pick_mesh_shape(n) == want, n


def test_padded_group_count():
    assert padded_group_count(16, 8) == 16
    assert padded_group_count(13, 8) == 16
    assert padded_group_count(1, 8) == 8
    assert padded_group_count(17, 8) == 24
    assert padded_group_count(7, 1) == 7


def test_pad_group_states_inert_tail():
    cfg = EngineConfig(n_groups=5, window=8, req_lanes=2, n_replicas=3)
    padded = pad_group_states(cfg, build_replica_states(cfg), 4)
    assert padded.bal.shape == (3, 8)
    assert (np.asarray(padded.member_mask)[:, 5:] == 0).all()
    assert (np.asarray(padded.bal)[:, 5:] == NULL).all()


def test_make_group_mesh_shapes():
    for n in (1, 2, 4, 8):
        mesh = make_group_mesh(n)
        assert dict(mesh.shape) == {"g": n}
    with pytest.raises(ValueError):
        make_group_mesh(len(jax.devices()) + 1)


def test_describe_state_mesh():
    """The stats-op mesh descriptor: sharded array reports the mesh,
    a plain single-device array reports n_devices=1, host data reports
    residency 0 (never raises)."""
    cfg = EngineConfig(n_groups=8, window=8, req_lanes=2, n_replicas=3)
    mesh = make_group_mesh(8)
    states, _r, _w = shard_group_inputs(
        mesh, cfg, build_replica_states(cfg),
        np.full((3, 8, 2), NULL, np.int32), np.zeros((3, 8), bool),
    )
    d = describe_state_mesh(states.bal)
    assert d["n_devices"] == 8
    assert d["shape"] == {"g": 8}
    assert d["platform"] == "cpu"

    single = describe_state_mesh(jnp.zeros((4,), jnp.int32))
    assert single["n_devices"] == 1 and single["platform"] == "cpu"

    host = describe_state_mesh(np.zeros((4,), np.int32))
    assert host["platform"] == "host" and host["n_devices"] == 0


_SUBPROC_PARITY = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
sys.path.insert(0, {root!r})
sys.path.insert(0, {tests!r})
assert len(jax.devices()) >= 8
from gigapaxos_tpu.ops.engine import EngineConfig
from test_group_sharded import _assert_parity
for G in (16, 13):
    _assert_parity(
        EngineConfig(n_groups=G, window=8, req_lanes=4, n_replicas=3), 8
    )
print("PARITY_OK")
"""


@pytest.mark.slow
def test_group_sharded_parity_subprocess():
    """The same parity pin from a pristine interpreter with the explicit
    XLA_FLAGS virtual-mesh bring-up (the ``__graft_entry__`` pattern) —
    proves the mode needs nothing from the test harness' conftest.
    Slow-marked: tier-1 already pins the identical parity in-process on
    the same 8-virtual-device mesh; this re-proves the bring-up path,
    and a fresh interpreter + two step compiles is ~1 min of the tier-1
    budget on a 1-core box."""
    code = _SUBPROC_PARITY.format(
        root=str(ROOT), tests=str(ROOT / "tests")
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PARITY_OK" in out.stdout


@pytest.mark.slow
def test_dryrun_multichip_prints_artifact_json():
    """The driver's multichip smoke must RECORD a measurement: one JSON
    line with n_devices, both mesh shapes, step wall time, and dec/s
    (the MULTICHIP_r0*.json ``tail`` was empty for five rounds).
    Slow-marked: the driver runs dryrun_multichip itself every round
    (the artifact IS the gate); this spawns a fresh interpreter + three
    mesh compiles."""
    code = (
        f"import sys; sys.path.insert(0, {str(ROOT)!r}); "
        "import __graft_entry__ as ge; ge.dryrun_multichip(8)"
    )
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    assert lines, out.stdout
    rec = json.loads(lines[-1])
    assert rec["n_devices"] == 8
    assert rec["mesh"] == {"g": 4, "r": 2}
    assert rec["step_wall_s"] > 0
    assert rec["dec_per_s"] > 0
    gs = rec["group_sharded"]
    assert gs["mesh"] == {"g": 8}
    assert gs["n_groups"] == 35 and gs["padded_groups"] == 40
    assert gs["dec_per_s"] > 0


def test_bench_capacity_cpu_run_creates_no_evidence_file():
    """The capacity run's CPU path: prints the {platform, G, no_oom,
    dec_per_s, per_device_hbm_bytes} shape but must NOT create
    TPU_EVIDENCE.json (only a run on the chip starts or extends that
    file; a host stand-in never does).  CAPACITY_G is overridden small
    so the full bench loop runs in test time; the G=2M shape itself is a
    bench-invocation concern, not a codepath difference."""
    ev = ROOT / "TPU_EVIDENCE.json"
    assert not ev.exists(), "the evidence file is made by chip runs only"
    code = (
        f"import os, sys; sys.path.insert(0, {str(ROOT)!r}); "
        "os.environ['BENCH_G'] = '4096'; "
        "os.environ['BENCH_W'] = '8'; os.environ['BENCH_K'] = '4'; "
        "import bench; bench.CAPACITY_G = 4096; sys.exit(bench.main())"
    )
    # JAX_PLATFORMS=cpu asked for from outside: bench.py's only way
    # onto the CPU
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=420, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    cap = rec["capacity"]
    assert cap["no_oom"] is True
    assert cap["platform"] == "cpu"
    assert cap["G"] == 4096
    assert cap["dec_per_s"] > 0
    assert "per_device_hbm_bytes" in cap
    assert not ev.exists(), "a CPU run must not create the evidence file"


def test_footprint_probe_sharded_budget():
    """--sharded N: per-device blob bytes per hosted group must sit AT
    the compact budget (16 + 16W) for every shard count — sharding adds
    zero per-group exchange overhead."""
    for n in (1, 2, 8):
        out = subprocess.run(
            [sys.executable, str(ROOT / "scripts" / "footprint_probe.py"),
             "--sharded", str(n)],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr
        rec = json.loads(out.stdout.strip())
        sh = rec["sharded"]
        assert sh["n_shards"] == n
        assert sh["within_budget"] is True
        assert sh["compact_budget_bytes_per_group"] == 528  # W=32
        assert sh["per_device_blob_bytes_per_group"] <= 528
        assert sh["groups_per_device"] * n == sh["padded_groups"]
        # per-device peak: the single-chip model at the LOCAL group count
        # (HBM = bytes_per_group x G / n_shards — the capacity lever)
        if n == 8:
            full = subprocess.run(
                [sys.executable,
                 str(ROOT / "scripts" / "footprint_probe.py")],
                capture_output=True, text=True, timeout=120,
            )
            peak_full = json.loads(full.stdout.strip())[
                "single_chip_peak_estimate_bytes"]
            assert sh["per_device_peak_estimate_bytes"] < peak_full / 6
