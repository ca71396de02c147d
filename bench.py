"""Headline benchmark: committed Paxos decisions/second.

The reference's benchmark is an in-process capacity probe
(``TESTPaxosClient.probeCapacity``, ``TESTPaxosClient.java:799-895``): N
virtual nodes in one JVM, load raised until the response rate degrades.
The analog here: all R=3 replica engines advanced on one chip (the
single-chip vmap mode, the N-nodes-in-one-JVM counterpart), G groups
committing in lock-step, with the client/request path generated on-device
so the measurement isolates the consensus engine exactly like the
reference's in-JVM probe isolates its JVM path.

Metric: committed decisions/s = slots executed per second by one replica
(each slot is one agreed client request), across all groups.  The north
star (BASELINE.json) is >= 10M decisions/s over ~1M groups.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The process touches the device itself, once: there is no probe in a
child process and no fall-back.  ``JAX_PLATFORMS=cpu`` asked for from
outside is the only way onto the CPU; any other backend that is not a
TPU exits non-zero (:func:`require_backend`).

Modes (env):

* default — single-chip vmap bench (above).
* ``BENCH_MODE=failover`` — same, under continuous leadership churn.
* ``BENCH_G=2097152`` — the G=2M capacity run (the reference's
  ``PINSTANCES_CAPACITY`` wall): on a real chip the result (no_oom,
  dec/s, per-device HBM high-water) is appended to ``TPU_EVIDENCE.json``
  under ``capacity_runs``; a CPU run prints the same shape with
  ``platform`` marked and leaves the evidence file untouched.
* ``BENCH_MULTICHIP=1`` — the scale-out weak-scaling bench: the
  group-sharded unified step (zero cross-device collectives,
  ``parallel/spmd.py:make_step`` over a ``('g',)`` mesh) over
  1 -> 2 -> 4 -> 8 mesh devices at constant groups-per-device, emitting
  the curve (aggregate dec/s, per-device dec/s, per-device HBM
  high-water) to ``MULTICHIP_r06.json`` (override:
  ``BENCH_MULTICHIP_OUT``).  Under ``JAX_PLATFORMS=cpu`` the same
  harness runs on a virtual CPU mesh
  (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` is forced)
  with ``platform`` marked in the artifact.
"""

import json
import os
import sys
import time

NORTH_STAR = 10_000_000.0  # decisions/s, BASELINE.json
CAPACITY_G = 2_097_152     # the reference's PINSTANCES_CAPACITY wall


def bench_provenance(donate=None) -> dict:
    """Provenance stamp for bench artifacts (obs/device.py): jax/jaxlib
    versions, platform, XLA flags, donation.  A perf number without its
    software/hardware coordinates can't be compared across rounds.
    Never fails the bench: degrades to an ``error`` marker."""
    try:
        from gigapaxos_tpu.obs.device import provenance

        return provenance(donate=donate)
    except Exception as e:  # noqa: BLE001 — bench must still print its line
        return {"error": repr(e)}


def require_backend() -> str:
    """Touch the device — in THIS process, once — and return its
    platform.  ``JAX_PLATFORMS=cpu`` asked for from outside is the only
    way onto the CPU; any other backend that is not a TPU exits
    non-zero (a measurement path that finds no chip fails, it does not
    fall back)."""
    import jax

    from gigapaxos_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print(
            f"bench.py needs a TPU, JAX found {platform!r}; only "
            "JAX_PLATFORMS=cpu, set from outside, runs it on the CPU",
            file=sys.stderr, flush=True,
        )
        sys.exit(1)
    return platform


def _append_evidence(entry: dict, key: str) -> None:
    """Append one entry under ``key`` in TPU_EVIDENCE.json — locked
    read-modify-write so concurrent bench invocations never drop a run.
    ONLY called for real on-chip results: a CPU run must leave the file
    untouched (the committed TPU numbers are the point of the file)."""
    import fcntl

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "TPU_EVIDENCE.json")
    with open(path + ".lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        try:
            with open(path) as f:
                doc = json.load(f)
            if not isinstance(doc, dict):
                doc = {"what": "raw on-chip bench runs", "runs": []}
        except (OSError, ValueError):
            doc = {"what": "raw on-chip bench runs", "runs": []}
        runs = doc.setdefault(key, [])
        if not isinstance(runs, list):
            runs = doc[key] = []
        runs.append(entry)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)


def record_tpu_evidence(result: dict, wall_s: float) -> None:
    """Append a successful on-chip headline run to the evidence file, so
    every run on a chip leaves its raw result behind."""
    _append_evidence({
        "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "device_platform": "tpu",
        "jax_platforms_env": os.environ.get("JAX_PLATFORMS", ""),
        "wall_s": round(wall_s, 1),
        "bench_json": result,
    }, key="runs")


def record_capacity_evidence(capacity: dict, wall_s: float) -> None:
    """Append a G=2M capacity verdict (no_oom + throughput + HBM
    high-water) — ROADMAP item 3 / PR-1's open on-chip verification."""
    _append_evidence({
        "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "jax_platforms_env": os.environ.get("JAX_PLATFORMS", ""),
        "wall_s": round(wall_s, 1),
        **capacity,
    }, key="capacity_runs")


def device_hbm_peak(devices) -> list:
    """Per-device HBM high-water (peak_bytes_in_use) where the backend
    reports it; None entries where it doesn't (the CPU backend)."""
    peaks = []
    for d in devices:
        try:
            ms = d.memory_stats()
            peaks.append(int(ms["peak_bytes_in_use"]) if ms else None)
        except Exception:
            peaks.append(None)
    return peaks


def _is_oom(e: BaseException) -> bool:
    s = f"{type(e).__name__}: {e}"
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s or "OOM" in s)


def _run_group_sharded_point(n_devices: int, g_per_dev: int, W: int, K: int,
                             n_chunks: int) -> dict:
    """One weak-scaling point: the group-sharded SPMD step over the first
    ``n_devices`` devices at G = g_per_dev x n_devices, steady-state scan
    loop, measured aggregate + per-device dec/s and HBM high-water."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from gigapaxos_tpu.ops.ballot import NULL
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.parallel.mesh import make_group_mesh
    from gigapaxos_tpu.parallel.spmd import (
        build_replica_states,
        make_step,
        shard_group_inputs,
    )

    R = 3
    G = g_per_dev * n_devices
    devs = jax.devices()[:n_devices]
    mesh = make_group_mesh(n_devices)
    cfg = EngineConfig(n_groups=G, window=W, req_lanes=K, n_replicas=R)
    states, _req0, _want0 = shard_group_inputs(
        mesh, cfg, build_replica_states(cfg),
        np.full((R, G, K), NULL, np.int32), np.zeros((R, G), bool),
    )
    Gp = _req0.shape[1]
    step_fn = make_step(cfg, mesh)
    vids = jnp.arange(1, K + 1, dtype=jnp.int32)
    CHUNK = 10

    @partial(jax.jit, donate_argnums=(0,))
    def run_chunk(states):
        # sharded on-device request generation: the offered-request plane
        # materializes INSIDE the jitted chunk (GSPMD lays the constant out
        # per shard), so the steady-state loop moves zero host bytes
        req = jnp.broadcast_to(vids[None, None, :], (R, Gp, K))
        want = jnp.zeros((R, Gp), bool)

        def body(s, _i):
            s, out = step_fn(s, req, want)
            return s, out.n_committed[0].sum()

        states, committed = jax.lax.scan(
            body, states, jnp.arange(CHUNK, dtype=jnp.int32)
        )
        return states, committed.sum()

    # warmup: compile + pipeline fill — timed SEPARATELY so the artifact
    # splits one-time compile cost from the steady-state rate (a compile
    # regression and a throughput regression are different bugs)
    tw = time.perf_counter()
    states, _ = run_chunk(states)
    states, c = run_chunk(states)
    jax.block_until_ready(c)
    warmup_s = time.perf_counter() - tw

    t0 = time.perf_counter()
    total = 0
    for _ in range(n_chunks):
        states, c = run_chunk(states)
        total += int(jax.block_until_ready(c))
    dt = time.perf_counter() - t0

    rate = total / dt
    peaks = device_hbm_peak(devs)
    known = [p for p in peaks if p is not None]
    return {
        "n_devices": n_devices,
        "mesh_shape": {"g": n_devices},
        "G": G,
        "groups_per_device": g_per_dev,
        "aggregate_dec_per_s": round(rate, 1),
        "per_device_dec_per_s": round(rate / n_devices, 1),
        "per_device_hbm_peak_bytes": max(known) if known else None,
        "hbm_peak_bytes_by_device": peaks,
        "steps_timed": n_chunks * CHUNK,
        "warmup_s": round(warmup_s, 2),
        "wall_s": round(dt, 2),
    }


def multichip_main() -> int:
    """BENCH_MULTICHIP=1: the weak-scaling headline — 1 -> 2 -> 4 -> 8
    devices, groups-per-device constant, group-sharded SPMD step.  Emits
    the curve to MULTICHIP_r06.json (BENCH_MULTICHIP_OUT overrides) and
    prints it as one JSON line."""
    import re

    t_start = time.perf_counter()
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        # the virtual mesh needs its device count forced BEFORE the
        # backend initializes
        n_virtual = int(os.environ.get("BENCH_MULTICHIP_DEVICES", "8"))
        flags = os.environ.get("XLA_FLAGS", "")
        count_flag = f"--xla_force_host_platform_device_count={n_virtual}"
        flags, n_sub = re.subn(
            r"--xla_force_host_platform_device_count=\d+", count_flag, flags
        )
        if not n_sub:
            flags = (flags + " " + count_flag).strip()
        os.environ["XLA_FLAGS"] = flags

    platform = require_backend()
    import jax

    n_avail = len(jax.devices())
    cpu_plat = platform.startswith("cpu")

    counts = [n for n in (1, 2, 4, 8) if n <= n_avail]
    g_per_dev = int(os.environ.get(
        "BENCH_G_PER_DEVICE", 8_192 if cpu_plat else 1_048_576
    ))
    W = int(os.environ.get("BENCH_W", 8 if cpu_plat else 32))
    K = int(os.environ.get("BENCH_K", 4 if cpu_plat else 16))
    n_chunks = int(os.environ.get("BENCH_MULTICHIP_CHUNKS",
                                  3 if cpu_plat else 5))

    curve = []
    for n in counts:
        pt = _run_group_sharded_point(n, g_per_dev, W, K, n_chunks)
        print(f"BENCH multichip point: {json.dumps(pt)}",
              file=sys.stderr, flush=True)
        curve.append(pt)

    base = curve[0]["aggregate_dec_per_s"]
    top = curve[-1]
    n_max = top["n_devices"]
    eff_parallel = top["aggregate_dec_per_s"] / (n_max * base)
    eff_serialized = top["aggregate_dec_per_s"] / base
    host_cores = os.cpu_count() or 1
    # on a virtual CPU mesh with fewer cores than devices the devices
    # TIME-SHARE the cores, so "linear" weak scaling is a flat aggregate
    # (the resource doesn't grow with n); on real parallel devices linear
    # is n x the single-device aggregate.  Both ratios are recorded; the
    # headline efficiency uses the model that matches the execution.
    serialized = cpu_plat and host_cores < n_max
    result = {
        "metric": "multichip_weak_scaling",
        "platform": platform,
        "host_cores": host_cores,
        "n_devices_available": n_avail,
        "mode": "group-sharded SPMD (zero cross-device collectives, "
                "all R replica rows device-local)",
        "shape": {"groups_per_device": g_per_dev, "W": W, "K": K,
                  "R": 3},
        "curve": curve,
        "scaling": {
            "at_n_devices": n_max,
            "efficiency_vs_linear": round(
                eff_serialized if serialized else eff_parallel, 3
            ),
            "linear_model": (
                f"host-serialized: {n_max} virtual devices time-share "
                f"{host_cores} core(s); linear = flat aggregate vs n=1"
            ) if serialized else (
                "parallel devices: linear = n x the n=1 aggregate"
            ),
            "efficiency_parallel_model": round(eff_parallel, 3),
            "efficiency_serialized_model": round(eff_serialized, 3),
        },
        "provenance": bench_provenance(donate=True),
        "wall_s": round(time.perf_counter() - t_start, 1),
    }
    out_path = os.environ.get("BENCH_MULTICHIP_OUT") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "MULTICHIP_r06.json"
    )
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    os.replace(tmp, out_path)
    print(json.dumps(result))
    return 0


def main() -> None:
    if os.environ.get("BENCH_MULTICHIP", "") not in ("", "0"):
        return multichip_main()
    t_start = time.perf_counter()
    platform = require_backend()
    import jax
    import jax.numpy as jnp

    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.parallel.spmd import build_replica_states, make_step

    # ~1M groups on TPU HBM; smaller on the CPU so the line still prints.
    on_cpu = platform.startswith("cpu")
    G = int(os.environ.get("BENCH_G", 8_192 if on_cpu else 1_048_576))
    # steady-state commits/group/step reach the K ceiling only when the
    # ring covers the full in-flight pipeline; the step cost grows with W,
    # so on CPU shallow wins.  On the chip the r4 sweep at G=1M measured
    # W16/K8 80.1M, W32/K16 84.2M, W16/K16 75.7M, W32/K8 65.3M dec/s;
    # W64/K32 and G=2M OOM — W=32/K=16 is the headline shape.
    W = int(os.environ.get("BENCH_W", 8 if on_cpu else 32))
    K = int(os.environ.get("BENCH_K", 4 if on_cpu else 16))
    R = 3
    cfg = EngineConfig(n_groups=G, window=W, req_lanes=K, n_replicas=R)
    states = build_replica_states(cfg)

    # On-device synthetic client load: K requests per group per step, sent to
    # the coordinator replica's request lanes (entry-replica batching analog;
    # coordinators are round-robin g % R, matching build_replica_states).
    rids = jnp.arange(R, dtype=jnp.int32)
    groups = jnp.arange(G, dtype=jnp.int32)
    vids = jnp.arange(1, K + 1, dtype=jnp.int32)  # constant vids; hashed anyway
    # requests offered at EVERY replica's lanes; only the group's ACTIVE
    # coordinator admits, so this models clients following the leader
    # (essential under failover churn: a new leader must find requests)
    req = jnp.broadcast_to(vids[None, None, :], (R, G, K))
    want = jnp.zeros((R, G), dtype=bool)
    step_fn = make_step(cfg)

    # BENCH_MODE=failover (BASELINE config 5): continuous ballot
    # contention — leadership of every group is forced to rotate around
    # the replica ring (each group re-elects every ~16 steps, with the
    # electing 1/16 slice staggered per step), so the measured rate
    # includes constant preempt/election/carryover churn.
    failover = os.environ.get("BENCH_MODE", "steady") == "failover"

    CHUNK = 10

    from functools import partial

    # donate the states: the previous chunk's buffers are dead once the
    # next chunk starts, so XLA reuses them in place — without this the
    # bench holds TWO full state copies across the dispatch boundary,
    # which is half the G=2M headroom on a 16GB chip
    @partial(jax.jit, donate_argnums=(0,))
    def run_chunk(states, base):
        def body(s, i):
            if failover:
                t = base + i
                sl = (groups & jnp.int32(15)) == (t & jnp.int32(15))
                target = (groups % R + 1 + (t >> 4)) % R
                w = (target[None, :] == rids[:, None]) & sl[None, :]
            else:
                w = want
            s, out = step_fn(s, req, w)
            return s, out.n_committed[0].sum()  # each slot once
        states, committed = jax.lax.scan(
            body, states, jnp.arange(CHUNK, dtype=jnp.int32)
        )
        return states, committed.sum()

    # G=2M is the capacity run (the reference's PINSTANCES_CAPACITY wall):
    # an OOM there is a RESULT to record, not a crash to swallow.
    is_capacity = G == CAPACITY_G
    try:
        # Warmup: compile + reach steady state (pipeline fill) — timed
        # into its own artifact field, separate from the steady rate
        tw = time.perf_counter()
        states, _ = run_chunk(states, jnp.int32(0))
        states, c = run_chunk(states, jnp.int32(CHUNK))
        jax.block_until_ready(c)
        warmup_s = time.perf_counter() - tw

        t0 = time.perf_counter()
        total = 0
        n_chunks = 5
        for i in range(n_chunks):
            states, c = run_chunk(states, jnp.int32((2 + i) * CHUNK))
            total += int(jax.block_until_ready(c))
        dt = time.perf_counter() - t0
    except Exception as e:
        if not (is_capacity and _is_oom(e)):
            raise
        capacity = {
            "platform": platform, "G": G, "W": W, "K": K,
            "no_oom": False, "dec_per_s": None,
            "per_device_hbm_bytes": None,
            "error": f"{type(e).__name__}: {e}"[:500],
        }
        if platform == "tpu":
            try:
                record_capacity_evidence(
                    capacity, time.perf_counter() - t_start
                )
            except Exception as e2:
                print(f"BENCH WARNING: could not record evidence: {e2!r}",
                      file=sys.stderr, flush=True)
        print(json.dumps({
            "metric": "committed_decisions_per_s", "value": 0.0,
            "unit": f"decisions/s ({G} groups, 3 replicas, 1 chip, OOM, "
                    f"{platform})",
            "vs_baseline": 0.0, "capacity": capacity,
        }))
        return 1

    rate = total / dt
    mode = "failover-churn" if failover else "steady-state"
    result = {
        "metric": "committed_decisions_per_s",
        "value": round(rate, 1),
        "unit": f"decisions/s ({G} groups, 3 replicas, 1 chip, "
                f"{mode}, {platform})",
        "vs_baseline": round(rate / NORTH_STAR, 3),
        "warmup_s": round(warmup_s, 2),
        "steady_s": round(dt, 2),
        "provenance": bench_provenance(donate=True),
    }
    if is_capacity:
        peaks = [p for p in device_hbm_peak(jax.devices()[:1])
                 if p is not None]
        result["capacity"] = {
            "platform": platform, "G": G, "W": W, "K": K,
            "no_oom": True, "dec_per_s": round(rate, 1),
            "per_device_hbm_bytes": peaks[0] if peaks else None,
        }
        if platform == "tpu":
            # the pending PR-1 verification: the G=2M verdict lands in the
            # committed evidence file; a CPU run leaves the file UNTOUCHED
            # (never overwrite TPU numbers with host-platform stand-ins)
            try:
                record_capacity_evidence(
                    result["capacity"], time.perf_counter() - t_start
                )
            except Exception as e:
                print(f"BENCH WARNING: could not record evidence: {e!r}",
                      file=sys.stderr, flush=True)
    # headline evidence entries are only meaningful for headline-shaped
    # runs — a debug run with BENCH_G/W/K overridden must not pollute them
    headline_shape = not any(
        v in os.environ for v in ("BENCH_G", "BENCH_W", "BENCH_K")
    )
    if platform == "tpu" and headline_shape:
        try:
            record_tpu_evidence(result, time.perf_counter() - t_start)
        except Exception as e:
            print(f"BENCH WARNING: could not record evidence: {e!r}",
                  file=sys.stderr, flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
