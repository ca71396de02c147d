"""Capacity prober: the reference's benchmark protocol against the full
SYSTEM (sockets + JSON + tick loop + engine + app), not just the engine.

Protocol (``TESTPaxosClient.probeCapacity``, ``TESTPaxosClient.java:
799-895`` with knobs from ``TESTPaxosConfig.java:190-229``): inject load
at rate R for a window; if the response rate stays >= PROBE_RESPONSE_
THRESHOLD (0.9) and mean latency <= PROBE_LATENCY_THRESHOLD (1s), raise
R by PROBE_LOAD_INCREASE_FACTOR (1.1) and repeat; the last sustainable R
is the capacity ("capacity >= X/s").

Boots an in-process loopback cluster of ReconfigurableNodes (3 actives +
3 reconfigurators — the N-nodes-in-one-process testing mode) and drives
it with the reconfiguration-aware client.  Emits one JSON line per round
and a final summary line.
"""

import argparse
import json
import os
import socket
import sys
import threading
import time

from gigapaxos_tpu.testing.ports import free_ports


def _probe_provenance(in_process: bool, node_mesh: dict) -> dict:
    """Provenance stamp for capacity artifacts: the platform it names is
    the NODES' — ``node_mesh`` is the ``engine.mesh`` block of a node's
    ``stats`` answer, the devices that back its engine arrays.  Only
    with ``--in-process`` is this process a node, and only then does it
    ask JAX itself; with child or attached nodes the parent stays off
    JAX (a parent that has touched it holds the chip)."""
    if in_process:
        from gigapaxos_tpu.obs.device import provenance

        return provenance(extra={"nodes": node_mesh})
    import platform as _platform
    from importlib.metadata import version

    return {
        "jax": version("jax"),
        "jaxlib": version("jaxlib"),
        "platform": node_mesh.get("platform", "unknown"),
        "n_devices": node_mesh.get("n_devices"),
        "nodes": node_mesh,
        "python": _platform.python_version(),
    }


def main() -> int:
    if "--bank-ledger" in sys.argv[1:]:
        # delegate to the bank-ledger transaction workload, passing every
        # OTHER argument through (its own argparse owns the flag set)
        import runpy

        sys.argv = [
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scenarios", "bank_ledger.py"),
        ] + [a for a in sys.argv[1:] if a != "--bank-ledger"]
        runpy.run_path(sys.argv[0], run_name="__main__")
        return 0  # bank_ledger sys.exit()s itself; not reached

    ap = argparse.ArgumentParser()
    ap.add_argument("--bank-ledger", action="store_true",
                    help="run the Zipfian bank-ledger 2PC transaction "
                         "workload (scenarios/bank_ledger.py) instead of "
                         "the capacity ramp; remaining args are ITS flags "
                         "(--accounts, --txns, --inflight, --out, ...)")
    ap.add_argument("--init-load", type=float, default=500.0,
                    help="starting request rate/s (PROBE_INIT_LOAD analog)")
    ap.add_argument("--factor", type=float, default=1.1)
    ap.add_argument("--threshold", type=float, default=0.9)
    ap.add_argument("--latency-ms", type=float, default=1000.0)
    ap.add_argument("--window-s", type=float, default=3.0,
                    help="measurement window per load step")
    ap.add_argument("--groups", type=int, default=10)
    ap.add_argument("--clients", type=int, default=4,
                    help="injector threads (NUM_CLIENTS analog)")
    ap.add_argument("--max-rounds", type=int, default=12)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the JAX backend to CPU")
    ap.add_argument("--unreplicated", action="store_true",
                    help="EMULATE_UNREPLICATED attribution mode "
                         "(PaxosManager.java:1731): answer at the entry "
                         "without consensus, isolating app+wire cost")
    ap.add_argument("--durable", action="store_true",
                    help="in-process nodes journal to disk (native "
                         "group-commit path under full system load)")
    ap.add_argument("--in-process", action="store_true",
                    help="all nodes in this process (default: one OS "
                         "process per node — the realistic deployment "
                         "shape; in-process shares one GIL across six "
                         "tick loops and saturates early)")
    ap.add_argument("--attach", metavar="PROPS", default=None,
                    help="probe an ALREADY-RUNNING cluster booted from "
                         "this properties file (scripts/gp_server.py "
                         "start all) instead of booting nodes here")
    ap.add_argument("--repeats", type=int, default=1,
                    help="independent ramps; >1 reports a noise band "
                         "(this host shows ~±40%% run-to-run)")
    ap.add_argument("--pin-cores", default=None, metavar="LIST",
                    help="comma-separated CPU ids to pin this process "
                         "to (perf convention: pinned, ramp-only)")
    ap.add_argument("--capacity-out", default=None, metavar="FILE",
                    help="merge this run's capacity record into FILE "
                         "(CAPACITY_rNN.json trajectory tracking)")
    ap.add_argument("--label", default=None,
                    help="record key inside --capacity-out (default: "
                         "derived from mode flags)")
    args = ap.parse_args()

    if args.pin_cores:
        cores = {int(c) for c in args.pin_cores.split(",") if c != ""}
        try:
            os.sched_setaffinity(0, cores)
        except (AttributeError, OSError) as e:
            print(json.dumps({"warn": f"pin-cores failed: {e}"}))

    if args.cpu:
        # single-threaded XLA: N tick loops sharing a small host thrash
        # an intra-op thread pool (measured: +20% capacity and ~3x lower
        # latency at equal load on a 1-core box with 6 in-process nodes)
        flags = os.environ.get("XLA_FLAGS", "")
        if "intra_op_parallelism_threads" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_cpu_multi_thread_eigen=false "
                "intra_op_parallelism_threads=1"
            ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")

    from gigapaxos_tpu.clients.reconfigurable_client import (
        ReconfigurableAppClient,
    )
    from gigapaxos_tpu.utils.config import Config

    Config.clear()
    if args.attach:
        # ops-parity mode: the cluster is already up (gp_server.py) —
        # build only the client's address book from the scenario file
        Config.load_file(args.attach)
    else:
        ports = free_ports(6)
        for i in range(3):
            Config.set(f"active.AR{i}", f"127.0.0.1:{ports[i]}")
            Config.set(f"reconfigurator.RC{i}",
                       f"127.0.0.1:{ports[3 + i]}")
    if args.unreplicated:
        Config.set("EMULATE_UNREPLICATED", "true")
        os.environ["GP_EMULATE_UNREPLICATED"] = "true"  # child processes
    node_names = [f"{r}{i}" for r in ("AR", "RC") for i in range(3)]
    nodes = []
    procs = []
    if args.attach:
        pass  # nothing to boot
    elif args.in_process:
        from gigapaxos_tpu.utils.compile_cache import (
            configure_compile_cache,
        )

        configure_compile_cache()
        from gigapaxos_tpu.models.apps import NoopPaxosApp
        from gigapaxos_tpu.ops.engine import EngineConfig
        from gigapaxos_tpu.reconfigurable_node import ReconfigurableNode

        ar_cfg = EngineConfig(
            n_groups=max(64, args.groups * 2), window=16, req_lanes=8,
            n_replicas=3,
        )
        rc_cfg = EngineConfig(n_groups=64, window=16, req_lanes=8,
                              n_replicas=3)  # match the child default
        log_root = None
        if args.durable:
            import atexit
            import shutil
            import tempfile

            log_root = tempfile.mkdtemp(prefix="gp_probe_journal_")
            atexit.register(shutil.rmtree, log_root, True)
        nodes = [
            ReconfigurableNode(
                n, NoopPaxosApp, ar_cfg=ar_cfg, rc_cfg=rc_cfg,
                log_dir=(f"{log_root}/{n}" if log_root else None),
            )
            for n in node_names
        ]
        for n in nodes:
            n.start()
    else:
        # one OS process per node (bin/gpServer.sh loopback parity):
        # properties file + `python -m gigapaxos_tpu.reconfigurable_node`
        import subprocess
        import tempfile

        props = tempfile.NamedTemporaryFile(
            "w", suffix=".properties", delete=False
        )
        for i in range(3):
            props.write(f"active.AR{i}=127.0.0.1:{ports[i]}\n")
            props.write(f"reconfigurator.RC{i}=127.0.0.1:{ports[3 + i]}\n")
        props.write(f"ENGINE_ROWS={max(64, args.groups * 2)}\n")
        props.write("SLOT_WINDOW=16\n")
        # NOTE: child RCs use the node's default rc_cfg (64 rows, window
        # SLOT_WINDOW); the in-process mode mirrors that below so the two
        # modes differ only in process topology
        props.write(
            "APPLICATION=gigapaxos_tpu.models.apps.NoopPaxosApp\n"
        )
        props.close()
        env = dict(os.environ)
        env["GIGAPAXOS_CONFIG"] = props.name
        # a chip belongs to one process, so six node processes run on
        # the CPU; on a chip the six names boot in ONE process
        # (chip_smoke.py, or --in-process here)
        env["JAX_PLATFORMS"] = "cpu"
        err_log = tempfile.NamedTemporaryFile(
            "w+", suffix=".nodes.log", delete=False
        )
        for n in node_names:
            procs.append(subprocess.Popen(
                [sys.executable, "-m", "gigapaxos_tpu.reconfigurable_node", n],
                env=env, stdout=err_log, stderr=err_log,
            ))
        # wait for every listener; fail fast if a child dies
        deadline = time.time() + 120
        while time.time() < deadline:
            dead = [pr for pr in procs if pr.poll() is not None]
            if dead:
                break
            up = 0
            for p in ports:
                try:
                    s_ = socket.create_connection(("127.0.0.1", p), 0.2)
                    s_.close()
                    up += 1
                except OSError:
                    pass
            if up == 6:
                break
            time.sleep(0.5)
        else:
            dead = procs
        if any(pr.poll() is not None for pr in procs) or (
            time.time() >= deadline
        ):
            for pr in procs:
                pr.kill()
            err_log.flush()
            err_log.seek(0)
            print(json.dumps({
                "error": "node processes failed to start",
                "node_log_tail": err_log.read()[-2000:],
            }))
            err_log.close()
            os.unlink(err_log.name)
            os.unlink(props.name)
            return 1
    client = ReconfigurableAppClient.from_properties()
    # echo-probe the actives FIRST: the redirector's estimates are seeded
    # before any real traffic, so even the warm-up requests route to the
    # measured-nearest active (placement-plane client orientation)
    seeded = client.probe_actives(wait_s=3.0)
    print(json.dumps({"echo_probe_seeded_actives": seeded}), flush=True)
    names = [f"probe{i}" for i in range(args.groups)]
    for nm in names:
        ack = client.create_name(nm, actives=[0, 1, 2], timeout=60)
        assert ack and ack.get("ok"), (nm, ack)
    # warm the path (first requests compile/settle everything) — timed
    # separately: this window holds the engine-step XLA compiles, and a
    # compile-time regression must be visible as its own artifact field,
    # not smeared into the capacity ramp
    t_warm = time.time()
    for nm in names:
        client.send_request_sync(nm, "warm", timeout=30)
    warmup_s = time.time() - t_warm
    print(json.dumps({"warmup_s": round(warmup_s, 2)}), flush=True)

    n_injectors = args.clients
    # pre-resolve every name's entry target ONCE (round-robin across the
    # actives): the injector must not pay resolution/redirector cost per
    # request — at probe rates the injector's own per-request constant
    # deflates the measured SYSTEM capacity (sampling-profiled at ~40%
    # of a loaded 1-core host before this fast path)
    # route each name's traffic at its COORDINATOR (initial coord =
    # members[row % |members|], the create-time rule): a non-coordinator
    # entry must forward every proposal — one extra frame encode/
    # decode + two extra latency legs per request for 2/3 of the
    # traffic.  Smart clients route at the leader; elections can move it
    # (the forward path still handles that correctly, it just costs).
    # Rows are emulated with the same deterministic probe the creator
    # uses (crc32 % G, linear probe over occupancy in creation order).
    from zlib import crc32 as _crc32

    engine_rows = Config.get("ENGINE_ROWS") if args.attach else None
    G_rows = int(engine_rows) if engine_rows else max(64, args.groups * 2)
    occ = set()
    targets = {}
    for i, nm in enumerate(names):
        acts = client.request_actives(nm) or [0, 1, 2]
        acts = [a for a in acts if int(a) in client.actives]
        row = _crc32(nm.encode("utf-8")) % G_rows
        while row in occ:
            row = (row + 1) % G_rows
        occ.add(row)
        target = acts[row % len(acts)] if acts else 0
        targets[nm] = tuple(client.actives[int(target)])
    # GC tuning: the request path allocates ~30 short-lived objects per
    # request; default gen-0 cadence (700 allocs) costs measurable core
    # at 25k+ req/s.  Harness-wide (all in-process nodes benefit).
    import gc

    gc.set_threshold(200000, 100, 100)

    def run_round(rate: float):
        """Fire at `rate` for window_s from N injector threads (the
        reference drives its probe with NUM_CLIENTS=9 senders,
        ``TESTPaxosConfig.java:115``).  Quantum-batched: each injector
        wakes every few ms and fires the accrued quantum through the
        prepared-send fast path, so harness overhead stays flat as the
        rate ramps.  Returns (resp_rate, latencies_sorted)."""
        lock = threading.Lock()
        lats = []  # response latencies, seconds
        sent_counts = [0] * n_injectors
        QUANTUM_S = 0.004

        def inject(idx: int):
            per_s = rate / n_injectors
            t0 = time.time()
            t_end = t0 + args.window_s
            fired = 0
            i = 0
            while True:
                now = time.time()
                if now >= t_end:
                    break
                due = int((now - t0) * per_s) - fired
                if due <= 0:
                    time.sleep(QUANTUM_S)
                    continue
                t_batch = now  # one clock read per quantum (≤4ms skew)

                def cb(rid, resp, error, _t=t_batch):
                    if not error:
                        lat = time.time() - _t
                        with lock:
                            lats.append(lat)

                # group the quantum by entry target: ONE client lock +
                # one aggregation enqueue per target per wake-up
                by_target = {}
                for _ in range(due):
                    nm = names[(i * n_injectors + idx) % len(names)]
                    i += 1
                    by_target.setdefault(targets[nm], []).append(
                        (nm, f"p{idx}x{i}")
                    )
                for addr, items in by_target.items():
                    client.send_prepared_batch(addr, items, cb, t0=t_batch)
                fired += due
                sent_counts[idx] += due
        threads = [
            threading.Thread(target=inject, args=(j,), daemon=True)
            for j in range(n_injectors)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # grace: late responses within the latency budget still count
        time.sleep(min(1.0, args.latency_ms / 1000.0))
        sent = sum(sent_counts)
        with lock:
            out = sorted(lats)
        return (len(out) / sent if sent else 0.0), out

    def pct(sorted_lats, q):
        if not sorted_lats:
            return float("inf")
        k = min(len(sorted_lats) - 1, int(q * len(sorted_lats)))
        return sorted_lats[k]

    def run_ramp():
        """One ramp-only capacity pass; returns (capacity, rounds)."""
        capacity = 0.0
        rate = args.init_load
        curve = []
        for rnd in range(args.max_rounds):
            resp_rate, lats = run_round(rate)
            mean = sum(lats) / len(lats) if lats else float("inf")
            ok = resp_rate >= args.threshold and \
                mean * 1000 <= args.latency_ms
            line = {
                "round": rnd, "load_rps": round(rate, 1),
                "response_rate": round(resp_rate, 3),
                "mean_latency_ms": round(mean * 1000, 1),
                "p50_ms": round(pct(lats, 0.50) * 1000, 1),
                "p99_ms": round(pct(lats, 0.99) * 1000, 1),
                "sustained": ok,
            }
            print(json.dumps(line), flush=True)
            curve.append(line)
            if not ok:
                break
            capacity = rate
            rate *= args.factor
        return capacity, curve

    repeats = []
    try:
        for rep in range(max(1, args.repeats)):
            if rep:
                time.sleep(1.0)  # settle between ramps (ramp-only, no
                # binary search: every repeat walks the same ladder)
                print(json.dumps({"ramp": rep}), flush=True)
            capacity, curve = run_ramp()
            repeats.append({"capacity_rps": capacity, "rounds": curve})
        caps = sorted(r["capacity_rps"] for r in repeats)
        median = caps[len(caps) // 2]
        noise_pct = (
            (caps[-1] - caps[0]) / median * 100.0 if median else 0.0
        )
        mode = "unreplicated (app+wire only)" if args.unreplicated \
            else ("durable full system path" if args.durable
                  else "full system path")
        # measured per-phase breakdown (the obs-plane SLO surface): the
        # server-side phase histograms from the stats admin op + this
        # client's end-to-end latency histogram, so a capacity artifact
        # says WHERE the budget went, not just how much survived
        def _hist_summary(h):
            return {
                "count": h["count"],
                "avg_ms": round(h["sum"] / h["count"] * 1e3, 3),
                "max_ms": round((h["max"] or 0.0) * 1e3, 3),
            }

        phases = {}
        node_mesh = {}
        try:
            from gigapaxos_tpu.clients import PaxosClientAsync

            stats_cli = PaxosClientAsync(
                [tuple(a) for a in client.actives.values()]
            )
            try:
                st = stats_cli.admin_sync(0, {"op": "stats"}, timeout=5)
            finally:
                stats_cli.close()
            engine = (st or {}).get("engine") or {}
            node_mesh = engine.get("mesh") or {}
            hists = engine.get("hists") or {}
            for k in ("engine_step_s", "phase_ingress_s",
                      "phase_execute_s", "phase_flush_s",
                      "phase_publish_s", "pipeline_overlap_s"):
                h = hists.get(k)
                if h and h.get("count"):
                    phases[k] = _hist_summary(h)
        except Exception as e:  # a stats hiccup must not void the run
            phases["stats_error"] = str(e)
        cl = client.metrics.snapshot()["hists"].get(
            "client_request_latency_s"
        )
        if cl and cl.get("count"):
            phases["client_request_latency_s"] = _hist_summary(cl)
        print(json.dumps({"phases": phases}), flush=True)
        summary = {
            "metric": "system_capacity_requests_per_s",
            "value": round(median, 1),
            "capacity_min_rps": round(caps[0], 1),
            "capacity_max_rps": round(caps[-1], 1),
            "noise_band_pct": round(noise_pct, 1),
            "repeats": len(caps),
            "unit": f"req/s ({args.groups} groups, 3 actives + 3 RCs, "
                    f"loopback sockets, {mode})",
            "protocol": f"ramp-only x{args.factor} until "
                        f"resp<{args.threshold} or "
                        f"latency>{args.latency_ms}ms, "
                        f"{max(1, args.repeats)} repeats",
            "warmup_s": round(warmup_s, 2),
        }
        print(json.dumps(summary), flush=True)
        if args.capacity_out:
            label = args.label or (
                "unreplicated" if args.unreplicated
                else ("durable" if args.durable else "in_process")
            )
            try:
                with open(args.capacity_out) as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                doc = {
                    "metric": "serving_capacity_trajectory",
                    "host": {},
                    "reference_floor_rps": 50000,
                    "target_rps": 32000,
                    "baseline_round5_rps": {"in_process": 15944,
                                            "durable": 7320},
                }
            doc["host"] = {
                "cpus": os.cpu_count(),
                "pinned_cores": sorted(
                    int(c) for c in (args.pin_cores or "").split(",")
                    if c != ""
                ),
            }
            doc[label] = {
                "capacity_rps": summary["value"],
                "min_rps": summary["capacity_min_rps"],
                "max_rps": summary["capacity_max_rps"],
                "noise_band_pct": summary["noise_band_pct"],
                "repeats": [r["capacity_rps"] for r in repeats],
                "curves": [r["rounds"] for r in repeats],
                "protocol": summary["protocol"],
                "phases": phases,
                "warmup_s": summary["warmup_s"],
                "provenance": _probe_provenance(
                    args.in_process, node_mesh
                ),
            }
            with open(args.capacity_out, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            print(json.dumps(
                {"capacity_out": args.capacity_out, "label": label}
            ), flush=True)
        if args.in_process:
            # per-segment attribution, node by node: each registry's
            # counters and every span's count, mean and maximum
            for n in nodes:
                for srv in n.servers:
                    print(f"stats node {srv.my_id}:",
                          srv.manager.metrics.summary_line(), flush=True)
    finally:
        client.close()
        for n in nodes:
            n.stop()
        for pr in procs:
            pr.terminate()
        for pr in procs:
            try:
                pr.wait(timeout=10)
            except Exception:
                pr.kill()
        if procs:
            for f in (props.name, err_log.name):
                try:
                    os.unlink(f)
                except OSError:
                    pass
            try:
                err_log.close()
            except OSError:
                pass
        Config.clear()
    return 0


if __name__ == "__main__":
    sys.exit(main())
