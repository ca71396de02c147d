"""One cell of the benchmark, once, in one process on one TPU chip.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The served path as ``chip_smoke.py`` drives it (six names booted in this
process, names created through the reconfigurators, writes over the
binary client frames with retransmission under the same id, every
acknowledged write read back from the app of each active), with a
traffic loop and a measured window in place of its lock-step rounds.

Set-up, all of it ``setup_s``: boot, wait for the reconfigurators'
start-up election, create the names, wait for the names' elections to settle, one warm-up write to every name, ten seconds of the
cell's own traffic.  Then the window; then, outside it, the drain, the
read-back and the comparison with the plain reference (``checker.py``).

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by its name in ``BENCHMARK.json``
(``README.md`` beside this file).  The last line of standard output is
the result object; everything else is on earlier lines or on standard
error.  There is no CPU branch and no platform option: without a TPU the
script exits non-zero and prints no result.
"""

import time

_T0 = time.perf_counter()  # process start, as nearly as Python can tell

import argparse
import collections
import importlib.util
import json
import math
import os
import resource
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import checker  # noqa: E402
import trace_reduce  # noqa: E402
from step_bytes import step_bytes  # noqa: E402

CREATE_BATCH = 100       # names per create call: a batch of hundreds keeps
#   an active's one event loop busy past the 6 s failure-detection timeout,
#   its peers go unheard, and every name elects a new coordinator
CREATE_RETRY_S = 15.0    # a create batch (1.3 s as a rule) unanswered this
#   long is sent again under a NEW batch id: an intent proposed while the
#   reconfigurators elect their coordinator is stranded, and a batch sent again
#   under its old id is skipped name by name as "pending" (`_create`)
CREATE_TRIES = 4
SETTLE_S = 3.0           # no coordinator change for this long: settled
SETTLE_MAX_S = 60.0
WARM_TRAFFIC_S = 10.0    # the cell's own traffic before the window, unmeasured
WARM_ROUND_RAMP_S = 10.0 # the warm-up writes go out over this long: all at
#   once they are the saturated load, a commit then takes most of the
#   retransmission interval, and what is sent again is proposed anew
WARM_ROUND_FAIL_S = 180.0
DRAIN_S = 30.0
READ_BACK_S = 30.0
POLL_S = 0.25
TRACE_S = 3.0            # the traced slice, in the middle of the window
PEAKS_FILE = os.path.join(HERE, "peaks.json")


def log(msg):
    print(f"[bench {time.perf_counter() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


def say(**line):
    """An information line on standard output (never the last)."""
    print(json.dumps(line), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + os.path.basename(path)[:-3].replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload):
    """The cell's entry, configuration, traffic mix and metric files."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, conf_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    metrics = []
    for m in bench["per_layer"]:
        if workload in m.get("workloads", [workload]):
            metrics.append(load_json(HERE, "layer_metrics", m["name"] + ".json"))
    end_to_end = [
        m["name"] for m in bench["end_to_end"]
        if workload in m.get("workloads", [workload])
    ]
    return cell, config, traffic, metrics, end_to_end


# ---------------------------------------------------------------------------
# snapshots of the program's counters
# ---------------------------------------------------------------------------
def snapshot(ars):
    """Each active's counters and histograms now, with its tick count and
    the step sentinels' compile counts among the counters."""
    out = []
    for s in ars:
        snap = s.manager.metrics.snapshot()
        compiles = s.manager.engine_compile_stats()
        snap["counters"]["ticks"] = s._tick
        snap["counters"]["step_compiles"] = sum(
            c["compiles"] for c in compiles.values())
        snap["counters"]["step_retraces"] = sum(
            c["retraces"] for c in compiles.values())
        out.append(snap)
    return out


def counter_delta(before, after, key):
    """Per active: the counter's growth between two snapshots."""
    return [a["counters"].get(key, 0) - b["counters"].get(key, 0)
            for b, a in zip(before, after)]


def hist_delta(before, after, key):
    """Per active: (sum, count) growth of a histogram."""
    out = []
    for b, a in zip(before, after):
        hb = b["hists"].get(key, {"sum": 0.0, "count": 0})
        ha = a["hists"].get(key, {"sum": 0.0, "count": 0})
        out.append((ha["sum"] - hb["sum"], ha["count"] - hb["count"]))
    return out


# ---------------------------------------------------------------------------
# per-layer metrics: one reader per kind, one file per metric
# ---------------------------------------------------------------------------
def _mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def read_stats_hist(spec, ctx):
    """Mean per observation of a histogram over the window, mean of the
    actives that observed anything."""
    per = [s / n for s, n in hist_delta(ctx["before"], ctx["after"],
                                        spec["hist"]) if n > 0]
    m = _mean(per)
    return None if m is None else m * spec.get("scale", 1.0)


def read_stats_counter(spec, ctx):
    deltas = counter_delta(ctx["before"], ctx["after"], spec["counter"])
    form = spec["per"]
    if form == "total":
        return float(sum(deltas))
    if form == "commit":
        return sum(deltas) / ctx["acks"] if ctx["acks"] else None
    if form == "second":
        return sum(deltas) / ctx["window_s"]
    if form == "ms_per_count":
        m = _mean(1000.0 * ctx["window_s"] / d for d in deltas if d > 0)
        return m
    raise ValueError(f"{spec['name']}: per {form!r}")


def read_trace(spec, ctx):
    """Device time of the events whose name matches ``pattern``: with
    several programs of that name, the one that took most time."""
    if ctx["trace"] is None:
        return None
    prog = trace_reduce.program_time(ctx["trace"], spec["pattern"])
    if prog is None:
        return None
    if spec["stat"] == "ms_per_event":
        return 1000.0 * prog["seconds"] / prog["events"]
    raise ValueError(f"{spec['name']}: stat {spec['stat']!r}")


def read_module(spec, ctx):
    mod = load_module(os.path.join(HERE, "layer_metrics", spec["module"] + ".py"))
    return mod.read(spec, ctx)


READERS = {"stats_hist": read_stats_hist, "stats_counter": read_stats_counter,
           "trace": read_trace, "module": read_module}


def layer_metrics(specs, ctx):
    out = {}
    for spec in specs:
        value = READERS[spec["reader"]](spec, ctx)
        if value is not None:  # nothing to read: left out of the line
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------
def percentile(sorted_xs, q):
    """Nearest rank."""
    if not sorted_xs:
        return None
    return sorted_xs[max(0, math.ceil(q * len(sorted_xs)) - 1)]


def end_to_end(reqs, t_start, t_end, fail_after_s, setup_s, create_s, n_names):
    """Everything a user of the service would see in the window: all the
    acknowledgements inside it over all of its seconds; the latencies of
    all of them, a failed request counting as slower than any."""
    window = t_end - t_start
    acked = [r for r in reqs if r.t_ack is not None and not r.failed
             and t_start <= r.t_ack < t_end]
    failed = [r for r in reqs if r.failed
              and t_start <= r.t_first + fail_after_s < t_end]
    lat = sorted([1000.0 * (r.t_ack - r.t_first) for r in acked]
                 + [1000.0 * fail_after_s] * len(failed))
    return {
        "committed_rps": (len(acked) / window, "req/s"),
        "commit_p50_ms": (percentile(lat, 0.50), "ms"),
        "commit_p95_ms": (percentile(lat, 0.95), "ms"),
        "create_names_per_s": (n_names / create_s, "names/s"),
        "setup_s": (setup_s, "s"),
    }, len(acked), lat


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def cell_names(config):
    n = int(config["names"])
    width = len(str(max(1, n - 1)))
    return [f"{config['name_prefix']}{i:0{width}d}" for i in range(n)]


def _configure(config, log_dir):
    from gigapaxos_tpu.testing.ports import free_ports
    from gigapaxos_tpu.utils.config import Config

    Config.clear()
    ar_names, rc_names = config["actives"], config["reconfigurators"]
    ports = free_ports(len(ar_names) + len(rc_names))
    for name, port in zip(ar_names, ports):
        Config.set(f"active.{name}", f"127.0.0.1:{port}")
    for name, port in zip(rc_names, ports[len(ar_names):]):
        Config.set(f"reconfigurator.{name}", f"127.0.0.1:{port}")
    for key, value in config["settings"].items():
        Config.set(key, value)
    if config["settings"].get("ENABLE_JOURNALING"):
        Config.set("PAXOS_LOGS_DIR", log_dir)


def _poll_until(loop, t, while_=lambda: True):
    """Sleep until ``t`` on the harness's clock, serving the loop's
    retransmissions meanwhile."""
    while while_():
        now = time.perf_counter()
        if now >= t:
            break
        time.sleep(min(POLL_S, t - now))
        loop.poll()


def _flips(ars):
    return [s.manager.metrics.snapshot()["counters"].get(
        "coordinator_flips", 0) for s in ars]


def _wait_for_elections(servers):
    """Until none of ``servers`` has seen a coordinator change for
    ``SETTLE_S``: a proposal that meets an election is stranded until it
    is sent again."""
    t0 = last_change = time.perf_counter()
    seen = _flips(servers)
    while time.perf_counter() - last_change < SETTLE_S \
            and time.perf_counter() - t0 < SETTLE_MAX_S:
        time.sleep(POLL_S)
        now = _flips(servers)
        if now != seen:
            seen, last_change = now, time.perf_counter()
    return time.perf_counter() - t0


def _entry_is_coordinator(ars, names):
    """A configuration of ONE name runs in one of two modes, which a race
    in set-up chooses (PERF.md section 7): per active, in the order of the
    traffic's targets, whether it leads the name's row by its own state
    (name 0 enters at target 0: true there means nothing is forwarded).
    Read after the drain, so no judged number pays for it."""
    return [s.manager.coordinator_of_row(s.manager.names[names[0]])
            == s.manager.my_id for s in ars]


def _create(client, names):
    """The names in batches of ``CREATE_BATCH``, one after the other, each
    sent once under its batch id: a batch sent again under the same id
    rotates to another reconfigurator, which forwards its names one by
    one.  What is unanswered after ``CREATE_RETRY_S`` goes out as a new
    batch (a second intent for a name that exists is ignored by the
    record).  Returns ({name: answer}, names sent again)."""
    acks, again = {}, 0
    for i in range(0, len(names), CREATE_BATCH):
        todo = names[i:i + CREATE_BATCH]
        for _ in range(CREATE_TRIES):
            acks.update(client.create_names(
                todo, timeout=CREATE_RETRY_S - 1.0,
                retransmit_every=CREATE_RETRY_S))
            todo = [n for n in todo if n not in acks]
            if not todo:
                break
            again += len(todo)
            log(f"{len(todo)} creates unanswered after {CREATE_RETRY_S:.0f}s"
                f" ({todo[0]}...): sent again as a new batch")
    return acks, again


def _run_loop_until_done(loop, timeout_s, what, ars):
    """Drive a loop with a ``budget`` until every client has stopped."""
    loop.start()
    deadline = time.perf_counter() + timeout_s
    while loop.outstanding() and time.perf_counter() < deadline:
        _poll_until(loop, min(deadline, time.perf_counter() + 5.0),
                    while_=loop.outstanding)
        log(f"{what}: {loop.outstanding()} to go, ticks "
            f"{[s._tick for s in ars]}, flips {_flips(ars)}")
    if loop.outstanding():
        raise RuntimeError(f"{what}: {loop.outstanding()} unanswered "
                           f"after {timeout_s:.0f}s")
    if loop.failed or loop.errors:
        raise RuntimeError(f"{what}: {len(loop.failed)} failed, "
                           f"refusals {loop.errors[:3]}")


def run_cell(config, traffic, metric_specs, end_to_end_names, seed, seconds,
             trace, expect_platform, chips=1, t_process_start=None):
    """Set up, measure, drain, check; returns the result object.  Plain
    function of its arguments: ``benchmark/tests`` calls it on the CPU
    with a tiny configuration, and ``control.py`` with a fault under it."""
    import jax

    from gigapaxos_tpu.clients.reconfigurable_client import (
        ReconfigurableAppClient,
    )
    from gigapaxos_tpu.reconfigurable_node import boot_nodes
    from gigapaxos_tpu.utils.config import Config

    t_process_start = time.perf_counter() if t_process_start is None \
        else t_process_start
    devices = jax.devices()[:chips]
    peaks = load_json(PEAKS_FILE).get(devices[0].device_kind)
    if peaks is None and trace:
        raise RuntimeError(f"no peaks for {devices[0].device_kind!r} "
                           f"in {PEAKS_FILE}")
    generator = load_module(
        os.path.join(HERE, "generators", traffic["loop"] + ".py"))
    scratch = tempfile.mkdtemp(prefix="gp_bench_")  # under TMPDIR
    names = cell_names(config)
    n_names = len(names)
    nodes, client = [], None
    try:
        # ---- set-up ---------------------------------------------------
        _configure(config, os.path.join(scratch, "journal"))
        ar_names = config["actives"]
        t0 = time.perf_counter()
        nodes = boot_nodes(ar_names + config["reconfigurators"])
        boot_s = time.perf_counter() - t0
        ars = [n.servers[0] for n in nodes[:len(ar_names)]]
        rcs = [n.servers[0] for n in nodes[len(ar_names):]]
        cfg = ars[0].cfg
        engine = {"rows": cfg.n_groups, "window": cfg.window,
                  "req_lanes": cfg.req_lanes, "replicas": cfg.n_replicas}
        if engine != config["engine"]:
            raise RuntimeError(f"the program booted {engine}, the "
                               f"configuration says {config['engine']}")
        journaled = [s.manager.logger is not None for s in ars]
        if set(journaled) != {bool(config["settings"].get("ENABLE_JOURNALING"))}:
            raise RuntimeError(f"journals {journaled}, the configuration "
                               "says otherwise")
        log(f"six names booted in {boot_s:.1f}s, journals {journaled}, "
            f"reconfigurators' flips {_flips(rcs)}")
        client = ReconfigurableAppClient.from_properties()
        # the reconfigurators elect their coordinator as they come up: a
        # create that meets that election is never answered
        rc_settle_s = _wait_for_elections(rcs)
        log(f"reconfigurators settled after {rc_settle_s:.1f}s, flips "
            f"{_flips(rcs)}")

        t0 = time.perf_counter()
        acks, created_again = _create(client, names)
        create_s = time.perf_counter() - t0
        everyone = list(range(len(ar_names)))
        bad = [n for n in names if not (acks.get(n) or {}).get("ok")
               or sorted(acks[n].get("actives", ())) != everyone]
        if bad:
            raise RuntimeError(f"{len(bad)} of {n_names} creates failed: "
                               f"{[(n, acks.get(n)) for n in bad[:3]]}")
        log(f"{n_names} creates answered in {create_s:.1f}s "
            f"({created_again} sent again), flips {_flips(ars)}, "
            f"reconfigurators' {_flips(rcs)}")
        settle_s = _wait_for_elections(ars)
        log(f"elections settled after {settle_s:.1f}s, flips {_flips(ars)}")

        targets = [tuple(client.actives[i]) for i in everyone]
        t0 = time.perf_counter()
        warm = generator.Loop(client, names, targets, {
            **traffic, "in_flight": n_names, "key_dist": "slot",
            "per_name_order": True, "budget": 1,
            "ramp_s": WARM_ROUND_RAMP_S if n_names > 1 else 0.0,
            "fail_after_s": WARM_ROUND_FAIL_S,
        }, seed)
        _run_loop_until_done(warm, WARM_ROUND_FAIL_S + 10, "warm-up round",
                             ars)
        log(f"warm-up round of {n_names} writes acknowledged in "
            f"{time.perf_counter() - t0:.1f}s "
            f"({sum(r.sends - 1 for r in warm.reqs)} sent again)")

        loop = generator.Loop(client, names, targets, traffic, seed + 1)
        loop.start()
        _poll_until(loop, time.perf_counter() + WARM_TRAFFIC_S)
        warm_s = time.perf_counter() - t0

        # ---- the window -----------------------------------------------
        before = snapshot(ars)
        t_start = time.perf_counter()
        setup_s = t_start - t_process_start
        t_end = t_start + seconds
        traced = None
        if trace:
            _poll_until(loop, t_start + max(0.0, (seconds - TRACE_S) / 2))
            traced = os.path.join(scratch, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(traced, profiler_options=opts)
            try:
                _poll_until(loop, min(time.perf_counter() + TRACE_S, t_end))
            finally:
                jax.profiler.stop_trace()
        _poll_until(loop, t_end)
        after = snapshot(ars)
        t_after = time.perf_counter()

        # ---- outside the window: drain, read back, check ----------------
        loop.stop()
        _poll_until(loop, time.perf_counter() + DRAIN_S,
                    while_=loop.outstanding)
        loop.fail_outstanding()
        reqs = warm.reqs + loop.reqs
        # the laggards execute a few ticks behind the entry replica
        deadline = time.perf_counter() + READ_BACK_S
        while True:
            totals = [dict(s.manager.app.totals) for s in ars]
            n_bad, _ = checker.replica_total_mismatches(reqs, totals, names)
            if not n_bad or time.perf_counter() > deadline:
                break
            time.sleep(POLL_S)
        # the warm-up round keeps per-name order whatever the mix does
        compared = checker.compare(
            [(warm.reqs, True), (loop.reqs, traffic["per_name_order"])],
            totals, names, config["replicas_per_name"],
        )
        meshes = [s.manager.mesh_info() for s in ars]
        off_platform = sum(m["platform"] != expect_platform for m in meshes)
        compiles = sum(counter_delta(before, after, "step_compiles")) \
            + sum(counter_delta(before, after, "step_retraces"))
        compared += [
            ("actives_off_" + expect_platform, off_platform, 0, meshes),
            ("compiles_in_window", compiles, 0, []),
            ("refusals", len(loop.errors), 0, loop.errors[:5]),
        ]
        correct = True
        for what, value, limit, examples in compared:
            ok = value <= limit
            correct = correct and ok
            say(check=what, value=value, limit=limit, ok=ok,
                **({"examples": examples} if not ok else {}))
        # each number compared beside its limit: the result's last key
        numbers = {what: {"value": value, "limit": limit}
                   for what, value, limit, _ in compared}

        e2e, n_acks, lat = end_to_end(
            loop.reqs, t_start, t_end, float(traffic["fail_after_s"]),
            setup_s, create_s, n_names,
        )
        per_second = collections.Counter(
            math.floor(r.t_ack - t_start) for r in loop.reqs
            if r.t_ack is not None)
        say(boot_s=boot_s, rc_settle_s=rc_settle_s, create_s=create_s,
            created_again=created_again, warm_s=warm_s,
            window_s=seconds, snapshot_span_s=t_after - t_start,
            acks_in_window=n_acks,
            latency_ms={"p50": e2e["commit_p50_ms"][0],
                        "p95": e2e["commit_p95_ms"][0],
                        "max": lat[-1] if lat else None},
            acks_per_s=[per_second[k] for k in range(int(seconds))],
            sent_again=sum(r.sends - 1 for r in loop.reqs),
            ticks=counter_delta(before, after, "ticks"),
            coordinator_flips=counter_delta(before, after,
                                            "coordinator_flips"),
            host_peak_rss_bytes=resource.getrusage(
                resource.RUSAGE_SELF).ru_maxrss * 1024,
            end_to_end={k: v[0] for k, v in e2e.items()},
            entry_is_coordinator=_entry_is_coordinator(ars, names)
            if n_names == 1 else None)

        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in devices),
        }
        result = {
            "correct": correct,
            "attempted": len(loop.reqs),
            "failed": len(loop.failed),
            "device": device,
        }
        if not trace:
            result["metrics"] = {
                k: {"value": e2e[k][0], "unit": e2e[k][1]}
                for k in end_to_end_names
            }
            result["compared"] = numbers
            return result
        reduced = trace_reduce.reduce_dir(traced)
        say(trace={k: reduced[k] for k in ("planes", "busy_s", "window_s",
                                           "programs")})
        ctx = {
            "before": before, "after": after, "window_s": t_after - t_start,
            # the counters span the two snapshots, so the acknowledgements
            # they are divided by do too (stopping the profiler can run on
            # past the window's end)
            "acks": sum(1 for r in loop.reqs if r.t_ack is not None
                        and t_start <= r.t_ack < t_after),
            "trace": reduced, "peaks": peaks, "device": device,
            "step_bytes": step_bytes(cfg.n_groups, cfg.window, cfg.req_lanes,
                                     cfg.n_replicas),
        }
        result["metrics"] = layer_metrics(metric_specs, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["top_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
        result["compared"] = numbers
        return result
    finally:
        if client is not None:
            client.close()
        for n in nodes:
            n.stop()
        Config.clear()
        shutil.rmtree(scratch, ignore_errors=True)


def reach_chip(chips):
    """Place the compile cache and look for the chips: the cache directory
    in use, or None (said on standard error) when JAX finds no TPU or too
    few."""
    import jax

    from gigapaxos_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache() \
        or os.environ.get("JAX_COMPILATION_CACHE_DIR")
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s), JAX found {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        return None
    return cache_dir


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    cell, config, traffic, metric_specs, e2e_names = load_cell(args.workload)

    cache_dir = reach_chip(cell["chips"])
    if cache_dir is None:
        return 1
    say(workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache=cache_dir)
    result = run_cell(config, traffic, metric_specs, e2e_names, args.seed,
                      args.seconds, bool(args.trace), "tpu",
                      chips=cell["chips"], t_process_start=_T0)
    for what, n in result["compared"].items():  # standard error's last lines
        print(f"compared {what} {n['value']} limit {n['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
