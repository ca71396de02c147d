#!/usr/bin/env python
"""Density bench: G >= 1M mostly-idle names on one host.

The group-density campaign's headline probe.  Boots ``--names`` paxos
groups (default 1,048,576) through the batched create + hibernate path —
paused names hold NO engine row, so the engine itself stays at
``--rows`` — then measures the three facts the campaign keys on:

* **bytes/name** — host RSS delta across the boot (the paused tail's
  RAM cost: spill index + by-name mirror + app residue) plus the HBM
  model (engine leaf bytes amortized over all names; paused names cost
  zero device bytes, so this is just the hot-row overhead).
* **batched-vs-per-name unpause ablation** — wall time to wake a
  ``--burst``-name cold set via the per-name ``restore`` loop vs ONE
  ``restore_batch`` (one fused create + one fused record install vs N
  device dispatches).  The acceptance gate: batched must be >= 5x.
* **churn** — Zipfian traffic over a ~``--hot-pct``% hot set whose head
  rotates every round; newly-hot names fault in from the packed spill
  store (wake p50/p99 recorded), names that fall out of the window are
  hibernated back, and the sustained request rate is measured WHILE the
  cold tail pages in and out.

Emits one JSON document (stdout + ``--out``); commit as
``DENSITY_rNN.json``.  Run on a QUIET box and treat single runs as
±40% (see the perf-measurement notes in README):

    JAX_PLATFORMS=cpu python scripts/density_probe.py \
        --names 1048576 --rows 32768 --out DENSITY_r01.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

# EngineState: 12 [G] + 7 [G, W] int32 leaves (ops/engine.py:EngineState)
STATE_G_LEAVES = 12
STATE_GW_LEAVES = 7


def rss_bytes() -> int:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    return 0


def ticks(m, n=4):
    for _ in range(n):
        m.tick_host(None, np.array([True]))


def pct(xs, q):
    if not xs:
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--names", type=int, default=1_048_576,
                    help="total names (G of the density claim)")
    ap.add_argument("--rows", type=int, default=32768,
                    help="engine rows (the AWAKE capacity; paused names "
                         "hold no row)")
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--boot-chunk", type=int, default=16384,
                    help="names per create+hibernate boot chunk "
                         "(must be <= --rows)")
    ap.add_argument("--burst", type=int, default=4096,
                    help="wake-burst size for the batched-vs-per-name "
                         "ablation (acceptance: >= 4096)")
    ap.add_argument("--hot-pct", type=float, default=1.0,
                    help="hot-set size as %% of --names")
    ap.add_argument("--rounds", type=int, default=20,
                    help="churn rounds (head rotates each round)")
    ap.add_argument("--round-requests", type=int, default=512,
                    help="Zipfian requests per churn round")
    ap.add_argument("--zipf-a", type=float, default=1.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--min-speedup", type=float, default=5.0,
                    help="ablation gate: batched must beat the per-name "
                         "loop by this factor")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from gigapaxos_tpu.manager import PaxosManager
    from gigapaxos_tpu.models import StatefulAdderApp
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.utils.config import Config

    if args.boot_chunk > args.rows:
        args.boot_chunk = args.rows
    hot_n = max(args.burst, int(args.names * args.hot_pct / 100.0))
    if hot_n > args.rows:
        print(f"FAIL: hot set {hot_n} exceeds engine rows {args.rows}",
              file=sys.stderr)
        return 1

    Config.set("PACKED_SPILL", "true")
    rng = np.random.default_rng(args.seed)
    cfg = EngineConfig(
        n_groups=args.rows, window=args.window, req_lanes=4, n_replicas=1
    )
    log_dir = tempfile.mkdtemp(prefix="gp_density_probe_")
    names = [f"svc{i:07d}" for i in range(args.names)]

    # ---- boot: create + hibernate in chunks ----------------------------
    rss0 = rss_bytes()
    t0 = time.monotonic()
    m = PaxosManager(
        0, StatefulAdderApp(), cfg, log_dir=log_dir,
        checkpoint_every=10 ** 9, sync_journal=False,
    )
    rss_mgr = rss_bytes()
    t_boot = time.monotonic()
    for lo in range(0, args.names, args.boot_chunk):
        chunk = names[lo:lo + args.boot_chunk]
        m.create_paxos_batch(chunk, [0])
        n_slept = m.hibernate_batch(chunk)
        assert n_slept == len(chunk), (n_slept, len(chunk))
        if (lo // args.boot_chunk) % 8 == 0:
            print(f"[boot] {lo + len(chunk)}/{args.names} names asleep, "
                  f"rss {rss_bytes() / 2**20:.0f} MiB", flush=True)
    t_boot = time.monotonic() - t_boot
    rss1 = rss_bytes()
    res_boot = m.residency_stats()
    assert res_boot["paused_names"] == args.names, res_boot
    engine_state_b = 4 * (STATE_G_LEAVES * args.rows
                          + STATE_GW_LEAVES * args.rows * args.window)
    print(f"[boot] {args.names} names in {t_boot:.1f}s "
          f"({args.names / t_boot:.0f} names/s), "
          f"host {(rss1 - rss0) / args.names:.0f} B/name", flush=True)

    # ---- ablation: per-name restore loop vs one restore_batch ----------
    # prewarm BOTH paths so neither measurement pays first-call tracing:
    # N=1 create/install/kill shapes via restore+hibernate, N=burst
    # shapes via restore_batch+hibernate_batch on a disjoint set
    A = names[: args.burst]
    B = names[args.burst: 2 * args.burst]
    assert m.restore(A[0]) and m.hibernate(A[0])
    assert m.restore_batch(B) == len(B)
    assert m.hibernate_batch(B) == len(B)

    t_seq = time.monotonic()
    for nm in A:
        assert m.restore(nm)
    t_seq = time.monotonic() - t_seq
    assert m.hibernate_batch(A) == len(A)

    t_batch = time.monotonic()
    assert m.restore_batch(A) == len(A)
    t_batch = time.monotonic() - t_batch
    assert m.hibernate_batch(A) == len(A)
    speedup = t_seq / t_batch if t_batch > 0 else float("inf")
    print(f"[ablation] seq {t_seq:.2f}s vs batch {t_batch:.3f}s on "
          f"{args.burst} names -> {speedup:.1f}x", flush=True)

    # ---- churn: Zipfian over a rotating hot window ---------------------
    delta = max(1, hot_n // 100)  # head advance per round (~1% of hot set)
    head = 2 * args.burst  # start past the ablation sets
    replies = [0]
    wake_lat: list[float] = []
    n_woken = 0
    n_proposed = 0

    def on_reply(_rid, _v):
        replies[0] += 1

    t_churn = time.monotonic()
    for rnd in range(args.rounds):
        window = [names[(head + i) % args.names] for i in range(hot_n)]
        ranks = np.minimum(rng.zipf(args.zipf_a, args.round_requests),
                           hot_n) - 1
        sampled = [window[int(r)] for r in ranks]
        cold = sorted({nm for nm in sampled if nm not in m.names})
        if cold:
            tw = time.monotonic()
            n_ok = m.restore_batch(cold)
            dt = time.monotonic() - tw
            assert n_ok == len(cold), (n_ok, len(cold))
            wake_lat.extend([dt] * len(cold))  # the whole burst waits
            n_woken += len(cold)
        for nm in sampled:
            m.propose(nm, "1", callback=on_reply)
        n_proposed += len(sampled)
        ticks(m, 3)
        head = (head + delta) % args.names
        in_window = set(window[delta:]) | {
            names[(head + hot_n - 1 - i) % args.names] for i in range(delta)
        }
        fell_out = [nm for nm in list(m.names) if nm not in in_window]
        if fell_out:
            m.hibernate_batch(fell_out)
    ticks(m, 8)  # drain in-flight decisions
    t_churn = time.monotonic() - t_churn
    rss2 = rss_bytes()
    res_end = m.residency_stats()
    store = res_end.get("store", {})
    m.close()

    out = {
        "bench": "density_probe",
        "names": args.names,
        "rows": args.rows,
        "window": args.window,
        "hot_set": hot_n,
        "burst": args.burst,
        "rounds": args.rounds,
        "zipf_a": args.zipf_a,
        "boot": {
            "boot_s": round(t_boot, 1),
            "names_per_s": round(args.names / t_boot, 1),
            "boot_chunk": args.boot_chunk,
        },
        "bytes_per_name": {
            "host_rss": round((rss1 - rss0) / args.names, 1),
            "host_rss_excl_manager": round(
                (rss1 - rss_mgr) / args.names, 1),
            "hbm_model": round(engine_state_b / args.names, 1),
            "spill_disk": store.get("bytes_per_record"),
        },
        "ablation": {
            "per_name_s": round(t_seq, 3),
            "batched_s": round(t_batch, 3),
            "speedup": round(speedup, 1),
            "per_name_wake_us_batched": round(
                1e6 * t_batch / args.burst, 1),
        },
        "churn": {
            "churn_s": round(t_churn, 1),
            "requests": n_proposed,
            "replies": replies[0],
            "sustained_rps": round(replies[0] / t_churn, 1),
            "names_woken": n_woken,
            "unpause_p50_s": round(pct(wake_lat, 50) or 0.0, 4),
            "unpause_p99_s": round(pct(wake_lat, 99) or 0.0, 4),
            "rss_end_mib": round(rss2 / 2**20, 1),
        },
        "store": store,
        "residency_end": {
            k: res_end.get(k)
            for k in ("active_names", "paused_names", "paused_in_memory",
                      "paused_on_disk")
        },
    }
    text = json.dumps(out, indent=2)
    print(text)
    if args.out:
        Path(args.out).write_text(text + "\n")
    # the acceptance facts the gate keys on
    if args.names < 1_000_000:
        print("note: run below the 1M-name density claim", file=sys.stderr)
    if speedup < args.min_speedup:
        print(f"FAIL: batched unpause only {speedup:.1f}x over the "
              f"per-name loop (need >= {args.min_speedup}x)",
              file=sys.stderr)
        return 1
    if replies[0] < n_proposed:
        print(f"FAIL: only {replies[0]}/{n_proposed} requests answered",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
