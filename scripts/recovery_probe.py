#!/usr/bin/env python
"""Recovery bench: restart-to-serving at production G with a multi-file
journal.

Builds a single-replica node hosting ``--g`` groups (bulk-created), runs
traffic over a recent slice, writes a sharded checkpoint, appends a
post-checkpoint journal tail across multiple files, then measures a cold
restart three ways:

* ``restart_to_serving_s`` — construction wall time: engine arrays
  loaded, journal segments replayed, hot set hydrated; the node serves.
* ``time_to_first_serve_s`` — restart start until a HOT name's request
  is answered (asserted to happen while phase == recovering, i.e. before
  background hydration finishes — the SLO the plane exists for).
* ``full_hydrate_s`` — restart start until the cold tail is drained and
  the phase flips to serving.

Emits one JSON document (stdout + ``--out``); commit as
``RECOVERY_rNN.json``.  Run on a QUIET box and treat single runs as
±40% (see the perf-measurement notes in README):

    JAX_PLATFORMS=cpu python scripts/recovery_probe.py \
        --g 262144 --names 262144 --shards 16 --workers 4 --out RECOVERY_r01.json
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


def ticks(m, n=4):
    for _ in range(n):
        m.tick_host(None, np.array([True]))


def make_app(state_bytes: int):
    """Adder whose checkpoint strings carry a realistic payload: the
    cost lazy hydration defers is the per-name restore + JSON parse,
    which scales with app-state size — a bare int undersells it."""
    from gigapaxos_tpu.models import StatefulAdderApp

    if state_bytes <= 0:
        return StatefulAdderApp()

    class PaddedStateApp(StatefulAdderApp):
        PAD = "x" * state_bytes

        def checkpoint(self, name):
            return json.dumps({"v": super().checkpoint(name),
                               "pad": self.PAD})

        def restore(self, name, state):
            if state and state.startswith("{"):
                state = json.loads(state)["v"]
            return super().restore(name, state)

    return PaddedStateApp()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--g", type=int, default=262144,
                    help="engine rows (>= --names)")
    ap.add_argument("--names", type=int, default=262144)
    ap.add_argument("--active", type=int, default=2048,
                    help="names that see traffic before the checkpoint")
    ap.add_argument("--tail", type=int, default=32768,
                    help="names with POST-checkpoint journal traffic")
    ap.add_argument("--pad-bytes", type=int, default=256,
                    help="request payload size in the journal tail "
                         "(forces the multi-file journal)")
    ap.add_argument("--state-bytes", type=int, default=512,
                    help="per-name app-state size in the checkpoint "
                         "(the cost lazy hydration defers)")
    ap.add_argument("--window", type=int, default=8)
    ap.add_argument("--shards", type=int, default=16)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--hot", type=int, default=1024)
    ap.add_argument("--journal-file-mb", type=float, default=4.0,
                    help="journal rotation size (small => multi-file)")
    ap.add_argument("--eager-baseline", action="store_true",
                    help="also time a full (non-lazy) restore")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from gigapaxos_tpu.manager import PaxosManager
    from gigapaxos_tpu.ops.engine import EngineConfig
    from gigapaxos_tpu.utils.config import Config

    Config.set("RECOVERY_CHECKPOINT_SHARDS", str(args.shards))
    Config.set("RECOVERY_REPLAY_WORKERS", str(args.workers))
    Config.set("RECOVERY_HOT_NAMES", str(args.hot))
    Config.set("MAX_LOG_FILE_SIZE",
               str(int(args.journal_file_mb * 1024 * 1024)))

    cfg = EngineConfig(
        n_groups=args.g, window=args.window, req_lanes=4, n_replicas=1
    )
    log_dir = tempfile.mkdtemp(prefix="gp_recovery_probe_")
    names = [f"svc{i:07d}" for i in range(args.names)]
    active = names[-args.active:]
    tail = names[-args.tail:]

    # ---- build phase ---------------------------------------------------
    t0 = time.monotonic()
    m = PaxosManager(
        0, make_app(args.state_bytes), cfg, log_dir=log_dir,
        checkpoint_every=10 ** 9, sync_journal=False,
    )
    for lo in range(0, len(names), 32768):
        m.create_paxos_batch(names[lo:lo + 32768], [0])
    t_create = time.monotonic() - t0
    print(f"[build] {len(names)} groups created in {t_create:.1f}s",
          flush=True)
    for lo in range(0, len(active), 4096):
        for i, nm in enumerate(active[lo:lo + 4096]):
            m.propose(nm, "1")
        ticks(m, 3)
    ticks(m, 6)
    t_ck = time.monotonic()
    m.checkpoint_now()
    m.logger.drain_checkpoints()
    t_ck = time.monotonic() - t_ck
    # post-checkpoint tail: padded payloads so the journal spans files
    # (leading zeros keep the adder delta at 10)
    value = "10".zfill(max(2, args.pad_bytes))
    for lo in range(0, len(tail), 4096):
        for nm in tail[lo:lo + 4096]:
            m.propose(nm, value)
        ticks(m, 3)
    ticks(m, 6)
    journal_files = len(m.logger.journal.file_indices())
    in_active = set(active)
    expected_hot = {nm: (11 if nm in in_active else 10) for nm in tail}
    m.close()
    du = sum(
        os.path.getsize(os.path.join(log_dir, f))
        for f in os.listdir(log_dir)
        if os.path.isfile(os.path.join(log_dir, f))
    )
    print(f"[build] checkpoint {t_ck:.1f}s, journal files "
          f"{journal_files}, dir {du / 1e6:.0f} MB", flush=True)

    # ---- restart phase (lazy) ------------------------------------------
    t_restart = time.monotonic()
    m2 = PaxosManager(
        0, make_app(args.state_bytes), cfg, log_dir=log_dir,
        checkpoint_every=10 ** 9, sync_journal=False,
    )
    restart_to_serving_s = time.monotonic() - t_restart
    rst = m2.recovery_stats()
    phase_at_serve = rst["phase"]
    backlog_at_serve = rst["hydration_backlog"]

    # first-serve: a HOT name answers (correctly) right now.  The phase
    # is captured INSIDE the callback — the instant the response fires —
    # so "served while still recovering" is measured, not raced
    hot_name = tail[-1]
    hot_is_hot = m2.names[hot_name] not in m2.hydrating_rows
    got = {}

    def on_reply(_rid, v):
        got["v"] = v
        got["phase"] = m2.recovery_phase
        got["t"] = time.monotonic() - t_restart

    m2.propose(hot_name, "5", callback=on_reply)
    ticks(m2, 8)
    time_to_first_serve_s = got.get("t", time.monotonic() - t_restart)
    phase_at_first_serve = got.get("phase", m2.recovery_phase)
    served_before_hydrated = (
        got.get("v") == str(expected_hot[hot_name] + 5)
        and phase_at_first_serve == "recovering"
    )

    # full hydration
    deadline = time.time() + 3600
    while m2.recovery_phase != "serving" and time.time() < deadline:
        time.sleep(0.05)
    full_hydrate_s = time.monotonic() - t_restart
    hydrated = m2.recovery_stats()["hydrated"]
    # spot-check convergence: never-driven names hold zero state, driven
    # names carry their full (pre + post checkpoint) history
    ok_cold = all(
        not m2.app.totals.get(nm)
        for nm in names[: max(0, args.names - max(args.active, args.tail))][:64]
    ) and all(
        m2.app.totals.get(nm) == expected_hot[nm] for nm in tail[:64]
    )
    m2.close()

    eager_s = None
    if args.eager_baseline:
        Config.set("RECOVERY_LAZY_HYDRATION", "false")
        t_eager = time.monotonic()
        m3 = PaxosManager(
            0, make_app(args.state_bytes), cfg, log_dir=log_dir,
            checkpoint_every=10 ** 9, sync_journal=False,
        )
        eager_s = time.monotonic() - t_eager
        m3.close()
        Config.set("RECOVERY_LAZY_HYDRATION", "true")

    out = {
        "bench": "recovery_probe",
        "g": args.g,
        "names": args.names,
        "window": args.window,
        "shards": args.shards,
        "replay_workers": args.workers,
        "hot_names": args.hot,
        "journal_files": journal_files,
        "journal_file_mb": args.journal_file_mb,
        "dir_bytes": du,
        "build": {
            "create_s": round(t_create, 3),
            "checkpoint_s": round(t_ck, 3),
        },
        "restart": {
            "restart_to_serving_s": round(restart_to_serving_s, 3),
            "time_to_first_serve_s": round(time_to_first_serve_s, 3),
            "full_hydrate_s": round(full_hydrate_s, 3),
            "phase_at_serve": phase_at_serve,
            "phase_at_first_serve": phase_at_first_serve,
            "hot_served_before_hydration_done": served_before_hydrated,
            "hot_name_is_hot": hot_is_hot,
            "hydration_backlog_at_serve": backlog_at_serve,
            "groups_hydrated_total": hydrated,
            "cold_tail_converged": ok_cold,
            "replay_segments": rst.get("segments"),
            "replay_blocks": rst.get("blocks"),
            "replay_s": round(rst.get("replay_s", 0.0), 3),
            "replay_blocks_per_s": (
                round(rst["blocks"] / rst["replay_s"], 1)
                if rst.get("replay_s") else None
            ),
            "checkpoint_generation": rst.get("checkpoint_generation"),
        },
        "eager_baseline_restart_s": (
            round(eager_s, 3) if eager_s is not None else None
        ),
    }
    text = json.dumps(out, indent=2)
    print(text)
    if args.out:
        Path(args.out).write_text(text + "\n")
    # the SLO facts the acceptance gate keys on
    if not served_before_hydrated:
        print("FAIL: hot name was not served before hydration finished",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
