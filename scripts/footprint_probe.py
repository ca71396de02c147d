"""Static memory-footprint probe for the engine's exchange + step.

Prints ONE JSON line with the blob bytes/replica (compact ``D`` layout vs
the pre-compact all-int32 layout), the engine state bytes, and a peak
step-transient estimate for a given (G, W, K, R) — pure arithmetic over
the engine's leaf tables, so CI and CPU-only rounds can assert the HBM
budget without a TPU.

Usage:
    python scripts/footprint_probe.py [--groups G] [--window W]
                                      [--req-lanes K] [--replicas R]
                                      [--sharded N]

``device_queue`` is the device-resident I/O of a deployed node's step
(``parallel/spmd.py:make_step``, packed-host flavor): the [G, K] request
ring going up and the packed out_vec it leaves on the device.

Defaults are the headline bench shape (G=1,048,576, W=32, K=16, R=3).

``--sharded N`` adds the group-sharded SPMD deployment arithmetic
(``parallel/spmd.py:make_step`` over a ``('g',)`` mesh): G pads up to a multiple of N,
each device hosts padded_G/N groups x all R replica rows, and the
per-device peak is exactly the single-chip model at the local group
count.  The mode ASSERTS the per-device blob cost per hosted group stays
at the compact-blob budget (16 + 16*W bytes/group/replica-row — 528 B at
W=32): sharding must never add per-group exchange overhead, and a future
format regression that fans a per-shard plane into the blob fails the
probe (exit 1), not a TPU run.

The transient model: the step's cross-replica reductions fold one peer
row at a time with [G, W] carries (11 planes across the two folds), the
per-row decode materializes ~7 more, and the execute/admission unrolls
plus the under-construction new state and outputs hold ~12 — call it
~30 live [G, W] int32 planes at the worst program point, plus the [R, N]
gathered compact rows and (with buffer donation) ONE state copy.  That
is an upper-bound envelope, not a measurement: the pre-compact step
additionally materialized [R, G, W] and [R+1, G, W] masked intermediates
and a [G, W, W] execute one-hot (~8 GB at G=1M/W=32/R=3), which is the
delta this probe exists to keep honest.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ~live [G, W] int32 planes at the step's worst point (see module docstring)
TRANSIENT_LANE_PLANES = 30
# EngineState: 12 [G] + 7 [G, W] int32 leaves (ops/engine.py:EngineState)
STATE_G_LEAVES = 12
STATE_GW_LEAVES = 7


def probe(G: int, W: int, K: int, R: int) -> dict:
    from gigapaxos_tpu.ops.engine import (
        EngineConfig,
        blob_vec_len,
        legacy_blob_vec_len,
        out_vec_len,
    )

    cfg = EngineConfig(n_groups=G, window=W, req_lanes=K, n_replicas=R)
    blob_b = 4 * blob_vec_len(cfg)
    legacy_b = 4 * legacy_blob_vec_len(cfg)
    state_b = 4 * (STATE_G_LEAVES * G + STATE_GW_LEAVES * G * W)
    gathered_b = R * blob_b
    transient_b = 4 * TRANSIENT_LANE_PLANES * G * W
    out_b = 4 * out_vec_len(cfg)
    # single-chip bench hosts all R replica states + the shared gathered
    # rows + one stepping replica's transients (vmap serializes per XLA
    # scheduling at this size; use R as the conservative upper bound)
    single_chip_peak_b = R * state_b + gathered_b + R * transient_b + R * out_b
    return {
        "shape": {"G": G, "W": W, "K": K, "R": R},
        "blob_bytes_per_replica": blob_b,
        "blob_bytes_per_group": round(blob_b / G, 1),
        "legacy_blob_bytes_per_replica": legacy_b,
        "blob_reduction_pct": round(100.0 * (1 - blob_b / legacy_b), 1),
        "state_bytes_per_replica": state_b,
        "gathered_rows_bytes": gathered_b,
        "step_transient_estimate_bytes": transient_b,
        "single_chip_peak_estimate_bytes": single_chip_peak_b,
        "single_chip_peak_estimate_gib": round(
            single_chip_peak_b / 2 ** 30, 2
        ),
    }


def device_queue(G: int, W: int, K: int, R: int) -> dict:
    """Device-resident I/O ring bytes for a deployed node (the unified
    step's packed-host flavor): the [G, K] request ring in, the packed
    out_vec back."""
    from gigapaxos_tpu.ops.engine import EngineConfig, out_vec_len

    cfg = EngineConfig(n_groups=G, window=W, req_lanes=K, n_replicas=R)
    req_b = 4 * G * K
    out_b = 4 * out_vec_len(cfg)
    return {
        "request_ring_bytes": req_b,
        "response_ring_bytes": out_b,
        "total_ring_bytes": req_b + out_b,
        "ring_bytes_per_group": round((req_b + out_b) / G, 1),
    }


def probe_sharded(G: int, W: int, K: int, R: int, n_shards: int) -> dict:
    """Group-sharded deployment arithmetic + the per-group budget assert."""
    from gigapaxos_tpu.parallel.spmd import padded_group_count

    Gp = padded_group_count(G, n_shards)
    g_loc = Gp // n_shards
    local = probe(g_loc, W, K, R)
    budget_b = 16 + 16 * W  # 4*(4 [G] + 4*W [G, W]) int32 -> 528 at W=32
    per_group = local["blob_bytes_per_replica"] / g_loc
    out = {
        "n_shards": n_shards,
        "padded_groups": Gp,
        "groups_per_device": g_loc,
        "pad_overhead_pct": round(100.0 * (Gp - G) / G, 2),
        # each device hosts ALL R replica rows of its shard: the exchange
        # is the locally stacked blobs (no gathered peer rows)
        "per_device_state_bytes": R * local["state_bytes_per_replica"],
        "per_device_blob_bytes": R * local["blob_bytes_per_replica"],
        "per_device_blob_bytes_per_group": round(per_group, 1),
        "compact_budget_bytes_per_group": budget_b,
        "per_device_peak_estimate_bytes":
            local["single_chip_peak_estimate_bytes"],
        "per_device_peak_estimate_gib":
            local["single_chip_peak_estimate_gib"],
        "within_budget": per_group <= budget_b,
    }
    return out


def probe_paused(n_paused: int, state_bytes: int, window: int) -> dict:
    """Deployment arithmetic for the PAUSED tail (the density campaign's
    cold names): bytes/name in the packed spill store on disk + index
    bytes in RAM, measured from real encodings of a representative
    quiescent pause record — not hand-waved constants — then asserted
    against a per-paused-name budget (a record-format regression that
    fans per-name cost out fails this probe, not a 1M-name run)."""
    import sys as _sys

    from gigapaxos_tpu.utils.packedstore import _HDR, _key_to_wire

    name = "svc0123456"  # representative 10-char service name
    key = (name, 0)
    # quiescent record shape (manager._extract_record): no window
    # remnants, single-member group, app state of the given size
    rec = {
        "name": name, "epoch": 0, "exec": 64, "bal": 7,
        "app_hash": 2 ** 30, "n_execd": 64,
        "app_state": "x" * max(1, state_bytes),
        "app_exec": 64, "acc": [], "dec": [], "dedup": {},
        "members": [0, 1, 2],
    }
    payload = json.dumps([_key_to_wire(key), rec]).encode("utf-8")
    disk_per_name = _HDR.size + len(payload)
    # RAM tier: the spill index entry (key -> (seg, off, len)) + the
    # by-name epoch mirror (manager._paused_by_name).  Dict slots cost
    # ~3 machine words amortized at CPython's 2/3 fill bound.
    dict_slot = 3 * 8 / (2 / 3)
    index_per_name = (
        _sys.getsizeof(key)
        + _sys.getsizeof(name)
        + _sys.getsizeof((0, 0, 0))
        + 3 * _sys.getsizeof(0)
        + dict_slot  # spill index slot
        + _sys.getsizeof(name) + _sys.getsizeof({0}) + dict_slot  # mirror
    )
    # budget: JSON framing + record scaffolding must stay O(100 B) over
    # the app state; the RAM index must stay pointer-sized, not
    # record-sized (the whole point of paging the records out)
    disk_budget = 640 + 2 * max(1, state_bytes)
    ram_budget = 1024
    return {
        "n_paused": n_paused,
        "app_state_bytes": state_bytes,
        "window": window,
        "disk_bytes_per_name": disk_per_name,
        "disk_budget_bytes_per_name": disk_budget,
        "index_ram_bytes_per_name": round(index_per_name, 1),
        "index_ram_budget_bytes_per_name": ram_budget,
        "paused_tail_disk_bytes": n_paused * disk_per_name,
        "paused_tail_index_ram_bytes": round(n_paused * index_per_name),
        "within_budget": (
            disk_per_name <= disk_budget and index_per_name <= ram_budget
        ),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--groups", "-G", type=int, default=1_048_576)
    ap.add_argument("--window", "-W", type=int, default=32)
    ap.add_argument("--req-lanes", "-K", type=int, default=16)
    ap.add_argument("--replicas", "-R", type=int, default=3)
    ap.add_argument("--sharded", "-N", type=int, default=0, metavar="N",
                    help="add group-sharded arithmetic for an N-device "
                         "mesh and assert the per-group blob budget")
    ap.add_argument("--paused", type=int, default=0, metavar="N",
                    help="add paused-tail arithmetic for N paused names "
                         "(packed spill store) and assert the "
                         "per-paused-name disk/RAM budgets")
    ap.add_argument("--paused-state-bytes", type=int, default=64,
                    help="representative app-state size inside the "
                         "pause record for --paused")
    args = ap.parse_args()
    out = probe(args.groups, args.window, args.req_lanes, args.replicas)
    out["device_queue"] = device_queue(
        args.groups, args.window, args.req_lanes, args.replicas)
    if args.sharded > 0:
        out["sharded"] = probe_sharded(
            args.groups, args.window, args.req_lanes, args.replicas,
            args.sharded,
        )
    if args.paused > 0:
        out["paused"] = probe_paused(
            args.paused, args.paused_state_bytes, args.window,
        )
    print(json.dumps(out))
    if args.sharded > 0 and not out["sharded"]["within_budget"]:
        print(
            f"FOOTPRINT BUDGET EXCEEDED: "
            f"{out['sharded']['per_device_blob_bytes_per_group']} B/group "
            f"> {out['sharded']['compact_budget_bytes_per_group']} B/group "
            f"compact-blob budget at {args.sharded} shards",
            file=sys.stderr,
        )
        return 1
    if args.paused > 0 and not out["paused"]["within_budget"]:
        p = out["paused"]
        print(
            f"PAUSED-TAIL BUDGET EXCEEDED: disk "
            f"{p['disk_bytes_per_name']} B/name (budget "
            f"{p['disk_budget_bytes_per_name']}) / index RAM "
            f"{p['index_ram_bytes_per_name']} B/name (budget "
            f"{p['index_ram_budget_bytes_per_name']})",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
