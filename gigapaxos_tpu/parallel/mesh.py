"""Device mesh construction for the consensus engine.

The replica axis ('r') is the TPU-native replacement for the reference's
NIO multicast between group members (``nio/NIOTransport.java:115`` et al.,
SURVEY.md §2.3): PREPARE/ACCEPT/ACCEPT_REPLY/COMMIT traffic rides one
``all_gather`` per step over ICI.  The group axis ('g') shards the
million-group state arrays — groups are fully independent, so 'g' needs no
collectives at all (the "group-parallelism" axis of SURVEY.md §2.8).

Two deployment shapes use these axes:

* ``make_mesh(n_replicas, n_group_shards)`` — the 2-D acceptor-per-chip
  mesh: each chip holds ONE replica row of a group shard and the blob
  exchange is an ``all_gather`` over 'r' (``spmd.make_step(cfg, mesh)``).
* ``make_group_mesh(n_devices)`` — the 1-D group-sharded mesh: every chip
  holds ALL R replica rows for its G/n slice, so the exchange is the
  device-local stacked blobs and the step has ZERO cross-device
  collectives (``spmd.make_step`` over this mesh).  This is the weak-scaling
  shape: capacity and throughput both scale with the device count.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

REPLICA_AXIS = "r"
GROUP_AXIS = "g"


def pick_mesh_shape(n_devices: int, n_replicas: Optional[int] = None) -> Tuple[int, int]:
    """Choose (group_shards, replicas): replica axis 3 when it divides the
    device count (the BASELINE v5e 3-acceptor layout), else 2, else 1."""
    if n_replicas is None:
        for r in (3, 2, 1):
            if n_devices % r == 0:
                n_replicas = r
                break
    if n_devices % n_replicas:
        raise ValueError(f"{n_replicas} replicas don't divide {n_devices} devices")
    return n_devices // n_replicas, n_replicas


def make_mesh(
    n_replicas: int,
    n_group_shards: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    devices = jax.devices() if devices is None else list(devices)
    need = n_replicas * n_group_shards
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    arr = np.array(devices[:need]).reshape(n_group_shards, n_replicas)
    return Mesh(arr, (GROUP_AXIS, REPLICA_AXIS))


def make_group_mesh(
    n_devices: Optional[int] = None,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """1-D mesh over the group axis only: every device hosts all R replica
    rows for its slice of the G axis (the zero-collective SPMD shape)."""
    devices = jax.devices() if devices is None else list(devices)
    n_devices = len(devices) if n_devices is None else n_devices
    if len(devices) < n_devices:
        raise ValueError(f"need {n_devices} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n_devices]), (GROUP_AXIS,))


def describe_state_mesh(leaf) -> Dict:
    """Runtime mesh descriptor of the devices backing one state array —
    {n_devices, shape, platform} for the ``stats`` admin op, so an
    accidentally-unsharded deployment (one device hosting a G meant to be
    spread over a mesh) is visible at runtime, not discovered in an OOM.

    Works on any jax.Array: a NamedSharding reports its mesh axes; a
    single-device array reports {n_devices: 1, shape: {}}."""
    try:
        sharding = leaf.sharding
        dev = sorted(sharding.device_set, key=lambda d: d.id)
        platform = dev[0].platform if dev else "unknown"
        shape: Dict[str, int] = {}
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None:
            shape = {str(k): int(v) for k, v in mesh.shape.items()}
        return {
            "n_devices": len(dev),
            "shape": shape,
            "platform": platform,
        }
    except (AttributeError, TypeError):
        # host numpy array or an abstract leaf: no device residency
        return {"n_devices": 0, "shape": {}, "platform": "host"}
