"""Wire codecs for the host transport.

Two frame families share the TCP substrate (ref: the reference mixes JSON
and hand-rolled byte layouts on one NIO channel,
``paxosutil/PaxosPacketDemultiplexerFast.java:1``):

* ``J`` frames — JSON control messages: host-channel deltas, client
  requests/responses, failure-detection pings, admin ops.
* ``D`` frames — packed engine blobs: sender id + tick + raw int32 leaf
  bytes in ``Blob._fields`` order (shapes are static per EngineConfig, so
  no per-leaf headers are needed — the reference's fixed-layout
  ``RequestPacket.toBytes`` idea applied to whole state arrays).  The
  kind byte doubles as the blob SCHEMA version (``B`` was the pre-tag
  layout; ``C`` the pre-compact all-int32 layout; ``D`` is the compact
  exec-anchored layout, ``ops/engine.py`` module docstring): a
  fixed-layout frame from a different schema must be dropped by kind,
  never parsed misaligned — a mixed-version node fails loudly instead
  of feeding misparsed ballots into consensus.
* ``d`` frames — row deltas of a ``D`` blob against the vector the
  sender had published at the tick it last wrote to THIS connection (the
  base, named by that tick):
  sender + tick + base tick + row count, the int32 row indices, then each
  leaf's changed rows in ``Blob._fields`` order.  The receiver patches
  them into the vector it holds of that sender, which then equals the
  sender's publish vector at ``tick`` bit for bit.  A connection's first
  blob, and any blob whose delta would be no smaller, is a ``D`` frame.
"""

from __future__ import annotations

import itertools
import json
import struct
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..ops.engine import Blob, EngineConfig, _leaf_shapes, blob_vec_len

_BHDR = struct.Struct(">cIQ")  # kind, sender, tick
_DHDR = struct.Struct(">cIQQI")  # kind, sender, tick, base tick, rows

# cross-node trace context (Dapper-style, obs/reqtrace.py): an OPTIONAL
# ``"tc": [trace_id, origin_node, hop]`` field on J-frame request bodies
# (client_request[_batch] items, forward / forward_rows entries, payload
# gossip).  Absent = untraced; bodies without it are byte-identical to the
# pre-trace wire format.  The binary R/S frames carry the same triple in
# a fixed 13-byte layout (net/hot_codec.py).
TRACE_KEY = "tc"


def attach_trace(body: Dict, tc) -> Dict:
    """Stamp a trace context onto a request body (no-op when None)."""
    if tc is not None:
        body[TRACE_KEY] = [int(tc[0]), int(tc[1]), int(tc[2])]
    return body


def extract_trace(body: Dict):
    """-> (trace_id, origin, hop) or None; malformed contexts drop (a
    trace field must never break request handling)."""
    tc = body.get(TRACE_KEY)
    if not tc:
        return None
    try:
        return (int(tc[0]), int(tc[1]), int(tc[2]))
    except (TypeError, ValueError, IndexError, KeyError):
        return None


def bump_hop(tc):
    """The per-process-boundary hop increment (forwards re-stamp with
    this so the merged timeline orders hops causally even under clock
    skew)."""
    return None if tc is None else (tc[0], tc[1], tc[2] + 1)


def encode_json(kind: str, sender: int, body: Dict) -> bytes:
    env = {"k": kind, "s": sender, "b": body}
    return b"J" + json.dumps(env, separators=(",", ":")).encode("utf-8")


def decode_kind(payload: bytes) -> str:
    return payload[:1].decode("ascii", "replace")


def decode_json(payload: bytes) -> Tuple[str, int, Dict]:
    env = json.loads(payload[1:].decode("utf-8"))
    return env["k"], int(env["s"]), env["b"]


def blob_shapes(cfg: EngineConfig):
    # derived from the engine's leaf table so the per-leaf codec and the
    # packed-vector codec can never disagree on the wire layout
    return dict(_leaf_shapes(Blob._fields, cfg))


def encode_blob(sender: int, tick: int, blob: Blob) -> bytes:
    parts = [_BHDR.pack(b"D", sender, tick)]
    for leaf in blob:
        parts.append(np.asarray(leaf, np.int32).tobytes())
    return b"".join(parts)


def encode_blob_vec(sender: int, tick: int, vec: np.ndarray) -> bytes:
    """Packed-vector fast path: `vec` is already the frame body (leaf
    C-order ravels in ``Blob._fields`` order — identical bytes to
    :func:`encode_blob`)."""
    return _BHDR.pack(b"D", sender, tick) + np.ascontiguousarray(
        vec, np.int32
    ).tobytes()


def decode_blob_vec(
    payload: bytes, cfg: EngineConfig
) -> Tuple[int, int, np.ndarray]:
    """Zero-split decode for the packed tick path: the frame body IS the
    [N] gathered-row vector.  Same size check as :func:`decode_blob`."""
    kind, sender, tick = _BHDR.unpack_from(payload, 0)
    if kind != b"D":
        raise ValueError(
            f"blob frame schema {kind!r} != expected b'D' "
            "(mixed-version peer; refusing to parse)"
        )
    n = blob_vec_len(cfg)
    if len(payload) != _BHDR.size + 4 * n:
        raise ValueError(
            f"blob frame size {len(payload)} != expected "
            f"{_BHDR.size + 4 * n} (peer blob-schema/config mismatch)"
        )
    return sender, tick, np.frombuffer(payload, np.int32, offset=_BHDR.size)


def _row_blocks(vec: np.ndarray, cfg: EngineConfig,
                rows: Optional[int] = None) -> List[np.ndarray]:
    """A packed vector of ``rows`` engine rows (all of them by default)
    as its runs of same-shaped leaves, each a ``[leaves, rows, words]``
    view in wire order — the four ``[G]`` leaves are one block, the four
    ``[G, W]`` leaves another, so a pass over the rows is two numpy calls
    and not eight (each gives up the interpreter lock and queues for it
    again; six tick threads and the loops contend for it)."""
    rows = cfg.n_groups if rows is None else rows
    shapes = [s for _name, s in _leaf_shapes(Blob._fields, cfg)]
    blocks, off = [], 0
    for shape, run in itertools.groupby(shapes):
        leaves, words = len(list(run)), int(np.prod(shape[1:]))
        n = leaves * rows * words
        blocks.append(vec[off:off + n].reshape(leaves, rows, words))
        off += n
    return blocks


def changed_rows(vec: np.ndarray, base: np.ndarray,
                 cfg: EngineConfig) -> np.ndarray:
    """The engine rows in which two packed vectors differ, ascending."""
    changed = np.zeros(cfg.n_groups, bool)
    for block in _row_blocks(vec != base, cfg):
        changed |= block.any(axis=(0, 2))
    return np.flatnonzero(changed).astype(np.int32)


def rows_of(vec: np.ndarray, rows: np.ndarray,
            cfg: EngineConfig) -> List[np.ndarray]:
    """The named rows of a packed vector, as :func:`_row_blocks` of
    ``len(rows)`` rows (what a ``d`` frame carries of them)."""
    return [block[:, rows] for block in _row_blocks(vec, cfg)]


def delta_is_smaller(n_rows: int, cfg: EngineConfig) -> bool:
    """The size rule: a ``d`` frame of ``n_rows`` rows wherever it is
    smaller than the whole vector's ``D`` frame."""
    words = blob_vec_len(cfg)
    return _DHDR.size + 4 * n_rows * (1 + words // cfg.n_groups) \
        < _BHDR.size + 4 * words


def encode_blob_delta(sender: int, tick: int, base_tick: int,
                      rows: np.ndarray, blocks: List[np.ndarray]) -> bytes:
    """The ``d`` frame of the int32 ``rows`` with their words
    (:func:`rows_of`) against the vector published at ``base_tick``."""
    parts = [_DHDR.pack(b"d", sender, tick, base_tick, int(rows.size)),
             rows.tobytes()]
    parts += [block.tobytes() for block in blocks]
    return b"".join(parts)


def encode_blob_frame(
    sender: int, cfg: EngineConfig,
    item: Tuple[int, np.ndarray],
    base: Optional[Tuple[int, np.ndarray]],
) -> Tuple[bytes, Optional[int]]:
    """The blob frame for one peer connection found by COMPARING two
    vectors: ``item`` is the newest (tick, publish vector), ``base`` the
    pair that connection last carried (None on a new one).  -> (frame,
    rows in the delta; None for a full ``D`` frame).  The rule is what
    the two vectors show, nothing else: a delta wherever it is smaller
    than the whole vector.  A node's sender is told its rows by the step
    and compares nothing (``net/mirror.py``); this is what the tests
    hold its frames against, byte for byte."""
    tick, vec = item
    if base is not None:
        rows = changed_rows(vec, base[1], cfg)
        if delta_is_smaller(rows.size, cfg):
            return encode_blob_delta(
                sender, tick, base[0], rows, rows_of(vec, rows, cfg),
            ), int(rows.size)
    return encode_blob_vec(sender, tick, vec), None


def decode_blob_delta(
    payload: bytes, cfg: EngineConfig
) -> Tuple[int, int, int, np.ndarray, List[np.ndarray]]:
    """-> (sender, tick, base tick, row indices [n], the rows' new
    values as :func:`_row_blocks` of n rows).  Same refusals as
    :func:`decode_blob_vec`: a frame of another schema or shape is never
    parsed misaligned, and a row index outside the engine raises."""
    kind, sender, tick, base_tick, n = _DHDR.unpack_from(payload, 0)
    if kind != b"d":
        raise ValueError(
            f"blob delta schema {kind!r} != expected b'd' "
            "(mixed-version peer; refusing to parse)"
        )
    row_words = blob_vec_len(cfg) // cfg.n_groups
    if len(payload) != _DHDR.size + 4 * n * (1 + row_words):
        raise ValueError(
            f"blob delta size {len(payload)} != expected "
            f"{_DHDR.size + 4 * n * (1 + row_words)} for {n} rows "
            "(peer blob-schema/config mismatch)"
        )
    rows = np.frombuffer(payload, np.int32, count=n, offset=_DHDR.size)
    if n and not (0 <= rows.min() and rows.max() < cfg.n_groups):
        raise ValueError("blob delta names a row outside the engine")
    body = np.frombuffer(payload, np.int32, offset=_DHDR.size + 4 * n)
    return sender, tick, base_tick, rows, _row_blocks(body, cfg, n)


def patch_blob_vec(vec: np.ndarray, rows: np.ndarray,
                   blocks: List[np.ndarray], cfg: EngineConfig) -> None:
    """Write a decoded delta's rows into the held (mutable) vector."""
    for held, new in zip(_row_blocks(vec, cfg), blocks):
        held[:, rows] = new


def decode_blob(payload: bytes, cfg: EngineConfig) -> Tuple[int, int, Blob]:
    kind, sender, tick = _BHDR.unpack_from(payload, 0)
    if kind != b"D":
        raise ValueError(
            f"blob frame schema {kind!r} != expected b'D' "
            "(mixed-version peer; refusing to parse)"
        )
    shapes = blob_shapes(cfg)
    expect = _BHDR.size + 4 * sum(int(np.prod(s)) for s in shapes.values())
    if len(payload) != expect:
        # fixed-layout frame: a size mismatch means the peer runs a
        # different blob schema (version skew) or a different
        # EngineConfig — misaligned leaves would feed garbage ballots
        # into consensus, so reject the frame outright
        raise ValueError(
            f"blob frame size {len(payload)} != expected {expect} "
            "(peer blob-schema/config mismatch)"
        )
    off = _BHDR.size
    leaves = []
    for name in Blob._fields:
        shape = shapes[name]
        n = int(np.prod(shape))
        arr = np.frombuffer(payload, np.int32, count=n, offset=off).reshape(shape)
        off += n * 4
        leaves.append(arr)
    return sender, tick, Blob(*leaves)
