"""This node's own publish vector on the host, and the frames cut from it.

The step names the rows its fresh blob changed (``ops/engine.py:
make_news``); the host keeps ONE mirror of the vector — patched with
those rows after every step, replaced whole when they do not fit — and
beside it, per row, the tick in which the row last changed.  A peer
connection's base is then a TICK, not a vector: the frame for it is the
rows changed since (``row_tick > base``) at their newest values, found
with one pass over ``[G]`` ticks instead of a compare of two whole
vectors per peer per tick.  A peer that missed k ticks gets the union of
their rows; a new connection, or one whose peer lost its base, gets the
whole vector.  The wire is ``net/codec.py``'s ``D`` and ``d`` frames,
unchanged.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np

from ..ops.engine import EngineConfig, update_rows
from .codec import (
    _row_blocks,
    changed_rows,
    delta_is_smaller,
    encode_blob_delta,
    encode_blob_vec,
    patch_blob_vec,
    rows_of,
)


def news_blocks(body: np.ndarray, n_rows: int,
                cfg: EngineConfig) -> List[np.ndarray]:
    """The words of a news vector's rows (``ops/engine.py:
    split_news_vec``: a packed vector of ``update_rows`` rows whose first
    ``n_rows`` hold them) as ``codec.rows_of`` would have cut them."""
    return [block[:, :n_rows]
            for block in _row_blocks(body, cfg, update_rows(cfg))]


class PublishMirror:
    """One writer (the thread that completes this node's steps), any
    number of encoders (the transport loop's senders).  ``lock`` guards
    ``vec``, ``row_tick`` and ``tick`` between them, so a frame holds
    every row as ONE tick left it; the writer reads without it."""

    def __init__(self, cfg: EngineConfig, sender: int):
        self.cfg = cfg
        self.sender = int(sender)
        self.lock = threading.Lock()
        self.vec: Optional[np.ndarray] = None  # before the first step
        self.tick = 0  # steps folded in: the tick a frame carries
        self.row_tick = np.zeros(cfg.n_groups, np.int64)

    # ---- the writer ----------------------------------------------------
    def patch(self, rows: np.ndarray, blocks: List[np.ndarray]) -> None:
        """A step's news: ``rows`` ascending and their words, as a ``d``
        frame carries them (``codec.rows_of``)."""
        with self.lock:
            self.tick += 1
            patch_blob_vec(self.vec, rows, blocks, self.cfg)
            self.row_tick[rows] = self.tick

    def replace(self, vec: np.ndarray) -> None:
        """A step whose news did not fit (or the first): its whole
        vector, writable and this mirror's from here on.  The rows that
        differ from the vector held are found here, once, and not by
        every connection."""
        rows = slice(None) if self.vec is None \
            else changed_rows(vec, self.vec, self.cfg)
        with self.lock:
            self.tick += 1
            self.vec = vec
            self.row_tick[rows] = self.tick

    # ---- the encoders --------------------------------------------------
    def encode(self, _marker, base_tick: Optional[int]
               ) -> Tuple[bytes, Optional[int], int]:
        """``net/transport.py``'s latest-wins encoder: the frame for a
        connection that last wrote and drained this node's vector of
        ``base_tick`` (None: a new connection, or a peer that lost its
        base).  -> (frame, rows in the delta or None for a ``D`` frame,
        the tick it carries: that connection's next base).  The marker
        says only that a tick was published: the frame is cut from what
        the mirror holds NOW."""
        cfg = self.cfg
        with self.lock:
            tick = self.tick
            rows = None
            if base_tick is not None:
                rows = np.flatnonzero(
                    self.row_tick > base_tick).astype(np.int32)
                if not delta_is_smaller(rows.size, cfg):
                    rows = None
            # either way what leaves the lock is a copy
            if rows is None:
                return encode_blob_vec(self.sender, tick, self.vec), None, tick
            words = rows_of(self.vec, rows, cfg)
        return encode_blob_delta(
            self.sender, tick, base_tick, rows, words), int(rows.size), tick
