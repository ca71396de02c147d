"""What a tick's frames brought of the peers, until a dispatch takes it.

The gathered stack lives on the device (``ops/engine.py:init_stack``);
the host keeps of each peer the packed vector its frames add up to, and
of each tick only the NEWS: the rows a ``d`` frame named, or "whole"
for a ``D`` frame.  :meth:`GatherNews.drain` merges a tick's news into
the ONE update the step scatters — fixed shape, no (peer, row) twice,
the later frame winning (an XLA scatter with repeated indices has no
order) — and names the peers whose vector goes up whole instead: those
a ``D`` frame replaced, and any whose rows do not fit.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

import numpy as np

from ..ops.engine import EngineConfig, update_rows, update_vec_len
from .codec import _row_blocks, changed_rows, patch_blob_vec, rows_of


class GatherUpdate(NamedTuple):
    """One dispatch's news of the peers."""

    whole: Tuple[Tuple[int, np.ndarray], ...]  # (peer, its [N] vector)
    rows: Optional[np.ndarray]  # the [update_vec_len] vector; None: no row
    n_rows: int                 # rows in it that are not padding
    n_scattered: int            # peers whose news of this tick it carries


NO_NEWS = GatherUpdate((), None, 0, 0)


def empty_update_vec(cfg: EngineConfig) -> np.ndarray:
    """An update of padding only: every index past the stack, distinct
    and ascending as the scatter is promised."""
    C = update_rows(cfg)
    vec = np.zeros(update_vec_len(cfg), np.int32)
    vec[:C] = cfg.n_replicas * cfg.n_groups + np.arange(C, dtype=np.int32)
    return vec


class GatherNews:
    """Not thread-safe: the owner calls every method under the lock that
    guards the vectors the news describes."""

    def __init__(self, cfg: EngineConfig):
        self.cfg = cfg
        # peer -> None (its whole vector) or the (rows, blocks) of each
        # delta frame since the last drain, in arrival order
        self._news: Dict[int, Optional[List]] = {}

    def whole(self, peer: int) -> None:
        self._news[peer] = None

    def rows(self, peer: int, rows: np.ndarray,
             blocks: List[np.ndarray]) -> None:
        frames = self._news.setdefault(peer, [])
        if frames is not None:  # rows on top of a whole vector are in it
            frames.append((rows, blocks))

    def hear(self, peer: int, vec: np.ndarray,
             held: Optional[np.ndarray]) -> np.ndarray:
        """A peer's newest vector as its frames would bring it to a
        holder of ``held`` (the stepped harnesses; a node's frames come
        decoded, ``server._on_blob``): whole the first time, then the
        rows that differ, patched into ``held``.  -> the vector held."""
        if held is None:
            self.whole(peer)
            return vec.copy()
        rows = changed_rows(vec, held, self.cfg)
        blocks = rows_of(vec, rows, self.cfg)
        patch_blob_vec(held, rows, blocks, self.cfg)
        self.rows(peer, rows, blocks)
        return held

    def all_whole(self, peers) -> None:
        """Forget the rows: the next drain sends these peers whole (a
        drained update that no dispatch applied cannot be taken back)."""
        self._news = {int(p): None for p in peers}

    def drain(self, vecs: Mapping[int, np.ndarray]) -> GatherUpdate:
        """The update for what came since the last drain; ``vecs`` are
        the peers' vectors as the frames left them.  A vector that goes
        up whole is copied here: later frames patch ``vecs`` in place."""
        if not self._news:
            return NO_NEWS
        news, self._news = self._news, {}
        cfg = self.cfg
        G, C = cfg.n_groups, update_rows(cfg)
        merged, whole, n = [], [], 0
        for peer in sorted(news):
            frames = news[peer]
            if frames is not None:
                rows, blocks = _merge_frames(frames)
                if n + rows.size <= C:
                    merged.append((peer, rows, blocks))
                    n += rows.size
                    continue
            whole.append((peer, vecs[peer].copy()))
        if not merged:
            return GatherUpdate(tuple(whole), None, 0, 0)
        vec = empty_update_vec(cfg)
        out = _row_blocks(vec[C:], cfg, C)
        at = 0
        for peer, rows, blocks in merged:
            k = rows.size
            vec[at:at + k] = peer * G + rows
            for dst, src in zip(out, blocks):
                dst[:, at:at + k] = src
            at += k
        return GatherUpdate(tuple(whole), vec, n, len(merged))


def _merge_frames(frames) -> Tuple[np.ndarray, List[np.ndarray]]:
    """One peer's delta frames of a tick as one: rows ascending, each
    once, with the words of the LAST frame that named it."""
    rows = frames[0][0]
    if len(frames) == 1 and (rows.size < 2 or (rows[1:] > rows[:-1]).all()):
        return frames[0]  # ascending and unique, as our senders write it
    rows = np.concatenate([r for r, _b in frames])
    blocks = [np.concatenate(bs, axis=1)
              for bs in zip(*[b for _r, b in frames])]
    # first occurrence in the reversed order = last frame that named it
    uniq, first = np.unique(rows[::-1], return_index=True)
    last = rows.size - 1 - first
    return uniq.astype(np.int32), [b[:, last] for b in blocks]
