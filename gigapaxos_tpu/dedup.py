"""Executed request ids: exactly-once as a function of the decided sequence.

A request id can be decided in two slots of one name — proposed again at
its entry replica after ``repropose_after_s``, or at a second entry
replica because its client moved.  Every replica executes the first and
skips the second, and must take that decision ALIKE: so what a replica
remembers of a name's executed ids may depend on nothing but the name's
own decided sequence.  (It was a per-node cache with a wall-clock TTL
and a size bound across names: three nodes forgot an id at three
different moments, and a duplicate decided later than that executed
twice — seed 103 of ``g1k-sat``, PERF.md section 7.)

The rule: a name's executions are numbered by the decided slots that
executed anything (``seq``: 1, 2, ...; the requests of one batch share
theirs), and an id is remembered while the name's newest ``seq`` is less
than ``DEDUP_SLOTS`` past its own.  A cold name thus keeps its last 256
writes, a hot one the requests of its last 256 batches, whatever the
clock says.  The entries travel with every hand-over of the name's app
state (state transfer, pause record, epoch-final state, checkpoint) in
the wire form ``{str(id): [time, response, name, seq]}``; the newest
``seq`` is the newest entry's, so nothing else has to.
"""

from __future__ import annotations

import collections
import time
from typing import Deque, Dict, Iterable, List, Optional, Tuple

DEDUP_SLOTS = 256

# (wall time of the execution, for a reader; response; name; seq)
Entry = Tuple[float, Optional[str], str, int]


class ExecutedIds:
    """Not thread-safe: the manager calls every method under its lock.
    ``entries`` is a plain dict so that ``id in m.response_cache`` and
    ``m.response_cache[id][1]`` stay what they were."""

    def __init__(self, slots: int = DEDUP_SLOTS):
        self.slots = int(slots)
        self.entries: Dict[int, Entry] = {}
        # name -> (seq, id) ascending in seq: the order they fall out in
        self._order: Dict[str, Deque[Tuple[int, int]]] = {}

    def add(self, name: str, items: Iterable[Tuple[int, Optional[str]]],
            now: Optional[float] = None) -> None:
        """One decided slot of ``name`` executed ``items`` [(id,
        response)]: they take the name's next ``seq``, and what is
        ``slots`` behind it falls out."""
        items = list(items)
        if not items:
            return
        now = time.time() if now is None else now
        order = self._order.get(name)
        if order is None:
            order = self._order[name] = collections.deque()
        seq = order[-1][0] + 1 if order else 1
        for rid, response in items:
            self.entries[rid] = (now, response, name, seq)
            order.append((seq, rid))
        self._prune(name, order)

    def _prune(self, name: str, order: Deque[Tuple[int, int]]) -> None:
        cut = order[-1][0] - self.slots
        entries = self.entries
        while order[0][0] <= cut:
            _seq, rid = order.popleft()
            ent = entries.get(rid)
            if ent is not None and ent[2] == name:
                del entries[rid]

    def of_name(self, name: str) -> Dict[str, list]:
        """The name's entries in the wire form."""
        entries = self.entries
        out = {}
        for _seq, rid in self._order.get(name, ()):
            ent = entries.get(rid)
            if ent is not None and ent[2] == name:
                out[str(rid)] = list(ent)
        return out

    def of_names(self, names: Iterable[str]) -> Dict[str, Dict[str, list]]:
        return {nm: ents for nm in names if (ents := self.of_name(nm))}

    def install(self, wire: Optional[Dict]) -> None:
        """Merge wire-form entries (of any names) into what is held: an
        id held already keeps its entry; each name touched is pruned by
        the rule against its newest ``seq`` after the merge.  An entry
        without a ``seq`` (a journal older than the rule) gets 0: it
        falls out when the name has executed ``slots`` more."""
        now = time.time()
        touched: Dict[str, List[Tuple[int, int]]] = {}
        for rid_s, ent in (wire or {}).items():
            rid, name = int(rid_s), str(ent[2])
            seq = int(ent[3]) if len(ent) > 3 else 0
            if rid not in self.entries:
                self.entries[rid] = (min(float(ent[0]), now), ent[1],
                                     name, seq)
                touched.setdefault(name, []).append((seq, rid))
        for name, new in touched.items():
            order = collections.deque(
                sorted(list(self._order.get(name, ())) + new))
            self._order[name] = order
            self._prune(name, order)

    def forget(self, name: str) -> None:
        """Drop the name's entries (its app state is about to be replaced
        by one that does not contain their executions)."""
        entries = self.entries
        for _seq, rid in self._order.pop(name, ()):
            ent = entries.get(rid)
            if ent is not None and ent[2] == name:
                del entries[rid]

    def wire(self) -> Dict[str, list]:
        """Everything held, in the wire form (a checkpoint)."""
        return {str(rid): list(ent) for rid, ent in self.entries.items()}
