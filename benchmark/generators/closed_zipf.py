"""The closed loop of ``closed.py`` with each request's name drawn as YCSB
draws a key: ``CoreWorkload`` with ``requestdistribution=zipfian``
(``ZipfianGenerator.ZIPFIAN_CONSTANT`` 0.99), the hot keys scattered over
the key space as ``ScrambledZipfianGenerator`` scatters them.  A few names
take most of the writes and a long tail takes the rest, many writers on a
hot name at once: what a key-value or coordination service on replicated
logs sees (a popular account, a lock, a counter).

Parameters, beside those of ``closed.py`` (a traffic file):

``key_dist``       ``"zipfian"``: rank r of the ``len(names)`` ranks is
                   drawn with probability r ** -``zipf_constant`` over
                   the sum of those, EXACTLY (YCSB's ``ZipfianGenerator``
                   over ``items`` = ``recordcount``, not its draw over a
                   larger item space folded back).  Any other value goes
                   to ``closed.ClosedLoop`` untouched: the harness's
                   warm-up round is ``"slot"`` with ``budget`` 1
``zipf_constant``  YCSB's 0.99
``scrambled``      true: rank r is name ``perm[r]``, ``perm`` a permutation
                   of the names drawn from the seed (YCSB hashes the rank
                   with FNV to the same end: the hot names are no
                   neighbours, so they spread over entry replicas and
                   coordinators; which names are hot differs from seed to
                   seed, as the data does).  false: rank r is name r
``per_name_order`` false only: a hot name has many writers at once.  true
                   is a ``ValueError`` (a busy hot name drawn again and
                   again would bend the distribution)

Ranks are drawn in blocks of 4,096 from ``numpy.random.default_rng(seed)``
(one ``choice`` a block, as ``closed.py`` draws), the permutation from the
same generator before the first block: the same seed gives the same
requests.  At the end of the drain (:meth:`fail_outstanding`) one JSON
line on standard error says what was drawn: requests, distinct names, the
hottest name's share and the ten hottest names' beside what the
distribution expects.
"""

import collections
import json
import sys
import time

import numpy as np

from generators import closed  # benchmark/ is on the loader's path

BLOCK = 4096


def probabilities(items, constant):
    """P(rank r), r = 1 .. ``items``: r ** -constant over their sum."""
    weights = np.arange(1, items + 1, dtype=np.float64) ** -float(constant)
    return weights / weights.sum()


class ClosedZipfLoop(closed.ClosedLoop):
    def __init__(self, client, names, targets, traffic, seed,
                 clock=time.perf_counter):
        if traffic["key_dist"] != "zipfian":
            raise ValueError(f"key_dist {traffic['key_dist']!r}")
        if traffic["per_name_order"]:
            raise ValueError("per_name_order with key_dist zipfian: a hot "
                             "name has many writers at once")
        constant = float(traffic["zipf_constant"])
        if constant <= 0:
            raise ValueError(f"zipf_constant {constant}")
        # the base class draws a name index per request and takes it as it
        # comes when the mix is unordered: `_draw` below gives it ours
        super().__init__(client, names, targets,
                         {**traffic, "key_dist": "uniform"}, seed,
                         clock=clock)
        self.p = probabilities(len(names), constant)
        self.perm = self.rng.permutation(len(names)).tolist() \
            if traffic["scrambled"] else list(range(len(names)))

    def _draw(self):
        """(name index, delta), in blocks."""
        try:
            return next(self._draws)
        except StopIteration:
            perm = self.perm
            ranks = self.rng.choice(len(perm), size=BLOCK, p=self.p)
            self._draws = iter(zip(
                [perm[r] for r in ranks.tolist()],
                self.rng.integers(1, 1000, size=BLOCK).tolist(),
            ))
            return next(self._draws)

    def fail_outstanding(self):
        super().fail_outstanding()
        print(json.dumps({"zipf": self.summary()}), file=sys.stderr,
              flush=True)

    def summary(self):
        """What was drawn, beside what the distribution expects."""
        with self.lock:
            drawn = collections.Counter(r.name for r in self.reqs)
        n = sum(drawn.values())
        top = [c for _name, c in drawn.most_common(10)]
        return {
            "requests": n, "names_drawn": len(drawn),
            "hottest_name": self.names[self.perm[0]],
            "hottest_share": top[0] / n if n else None,
            "hottest_expected": float(self.p[0]),
            "top10_share": sum(top) / n if n else None,
            "top10_expected": float(self.p[:10].sum()),
        }


def Loop(client, names, targets, traffic, seed):
    if traffic["key_dist"] != "zipfian":
        return closed.ClosedLoop(client, names, targets, traffic, seed)
    return ClosedZipfLoop(client, names, targets, traffic, seed)
