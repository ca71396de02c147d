"""The plain reference and the comparison that decides ``correct``.

The reference is a sequential model of the adder service: per name, a
running sum of the deltas.  It imports nothing of the program and takes
nothing the program made; its inputs are the requests the generator sent
(name, delta, the acknowledged value) and the totals read from the app of
each active after the drain.

Every comparison is exact, so every limit is 0:

``ack_value_mismatches``     with ``per_name_order``: an acknowledgement
    whose value is not its name's running sum (a dropped, repeated or
    reordered write shows here).  Without it (many writers on one name):
    two acknowledgements of one name with the same value, or a value that
    is below its own delta or above the sum of everything sent there.
``replica_total_mismatches`` (active, name) pairs whose total after the
    drain is not the sum of the acknowledged deltas.  A name with failed
    requests may hold any subset of their deltas too — the same subset on
    every active.
``replicas_missing``         actives short of the configuration's count.
"""

from collections import defaultdict


def _by_name(reqs):
    out = defaultdict(list)
    for r in reqs:
        out[r.name].append(r)
    return out


def _value(response):
    try:
        return int(response)
    except (TypeError, ValueError):
        return None


def ack_value_mismatches(reqs, per_name_order, offset=None):
    """``reqs`` in order of first send.  ``offset``: per name, what was
    acknowledged before these (added to, for the next group).  Returns
    (count, examples)."""
    bad = []
    offset = defaultdict(int) if offset is None else offset
    for name, rs in _by_name(reqs).items():
        base = offset[name]
        offset[name] += sum(r.delta for r in rs)
        if per_name_order:
            total = base
            for r in rs:
                if r.t_ack is None:
                    break  # a failed write: what follows is not knowable
                total += r.delta
                if _value(r.response) != total:
                    bad.append((name, r.response, total))
        else:
            sent = base + sum(r.delta for r in rs)
            seen = set()
            for r in rs:
                if r.t_ack is None:
                    continue
                v = _value(r.response)
                if v is None or v in seen or v < base + r.delta or v > sent:
                    bad.append((name, r.response, sent))
                seen.add(v)
    return len(bad), bad[:5]


def _subset_sums(deltas):
    sums = {0}
    for d in deltas:
        sums |= {s + d for s in sums}
    return sums


def replica_total_mismatches(reqs, totals_per_active, names):
    """``totals_per_active``: one ``{name: total}`` per active, read after
    the drain.  Returns (count, examples)."""
    bad = []
    for name, rs in _by_name(reqs).items():
        acked = sum(r.delta for r in rs if r.t_ack is not None)
        maybe = [r.delta for r in rs if r.t_ack is None]
        got = [t.get(names[name], 0) for t in totals_per_active]
        allowed = {acked + s for s in _subset_sums(maybe)}
        for i, g in enumerate(got):
            if g not in allowed or g != got[0]:
                bad.append((i, names[name], g, acked))
    return len(bad), bad[:5]


def compare(groups, totals_per_active, names, n_replicas):
    """Every number compared, beside its limit:
    ``[(what, value, limit, examples)]``; correct when none is over.
    ``groups``: ``[(requests in order of first send, per_name_order)]``,
    one after the other in time."""
    acks, ack_ex, offset = 0, [], defaultdict(int)
    for reqs, ordered in groups:
        n, ex = ack_value_mismatches(reqs, ordered, offset)
        acks, ack_ex = acks + n, ack_ex + ex
    every = [r for reqs, _ in groups for r in reqs]
    tot, tot_ex = replica_total_mismatches(every, totals_per_active, names)
    return [
        ("ack_value_mismatches", acks, 0, ack_ex[:5]),
        ("replica_total_mismatches", tot, 0, tot_ex),
        ("replicas_missing", max(0, n_replicas - len(totals_per_active)),
         0, []),
    ]
