"""Growth of some of the program's histograms and counters over the
growth of others, in percent, the three actives pooled.

``num`` and ``den`` each name ``hists`` (the growth of their sums, in
seconds) and ``counters``; ``complement`` reports 100 less the ratio.
None where the denominator did not grow, and where NONE of a side's names
is in any active's snapshot: a program from before those spans and
counters existed reports nothing instead of a share of zero.  A single
name that is missing (a span that never ran in this cell) counts as 0."""



def _growth(side, ctx):
    """Sum over the named series and the actives of after less before
    (a series a snapshot does not hold counts as 0 there); None if no
    snapshot holds any of them."""
    total, found = 0.0, False
    for kind, value in (("hists", lambda h: h["sum"]),
                        ("counters", lambda c: c)):
        for key in side.get(kind, ()):
            for b, a in zip(ctx["before"], ctx["after"]):
                if key in a[kind]:
                    total += value(a[kind][key])
                    found = True
                if key in b[kind]:
                    total -= value(b[kind][key])
    return total if found else None


def read(spec, ctx):
    num, den = _growth(spec["num"], ctx), _growth(spec["den"], ctx)
    if num is None or den is None or den <= 0:
        return None
    share = 100.0 * num / den
    return 100.0 - share if spec.get("complement") else share
