"""Growth of one of the program's counters over the growth of another,
as a plain quotient (``ratio.py`` gives shares in percent), the actives
pooled.

``num`` and ``den`` name the two counters.  None where no active's
snapshot holds one of them (a program from before the counters existed
reports nothing instead of a quotient of zero) and where the
denominator did not grow."""


def _growth(key, ctx):
    if not any(key in a["counters"] for a in ctx["after"]):
        return None
    return sum(a["counters"].get(key, 0) - b["counters"].get(key, 0)
               for b, a in zip(ctx["before"], ctx["after"]))


def read(spec, ctx):
    num, den = _growth(spec["num"], ctx), _growth(spec["den"], ctx)
    if num is None or den is None or den <= 0:
        return None
    return num / den
