"""The largest active's share of a counter's growth over the window, in
percent: how unevenly the actives were loaded.  With three actives 33.3
is even and 100 is one active doing everything.

``counter`` names the series.  None where no active's snapshot holds it
(a program from before the counter existed) and where it did not grow."""


def read(spec, ctx):
    key = spec["counter"]
    if not any(key in a["counters"] for a in ctx["after"]):
        return None
    growth = [a["counters"].get(key, 0) - b["counters"].get(key, 0)
              for b, a in zip(ctx["before"], ctx["after"])]
    total = sum(growth)
    if total <= 0:
        return None
    return 100.0 * max(growth) / total
