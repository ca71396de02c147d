"""``layer_metrics/ratio.py``: growth of some of the program's
histograms and counters over the growth of others, against snapshots
made by hand; and its metric files against the program's inventory."""

import json
import os
import re

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


# ---- layer_metrics/ratio.py against snapshots made by hand -------------
def _rsnap(counters, hists):
    return {"counters": dict(counters),
            "hists": {k: {"sum": v, "count": 1} for k, v in hists.items()}}


RATIO_BEFORE = [_rsnap({"ticks": 10, "stale": 2}, {"a_s": 1.0, "t_s": 2.0}),
                _rsnap({"ticks": 0}, {"t_s": 0.5}),
                _rsnap({"ticks": 5, "stale": 5}, {"a_s": 0.0, "t_s": 0.0})]
RATIO_AFTER = [_rsnap({"ticks": 30, "stale": 7}, {"a_s": 2.5, "t_s": 4.0}),
               _rsnap({"ticks": 20, "stale": 5}, {"a_s": 0.5, "t_s": 1.5}),
               _rsnap({"ticks": 5, "stale": 5}, {"a_s": 0.0, "t_s": 0.0})]


@pytest.mark.parametrize("spec, expected", [
    # counters, the actives pooled: (5 + 5 + 0) over (20 + 20 + 0)
    ({"num": {"counters": ["stale"]}, "den": {"counters": ["ticks"]}}, 25.0),
    # histogram sums: (1.5 + 0.5 + 0) over (2 + 1 + 0)
    ({"num": {"hists": ["a_s"]}, "den": {"hists": ["t_s"]}},
     100 * 2.0 / 3.0),
    ({"num": {"hists": ["a_s"]}, "den": {"hists": ["t_s"]},
      "complement": True}, 100 - 100 * 2.0 / 3.0),
    # several series on a side add; a side may mix the two kinds
    ({"num": {"counters": ["stale"]},
      "den": {"counters": ["stale", "ticks"]}}, 100 * 10 / 50),
    ({"num": {"hists": ["a_s"], "counters": ["stale"]},
      "den": {"counters": ["ticks"]}}, 100 * 12.0 / 40),
    # a side none of whose names any active has: the program has no such
    # span or counter
    ({"num": {"counters": ["absent"]}, "den": {"counters": ["ticks"]}}, None),
    ({"num": {"hists": ["a_s"]}, "den": {"hists": ["absent_s"]}}, None),
    # one missing name among others: a span that never ran here, 0
    ({"num": {"hists": ["a_s", "absent_s"]}, "den": {"hists": ["t_s"]}},
     100 * 2.0 / 3.0),
    # a denominator that did not grow
    ({"num": {"counters": ["ticks"]}, "den": {"counters": ["frozen"]}}, None),
])
def test_ratio_reader(spec, expected):
    after = [dict(a, counters={**a["counters"], "frozen": 3})
             for a in RATIO_AFTER]
    before = [dict(b, counters={**b["counters"], "frozen": 3})
              for b in RATIO_BEFORE]
    spec = {"name": "m", "unit": "%", "reader": "module", "module": "ratio",
            **spec}
    got = run.layer_metrics([spec], {"before": before, "after": after})
    if expected is None:
        assert got == {}
    else:
        assert got == {"m": {"value": pytest.approx(expected), "unit": "%"}}


def test_every_ratio_metric_names_series_the_program_registers():
    """The names in the ratio metrics' files are rows of METRICS.md (the
    program's inventory, which its own tests hold to the code)."""
    doc = open(os.path.join(ROOT, "METRICS.md")).read()
    rows = set(re.findall(r"^\| `([a-z0-9_]+)` \|", doc, re.M))
    assert "phase_*_cpu_s" in doc
    d = os.path.join(BENCH, "layer_metrics")
    seen = 0
    for f in sorted(os.listdir(d)):
        spec = json.load(open(os.path.join(d, f))) if f.endswith(".json") \
            else {}
        if spec.get("module") != "ratio":
            continue
        seen += 1
        for side in (spec["num"], spec["den"]):
            for key in side.get("hists", []) + side.get("counters", []):
                # the phases' CPU twins are one row, ``phase_*_cpu_s``
                plain = key[:-len("_cpu_s")] + "_s" \
                    if re.match(r"^phase_.*_cpu_s$", key) else key
                assert plain in rows, (f, key)
    assert seen >= 1
