#!/usr/bin/env python
"""Observability hygiene gate: no ad-hoc stdout/stderr in the package,
and the metric inventory (METRICS.md) may never drift from the code.

AST-based static pass over ``gigapaxos_tpu/`` forbidding the two escape
hatches the logging plane replaced:

* bare ``print(...)`` calls;
* ``<anything>.stderr.write(...)`` / ``<anything>.stdout.write(...)``
  (catches ``sys.stderr.write`` and aliased imports like ``_sys``).

``gigapaxos_tpu/obs/`` is exempt from the stream rule — it is the one
place allowed to own a stream handler.

Second pass (the inventory gate): every metric name registered in code
(``.count("…")`` / ``.gauge("…")`` / ``.observe("…")`` /
``.observe_bulk("…")`` with a literal or f-string first argument) must
appear in ``METRICS.md``, and every name documented there must exist in
code.  Dynamically-labeled series (f-strings like
``probe_rtt_ms_active_{id}``) are documented with a ``*`` wildcard
(``probe_rtt_ms_active_*``) and matched by their literal prefix.  A span
(``span(registry, "step.dispatch", ...)`` of ``obs/spans.py``, or a
node's ``self._span("step.dispatch")``) registers the histogram
``phase_step_dispatch_s``, which needs its own row, and with CPU time
(``cpu=True``; a ``_span`` without ``cpu=False``)
``phase_step_dispatch_cpu_s``, which the one family row
``phase_*_cpu_s`` documents.

Third pass (the hot-path pull gate): ``_np("leaf")`` device pulls
inside the tick/dispatch hot path — the functions named in
``HOT_NP_ALLOW`` — must stay within each function's allowlist.  A pull
is a device sync: one stray ``_np("bal")`` added to the per-tick path
once wedged a pinned chaos seed for minutes of wall time (the ballot
cache exists precisely so the hot path never re-pulls it).  Adding a
pull to a hot function means consciously widening the allowlist here,
with the latency argument in the PR.

The same pass gates ``pull_group_heat()`` — the group-heat device pull
— under the pseudo-leaf ``__group_heat__``.  It drains AND RESETS the
on-device ``[G]`` accumulator, so a second call site would silently
halve every heat histogram besides adding a per-tick sync; the one
sanctioned caller is the server's stats-cadence hook
(``_maybe_stats_line``), which runs at ``STATS_LOG_PERIOD_S``, not per
tick.

Run standalone (exit 1 on violations) or through the tier-1 test
``tests/test_obs.py::test_obs_hygiene_gate`` so future code stays on
the logging plane, the inventory stays true, and the hot path stays
pull-free.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys
from typing import Iterator, Set, Tuple

PACKAGE = "gigapaxos_tpu"
EXEMPT_TOP_DIRS = ("obs",)
METRIC_METHODS = ("count", "gauge", "observe", "observe_bulk",
                  "register_hist")
METRICS_DOC = "METRICS.md"

# Pseudo-leaf for the group-heat accumulator pull: `pull_group_heat()`
# calls in gated functions are checked against the allowlist under this
# name (it is a device sync AND a destructive drain — see module doc).
GROUP_HEAT_LEAF = "__group_heat__"

# The tick/dispatch hot path: every `_np("leaf")` pull these functions
# are ALLOWED to make.  An empty set means the function must never pull
# (the dispatch cycle's device traffic is exactly the packed I/O
# buffers).  A dynamic (non-literal) pull argument in any hot function
# is always a violation.
HOT_NP_ALLOW = {
    # the two ways to run a tick, and the helper both dispatch through
    ("manager.py", "step_dispatch"): frozenset(),
    ("manager.py", "step_complete"): frozenset(),
    ("manager.py", "tick_host"): frozenset(),
    ("manager.py", "_dispatch_locked"): frozenset(),
    ("manager.py", "_execute"): frozenset(),
    ("manager.py", "_execute_one"): frozenset({"version"}),
    ("manager.py", "build_request_ring"): frozenset({"bal", "version"}),
    ("manager.py", "_filter_stale_vids"): frozenset({"version"}),
    # `bal` and `exec_slot` of a completed step are seeded into the host
    # cache from its blob (_complete_locked), `member_mask` is carried
    # across the swap: no pull below reaches the device on the tick path
    ("manager.py", "_complete_locked"): frozenset(),
    ("manager.py", "_post_step_locked"): frozenset({"bal", "member_mask"}),
    ("manager.py", "_log_decisions"): frozenset(),
    ("manager.py", "engine_work_in_flight"): frozenset(),
    # the ONE place a [G, W] leaf crosses to the host on the tick path: a
    # step whose busy rows overflowed the step's digest
    ("manager.py", "_whole_planes_locked"): frozenset(
        {"acc_slot", "acc_bal", "acc_vid"}
    ),
    # the election mask's inputs come through manager.election_inputs,
    # which reads the carried copies (a lifecycle operation writes its
    # rows into them: no pull follows one)
    ("server.py", "_should_tick"): frozenset(),
    ("server.py", "_tick_once_inner"): frozenset(),
    # no blob vector is pulled or copied here: the stack is the device's
    ("server.py", "_gather"): frozenset(),
    ("server.py", "_finish_tick"): frozenset(),
    # stats-cadence hook: the ONE sanctioned group-heat drain (runs at
    # STATS_LOG_PERIOD_S inside the tick loop, not per tick)
    ("server.py", "_maybe_stats_line"): frozenset({GROUP_HEAT_LEAF}),
}


def _stream_write(func: ast.AST) -> bool:
    """True for ``<expr>.{stderr,stdout}.write``."""
    return (
        isinstance(func, ast.Attribute)
        and func.attr == "write"
        and isinstance(func.value, ast.Attribute)
        and func.value.attr in ("stderr", "stdout")
    )


def iter_violations(pkg_root: pathlib.Path) -> Iterator[Tuple[str, int, str]]:
    """Yield (relative path, line, description) per violation."""
    for path in sorted(pkg_root.rglob("*.py")):
        rel = path.relative_to(pkg_root)
        if rel.parts[0] in EXEMPT_TOP_DIRS:
            continue
        tree = ast.parse(path.read_text(encoding="utf-8"), filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if isinstance(func, ast.Name) and func.id == "print":
                yield (str(rel), node.lineno,
                       "bare print() — use gigapaxos_tpu.obs.gplog")
            elif _stream_write(func):
                yield (str(rel), node.lineno,
                       f"direct {func.value.attr}.write() — "
                       "use gigapaxos_tpu.obs.gplog")


def _span_names(node: ast.Call) -> Set[str]:
    """The histograms a span call registers (obs/spans.py): the phase
    is the second argument of ``span(...)``, the first of a node's
    ``self._span(...)``, which takes CPU time too unless told not to."""
    func = node.func
    if isinstance(func, ast.Name) and func.id == "span":
        arg = node.args[1] if len(node.args) > 1 else None
        cpu = any(k.arg == "cpu" and isinstance(k.value, ast.Constant)
                  and k.value.value is True for k in node.keywords)
    elif isinstance(func, ast.Name) and func.id == "observe_interval":
        # a phase between two messages: the histogram alone (obs/spans.py)
        arg = node.args[1] if len(node.args) > 1 else None
        cpu = False
    elif isinstance(func, ast.Attribute) and func.attr == "_span":
        arg = node.args[0] if node.args else None
        cpu = not any(k.arg == "cpu" and isinstance(k.value, ast.Constant)
                      and k.value.value is False for k in node.keywords)
    else:
        return set()
    if not (isinstance(arg, ast.Constant) and isinstance(arg.value, str)):
        return set()
    stem = "phase_" + arg.value.replace(".", "_")
    return {stem + "_s", stem + "_cpu_s"} if cpu else {stem + "_s"}


def collect_metric_names(pkg_root: pathlib.Path) -> Tuple[Set[str], Set[str]]:
    """Scan registration sites: returns (literal names, f-string
    prefixes).  Only string-literal / f-string FIRST arguments to
    ``.count/.gauge/.observe`` count — a non-string first arg (e.g. the
    sim checker's ``observe(i, …)``) is not a metric registration.
    Span calls register their ``phase_*`` histograms."""
    literals: Set[str] = set()
    prefixes: Set[str] = set()
    for path in sorted(pkg_root.rglob("*.py")):
        tree = ast.parse(path.read_text(encoding="utf-8"),
                         filename=str(path))
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                literals |= _span_names(node)
            if not (isinstance(node, ast.Call) and node.args
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in METRIC_METHODS):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                literals.add(arg.value)
            elif isinstance(arg, ast.JoinedStr):
                prefix = ""
                for part in arg.values:
                    if isinstance(part, ast.Constant) and \
                            isinstance(part.value, str):
                        prefix += part.value
                    else:
                        break
                if prefix:
                    prefixes.add(prefix)
    return literals, prefixes


def parse_metrics_doc(
    doc_path: pathlib.Path,
) -> Tuple[Set[str], Set[Tuple[str, str]]]:
    """Inventory rows in METRICS.md — the backticked name leading a
    table row (``| `name` | …``): (exact names, families — one ``*``
    stands for the varying part, kept as (prefix, suffix):
    ``probe_rtt_ms_active_*``, ``phase_*_cpu_s``).  Backticked words in
    prose are NOT inventory entries."""
    exact: Set[str] = set()
    wild: Set[Tuple[str, str]] = set()
    if not doc_path.exists():
        return exact, wild
    for line in doc_path.read_text().splitlines():
        m = re.match(r"^\|\s*`([a-z0-9_]+(?:\*[a-z0-9_]*)?)`\s*\|", line)
        if not m:
            continue
        name = m.group(1)
        if "*" in name:
            wild.add(tuple(name.split("*")))
        else:
            exact.add(name)
    return exact, wild


def _in_family(name: str, family: Tuple[str, str]) -> bool:
    pre, suf = family
    return len(name) > len(pre) + len(suf) and name.startswith(pre) \
        and name.endswith(suf)


def iter_inventory_violations(
    pkg_root: pathlib.Path, doc_path: pathlib.Path
) -> Iterator[str]:
    """Two-way drift check between code registrations and METRICS.md."""
    if not doc_path.exists():
        yield f"{doc_path.name} missing (the metric inventory is tier-1)"
        return
    literals, prefixes = collect_metric_names(pkg_root)
    exact, wild = parse_metrics_doc(doc_path)
    for name in sorted(literals):
        if name in exact or any(_in_family(name, w) for w in wild):
            continue
        yield (f"metric {name!r} registered in code but absent from "
               f"{doc_path.name}")
    for pre in sorted(prefixes):
        if (pre, "") in wild or pre in exact:
            continue
        yield (f"dynamic metric family {pre + '*'!r} registered in code "
               f"but absent from {doc_path.name}")
    for name in sorted(exact):
        if name in literals or any(p.startswith(name) for p in prefixes):
            continue
        yield (f"{doc_path.name} documents {name!r} but no code "
               "registers it")
    for w in sorted(wild):
        if (w[0] in prefixes and not w[1]) \
                or any(_in_family(n, w) for n in literals):
            continue
        yield (f"{doc_path.name} documents family {'*'.join(w)!r} but no "
               "code registers it")


def iter_hot_np_violations(
    pkg_root: pathlib.Path,
) -> Iterator[Tuple[str, int, str]]:
    """Hot-path pull gate: ``_np(...)`` calls inside the functions named
    in ``HOT_NP_ALLOW`` must pull only their allowlisted leaves."""
    files = {fname for fname, _ in HOT_NP_ALLOW}
    for path in sorted(pkg_root.rglob("*.py")):
        if path.name not in files:
            continue
        rel = path.relative_to(pkg_root)
        tree = ast.parse(path.read_text(encoding="utf-8"),
                         filename=str(path))
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            allow = HOT_NP_ALLOW.get((path.name, node.name))
            if allow is None:
                continue
            for call in ast.walk(node):
                if not isinstance(call, ast.Call):
                    continue
                fn = call.func
                fn_name = fn.attr if isinstance(fn, ast.Attribute) \
                    else getattr(fn, "id", None)
                if fn_name == "pull_group_heat":
                    if GROUP_HEAT_LEAF not in allow:
                        yield (str(rel), call.lineno,
                               f"pull_group_heat() in hot path "
                               f"{node.name}() — a device sync AND a "
                               "destructive accumulator drain; the stats-"
                               "cadence hook is the one sanctioned caller")
                    continue
                if fn_name != "_np":
                    continue
                arg = call.args[0] if call.args else None
                if isinstance(arg, ast.Constant) and \
                        isinstance(arg.value, str):
                    if arg.value in allow:
                        continue
                    yield (str(rel), call.lineno,
                           f"_np({arg.value!r}) in hot path "
                           f"{node.name}() — a device pull per "
                           "tick/dispatch; widen HOT_NP_ALLOW only with "
                           "a latency argument")
                else:
                    yield (str(rel), call.lineno,
                           f"dynamic _np(...) in hot path {node.name}() "
                           "— pulls must be literal and allowlisted")


def main(argv=None) -> int:
    root = pathlib.Path(
        (argv or sys.argv[1:] or [None])[0]
        or pathlib.Path(__file__).resolve().parent.parent / PACKAGE
    )
    bad = list(iter_violations(root))
    bad += list(iter_hot_np_violations(root))
    for rel, line, why in bad:
        print(f"{PACKAGE}/{rel}:{line}: {why}")
    inv = list(iter_inventory_violations(root, root.parent / METRICS_DOC))
    for why in inv:
        print(why)
    if bad or inv:
        print(f"{len(bad) + len(inv)} obs-hygiene violation(s)")
        return 1
    print("obs hygiene clean (streams + metric inventory + hot-path pulls)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
