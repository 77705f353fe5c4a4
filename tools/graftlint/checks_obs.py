"""GL005: observability-name drift.

The roofline report is only as good as the names agreeing: a counter
the engine emits but nothing renders is invisible evidence, and a row
`tools/run_report.py` renders from a counter nothing emits any more is
a silently-empty report line — exactly the missing-roofline-row
failure a fallback-round measurement window cannot afford.  This check
diffs the two directions:

* EMITTED: every constant (or f-string-prefix) dotted name passed to
  `obs.inc/gauge/observe/timer`, a span's `also=` timer and the
  ledger-event emitters, across the lint targets — plus the dotted
  constants of the registered EMIT_SURFACES (the jax-free supervisor
  writes counter names as raw snapshot-dict keys).
* CONSUMED: `obs.counter(...)` reads in runtime code, plus every
  dotted string constant in the render surfaces (tools/run_report.py,
  tools/top.py) and in tests/ — tests count as consumers because they
  pin names on purpose.

A METRIC name emitted but consumed nowhere fails (dead telemetry, or
a missing report row).  Ledger-event KINDS only participate in the
reverse direction — the merged timeline renders every kind generically,
so an unmatched kind is still visible evidence — but a dotted name a
render surface mentions that nothing emits fails either way (phantom
row).  Prefix matching is symmetric on "." boundaries so
`engine.achieved_gbps.<tier>.<tag>` gauges match the report's
`engine.achieved_gbps.` scan.  Names without a dot ("dispatch",
ledger kind "run") are out of scope: too short to drift-match.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Set, Tuple

from tools.graftlint import config
from tools.graftlint.astutil import call_name, const_str, fstring_prefix
from tools.graftlint.core import Finding, Project

# A metric/ledger name or prefix: dotted lowercase, optionally
# '.'-terminated, not a path or file name.
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)*\.?$")
_FILEISH = (".py", ".json", ".jsonl", ".md", ".sh", ".yml", ".gz",
            ".tmp", ".txt")


def _is_namey(s: str) -> bool:
    return "." in s and bool(_NAME_RE.match(s)) \
        and not s.endswith(_FILEISH) and "/" not in s


def _name_arg(node: ast.Call) -> ast.AST:
    """The metric-name argument: first positional, or `name=`."""
    for kw in node.keywords:
        if kw.arg == "name":
            return kw.value
    return node.args[0] if node.args else None


def _emits(lf) -> List[Tuple[str, int, bool]]:
    """[(name_or_prefix, line, is_ledger)] emitted by a file."""
    out = []
    for node in ast.walk(lf.tree):
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node) or ""
        last = cn.rsplit(".", 1)[-1]
        is_metric = last in config.OBS_EMIT_METHODS
        is_ledger = last in config.LEDGER_EMIT_METHODS
        if last in config.OBS_SPAN_METHODS:
            # A span feeds the timer of its own name (never dotted) and,
            # through `also=`, one more registry timer by name.
            is_metric = True
            arg = next((kw.value for kw in node.keywords
                        if kw.arg == "also"), None)
        elif is_metric or is_ledger:
            arg = _name_arg(node)
        else:
            continue
        if arg is None:
            continue
        s = const_str(arg)
        if s is None:
            s = fstring_prefix(arg)
        if s and _is_namey(s):
            out.append((s, node.lineno, is_ledger))
    return out


def _consumes(lf, render: bool) -> Set[str]:
    """Names a file consumes: obs.counter() reads everywhere, plus —
    on render/test surfaces — every dotted string constant."""
    names: Set[str] = set()
    for node in ast.walk(lf.tree):
        if isinstance(node, ast.Call) and node.args:
            last = (call_name(node) or "").rsplit(".", 1)[-1]
            if last in config.OBS_CONSUME_METHODS:
                s = const_str(node.args[0]) or fstring_prefix(node.args[0])
                if s:
                    names.add(s)
        if render and isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and _is_namey(node.value) \
                and node.value not in config.RENDER_NAME_ALLOW:
            names.add(node.value)
    return names


def _matches(a: str, b: str) -> bool:
    """Symmetric dotted-prefix match: exact, or one side extends the
    other at a '.' boundary (either may be an explicit '.'-terminated
    prefix)."""
    if a == b:
        return True
    for x, y in ((a, b), (b, a)):
        if x.endswith(".") and y.startswith(x):
            return True
        if y.startswith(x + "."):
            return True
    return False


def check_obs_drift(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    metric_emits: Dict[str, Tuple[str, int]] = {}
    all_emits: Set[str] = set()
    for f in project.files:
        if f.tree is None:
            continue
        for name, line, is_ledger in _emits(f):
            all_emits.add(name)
            if not is_ledger:
                metric_emits.setdefault(name, (f.path, line))
        if f.path in config.EMIT_SURFACES:
            # The jax-free supervisor writes counters as raw dict keys
            # into the merged snapshot; its dotted constants are emits
            # (phantom direction only — emit vs read is ambiguous).
            all_emits |= _consumes(f, render=True)

    consumed: Set[str] = set()
    render_names: Dict[str, str] = {}        # name -> render file
    for f in project.files:
        if f.tree is None:
            continue
        render = f.path in config.RENDER_FILES
        got = _consumes(f, render)
        consumed |= got
        if render:
            for n in got:
                render_names.setdefault(n, f.path)
    for f in project.test_files:
        if f.tree is None:
            continue
        consumed |= _consumes(f, render=True)

    for name in sorted(metric_emits):
        if any(_matches(name, c) for c in consumed):
            continue
        path, line = metric_emits[name]
        findings.append(Finding(
            "GL005", path, line,
            f"obs name {name!r} is emitted but nothing renders or "
            "asserts it (run_report.py / top.py / tests) — dead "
            "telemetry, or a missing report row",
            f"{path}::obs-unrendered::{name}"))

    for name in sorted(render_names):
        if any(_matches(name, e) for e in all_emits):
            continue
        path = render_names[name]
        findings.append(Finding(
            "GL005", path, 1,
            f"render surface reads obs name {name!r} but nothing emits "
            "it — a silently-empty report row",
            f"{path}::obs-phantom::{name}"))
    return findings


check_obs_drift.check_id = "GL005"
