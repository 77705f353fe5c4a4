"""GL004: the EXAML_* environment-variable registry.

154 env reads with zero drift detection is how a roofline round loses
a row: a typo'd var silently reads its default forever, a deleted
feature leaves its flag documented, and an IMPORT-time read freezes
the value before a subprocess parent can pin it (the
`EXAML_UNIVERSAL=0` degradation pin, the bank's escape hatches and the
supervisor's tier ladder all work by mutating a child's env — a
module-level read defeats all three).

Every read site is cross-checked against tools/graftlint/
envregistry.py: unregistered reads, registry entries that no code
reads any more (dead flags), registry entries pointing at README
documentation that is not actually there, and import-time-scoped reads
without a registered justification all fail.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Tuple

from tools.graftlint import config
from tools.graftlint.astutil import (call_name, const_str,
                                     module_str_constants, walk_scoped)
from tools.graftlint.core import Finding, Project
from tools.graftlint.envregistry import ENV_REGISTRY

_ENV_NAME = re.compile(r"^EXAML_[A-Z0-9_]+$")


def _documented(var: str, text: str) -> bool:
    """Whole-token presence: EXAML_COMPILE must not pass because the text
    contains EXAML_COMPILE_CACHE (substring matching would make every
    prefix of a documented name vacuously documented)."""
    return re.search(r"(?<![A-Z0-9_])" + re.escape(var) + r"(?![A-Z0-9_])",
                     text) is not None


def _env_reads(lf, global_consts: Dict[str, str]
               ) -> List[Tuple[str, int, bool]]:
    """[(var, line, import_time)] for every EXAML_* read in a file:
    `.get(X)` on environ or an env-dict copy, `os.getenv(X)`,
    `os.environ[X]` (load context) and the registered typed helpers,
    where X is a string constant, a module-level constant name, or a
    cross-module constant attribute (`quarantine.ENV_HANG_ATTEMPTS`)."""
    consts = module_str_constants(lf.tree)

    def resolve(node) -> str:
        s = const_str(node)
        if s is None and isinstance(node, ast.Name):
            s = consts.get(node.id) or global_consts.get(node.id)
        if s is None and isinstance(node, ast.Attribute):
            s = global_consts.get(node.attr)
        return s if s and _ENV_NAME.match(s) else ""

    out: List[Tuple[str, int, bool]] = []
    for node, stack in walk_scoped(lf.tree):
        import_time = not stack
        if isinstance(node, ast.Call):
            cn = call_name(node) or ""
            last = cn.rsplit(".", 1)[-1]
            var = ""
            if last in ("get", "getenv") and node.args:
                var = resolve(node.args[0])
            elif last in config.ENV_READ_HELPERS and node.args:
                var = resolve(node.args[0])
            if var:
                out.append((var, node.lineno, import_time))
        elif isinstance(node, ast.Subscript) and \
                isinstance(node.ctx, ast.Load):
            v = node.value
            if isinstance(v, ast.Attribute) and v.attr == "environ":
                var = resolve(node.slice)
                if var:
                    out.append((var, node.lineno, import_time))
    return out


def check_env_registry(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    # Cross-module resolution for the `MODULE_CONST = "EXAML_X"` +
    # `os.environ.get(other.MODULE_CONST)` idiom (quarantine/driver).
    global_consts: Dict[str, str] = {}
    for f in project.files:
        if f.tree is None:
            continue
        for name, val in module_str_constants(f.tree).items():
            if _ENV_NAME.match(val):
                global_consts.setdefault(name, val)
    reads: Dict[str, List[Tuple[str, int, bool]]] = {}
    for f in project.files:
        if f.tree is None:
            continue
        for var, line, imp in _env_reads(f, global_consts):
            reads.setdefault(var, []).append((f.path, line, imp))

    for var in sorted(reads):
        sites = reads[var]
        entry = ENV_REGISTRY.get(var)
        if entry is None:
            path, line, _ = sites[0]
            findings.append(Finding(
                "GL004", path, line,
                f"unregistered env var {var}: add it to tools/graftlint/"
                "envregistry.py (and the README flag table if it is "
                "operator-facing)",
                f"{path}::env-unregistered::{var}"))
            continue
        if entry.get("doc") == "readme" and \
                not _documented(var, project.readme):
            path, line, _ = sites[0]
            findings.append(Finding(
                "GL004", path, line,
                f"env var {var} is registered as README-documented but "
                "the README never names it",
                f"{path}::env-undocumented::{var}"))
        for path, line, imp in sites:
            if imp and not entry.get("import_time_ok"):
                findings.append(Finding(
                    "GL004", path, line,
                    f"import-time read of {var}: module-scope env reads "
                    "freeze the value before a parent can pin it "
                    "(supervisor tier ladder, bank escape hatches) — "
                    "hoist into a call-time lookup",
                    f"{path}::env-import-time::{var}"))

    for var in sorted(ENV_REGISTRY):
        if var not in reads:
            findings.append(Finding(
                "GL004", "tools/graftlint/envregistry.py", 1,
                f"dead registry entry {var}: no code under "
                f"{'/'.join(config.LINT_ROOTS)} reads it — delete the "
                "flag or the entry",
                f"tools/graftlint/envregistry.py::env-dead::{var}"))
        elif not str(ENV_REGISTRY[var].get("note", "")).strip():
            findings.append(Finding(
                "GL004", "tools/graftlint/envregistry.py", 1,
                f"registry entry {var} has no note — the registry IS "
                "the documentation for non-README vars",
                f"tools/graftlint/envregistry.py::env-nonote::{var}"))
    return findings


check_env_registry.check_id = "GL004"
