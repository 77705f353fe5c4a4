"""GL001-GL003: the JAX dispatch disciplines.

GL001 pins the PR10 measurement forever: XLA copies carry buffers that
are WRITTEN inside `lax.cond`/`lax.switch` branches (7.6x slower on
the universal interpreter's arena until the write moved out), while
read-only operands flow through for free.  So branches may only
COMPUTE; the `.at[...].set` / `dynamic_update_slice` belongs outside
the conditional.

GL002 keeps the program family CLOSED: every int reaching a
`cache_get`/`cache_put` key must have passed a bounding helper
(utils.bucket_len / next_pow2 / the registered pad pickers), otherwise
key cardinality grows with topology size and the bank/AOT-export
family stops being enumerable — the compile-storm failure mode the
PR2/PR5/PR10 line of work exists to prevent.

GL003 keeps dispatch asynchronous: `float()`/`.item()`/`bool()`/
`np.asarray` on a dispatch result blocks the host, and only the
registered blocking trav-eval seams (whose wall time IS the traffic-
window measurement) are allowed to do that.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set

from tools.graftlint import config
from tools.graftlint.astutil import (call_name, contains_call_to,
                                     local_assignments, module_functions,
                                     param_names)
from tools.graftlint.core import Finding, Project

# -- GL001: cond-write hazard ------------------------------------------------

_AT_WRITE_METHODS = frozenset({"set", "add", "multiply", "divide",
                               "min", "max", "apply", "power"})
_DUS_NAMES = frozenset({"dynamic_update_slice", "dynamic_update_slice_in_dim"})


def _lax_branch_callables(file_tree: ast.AST) -> Iterator[tuple]:
    """Yield (call_node, [branch_arg_nodes]) for every lax.cond /
    lax.switch call, including `from jax.lax import cond` imports."""
    bare: Set[str] = set()
    for node in ast.walk(file_tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.endswith("lax"):
            for alias in node.names:
                if alias.name in ("cond", "switch"):
                    bare.add(alias.asname or alias.name)
    for node in ast.walk(file_tree):
        if not isinstance(node, ast.Call):
            continue
        cn = call_name(node) or ""
        last = cn.rsplit(".", 1)[-1]
        is_lax = cn.endswith("lax.cond") or cn.endswith("lax.switch") \
            or cn in bare
        if not is_lax:
            continue
        if last == "cond":
            yield node, list(node.args[1:3])
        else:                                  # switch(index, branches, ...)
            yield node, list(node.args[1:2])


def _resolve_callables(node: ast.AST,
                       funcs: Dict[str, List[ast.FunctionDef]],
                       assigns: Dict[str, List[ast.AST]],
                       depth: int = 0) -> List[ast.AST]:
    """Best-effort lexical resolution of a branch argument to the
    function bodies it names: lambdas, local/module function names,
    `branches = [...]` locals, and the `[make_branch(k) for k in ...]`
    factory idiom (the factory body — including the closure it
    returns — is inspected whole)."""
    if depth > 4:
        return []
    out: List[ast.AST] = []
    if isinstance(node, ast.Lambda):
        out.append(node)
    elif isinstance(node, ast.Name):
        out.extend(funcs.get(node.id, []))
        for val in assigns.get(node.id, []):
            out.extend(_resolve_callables(val, funcs, assigns,
                                          depth + 1))
    elif isinstance(node, (ast.List, ast.Tuple)):
        for elt in node.elts:
            out.extend(_resolve_callables(elt, funcs, assigns,
                                          depth + 1))
    elif isinstance(node, (ast.ListComp, ast.GeneratorExp)):
        out.extend(_resolve_callables(node.elt, funcs, assigns,
                                      depth + 1))
    elif isinstance(node, ast.Call):
        # A factory call (make_branch(k), functools.partial(f, x)):
        # inspect the factory's body and any function-valued args.
        cn = (call_name(node) or "").rsplit(".", 1)[-1]
        out.extend(funcs.get(cn, []))
        for arg in node.args:
            if isinstance(arg, (ast.Name, ast.Lambda)):
                out.extend(_resolve_callables(arg, funcs, assigns,
                                              depth + 1))
    return out


def _writes_in(body: ast.AST) -> Iterator[tuple]:
    """(line, description) for every carry/arena write inside `body`."""
    for node in ast.walk(body):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _AT_WRITE_METHODS \
                and isinstance(fn.value, ast.Subscript) \
                and isinstance(fn.value.value, ast.Attribute) \
                and fn.value.value.attr == "at":
            yield node.lineno, f".at[...].{fn.attr}"
        else:
            cn = (call_name(node) or "").rsplit(".", 1)[-1]
            if cn in _DUS_NAMES:
                yield node.lineno, cn


def check_cond_write(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for f in project.files:
        if f.tree is None:
            continue
        funcs = module_functions(f.tree)
        assigns = local_assignments(f.tree)   # whole-file name -> values
        seen = set()
        for call, branch_args in _lax_branch_callables(f.tree):
            for arg in branch_args:
                for target in _resolve_callables(arg, funcs, assigns):
                    owner = getattr(target, "name", "<lambda>")
                    for line, what in _writes_in(target):
                        key = (f.path, line, what)
                        if key in seen:
                            continue
                        seen.add(key)
                        findings.append(Finding(
                            "GL001", f.path, line,
                            f"carry-buffer write {what} inside a callable "
                            f"({owner}) passed to lax.cond/lax.switch — "
                            "XLA copies carry buffers written inside "
                            "branches (7.6x, PR10); compute in the "
                            "branch, write outside",
                            f"{f.path}::cond-write::{owner}::{what}"))
    return findings


check_cond_write.check_id = "GL001"

# -- GL002: jit-key hygiene --------------------------------------------------


def _key_tuple(expr: ast.AST, env: Dict[str, List[ast.AST]]
               ) -> Optional[ast.Tuple]:
    if isinstance(expr, ast.Tuple):
        return expr
    if isinstance(expr, ast.Name):
        for val in env.get(expr.id, []):
            if isinstance(val, ast.Tuple):
                return val
    return None


def _classify(expr: ast.AST, env: Dict[str, List[ast.AST]],
              params: List[str], depth: int = 0) -> Optional[str]:
    """None = bounded/unknown-safe; "param:<name>" = needs caller
    propagation; any other string = the violation description."""
    if depth > 6:
        return None
    if isinstance(expr, ast.Constant):
        return None
    if contains_call_to(expr, config.BOUNDING_HELPERS):
        return None
    if isinstance(expr, ast.Name):
        vals = env.get(expr.id)
        if vals:
            for v in vals:
                verdict = _classify(v, env, params, depth + 1)
                if verdict:
                    return verdict
            return None
        if expr.id in params:
            return f"param:{expr.id}"
        return None                      # module constant / closure
    if isinstance(expr, ast.Call):
        cn = (call_name(expr) or "").rsplit(".", 1)[-1]
        if cn == "len":
            return "len(...) reaches the key unbucketed"
        if cn == "int":
            return (_classify(expr.args[0], env, params, depth + 1)
                    if expr.args else None)
        if cn in ("min", "max"):
            for a in expr.args:
                verdict = _classify(a, env, params, depth + 1)
                if verdict and not verdict.startswith("param:"):
                    return verdict
            return None
        return None                      # other calls assumed bounded
    if isinstance(expr, ast.Attribute):
        chain = []
        n: ast.AST = expr
        while isinstance(n, ast.Attribute):
            chain.append(n.attr)
            n = n.value
        if "shape" in chain or "size" in chain:
            return "array shape/size reaches the key unbucketed"
        return None
    if isinstance(expr, ast.Subscript):
        return _classify(expr.value, env, params, depth + 1)
    if isinstance(expr, (ast.BinOp, ast.UnaryOp)):
        return ("arithmetic on a raw int reaches the key without a "
                "bounding helper")
    return None


def _iter_functions(tree: ast.AST) -> Iterator[ast.AST]:
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def check_jit_key(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for f in project.files:
        if f.tree is None:
            continue
        # (fn_name, param, key_line) needing one-level caller checks.
        pending: List[tuple] = []
        seen = set()      # a key Name feeds both cache_get and
        for fn in _iter_functions(f.tree):    # cache_put: report once
            env = local_assignments(fn)
            params = param_names(fn)
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                cn = (call_name(node) or "").rsplit(".", 1)[-1]
                if cn not in config.CACHE_KEY_METHODS or not node.args:
                    continue
                tup = _key_tuple(node.args[0], env)
                if tup is None:
                    continue
                for i, elt in enumerate(tup.elts):
                    verdict = _classify(elt, env, params)
                    if verdict is None:
                        continue
                    if verdict.startswith("param:"):
                        pending.append((fn.name, verdict[6:], i,
                                        node.lineno))
                        continue
                    src = ast.unparse(elt)
                    ident = f"{f.path}::jit-key::{fn.name}::{src}"
                    if ident in seen:
                        continue
                    seen.add(ident)
                    findings.append(Finding(
                        "GL002", f.path, node.lineno,
                        f"program-cache key element {src!r}: {verdict} "
                        "(pass it through utils.bucket_len or a "
                        "registered pad helper so the program family "
                        "stays closed)",
                        ident))
        # One-level propagation: a key element that is a raw parameter
        # is judged at this module's call sites of that function.
        if pending:
            findings.extend(_propagate_params(f, pending))
    return findings


def _propagate_params(f, pending: List[tuple]) -> List[Finding]:
    findings: List[Finding] = []
    emitted = set()   # a param feeding cache_get AND cache_put queues
    # two pending entries: report each call site once.
    sites: Dict[str, List[tuple]] = {}
    for fn in _iter_functions(f.tree):
        env = local_assignments(fn)
        params = param_names(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                cn = (call_name(node) or "").rsplit(".", 1)[-1]
                sites.setdefault(cn, []).append((node, env, params,
                                                 fn.name))
    for fname, pname, _idx, _kline in pending:
        # Positional index of the parameter in the callee signature.
        defs = [d for d in _iter_functions(f.tree) if d.name == fname]
        if not defs:
            continue
        callee_params = param_names(defs[0])
        try:
            pos = callee_params.index(pname)
        except ValueError:
            continue
        is_method = bool(callee_params) and callee_params[0] in ("self",
                                                                "cls")
        for node, env, params, caller in sites.get(fname, []):
            # A bound-method call (`self._lookup(x)`) does not pass
            # `self` positionally: shift the index for Attribute calls.
            eff = pos - 1 if is_method and isinstance(node.func,
                                                      ast.Attribute) \
                else pos
            arg: Optional[ast.AST] = None
            if 0 <= eff < len(node.args):
                arg = node.args[eff]
            else:
                for kw in node.keywords:
                    if kw.arg == pname:
                        arg = kw.value
            if arg is None:
                continue
            verdict = _classify(arg, env, params)
            if verdict is None or verdict.startswith("param:"):
                continue
            src = ast.unparse(arg)
            ident = f"{f.path}::jit-key::{caller}->{fname}::{src}"
            key = (ident, node.lineno)
            if key in emitted:
                continue
            emitted.add(key)
            findings.append(Finding(
                "GL002", f.path, node.lineno,
                f"argument {src!r} for {fname}({pname}=...) feeds a "
                f"program-cache key: {verdict}",
                ident))
    return findings


check_jit_key.check_id = "GL002"

# -- GL003: hidden host-sync -------------------------------------------------

_DISPATCH_FN_HINTS = ("_fn", "_program")


def _is_dispatch_factory(callee_last: str) -> bool:
    if callee_last in config.DISPATCH_FN_SOURCES:
        return True
    return any(callee_last.endswith(h) or (h + "_") in callee_last
               for h in _DISPATCH_FN_HINTS)


def _names_in(expr: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def check_host_sync(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for f in project.files:
        if f.tree is None:
            continue
        for fn in _iter_functions(f.tree):
            if config.is_sync_seam(f.path, fn.name):
                continue
            # Two passes over the function's assignments: collect the
            # dispatch-fn names first, THEN the results tainted by
            # calling them — ast.walk order is breadth-first, so a
            # single pass would miss `fn = eng.cache_get(k)` nested in
            # a try/if block that walk visits after the flat
            # `r = fn(x)` statement using it.
            def _assigns():
                for node in ast.walk(fn):
                    if isinstance(node, ast.Assign) and \
                            isinstance(node.value, ast.Call):
                        cn = (call_name(node.value) or
                              "").rsplit(".", 1)[-1]
                        tgts: List[str] = []
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                tgts.append(t.id)
                            elif isinstance(t, ast.Tuple):
                                tgts.extend(e.id for e in t.elts
                                            if isinstance(e, ast.Name))
                        yield cn, tgts
            dispatch_fns: Set[str] = set()
            tainted: Set[str] = set()
            for cn, tgts in _assigns():
                if _is_dispatch_factory(cn):
                    dispatch_fns.update(tgts)
            for cn, tgts in _assigns():
                if cn in dispatch_fns:
                    tainted.update(tgts)
            if not tainted:
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                cn = call_name(node) or ""
                last = cn.rsplit(".", 1)[-1]
                sync = None
                if cn in ("float", "bool", "int") and node.args and \
                        _names_in(node.args[0]) & tainted:
                    sync = cn
                elif last in ("asarray", "array") and \
                        cn.split(".", 1)[0] in ("np", "numpy") and \
                        node.args and _names_in(node.args[0]) & tainted:
                    sync = cn
                elif last == "item" and not node.args and \
                        isinstance(node.func, ast.Attribute) and \
                        _names_in(node.func.value) & tainted:
                    sync = ".item()"
                if sync is None:
                    continue
                src = ast.unparse(node)[:60]
                findings.append(Finding(
                    "GL003", f.path, node.lineno,
                    f"host sync {sync} on a dispatch result in "
                    f"{fn.name}() — only the registered blocking "
                    "trav-eval seams may block "
                    "(register the seam in tools/graftlint/config.py "
                    "if this blocking is the measurement)",
                    f"{f.path}::host-sync::{fn.name}::{src}"))
    return findings


check_host_sync.check_id = "GL003"
