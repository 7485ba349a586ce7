"""tpulint rules TPL000-TPL007 (TPL008 doc-consistency: doccheck.py).

Each rule is ``rule(fi, ctx) -> [Finding]``; the runner applies inline
suppressions and the baseline afterwards.  Messages carry a fix-it: the
gate should teach the idiom, not just block the merge.

| id     | hazard                                                        |
|--------|---------------------------------------------------------------|
| TPL000 | ``tpulint: disable`` comment without a ``-- reason``          |
| TPL001 | implicit host sync inside traced code (.item(), np.asarray,   |
|        | float()/int()/bool() on array exprs, device_get, iteration)   |
| TPL002 | recompile hazards: non-static scalar/shape params, mutable    |
|        | defaults, jit closure over a mutated module global            |
| TPL003 | dtype creep: np/jnp.float64 in traced code, dtype-less        |
|        | np.array in jax-adjacent modules                              |
| TPL004 | collective primitive call outside a utils/retry wrapper       |
| TPL005 | Pallas kernel module without an interpret-mode oracle test    |
| TPL006 | bare/broad except that swallows errors without logging        |
| TPL007 | bare print( in library code (cli.py/plotting.py allowed)      |

Traced-code scope (TPL001/TPL003) comes from ``callgraph.compute_traced``;
each traced function is scanned over its OWN body only (nested defs are
their own graph nodes), so host wrappers that merely BUILD traced
closures aren't swept in.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Set

from .callgraph import FunctionInfo, _callee_name, compute_traced
from .core import FileInfo, Finding

NP_ALIASES = {"np", "numpy", "onp"}
JAX_ALIASES = {"jnp", "jax", "lax", "pl", "pltpu"}

RULE_TITLES = {
    "TPL000": "suppression without justification",
    "TPL001": "implicit host sync in traced code",
    "TPL002": "recompile hazard",
    "TPL003": "dtype creep into device code",
    "TPL004": "unguarded collective",
    "TPL005": "Pallas kernel without interpret-mode oracle",
    "TPL006": "silently swallowed broad except",
    "TPL007": "bare print in library code",
    "TPL008": "README perf figure drifted from BENCH artifact",
}


@dataclass
class LintContext:
    root: str
    files: List[FileInfo]
    by_rel: Dict[str, FileInfo]
    functions: Dict[str, FunctionInfo]
    traced: Set[str]
    project_rules: bool = True


def build_context(files: Sequence[FileInfo], root: str,
                  project_rules: bool = True) -> LintContext:
    functions, traced = compute_traced(files)
    return LintContext(root=root, files=list(files),
                       by_rel={fi.rel: fi for fi in files},
                       functions=functions, traced=traced,
                       project_rules=project_rules)


# -- shared AST helpers ---------------------------------------------------
def _root_name(node: ast.AST) -> Optional[str]:
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _walk_own(fn_node: ast.AST):
    """Walk a function body EXCLUDING nested def/lambda subtrees."""
    stack = list(ast.iter_child_nodes(fn_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _is_jaxish(expr: ast.AST) -> bool:
    """Does the expression contain a jnp./jax./lax. call — i.e. is it an
    array-valued expression rather than Python-scalar bookkeeping?"""
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            if _root_name(node.func) in JAX_ALIASES:
                return True
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"):
                return True
    return False


def _param_names(fn_node: ast.AST) -> Set[str]:
    a = fn_node.args
    names = [p.arg for p in
             list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return {n for n in names if n != "self"}


def _traced_functions(fi: FileInfo, ctx: LintContext) -> List[FunctionInfo]:
    return [info for q, info in ctx.functions.items()
            if q in ctx.traced and info.fi.rel == fi.rel]


# -- TPL000 ---------------------------------------------------------------
def rule_tpl000(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    return [Finding(fi.rel, line, "TPL000",
                    "suppression without justification: add "
                    "`-- <why this hazard is intended>` to the disable "
                    "comment")
            for line in fi.unjustified]


# -- TPL001 ---------------------------------------------------------------
_SYNC_CONVERSIONS = {"float", "int", "bool"}


def rule_tpl001(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []

    def flag(node: ast.AST, what: str, fix: str) -> None:
        out.append(Finding(fi.rel, node.lineno, "TPL001",
                           f"{what} inside traced code forces a host "
                           f"sync (or fails to trace); {fix}"))

    for info in _traced_functions(fi, ctx):
        params = _param_names(info.node) - info.static_argnames
        for node in _walk_own(info.node):
            if isinstance(node, ast.Call):
                func = node.func
                if (isinstance(func, ast.Attribute) and func.attr == "item"
                        and not node.args):
                    flag(node, ".item()",
                         "keep the value on device (jnp.where/select on "
                         "the array) or move the read after the block")
                elif (isinstance(func, ast.Attribute)
                      and func.attr in ("asarray", "array")
                      and _root_name(func) in NP_ALIASES):
                    flag(node, f"np.{func.attr}()",
                         "use jnp equivalents in traced code; convert on "
                         "the host side of the jit boundary")
                elif (isinstance(func, ast.Attribute)
                      and func.attr == "device_get"):
                    flag(node, "jax.device_get()",
                         "fetch after the traced block returns")
                elif (isinstance(func, ast.Name)
                      and func.id in _SYNC_CONVERSIONS
                      and len(node.args) == 1 and not node.keywords):
                    arg = node.args[0]
                    if _is_jaxish(arg) or (isinstance(arg, ast.Name)
                                           and arg.id in params):
                        flag(node, f"{func.id}() on an array expression",
                             "keep arithmetic in jnp, or declare the "
                             "argument static if it is a Python scalar")
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                it = node.iter
                if _is_jaxish(it) or (isinstance(it, ast.Name)
                                      and it.id in params):
                    flag(node, "iteration over a traced array",
                         "use lax.scan/fori_loop, or iterate a static "
                         "Python sequence")
    return out


# -- TPL002 ---------------------------------------------------------------
def _mutated_module_globals(fi: FileInfo) -> Set[str]:
    """Module-level names that some function mutates: ``global`` rebinds,
    subscript/attribute stores (``_FLAG[0] = True``), and aug-assigns."""
    module_names: Set[str] = set()
    for node in fi.tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    module_names.add(t.id)
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                           ast.Name):
            module_names.add(node.target.id)
    mutated: Set[str] = set()
    for node in ast.walk(fi.tree):
        if isinstance(node, ast.Global):
            mutated.update(n for n in node.names if n in module_names)
        elif isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if isinstance(t, (ast.Subscript, ast.Attribute)):
                    rn = _root_name(t.value if isinstance(t, ast.Attribute)
                                    else t.value)
                    if rn in module_names:
                        mutated.add(rn)
    return mutated


def rule_tpl002(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    mutated = _mutated_module_globals(fi)
    for info in _traced_functions(fi, ctx):
        if not info.is_root:
            continue
        node = info.node
        a = node.args
        pos_params = list(a.posonlyargs) + list(a.args)
        defaults = list(a.defaults)
        pairs = list(zip(pos_params[len(pos_params) - len(defaults):],
                         defaults))
        pairs += [(p, d) for p, d in zip(a.kwonlyargs, a.kw_defaults)
                  if d is not None]
        for param, dflt in pairs:
            if isinstance(dflt, (ast.List, ast.Dict, ast.Set)) or (
                    isinstance(dflt, ast.Call)
                    and _callee_name(dflt.func) in ("list", "dict", "set")):
                out.append(Finding(
                    fi.rel, dflt.lineno, "TPL002",
                    f"mutable default for `{param.arg}` on a traced "
                    f"function: mutation never re-traces; use None + "
                    f"in-body default"))
            elif (info.jit_like
                  and isinstance(dflt, ast.Constant)
                  and isinstance(dflt.value, (int, float, bool))
                  and param.arg not in info.static_argnames):
                out.append(Finding(
                    fi.rel, dflt.lineno, "TPL002",
                    f"jit function takes Python scalar `{param.arg}` "
                    f"not in static_argnames: every distinct value "
                    f"retraces (weak-type permitting); declare it "
                    f"static or pass a jnp scalar"))
        if info.jit_like and mutated:
            seen: Set[str] = set()
            for sub in _walk_own(node):
                if (isinstance(sub, ast.Name) and sub.id in mutated
                        and isinstance(sub.ctx, ast.Load)
                        and sub.id not in seen):
                    seen.add(sub.id)
                    out.append(Finding(
                        fi.rel, sub.lineno, "TPL002",
                        f"jit function closes over module global "
                        f"`{sub.id}` that is mutated elsewhere: the "
                        f"compiled program bakes the traced value in; "
                        f"pass it as an argument or a static cache key"))
    return out


# -- TPL003 ---------------------------------------------------------------
def rule_tpl003(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    traced_lines: Set[int] = set()
    for info in _traced_functions(fi, ctx):
        for node in _walk_own(info.node):
            if hasattr(node, "lineno"):
                traced_lines.add(node.lineno)
            if (isinstance(node, ast.Attribute)
                    and node.attr in ("float64", "double")
                    and _root_name(node) in (NP_ALIASES | JAX_ALIASES)):
                out.append(Finding(
                    fi.rel, node.lineno, "TPL003",
                    "float64 in traced code: TPU computes f32/bf16 — "
                    "with x64 disabled this silently downcasts, with it "
                    "enabled it recompiles everything wider; use an "
                    "explicit f32 dtype (f64 only host-side)"))
    module_jax = fi.imports_jax()
    for node in ast.walk(fi.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "array"
                and _root_name(node.func) in NP_ALIASES):
            continue
        has_dtype = len(node.args) >= 2 or any(
            kw.arg == "dtype" for kw in node.keywords)
        if has_dtype:
            continue
        if module_jax or node.lineno in traced_lines:
            out.append(Finding(
                fi.rel, node.lineno, "TPL003",
                "dtype-less np.array in a jax-adjacent module defaults "
                "to float64/int64 and drifts when it reaches the device; "
                "state the dtype explicitly"))
    return out


# -- TPL004 ---------------------------------------------------------------
def _is_collective_primitive(call: ast.Call) -> Optional[str]:
    func = call.func
    if isinstance(func, ast.Attribute):
        if func.attr == "process_allgather":
            return "process_allgather"
        if (func.attr == "initialize"
                and isinstance(func.value, ast.Attribute)
                and func.value.attr == "distributed"):
            return "jax.distributed.initialize"
    return None


def rule_tpl004(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    # function names handed to utils/retry (retry_call(f,...)/retrying(f))
    guarded: Set[str] = set()
    for node in ast.walk(fi.tree):
        if (isinstance(node, ast.Call)
                and _callee_name(node.func) in ("retry_call", "retrying")
                and node.args and isinstance(node.args[0], ast.Name)):
            guarded.add(node.args[0].id)

    out: List[Finding] = []

    def visit(node: ast.AST, enclosing: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit(child, child.name)
                continue
            if isinstance(child, ast.Call):
                prim = _is_collective_primitive(child)
                if prim is not None and enclosing not in guarded:
                    out.append(Finding(
                        fi.rel, child.lineno, "TPL004",
                        f"{prim} outside a utils/retry wrapper: a "
                        f"transient DCN/rendezvous fault kills the run; "
                        f"wrap the enclosing function with "
                        f"retry_call/retrying (see io/distributed.py)"))
            visit(child, enclosing)

    visit(fi.tree, None)
    return out


# -- TPL005 ---------------------------------------------------------------
def rule_tpl005(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    if not ctx.project_rules or "pallas_call" not in fi.source:
        return []
    first_line = next(
        (n.lineno for n in ast.walk(fi.tree)
         if isinstance(n, ast.Call)
         and _callee_name(n.func) == "pallas_call"), None)
    if first_line is None:
        return []
    stem = os.path.splitext(fi.basename)[0]
    tests_dir = os.path.join(ctx.root, "tests")
    try:
        test_files = [f for f in os.listdir(tests_dir) if f.endswith(".py")]
    except OSError:
        test_files = []
    for name in test_files:
        try:
            with open(os.path.join(tests_dir, name), encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        if stem in text and "interpret" in text:
            return []
    return [Finding(
        fi.rel, first_line, "TPL005",
        f"Pallas kernel module `{stem}` has no interpret-mode oracle "
        f"test under tests/: add one asserting parity with the XLA "
        f"path (see tests/test_pallas_split.py)")]


# -- TPL006 ---------------------------------------------------------------
_BROAD = {"Exception", "BaseException"}
_HANDLED_CALLS = {
    "log_warning", "log_once", "log_info", "log_error", "log_debug",
    "warn", "warning", "error", "exception", "event", "counter_add",
    "fail", "perror", "print_exc",
}


def _handler_is_broad(h: ast.ExceptHandler) -> bool:
    t = h.type
    if t is None:
        return True
    if isinstance(t, ast.Name):
        return t.id in _BROAD
    if isinstance(t, ast.Tuple):
        return any(isinstance(e, ast.Name) and e.id in _BROAD
                   for e in t.elts)
    return False


def rule_tpl006(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(fi.tree):
        if not (isinstance(node, ast.ExceptHandler)
                and _handler_is_broad(node)):
            continue
        handled = False
        for sub in node.body:
            for n in ast.walk(sub):
                if isinstance(n, ast.Raise):
                    handled = True
                elif isinstance(n, ast.Call):
                    cn = _callee_name(n.func) or ""
                    if cn in _HANDLED_CALLS or "fallback" in cn:
                        handled = True
        if not handled:
            out.append(Finding(
                fi.rel, node.lineno, "TPL006",
                "broad except swallows errors (including jit/Mosaic "
                "compile failures) silently: log a warning, re-raise, "
                "or route through the pallas_split.py logged-fallback "
                "pattern"))
    return out


# -- TPL007 ---------------------------------------------------------------
_PRINT_ALLOWED = {"cli.py", "plotting.py"}


def rule_tpl007(fi: FileInfo, ctx: LintContext) -> List[Finding]:
    if fi.basename in _PRINT_ALLOWED:
        return []
    return [Finding(
        fi.rel, node.lineno, "TPL007",
        "bare print( in library code: route through utils/log.py "
        "(leveled, rank-prefixed) or obs/ (structured telemetry)")
        for node in ast.walk(fi.tree)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
        and node.func.id == "print"]


FILE_RULES: List[Callable[[FileInfo, LintContext], List[Finding]]] = [
    rule_tpl000, rule_tpl001, rule_tpl002, rule_tpl003, rule_tpl004,
    rule_tpl005, rule_tpl006, rule_tpl007,
]
