"""memcheck rules MEM001-MEM005 — device-memory & donation hazards.

tpulint pins intra-rank host-sync/recompile hazards, spmdcheck pins
cross-rank schedule divergence; memcheck pins the hazard class PR 7
hit for real: device-memory lifetime.  The triggering incident —
zero-copy ``np.asarray`` host reads of a buffer a ``donate_argnums``
jit had consumed flakily SIGSEGV'd tier-1 eval on CPU — was only
caught by rerunning tests; these rules make that class (and its
siblings: missed donations, per-dispatch footprint blowups, unguarded
Pallas VMEM, live-buffer leaks) fail the gate instead.

| id     | hazard                                                       |
|--------|--------------------------------------------------------------|
| MEM001 | host materialization (np.asarray/np.array/.item()/           |
|        | device_get/memoryview/np.frombuffer) of a name that an       |
|        | UNGATED donate_argnums jit in the same module may have       |
|        | consumed — the PR 7 segfault class.  A donation site guarded |
|        | by a backend gate (an enclosing ``if`` referencing a         |
|        | ``*donat*`` predicate, e.g. ``_donation_enabled()``) is the  |
|        | sanctioned idiom and exempts its donated names               |
| MEM002 | a jit-bound callable with NO donation path threading the     |
|        | same array name in and out (``x = step(x)``): every dispatch |
|        | allocates a second live copy of persistent state instead of  |
|        | updating in place                                            |
| MEM003 | static per-dispatch footprint model: the closed-form live-   |
|        | bytes estimate (tools/memcheck/footprint.py) at each         |
|        | declared representative shape (tools/memcheck/shapes.json)   |
|        | exceeds that target's HBM budget                             |
| MEM004 | a ``pallas_call`` site whose module references no VMEM-model |
|        | predicate (``lightgbm_tpu/ops/vmem.py`` ``VMEM_GUARDS``, or  |
|        | any ``*vmem*`` name) and is not dispatched through a module  |
|        | that does — the ADVICE-r5 Mosaic-crash class                 |
| MEM005 | device arrays captured in module globals or appended to      |
|        | module-level containers (live-buffer leak: module lifetime   |
|        | pins device memory for the whole process)                    |

Name resolution is deliberately coarse (same contract as tpulint's
call-graph walk): a donated name taints every same-named read in the
module, and the baseline/suppressions absorb the rare over-taint.
Suppression syntax is shared (``# memcheck: disable=MEMxxx -- why``).
"""
from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Set, Tuple

from tools.analysis_core import FileInfo, Finding
from tools.tpulint.callgraph import _callee_name
from tools.tpulint.rules import JAX_ALIASES, NP_ALIASES, _root_name

RULE_TITLES = {
    "MEM001": "host read of a possibly-donated buffer",
    "MEM002": "persistent state threaded through jit without donation",
    "MEM003": "per-dispatch footprint exceeds the target HBM budget",
    "MEM004": "pallas_call without a VMEM-model guard",
    "MEM005": "device array pinned by a module global / container",
}

# fallback guard registry when lightgbm_tpu/ops/vmem.py is not under
# the analyzed root (fixture temp dirs); kept in sync by
# tests/test_memcheck.py::test_guard_registry_matches_ops_vmem
DEFAULT_VMEM_GUARDS = (
    "pallas_config_ok", "fused_config_ok", "hist_cell_ok",
    "hist_fold_cell_ok", "split_lane_chunk_features",
    "split_scan_chunk_features",
)

_DONATION_GATE_RE = re.compile(r"donat", re.IGNORECASE)
_VMEM_NAME_RE = re.compile(r"vmem", re.IGNORECASE)

_MATERIALIZE_NP = {"asarray", "array", "frombuffer"}
_ARRAY_CTORS = {"zeros", "ones", "full", "empty", "arange", "asarray",
                "array", "linspace", "eye"}


@dataclass
class MemContext:
    root: str
    files: List[FileInfo]
    by_rel: Dict[str, FileInfo]
    vmem_guards: Tuple[str, ...]
    project_rules: bool = True


def _load_vmem_guards(root: str) -> Tuple[str, ...]:
    """Statically read ``VMEM_GUARDS`` from the analyzed tree's
    ops/vmem.py (no library import — tools stay jax-free)."""
    path = os.path.join(root, "lightgbm_tpu", "ops", "vmem.py")
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read())
    except (OSError, SyntaxError, ValueError):
        return DEFAULT_VMEM_GUARDS
    for node in tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "VMEM_GUARDS"
                and isinstance(node.value, (ast.Tuple, ast.List))):
            names = [el.value for el in node.value.elts
                     if isinstance(el, ast.Constant)
                     and isinstance(el.value, str)]
            if names:
                return tuple(names)
    return DEFAULT_VMEM_GUARDS


def build_context(files: Sequence[FileInfo], root: str,
                  project_rules: bool = True) -> MemContext:
    return MemContext(root=root, files=list(files),
                      by_rel={fi.rel: fi for fi in files},
                      vmem_guards=_load_vmem_guards(root),
                      project_rules=project_rules)


# -- shared helpers -------------------------------------------------------
def _leaf_name(node: ast.AST) -> Optional[str]:
    """`x` -> x, `self.scores` -> scores, `a.b.c` -> c."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


def _is_jit_call(node: ast.Call) -> bool:
    return _callee_name(node.func) in ("jit", "pjit")


def _donate_kw(node: ast.Call) -> Optional[ast.keyword]:
    for kw in node.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            return kw
    return None


def _donate_indices(kw: ast.keyword) -> Optional[List[int]]:
    """Constant donate_argnums indices, or None when unresolvable."""
    v = kw.value
    if isinstance(v, ast.Constant) and isinstance(v.value, int):
        return [v.value]
    if isinstance(v, (ast.Tuple, ast.List)):
        out = []
        for el in v.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return out
    return None


class _GateWalker:
    """Tracks, per AST node, whether any enclosing If/IfExp/While test
    references a donation-gate name (``*donat*``): the sanctioned
    backend-gating idiom (``if _donation_enabled(): ...``)."""

    def __init__(self, tree: ast.AST):
        self.gated_lines: Set[int] = set()
        self._walk(tree, False)

    @staticmethod
    def _test_is_gate(test: ast.AST) -> bool:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Name) and _DONATION_GATE_RE.search(sub.id):
                return True
            if isinstance(sub, ast.Attribute) and _DONATION_GATE_RE.search(
                    sub.attr):
                return True
        return False

    def _walk(self, node: ast.AST, gated: bool) -> None:
        if gated and hasattr(node, "lineno"):
            self.gated_lines.add(node.lineno)
        if isinstance(node, (ast.If, ast.While)):
            self._walk(node.test, gated)
            branch = gated or self._test_is_gate(node.test)
            # an `elif` chain is a nested If in orelse: the recursion
            # re-dispatches here, so each arm gets its own test's gate
            for stmt in list(node.body) + list(node.orelse):
                self._walk(stmt, branch)
            return
        if isinstance(node, ast.IfExp):
            self._walk(node.test, gated)
            branch = gated or self._test_is_gate(node.test)
            self._walk(node.body, branch)
            self._walk(node.orelse, branch)
            return
        for child in ast.iter_child_nodes(node):
            self._walk(child, gated)


@dataclass
class _DonationInfo:
    """Per-file donation facts MEM001/MEM002 share."""
    # callee leaf names bound to an UNGATED donating jit -> donated
    # positional indices (None = unresolvable, treat all args donated)
    ungated_donating: Dict[str, Optional[List[int]]] = field(
        default_factory=dict)
    # callee leaf names bound to ANY donating jit (gated or not)
    donating_names: Set[str] = field(default_factory=set)
    # callee leaf names bound to a PLAIN jit (no donation anywhere)
    plain_jit_names: Set[str] = field(default_factory=set)
    # names donated at call sites of ungated donating callables
    donated_value_names: Set[str] = field(default_factory=set)
    # lines of direct `jax.jit(f, donate_argnums=..)(x)` immediate calls
    # contribute donated names too


def _dict_donation_kwargs(fn_node: ast.AST, gates: _GateWalker) -> Dict[
        str, bool]:
    """kwarg-dict names that receive a ``donate_argnums`` store inside
    ``fn_node`` -> whether that store is donation-gated."""
    out: Dict[str, bool] = {}
    for node in ast.walk(fn_node):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1):
            continue
        t = node.targets[0]
        if (isinstance(t, ast.Subscript) and isinstance(t.value, ast.Name)
                and isinstance(t.slice, ast.Constant)
                and t.slice.value in ("donate_argnums", "donate_argnames")):
            name = t.value.id
            gated = node.lineno in gates.gated_lines
            out[name] = out.get(name, True) and gated
    return out


def _collect_donation(fi: FileInfo) -> _DonationInfo:
    info = _DonationInfo()
    gates = _GateWalker(fi.tree)
    # kwarg-dict donation stores, resolved per enclosing function
    dict_kwargs: Dict[str, bool] = {}
    for node in ast.walk(fi.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            dict_kwargs.update(_dict_donation_kwargs(node, gates))

    def classify_jit(call: ast.Call) -> Tuple[bool, Optional[List[int]],
                                              bool]:
        """-> (donating, indices, gated)."""
        kw = _donate_kw(call)
        if kw is not None:
            return True, _donate_indices(kw), (
                call.lineno in gates.gated_lines)
        for k in call.keywords:
            if k.arg is None and isinstance(k.value, ast.Name) \
                    and k.value.id in dict_kwargs:       # jax.jit(f, **kw)
                return True, None, dict_kwargs[k.value.id]
        return False, None, False

    for node in ast.walk(fi.tree):
        # name = jax.jit(f, ...) / self.attr = jax.jit(f, ...)
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and _is_jit_call(node.value):
            donating, idx, gated = classify_jit(node.value)
            for t in node.targets:
                leaf = _leaf_name(t)
                if leaf is None:
                    continue
                if donating:
                    info.donating_names.add(leaf)
                    if not gated:
                        info.ungated_donating[leaf] = idx
                else:
                    info.plain_jit_names.add(leaf)
        # immediate call: jax.jit(f, donate_argnums=(0,))(x, ...)
        elif isinstance(node, ast.Call) and isinstance(node.func, ast.Call) \
                and _is_jit_call(node.func):
            donating, idx, gated = classify_jit(node.func)
            if donating and not gated:
                args = node.args
                for i in (idx if idx is not None else range(len(args))):
                    if i < len(args):
                        leaf = _leaf_name(args[i])
                        if leaf is not None:
                            info.donated_value_names.add(leaf)
        # @jax.jit / @partial(jax.jit, donate_argnums=...) decorations
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    target = dec
                    if (_callee_name(dec.func) == "partial" and dec.args
                            and _callee_name(dec.args[0]) in ("jit", "pjit")):
                        target = dec
                    elif not _is_jit_call(dec):
                        continue
                    donating, idx, gated = classify_jit(target)
                    if donating:
                        info.donating_names.add(node.name)
                        if not gated:
                            info.ungated_donating[node.name] = idx
                    else:
                        info.plain_jit_names.add(node.name)
                elif _callee_name(dec) in ("jit", "pjit"):
                    info.plain_jit_names.add(node.name)

    # a name with any donating binding is not "plain"
    info.plain_jit_names -= info.donating_names

    # call sites of ungated donating callables -> donated value names
    for node in ast.walk(fi.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _leaf_name(node.func)
        if callee not in info.ungated_donating:
            continue
        idx = info.ungated_donating[callee]
        args = node.args
        for i in (idx if idx is not None else range(len(args))):
            if i < len(args):
                leaf = _leaf_name(args[i])
                if leaf is not None:
                    info.donated_value_names.add(leaf)
    return info


_DONATION_CACHE: Dict[str, Tuple[str, _DonationInfo]] = {}


def _donation(fi: FileInfo) -> _DonationInfo:
    cached = _DONATION_CACHE.get(fi.path)
    if cached is not None and cached[0] == fi.source:
        return cached[1]
    info = _collect_donation(fi)
    _DONATION_CACHE[fi.path] = (fi.source, info)
    return info


# -- MEM001 ---------------------------------------------------------------
def rule_mem001(fi: FileInfo, ctx: MemContext) -> List[Finding]:
    info = _donation(fi)
    if not info.donated_value_names:
        return []
    out: List[Finding] = []

    def flag(node: ast.AST, what: str, name: str) -> None:
        out.append(Finding(
            fi.rel, node.lineno, "MEM001",
            f"{what} of `{name}`, which an ungated donate_argnums jit "
            f"in this module may have consumed: on CPU the host view "
            f"aliases the donated XLA buffer and reads race the next "
            f"dispatch (the PR 7 SIGSEGV class); gate the donation on "
            f"a backend predicate (see gbdt._donation_enabled) or read "
            f"a fresh, undonated result"))

    for node in ast.walk(fi.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        # np.asarray / np.array / np.frombuffer / memoryview / device_get
        if (isinstance(func, ast.Attribute)
                and func.attr in _MATERIALIZE_NP
                and _root_name(func) in NP_ALIASES and node.args):
            leaf = _leaf_name(node.args[0])
            if leaf in info.donated_value_names:
                flag(node, f"np.{func.attr}() host view", leaf)
        elif (isinstance(func, ast.Attribute) and func.attr == "device_get"
              and node.args):
            leaf = _leaf_name(node.args[0])
            if leaf in info.donated_value_names:
                flag(node, "jax.device_get()", leaf)
        elif (isinstance(func, ast.Name) and func.id == "memoryview"
              and node.args):
            leaf = _leaf_name(node.args[0])
            if leaf in info.donated_value_names:
                flag(node, "memoryview() buffer-protocol read", leaf)
        elif (isinstance(func, ast.Attribute) and func.attr == "item"
              and not node.args):
            leaf = _leaf_name(func.value)
            if leaf in info.donated_value_names:
                flag(node, ".item()", leaf)
    return out


# -- MEM002 ---------------------------------------------------------------
def rule_mem002(fi: FileInfo, ctx: MemContext) -> List[Finding]:
    info = _donation(fi)
    if not info.plain_jit_names:
        return []
    out: List[Finding] = []
    for node in ast.walk(fi.tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        call = node.value
        callee = _leaf_name(call.func)
        if callee not in info.plain_jit_names:
            continue
        arg_names = {_leaf_name(a) for a in call.args} - {None}
        for t in node.targets:
            targets = t.elts if isinstance(t, (ast.Tuple, ast.List)) \
                else [t]
            for tt in targets:
                leaf = _leaf_name(tt)
                if leaf is not None and leaf in arg_names:
                    out.append(Finding(
                        fi.rel, node.lineno, "MEM002",
                        f"`{leaf}` threads in and out of jit-bound "
                        f"`{callee}` with no donation path: every "
                        f"dispatch keeps TWO live copies of the state "
                        f"instead of updating in place; add "
                        f"donate_argnums behind a backend gate (the "
                        f"gbdt.py block-fn idiom) or justify why the "
                        f"old buffer must stay live"))
    return out


# -- MEM003 ---------------------------------------------------------------
def rule_mem003_project(ctx: MemContext) -> List[Finding]:
    """Project-level rule: evaluate the closed-form footprint model at
    every target declared in tools/memcheck/shapes.json (absent file =>
    rule inactive, e.g. fixture temp roots)."""
    from .footprint import load_targets, target_footprint
    shapes_rel = "tools/memcheck/shapes.json"
    path = os.path.join(ctx.root, shapes_rel)
    targets, err = load_targets(path)
    if err is not None:
        return [Finding(shapes_rel, 1, "MEM003",
                        f"shapes.json unreadable: {err}")]
    out: List[Finding] = []
    for t in targets:
        fp = target_footprint(t)
        if fp.total_bytes > t.budget_bytes:
            top = ", ".join(f"{k}={v / 1e6:.0f}MB" for k, v in sorted(
                fp.parts.items(), key=lambda kv: -kv[1])[:3])
            out.append(Finding(
                shapes_rel, 1, "MEM003",
                f"target `{t.name}`: estimated per-dispatch live bytes "
                f"{fp.total_bytes / 1e9:.2f} GB exceed the declared "
                f"budget {t.budget_bytes / 1e9:.2f} GB (largest: {top});"
                f" shrink the working set or justify a budget raise in "
                f"shapes.json"))
    return out


# -- MEM004 ---------------------------------------------------------------
def _module_guard_names(fi: FileInfo, guards: Sequence[str]) -> bool:
    guard_set = set(guards)
    for node in ast.walk(fi.tree):
        ident = None
        if isinstance(node, ast.Name):
            ident = node.id
        elif isinstance(node, ast.Attribute):
            ident = node.attr
        elif isinstance(node, ast.alias):
            ident = node.name.rsplit(".", 1)[-1]
        if ident is None:
            continue
        if ident in guard_set or _VMEM_NAME_RE.search(ident):
            return True
    return False


def _imported_module_stems(fi: FileInfo) -> Set[str]:
    stems: Set[str] = set()
    for node in ast.walk(fi.tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            stems.add(node.module.rsplit(".", 1)[-1])
        elif isinstance(node, ast.Import):
            for a in node.names:
                stems.add(a.name.rsplit(".", 1)[-1])
    return stems


def rule_mem004(fi: FileInfo, ctx: MemContext) -> List[Finding]:
    if "pallas_call" not in fi.source:
        return []
    calls = [n for n in ast.walk(fi.tree)
             if isinstance(n, ast.Call)
             and _callee_name(n.func) == "pallas_call"]
    if not calls:
        return []
    if _module_guard_names(fi, ctx.vmem_guards):
        return []
    # dispatch-seam exemption: another analyzed module imports this one
    # AND references a guard (the serial.py `resolve_backend` pattern
    # guarding pallas_route's kernels)
    stem = os.path.splitext(fi.basename)[0]
    for other in ctx.files:
        if other.rel == fi.rel:
            continue
        if stem in _imported_module_stems(other) \
                and _module_guard_names(other, ctx.vmem_guards):
            return []
    return [Finding(
        fi.rel, c.lineno, "MEM004",
        "pallas_call with no VMEM-model guard on its dispatch path: an "
        "infeasible config surfaces as a Mosaic compile crash (or "
        "silent VMEM thrash) instead of a fallback; key the config "
        "gate on lightgbm_tpu/ops/vmem.py (VMEM_GUARDS) like "
        "pallas_config_ok/fused_config_ok do") for c in calls]


# -- MEM005 ---------------------------------------------------------------
def _module_container_names(fi: FileInfo) -> Set[str]:
    names: Set[str] = set()
    for node in fi.tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        else:
            continue
        value = node.value
        is_container = isinstance(value, (ast.List, ast.Dict, ast.Set)) or (
            isinstance(value, ast.Call)
            and _callee_name(value.func) in ("list", "dict", "set",
                                             "deque", "defaultdict"))
        if not is_container:
            continue
        for t in targets:
            if isinstance(t, ast.Name):
                names.add(t.id)
    return names


def _is_device_array_expr(node: ast.AST) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            func = sub.func
            if (isinstance(func, ast.Attribute)
                    and func.attr in _ARRAY_CTORS
                    and _root_name(func) in JAX_ALIASES):
                return True
    return False


def rule_mem005(fi: FileInfo, ctx: MemContext) -> List[Finding]:
    if not fi.imports_jax():
        return []
    out: List[Finding] = []
    # (a) module-scope device-array constant: lives for the process
    for node in fi.tree.body:
        value = getattr(node, "value", None)
        if isinstance(node, (ast.Assign, ast.AnnAssign)) \
                and value is not None and _is_device_array_expr(value):
            out.append(Finding(
                fi.rel, node.lineno, "MEM005",
                "device array bound at module scope: the buffer pins "
                "device memory for the whole process (and embeds as a "
                "compile-payload constant when closed over); build it "
                "inside the function or pass it as an argument"))
    # (b) appends into module-level containers: unbounded live-buffer
    # growth (the leak class the runtime watermark contract catches)
    containers = _module_container_names(fi)
    if containers:
        for node in ast.walk(fi.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("append", "extend", "add")
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in containers
                    and node.args):
                continue
            arg = node.args[0]
            # literals (strings, numbers) can't pin device buffers
            if isinstance(arg, ast.Constant):
                continue
            out.append(Finding(
                fi.rel, node.lineno, "MEM005",
                f"append into module-level container "
                f"`{node.func.value.id}`: if the value holds device "
                f"arrays this is an unbounded live-buffer leak (the "
                f"class the LGBM_TPU_MEM_CONTRACT watermark gate "
                f"catches at runtime); bound or scope the container, "
                f"or justify why growth is bounded"))
    return out


FILE_RULES: List[Callable[[FileInfo, MemContext], List[Finding]]] = [
    rule_mem001, rule_mem002, rule_mem004, rule_mem005,
]
PROJECT_RULES = [rule_mem003_project]
