"""TraceLint: jit-hot-path rules over the reachable traced call graph.

Three phases, all pure ``ast``:

1. **Collection** -- parse every module under the given paths, build
   per-module alias maps (``import jax.numpy as jnp``, relative
   ``from ..core.control import vectorized_step``, module-level
   fallback assignments like ``_shard_map = jax.shard_map``) and a
   registry of every function/method/lambda with its nesting structure.
2. **Seeding** -- find tracing entry points: ``@jax.jit`` /
   ``@functools.partial(jax.jit, static_argnames=...)`` decorators and
   callables handed to ``jax.jit`` / ``jax.vmap`` / ``jax.lax.scan`` /
   ``fori_loop`` / ``while_loop`` / ``cond`` / ``shard_map`` /
   ``pallas_call`` (including through a local ``functools.partial``
   binding, whose bound arguments become static).
3. **Taint fixpoint** -- walk each traced function with a value-taint
   environment: positional parameters are traced, keyword-only and
   ``static_argnames`` parameters are static (the repo's calling
   convention), and call sites propagate the *actual* argument taint
   into resolvable callees until the per-parameter taint stabilizes.
   ``.shape``/``.dtype``-style attributes, ``isinstance``/``len``, and
   ``is None`` comparisons launder taint (they are static under
   tracing); nested functions inherit a snapshot of the enclosing
   environment as closure taint.  The final pass emits findings.

The taint discipline is what keeps the rules quiet on the real tree:
``float(cache.reuse_skew)`` in the sweep's traced body is fine (the
cache spec is a trace-time constant), while ``float(r)`` on the scanned
utilization would fire PC-T002.
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .findings import Finding, relpath

# Entry points that trace their N-th positional argument as jax code.
_TRACED_ARG_POS: Dict[str, Tuple[int, ...]] = {
    "jax.jit": (0,),
    "jax.vmap": (0,),
    "jax.pmap": (0,),
    "jax.grad": (0,),
    "jax.value_and_grad": (0,),
    "jax.checkpoint": (0,),
    "jax.remat": (0,),
    "jax.lax.scan": (0,),
    "jax.lax.map": (0,),
    "jax.lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "jax.lax.cond": (1, 2),
    "jax.shard_map": (0,),
    "jax.experimental.pallas.pallas_call": (0,),
}

# Attributes of a traced value that are static under tracing.
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "nbytes", "itemsize"}

# Builtins whose result is always static (trace-time metadata).
_STATIC_FUNCS = {"isinstance", "len", "type", "hasattr", "callable",
                 "id", "range", "repr", "issubclass"}

# Builtins that concretize their argument (host round trip under jit).
_CAST_FUNCS = {"float", "int", "bool"}
_COERCE_FUNCS = {"min", "max", "sum", "sorted", "any", "all", "list",
                 "tuple"}

_SYNC_METHODS = {"item", "tolist", "block_until_ready", "copy_to_host_async"}

_SORT_FAMILY = {"sort", "argsort", "lexsort", "quantile", "nanquantile",
                "percentile", "nanpercentile", "median", "nanmedian",
                "unique", "msort", "partition", "argpartition"}

_F64_NAMES = {"numpy.float64", "jax.numpy.float64", "numpy.double"}

_IGNORE_RE = re.compile(r"#\s*planecheck:\s*ignore\[([A-Z0-9-]+)\]")


# ---------------------------------------------------------------------------
# Module / function registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FuncInfo:
    module: "ModuleInfo"
    qualname: str
    node: ast.AST                        # FunctionDef | Lambda
    cls_name: Optional[str] = None
    parent: Optional["FuncInfo"] = None
    traced: bool = False
    is_seed: bool = False
    seed_reason: str = ""
    static_params: Set[str] = dataclasses.field(default_factory=set)
    param_taint: Dict[str, bool] = dataclasses.field(default_factory=dict)
    closure_taint: Set[str] = dataclasses.field(default_factory=set)
    nested: Dict[str, "FuncInfo"] = dataclasses.field(default_factory=dict)

    @property
    def positional_params(self) -> List[str]:
        a = self.node.args
        return [p.arg for p in list(a.posonlyargs) + list(a.args)]

    @property
    def kwonly_params(self) -> List[str]:
        return [p.arg for p in self.node.args.kwonlyargs]

    @property
    def all_params(self) -> List[str]:
        names = self.positional_params + self.kwonly_params
        if self.node.args.vararg:
            names.append(self.node.args.vararg.arg)
        if self.node.args.kwarg:
            names.append(self.node.args.kwarg.arg)
        return names

    def seed_taint(self) -> Dict[str, bool]:
        """Initial per-parameter taint for a tracing entry point."""
        taint = {}
        for name in self.positional_params:
            taint[name] = name not in self.static_params
        for name in self.kwonly_params:
            taint[name] = False
        if self.node.args.vararg:
            taint[self.node.args.vararg.arg] = True
        if self.node.args.kwarg:
            taint[self.node.args.kwarg.arg] = False
        # Methods: the bound instance is a static container.
        if self.cls_name and self.positional_params[:1] == ["self"]:
            taint["self"] = False
        return taint


@dataclasses.dataclass
class ModuleInfo:
    name: str                           # dotted module name
    path: str                           # filesystem path
    tree: ast.Module
    lines: List[str]
    aliases: Dict[str, str] = dataclasses.field(default_factory=dict)
    top_funcs: Dict[str, FuncInfo] = dataclasses.field(default_factory=dict)
    class_methods: Dict[str, Dict[str, FuncInfo]] = dataclasses.field(
        default_factory=dict)
    all_funcs: List[FuncInfo] = dataclasses.field(default_factory=list)
    by_node: Dict[int, FuncInfo] = dataclasses.field(default_factory=dict)

    def line_has_ignore(self, lineno: int, rule: str) -> bool:
        for ln in (lineno, lineno - 1):
            if 1 <= ln <= len(self.lines):
                m = _IGNORE_RE.search(self.lines[ln - 1])
                if m and m.group(1) in (rule, "ALL"):
                    return True
        return False


def _dotted(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else None
    return None


def _module_name_for(path: str) -> str:
    """Dotted module name from the path, walking up ``__init__.py`` dirs."""
    path = os.path.abspath(path)
    parts = [os.path.splitext(os.path.basename(path))[0]]
    d = os.path.dirname(path)
    while os.path.exists(os.path.join(d, "__init__.py")):
        parts.append(os.path.basename(d))
        d = os.path.dirname(d)
    if parts[0] == "__init__":
        parts = parts[1:]
    return ".".join(reversed(parts)) or os.path.basename(path)


def _collect_aliases(mod: ModuleInfo) -> None:
    pkg_parts = mod.name.split(".")

    def visit(stmts):
        for s in stmts:
            if isinstance(s, ast.Import):
                for a in s.names:
                    mod.aliases[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0])
            elif isinstance(s, ast.ImportFrom):
                if s.level:
                    base = pkg_parts[:-s.level] if s.level <= len(pkg_parts) \
                        else []
                    target = ".".join(base + ([s.module] if s.module else []))
                else:
                    target = s.module or ""
                for a in s.names:
                    if a.name == "*":
                        continue
                    mod.aliases[a.asname or a.name] = (
                        f"{target}.{a.name}" if target else a.name)
            elif isinstance(s, ast.Assign) and len(s.targets) == 1 and \
                    isinstance(s.targets[0], ast.Name):
                d = _dotted(s.value)
                if d:
                    resolved = resolve_dotted(mod, d)
                    if resolved:
                        mod.aliases[s.targets[0].id] = resolved
            elif isinstance(s, (ast.Try, ast.If)):
                visit(getattr(s, "body", []))
                visit(getattr(s, "orelse", []))
                for h in getattr(s, "handlers", []):
                    visit(h.body)
                visit(getattr(s, "finalbody", []))

    visit(mod.tree.body)


def resolve_dotted(mod: ModuleInfo, dotted: Optional[str]) -> Optional[str]:
    """Expand the leading component of ``dotted`` through the alias map."""
    if not dotted:
        return None
    head, _, rest = dotted.partition(".")
    target = mod.aliases.get(head, head)
    return f"{target}.{rest}" if rest else target


class _Collector(ast.NodeVisitor):
    def __init__(self, mod: ModuleInfo):
        self.mod = mod
        self.func_stack: List[FuncInfo] = []
        self.cls_stack: List[str] = []

    def _register(self, node, name: str) -> FuncInfo:
        parent = self.func_stack[-1] if self.func_stack else None
        cls = self.cls_stack[-1] if (self.cls_stack and not parent) else None
        qual = name
        if parent is not None:
            qual = f"{parent.qualname}.{name}"
        elif cls is not None:
            qual = f"{cls}.{name}"
        fi = FuncInfo(module=self.mod, qualname=qual, node=node,
                      cls_name=cls, parent=parent)
        self.mod.all_funcs.append(fi)
        self.mod.by_node[id(node)] = fi
        if parent is not None:
            parent.nested[name] = fi
        elif cls is not None:
            self.mod.class_methods.setdefault(cls, {})[name] = fi
        else:
            self.mod.top_funcs[name] = fi
        return fi

    def visit_ClassDef(self, node):
        self.cls_stack.append(node.name)
        self.generic_visit(node)
        self.cls_stack.pop()

    def _visit_func(self, node, name):
        fi = self._register(node, name)
        self.func_stack.append(fi)
        self.generic_visit(node)
        self.func_stack.pop()

    def visit_FunctionDef(self, node):
        self._visit_func(node, node.name)

    def visit_AsyncFunctionDef(self, node):
        self._visit_func(node, node.name)

    def visit_Lambda(self, node):
        self._visit_func(node, f"<lambda:{node.lineno}>")


def load_module(path: str) -> Optional[ModuleInfo]:
    try:
        with open(path, encoding="utf-8") as fh:
            src = fh.read()
        tree = ast.parse(src, filename=path)
    except (OSError, SyntaxError):
        return None
    mod = ModuleInfo(name=_module_name_for(path), path=path, tree=tree,
                     lines=src.splitlines())
    _collect_aliases(mod)
    _Collector(mod).visit(tree)
    return mod


# ---------------------------------------------------------------------------
# The analysis engine
# ---------------------------------------------------------------------------

class TraceLint:
    def __init__(self, paths: Sequence[str], root: Optional[str] = None):
        self.root = root or os.getcwd()
        self.modules: Dict[str, ModuleInfo] = {}
        for path in _python_files(paths):
            mod = load_module(path)
            if mod is not None:
                self.modules[mod.name] = mod
        self.findings: List[Finding] = []
        self._changed = False

    # -- resolution ---------------------------------------------------------
    def resolve_callable(self, mod: ModuleInfo, fi: Optional[FuncInfo],
                         node: ast.AST,
                         local_bindings: Optional[dict] = None
                         ) -> Optional[Tuple[FuncInfo, Set[str]]]:
        """Resolve an expression to ``(FuncInfo, static_param_names)``."""
        if isinstance(node, ast.Lambda):
            got = mod.by_node.get(id(node))
            return (got, set()) if got else None
        if isinstance(node, ast.Call):
            # functools.partial(f, ...) -- bound args become static
            fname = resolve_dotted(mod, _dotted(node.func))
            if fname == "functools.partial" and node.args:
                inner = self.resolve_callable(mod, fi, node.args[0],
                                              local_bindings)
                if inner is None:
                    return None
                target, statics = inner
                statics = set(statics)
                pos = target.positional_params
                for i in range(1, len(node.args)):
                    if i - 1 < len(pos):
                        statics.add(pos[i - 1])
                statics.update(kw.arg for kw in node.keywords if kw.arg)
                return target, statics
            return None
        dotted = _dotted(node)
        if dotted is None:
            return None
        if local_bindings and dotted in local_bindings:
            return local_bindings[dotted]
        if "." not in dotted:
            got = self._lookup_name(mod, fi, dotted)
            return (got, set()) if got else None
        # self.method / alias.func
        head, _, rest = dotted.partition(".")
        if head == "self" and fi is not None and fi.cls_name and \
                "." not in rest:
            got = mod.class_methods.get(fi.cls_name, {}).get(rest)
            return (got, set()) if got else None
        resolved = resolve_dotted(mod, dotted)
        if resolved:
            mmod, _, func = resolved.rpartition(".")
            target = self.modules.get(mmod)
            if target and func in target.top_funcs:
                return target.top_funcs[func], set()
        return None

    def _lookup_name(self, mod: ModuleInfo, fi: Optional[FuncInfo],
                     name: str) -> Optional[FuncInfo]:
        f = fi
        while f is not None:
            if name in f.nested:
                return f.nested[name]
            f = f.parent
        if fi is not None and fi.cls_name and fi.parent is None:
            pass  # bare names inside methods do not resolve to methods
        if name in mod.top_funcs:
            return mod.top_funcs[name]
        target = mod.aliases.get(name)
        if target:
            mmod, _, func = target.rpartition(".")
            tm = self.modules.get(mmod)
            if tm and func in tm.top_funcs:
                return tm.top_funcs[func]
        return None

    # -- seeding ------------------------------------------------------------
    def seed(self) -> None:
        for mod in self.modules.values():
            for fi in mod.all_funcs:
                self._seed_decorators(mod, fi)
            for fi in mod.all_funcs:
                self._seed_calls(mod, fi, fi.node, {})
            self._seed_calls(mod, None, mod.tree, {})

    def _mark_seed(self, fi: FuncInfo, reason: str,
                   statics: Set[str] = frozenset()) -> None:
        fi.is_seed = True
        fi.seed_reason = fi.seed_reason or reason
        fi.static_params |= set(statics)
        fi.traced = True
        for name, tainted in fi.seed_taint().items():
            if tainted:
                fi.param_taint[name] = True

    def _seed_decorators(self, mod: ModuleInfo, fi: FuncInfo) -> None:
        for dec in getattr(fi.node, "decorator_list", []):
            statics: Set[str] = set()
            if isinstance(dec, ast.Call):
                fname = resolve_dotted(mod, _dotted(dec.func))
                if fname == "functools.partial" and dec.args:
                    inner = resolve_dotted(mod, _dotted(dec.args[0]))
                    if inner != "jax.jit":
                        continue
                elif fname != "jax.jit":
                    continue
                statics = _static_argnames(dec, fi)
                self._mark_seed(fi, "jax.jit decorator", statics)
            else:
                fname = resolve_dotted(mod, _dotted(dec))
                if fname == "jax.jit":
                    self._mark_seed(fi, "jax.jit decorator")

    def _seed_calls(self, mod: ModuleInfo, fi: Optional[FuncInfo],
                    scope_node: ast.AST, bindings: dict) -> None:
        """Walk one scope (not into nested defs) seeding wrapper calls."""
        for node in _walk_scope(scope_node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                resolved = self.resolve_callable(mod, fi, node.value,
                                                 bindings)
                if resolved is not None:
                    bindings[node.targets[0].id] = resolved
            if not isinstance(node, ast.Call):
                continue
            fname = resolve_dotted(mod, _dotted(node.func))
            positions = _TRACED_ARG_POS.get(fname or "")
            if positions is None:
                continue
            statics = _static_argnames(node, None)
            for pos in positions:
                if pos >= len(node.args):
                    continue
                resolved = self.resolve_callable(mod, fi, node.args[pos],
                                                 bindings)
                if resolved is None:
                    continue
                target, bound_statics = resolved
                own = _static_argnames(node, target) if fname == "jax.jit" \
                    else statics
                self._mark_seed(target, f"{fname} call site",
                                bound_statics | own)
        # Recurse into nested function scopes with a copy of the bindings
        for child in _nested_defs(scope_node):
            child_fi = mod.by_node.get(id(child))
            self._seed_calls(mod, child_fi, child, dict(bindings))

    # -- fixpoint -----------------------------------------------------------
    def run(self) -> List[Finding]:
        self.seed()
        for _ in range(8):
            self._changed = False
            for mod in self.modules.values():
                for fi in mod.all_funcs:
                    if fi.traced:
                        _FunctionWalker(self, fi, emit=False).walk()
            if not self._changed:
                break
        for mod in self.modules.values():
            for fi in mod.all_funcs:
                if fi.traced:
                    _FunctionWalker(self, fi, emit=True).walk()
                else:
                    _LoopJitScan(self, fi).walk()
        return self.findings

    # -- taint propagation into callees --------------------------------------
    def propagate_call(self, callee: FuncInfo, node: ast.Call,
                       arg_taints: List[bool],
                       kw_taints: Dict[str, bool]) -> None:
        if not callee.traced:
            callee.traced = True
            self._changed = True
        pos = callee.positional_params
        skip = 1 if (callee.cls_name and pos[:1] == ["self"] and
                     isinstance(node.func, ast.Attribute)) else 0
        for i, taint in enumerate(arg_taints):
            idx = i + skip
            if idx < len(pos):
                self._taint_param(callee, pos[idx], taint)
            elif callee.node.args.vararg:
                self._taint_param(callee, callee.node.args.vararg.arg, taint)
        for name, taint in kw_taints.items():
            if name in callee.all_params:
                self._taint_param(callee, name, taint)

    def _taint_param(self, fi: FuncInfo, name: str, taint: bool) -> None:
        if taint and not fi.param_taint.get(name):
            fi.param_taint[name] = True
            self._changed = True

    def report(self, fi: FuncInfo, node: ast.AST, rule: str, message: str,
               hint: str = "") -> None:
        line = getattr(node, "lineno", 1)
        if fi.module.line_has_ignore(line, rule):
            return
        f = Finding(
            rule=rule, file=relpath(fi.module.path, self.root), line=line,
            symbol=fi.qualname, message=message, hint=hint)
        if f not in self.findings:
            self.findings.append(f)


def _static_argnames(call: ast.Call, fi: Optional[FuncInfo]) -> Set[str]:
    statics: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            statics |= _const_strs(kw.value)
        elif kw.arg == "static_argnums" and fi is not None:
            pos = fi.positional_params
            for idx in _const_ints(kw.value):
                if 0 <= idx < len(pos):
                    statics.add(pos[idx])
    return statics


def _const_strs(node: ast.AST) -> Set[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        out = set()
        for e in node.elts:
            out |= _const_strs(e)
        return out
    return set()


def _const_ints(node: ast.AST) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            out.extend(_const_ints(e))
        return out
    return []


def _walk_scope(node: ast.AST):
    """Yield nodes of one function/module scope in document order,
    not entering nested defs (binding-before-use matters for the
    ``fn = partial(...); jax.jit(fn)`` idiom)."""
    for n in ast.iter_child_nodes(node):
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda, ast.ClassDef)):
            continue
        yield from _walk_scope(n)


def _nested_defs(node: ast.AST):
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            yield n
            continue
        if isinstance(n, ast.ClassDef):
            continue
        stack.extend(ast.iter_child_nodes(n))


def _python_files(paths: Sequence[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
            continue
        for base, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs
                       if d not in ("__pycache__", ".git", ".tmp")]
            out.extend(os.path.join(base, f) for f in sorted(files)
                       if f.endswith(".py"))
    return out


# ---------------------------------------------------------------------------
# Per-function taint walk
# ---------------------------------------------------------------------------

class _LoopJitScan:
    """PC-T007 only, for host-side (untraced) functions."""

    def __init__(self, engine: TraceLint, fi: FuncInfo):
        self.engine = engine
        self.fi = fi

    def walk(self) -> None:
        mod = self.fi.module
        for node in _walk_scope(self.fi.node):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, (ast.FunctionDef, ast.Lambda)):
                    continue
                if isinstance(sub, ast.Call) and resolve_dotted(
                        mod, _dotted(sub.func)) == "jax.jit":
                    self.engine.report(
                        self.fi, sub, "PC-T007",
                        "jax.jit constructed inside a loop body builds a "
                        "fresh executable (and cache entry) per iteration",
                        hint="hoist the jit (or an lru_cached builder) out "
                             "of the loop")


class _FunctionWalker:
    def __init__(self, engine: TraceLint, fi: FuncInfo, emit: bool):
        self.engine = engine
        self.fi = fi
        self.mod = fi.module
        self.emit = emit
        self.loop_depth = 0
        self.env: Dict[str, bool] = {}
        for name in fi.all_params:
            self.env[name] = bool(fi.param_taint.get(name))
        if fi.is_seed:
            for name, t in fi.seed_taint().items():
                if t:
                    self.env[name] = True
        for name in fi.closure_taint:
            self.env.setdefault(name, True)

    # -- driver -------------------------------------------------------------
    def walk(self) -> None:
        node = self.fi.node
        if isinstance(node, ast.Lambda):
            self.ev(node.body)
            return
        self.block(node.body)

    def block(self, stmts) -> None:
        for s in stmts:
            self.stmt(s)

    # -- statements ---------------------------------------------------------
    def stmt(self, s: ast.stmt) -> None:
        if isinstance(s, ast.Assign):
            taint = self.ev(s.value)
            for t in s.targets:
                self.assign(t, taint, s.value)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                self.assign(s.target, self.ev(s.value), s.value)
        elif isinstance(s, ast.AugAssign):
            taint = self.ev(s.value)
            if isinstance(s.target, ast.Name):
                self.env[s.target.id] = self.env.get(s.target.id,
                                                     False) or taint
        elif isinstance(s, ast.Expr):
            self.ev(s.value)
        elif isinstance(s, ast.Return):
            if s.value is not None:
                self.ev(s.value)
        elif isinstance(s, (ast.If, ast.While)):
            if self.ev(s.test):
                self.flag_branch(s)
            if isinstance(s, ast.While):
                self.loop_depth += 1
            self.block(s.body)
            self.block(s.orelse)
            if isinstance(s, ast.While):
                self.loop_depth -= 1
        elif isinstance(s, ast.For):
            self.assign(s.target, self.ev(s.iter), None)
            self.loop_depth += 1
            self.block(s.body)
            self.block(s.orelse)
            self.loop_depth -= 1
        elif isinstance(s, ast.Assert):
            if self.ev(s.test):
                self.flag_branch(s)
            if s.msg is not None:
                self.ev(s.msg)
        elif isinstance(s, ast.With):
            for item in s.items:
                taint = self.ev(item.context_expr)
                if item.optional_vars is not None:
                    self.assign(item.optional_vars, taint, None)
            self.block(s.body)
        elif isinstance(s, ast.Try):
            self.block(s.body)
            for h in s.handlers:
                self.block(h.body)
            self.block(s.orelse)
            self.block(s.finalbody)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
            nested = self.mod.by_node.get(id(s.node if False else s))
            if nested is not None:
                snap = {n for n, t in self.env.items() if t}
                if not snap <= nested.closure_taint:
                    nested.closure_taint |= snap
                    self.engine._changed = True
        elif isinstance(s, ast.Raise):
            if s.exc is not None:
                self.ev(s.exc)
        elif isinstance(s, ast.Delete):
            pass
        # Pass/Break/Continue/Import/Global/Nonlocal: nothing to do.

    def flag_branch(self, node: ast.stmt) -> None:
        if not self.emit:
            return
        kind = {ast.If: "if", ast.While: "while",
                ast.Assert: "assert"}.get(type(node), "branch")
        self.engine.report(
            self.fi, node, "PC-T003",
            f"Python `{kind}` on a traced value concretizes it at trace "
            "time (ConcretizationTypeError under jit, host sync otherwise)",
            hint="use jnp.where / lax.cond, or hoist the decision to a "
                 "static (keyword-only) argument")

    def assign(self, target: ast.AST, taint: bool,
               value: Optional[ast.AST]) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = taint
        elif isinstance(target, (ast.Tuple, ast.List)):
            if isinstance(value, (ast.Tuple, ast.List)) and \
                    len(value.elts) == len(target.elts):
                for t, v in zip(target.elts, value.elts):
                    self.assign(t, self.ev(v), v)
            else:
                for t in target.elts:
                    self.assign(t, taint, None)
        elif isinstance(target, ast.Starred):
            self.assign(target.value, taint, None)
        # Attribute / Subscript stores don't enter the name environment.

    # -- expressions ---------------------------------------------------------
    def ev(self, node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return self.env.get(node.id, False)
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Attribute):
            base = self.ev(node.value)
            if node.attr in _STATIC_ATTRS:
                return False
            return base
        if isinstance(node, ast.Subscript):
            return self.ev(node.value) or self.ev(node.slice)
        if isinstance(node, ast.Slice):
            return (self.ev(node.lower) or self.ev(node.upper)
                    or self.ev(node.step))
        if isinstance(node, ast.BinOp):
            return self.ev(node.left) or self.ev(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.ev(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.ev(v) for v in node.values)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
            # `"key" in pytree` tests trace-time dict structure, not data
            if all(isinstance(op, (ast.In, ast.NotIn)) for op in node.ops) \
                    and isinstance(node.left, ast.Constant) and \
                    isinstance(node.left.value, str):
                return False
            return self.ev(node.left) or any(self.ev(c)
                                             for c in node.comparators)
        if isinstance(node, ast.IfExp):
            if self.ev(node.test) and self.emit:
                self.engine.report(
                    self.fi, node, "PC-T003",
                    "ternary on a traced value concretizes it at trace time",
                    hint="use jnp.where")
            return self.ev(node.body) or self.ev(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.ev(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.ev(v) for v in list(node.keys) +
                       list(node.values) if v is not None)
        if isinstance(node, ast.Starred):
            return self.ev(node.value)
        if isinstance(node, ast.Lambda):
            return False
        if isinstance(node, ast.NamedExpr):
            taint = self.ev(node.value)
            self.assign(node.target, taint, node.value)
            return taint
        if isinstance(node, ast.Call):
            return self.call(node)
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            for gen in node.generators:
                self.assign(gen.target, self.ev(gen.iter), None)
            if isinstance(node, ast.DictComp):
                return self.ev(node.key) or self.ev(node.value)
            return self.ev(node.elt)
        if isinstance(node, ast.JoinedStr):
            return False
        if isinstance(node, ast.Await):
            return self.ev(node.value)
        return False

    # -- calls ---------------------------------------------------------------
    def call(self, node: ast.Call) -> bool:
        arg_taints = [self.ev(a.value if isinstance(a, ast.Starred) else a)
                      for a in node.args]
        kw_taints = {kw.arg: self.ev(kw.value) for kw in node.keywords
                     if kw.arg}
        for kw in node.keywords:
            if kw.arg is None:
                self.ev(kw.value)
        any_taint = any(arg_taints) or any(kw_taints.values())
        fname = resolve_dotted(self.mod, _dotted(node.func)) or ""

        # `.at[traced_idx].set(...)` scatter -- checked before the generic
        # attribute-method handling below.
        if isinstance(node.func, ast.Attribute) and \
                isinstance(node.func.value, ast.Subscript) and \
                isinstance(node.func.value.value, ast.Attribute) and \
                node.func.value.value.attr == "at":
            idx_taint = self.ev(node.func.value.slice)
            recv = self.ev(node.func.value.value.value)
            if idx_taint and self.emit:
                self.engine.report(
                    self.fi, node, "PC-T006",
                    "scatter with a traced index inside traced code "
                    "(XLA CPU scatter is pathologically slow)",
                    hint="restructure as a dense select (jnp.where over "
                         "an arange mask) or move it off the hot path")
            return recv or any_taint

        if isinstance(node.func, ast.Attribute):
            recv_taint = self.ev(node.func.value)
            if node.func.attr in _SYNC_METHODS and recv_taint:
                if self.emit:
                    self.engine.report(
                        self.fi, node, "PC-T001",
                        f".{node.func.attr}() on a traced value forces a "
                        "host sync inside traced code",
                        hint="keep the value on device; reduce with jnp "
                             "and transfer once outside the jit boundary")
                return False
            if node.func.attr == "astype" and recv_taint and \
                    self._is_f64(node.args[0] if node.args else None):
                if self.emit:
                    self._report_f64(node)
                return True

        if fname in _CAST_FUNCS:
            if any_taint:
                if self.emit:
                    self.engine.report(
                        self.fi, node, "PC-T002",
                        f"{fname}() on a traced value concretizes it "
                        "(host round trip; breaks under jit)",
                        hint="keep it as a jnp scalar, or make the "
                             "operand a static (keyword-only) argument")
                return False
            return False
        if fname in _COERCE_FUNCS:
            if any_taint and self.emit:
                self.engine.report(
                    self.fi, node, "PC-T002",
                    f"builtin {fname}() iterates/concretizes a traced "
                    "value on the host",
                    hint=f"use the jnp.{fname} reduction instead")
            return any_taint
        if fname in _STATIC_FUNCS:
            return False
        if fname == "getattr":
            return arg_taints[0] if arg_taints else False

        if fname.startswith("numpy."):
            base = fname.rpartition(".")[2]
            if any_taint:
                if base in _F64_NAMES or fname in _F64_NAMES:
                    if self.emit:
                        self._report_f64(node)
                elif self.emit:
                    self.engine.report(
                        self.fi, node, "PC-T004",
                        f"np.{base}() on a traced value silently syncs "
                        "and computes on host",
                        hint=f"use jnp.{base} (or hoist the numpy work "
                             "outside the traced function)")
                return False
            return False

        if fname.startswith("jax.numpy."):
            base = fname.rpartition(".")[2]
            if base == "float64" and any_taint:
                if self.emit:
                    self._report_f64(node)
                return True
            if base in _SORT_FAMILY and any_taint:
                if self.emit:
                    self.engine.report(
                        self.fi, node, "PC-T006",
                        f"jnp.{base} inside traced code (sort-family ops "
                        "are 10-40x slower than streaming reductions on "
                        "XLA CPU)",
                        hint="stream the statistic through the scan carry "
                             "(see lab.score's fixed-bin quantile)")
            if self._f64_dtype_arg(node):
                if self.emit:
                    self._report_f64(node)
                return True
            return any_taint

        if fname == "jax.lax.sort" and any_taint:
            if self.emit:
                self.engine.report(
                    self.fi, node, "PC-T006",
                    "lax.sort inside traced code", hint="stream instead")
            return True

        if fname == "jax.jit" and self.loop_depth > 0:
            if self.emit:
                self.engine.report(
                    self.fi, node, "PC-T007",
                    "jax.jit constructed inside a loop body builds a fresh "
                    "executable per iteration",
                    hint="hoist the jit out of the loop")

        resolved = self.engine.resolve_callable(self.mod, self.fi, node.func)
        if resolved is not None:
            callee, _ = resolved
            if callee is not self.fi:
                self.engine.propagate_call(callee, node, arg_taints,
                                           kw_taints)
        return any_taint

    def _is_f64(self, node: Optional[ast.AST]) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Constant):
            return node.value in ("float64", "double")
        return (resolve_dotted(self.mod, _dotted(node)) or "") in _F64_NAMES

    def _f64_dtype_arg(self, node: ast.Call) -> bool:
        for kw in node.keywords:
            if kw.arg == "dtype" and self._is_f64(kw.value):
                return True
        return any(self._is_f64(a) for a in node.args[1:])

    def _report_f64(self, node: ast.AST) -> None:
        self.engine.report(
            self.fi, node, "PC-T005",
            "float64 promotion in traced code (the streaming accumulators "
            "are float32 + Kahan compensation by design)",
            hint="stay in float32 and compensate (lab.score.kahan_add), "
                 "or cast outside the traced region")


def analyze_traced(paths: Sequence[str],
                   root: Optional[str] = None) -> List[Finding]:
    """Run TraceLint over ``paths``; returns findings."""
    return TraceLint(paths, root=root).run()
