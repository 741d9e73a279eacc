"""Shared AST machinery for the repro.lint rules.

Everything here is stdlib-only (``ast``): the lint pass must run in a bare
CI job without jax installed.  The central object is :class:`ModuleCtx` — one
parsed module with

  - an import map (alias → fully-qualified dotted name), so rules match
    resolved names (``PL.delta_tree`` → ``repro.fedsim.pipeline.delta_tree``)
    instead of guessing at aliases,
  - a function table with parent links (nested defs included),
  - the *traced set*: functions that execute under a jax trace — seeded by
    ``@jax.jit``-style decorators and by being passed to trace-inducing
    callables (``jax.lax.scan``, ``vmap``, ``shard_map``, ``pl.pallas_call``,
    …), then closed over same-module nested defs and callees,
  - a per-function taint analysis classifying names as ``traced`` (derived
    from traced arguments / jnp ops) or ``static`` (shapes, dtypes, Python
    config), with call-site propagation so a helper that only ever receives
    static block sizes is not blamed for branching on them.

Scope note: discovery is per-module by design.  A function handed across
module boundaries (e.g. a model method passed to ``value_and_grad`` in
another file) is analyzed where its *call sites* live, not here — the
baseline workflow absorbs the difference.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Any, Iterator

# Callables whose *decorated/first-arg* function runs under trace.
TRACE_WRAPPERS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat",
}
# Callables whose function-valued *arguments* run under trace.
TRACE_CONSUMERS = TRACE_WRAPPERS | {
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.shard_map", "jax.experimental.shard_map.shard_map",
    "jax.experimental.pallas.pallas_call",
}
PARTIAL_NAMES = {"functools.partial", "partial"}

# Attribute reads that break value taint: shape arithmetic is trace-static.
STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "sharding"}
# Builtins whose result is host/static for *branching* purposes (misusing
# them on traced values is RL2's job, not a taint question).
STATIC_CALLS = {"len", "int", "float", "bool", "str", "isinstance", "range",
                "getattr", "hasattr", "type", "min", "max", "abs", "round"}


def dotted_name(node: ast.AST) -> str | None:
    """``a.b.c`` attribute chain → "a.b.c"; anything else → None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def target_names(target: ast.AST) -> list[str]:
    """Flat list of plain names bound by an assignment target."""
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out = []
        for el in target.elts:
            out.extend(target_names(el))
        return out
    if isinstance(target, ast.Starred):
        return target_names(target.value)
    return []


def names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


@dataclasses.dataclass(eq=False)
class FuncInfo:
    node: ast.AST                      # FunctionDef | AsyncFunctionDef
    name: str
    qualpath: str                      # outer.inner dotted path
    parent: "FuncInfo | None"
    traced: bool = False
    traced_why: str = ""               # "decorator" | "callsite" | "nested" ...
    static_params: set[str] = dataclasses.field(default_factory=set)
    # param name -> "traced" | "static"; filled by taint propagation
    param_kinds: dict[str, str] = dataclasses.field(default_factory=dict)
    env: dict[str, str] | None = None  # name -> kind after taint fixpoint


class ModuleCtx:
    def __init__(self, path: str, source: str):
        self.path = path
        self.source = source
        self.tree = ast.parse(source, filename=path)
        self.lines = source.splitlines()
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                child._lint_parent = parent  # type: ignore[attr-defined]
        self.imports = self._collect_imports()
        self.functions = self._collect_functions()
        self._by_node = {f.node: f for f in self.functions}
        self._discover_traced()
        self._propagate_taint()

    # ---- imports -----------------------------------------------------------

    def _collect_imports(self) -> dict[str, str]:
        imp: dict[str, str] = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    imp[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom):
                mod = ("." * node.level) + (node.module or "")
                for a in node.names:
                    imp[a.asname or a.name] = f"{mod}.{a.name}" if mod \
                        else a.name
        return imp

    @property
    def uses_jax(self) -> bool:
        return any(q == "jax" or q.startswith("jax.")
                   for q in self.imports.values())

    @property
    def uses_pallas(self) -> bool:
        return any("pallas" in q for q in self.imports.values())

    def qual(self, node: ast.AST) -> str | None:
        """Resolved dotted name of an expression (imports applied)."""
        d = dotted_name(node)
        if d is None:
            return None
        head, _, rest = d.partition(".")
        head = self.imports.get(head, head)
        return f"{head}.{rest}" if rest else head

    def call_qual(self, call: ast.Call) -> str | None:
        return self.qual(call.func)

    def unwrap_partial(self, node: ast.AST) -> ast.AST:
        """functools.partial(f, ...) → f (one level)."""
        if isinstance(node, ast.Call) \
                and self.qual(node.func) in PARTIAL_NAMES and node.args:
            return node.args[0]
        return node

    # ---- function table ----------------------------------------------------

    def _collect_functions(self) -> list[FuncInfo]:
        out: list[FuncInfo] = []

        def walk(node: ast.AST, parent: FuncInfo | None, prefix: str):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qp = f"{prefix}.{child.name}" if prefix else child.name
                    fi = FuncInfo(child, child.name, qp, parent)
                    out.append(fi)
                    walk(child, fi, qp)
                else:
                    walk(child, parent, prefix)

        walk(self.tree, None, "")
        return out

    def func_of(self, node: ast.AST) -> FuncInfo | None:
        """Innermost enclosing function of a node."""
        cur = getattr(node, "_lint_parent", None)
        while cur is not None:
            if cur in self._by_node:
                return self._by_node[cur]
            cur = getattr(cur, "_lint_parent", None)
        return None

    def enclosing_loop(self, node: ast.AST, within: ast.AST | None = None
                       ) -> ast.AST | None:
        """Innermost For/While statement around node (stopping at a def)."""
        cur = getattr(node, "_lint_parent", None)
        while cur is not None and cur is not within:
            if isinstance(cur, (ast.For, ast.While)):
                return cur
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return None
            cur = getattr(cur, "_lint_parent", None)
        return None

    def calls(self, root: ast.AST | None = None) -> Iterator[ast.Call]:
        for node in ast.walk(root if root is not None else self.tree):
            if isinstance(node, ast.Call):
                yield node

    # ---- traced discovery --------------------------------------------------

    def _lookup_local_fn(self, name: str, near: ast.AST) -> FuncInfo | None:
        """A function def visible from ``near``: same scope chain first,
        else any module function with that name."""
        scope = self.func_of(near)
        while scope is not None:
            for f in self.functions:
                if f.name == name and f.parent is scope:
                    return f
            scope = scope.parent
        for f in self.functions:
            if f.name == name and f.parent is None:
                return f
        for f in self.functions:
            if f.name == name:
                return f
        return None

    def _static_from_jit_kwargs(self, call: ast.Call, fn: FuncInfo) -> None:
        args = fn.node.args
        pos = [a.arg for a in args.posonlyargs + args.args]
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for s in ast.walk(kw.value):
                    if isinstance(s, ast.Constant) and isinstance(s.value,
                                                                  str):
                        fn.static_params.add(s.value)
            elif kw.arg == "static_argnums":
                for s in ast.walk(kw.value):
                    if isinstance(s, ast.Constant) and isinstance(s.value,
                                                                  int):
                        if 0 <= s.value < len(pos):
                            fn.static_params.add(pos[s.value])

    def _discover_traced(self) -> None:
        # seeds: decorators
        for f in self.functions:
            for dec in f.node.decorator_list:
                base = dec.func if isinstance(dec, ast.Call) else dec
                inner = self.unwrap_partial(base) if isinstance(base, ast.Call)\
                    else base
                q = self.qual(inner) or self.qual(base)
                if isinstance(dec, ast.Call) \
                        and self.qual(dec.func) in PARTIAL_NAMES and dec.args:
                    q = self.qual(dec.args[0])
                    if q in TRACE_WRAPPERS:
                        f.traced, f.traced_why = True, "decorator"
                        self._static_from_jit_kwargs(dec, f)
                        continue
                if q in TRACE_WRAPPERS:
                    f.traced, f.traced_why = True, "decorator"
                    if isinstance(dec, ast.Call):
                        self._static_from_jit_kwargs(dec, f)
        # seeds: call sites (jit(f), lax.scan(f, ...), pallas_call(kernel))
        for call in self.calls():
            q = self.call_qual(call)
            if q not in TRACE_CONSUMERS:
                continue
            for arg in list(call.args) + [kw.value for kw in call.keywords
                                          if kw.arg in ("body", "f", "fun",
                                                        "kernel", "cond_fun",
                                                        "body_fun")]:
                cand = self.unwrap_partial(arg)
                if isinstance(cand, ast.Name):
                    fn = self._lookup_local_fn(cand.id, call)
                    if fn is not None and not fn.traced:
                        fn.traced, fn.traced_why = True, "callsite"
                        if q == "jax.jit":
                            self._static_from_jit_kwargs(call, fn)
        # closure: nested defs + same-module callees of traced functions
        changed = True
        while changed:
            changed = False
            for f in self.functions:
                if not f.traced:
                    continue
                for g in self.functions:
                    if g.parent is f and not g.traced:
                        g.traced, g.traced_why = True, "nested"
                        changed = True
                for call in self.calls(f.node):
                    if self.func_of(call) is not f and \
                            self.func_of(call) not in self._nested_of(f):
                        continue
                    if isinstance(call.func, ast.Name):
                        fn = self._lookup_local_fn(call.func.id, call)
                        if fn is not None and not fn.traced \
                                and fn.parent is None:
                            fn.traced, fn.traced_why = True, "callee"
                            changed = True

    def _nested_of(self, f: FuncInfo) -> set[FuncInfo]:
        out, frontier = set(), [f]
        while frontier:
            cur = frontier.pop()
            for g in self.functions:
                if g.parent is cur:
                    out.add(g)
                    frontier.append(g)
        return out

    # ---- taint -------------------------------------------------------------

    def expr_kind(self, node: ast.AST, env: dict[str, str]) -> str:
        """"traced" | "static" for an expression under ``env``."""
        if isinstance(node, ast.Name):
            return env.get(node.id, "static")
        if isinstance(node, ast.Attribute):
            if node.attr in STATIC_ATTRS:
                return "static"
            return self.expr_kind(node.value, env)
        if isinstance(node, ast.Call):
            q = self.call_qual(node) or ""
            root = q.split(".")[0]
            if q in STATIC_CALLS or root in ("math", "numpy", "os",
                                             "dataclasses", "itertools"):
                return "static"
            if root in ("jax", "jnp") or q.startswith("jax."):
                # jnp resolves to jax.numpy via the import map
                return "traced"
            kinds = [self.expr_kind(a, env) for a in node.args]
            kinds += [self.expr_kind(kw.value, env) for kw in node.keywords]
            if isinstance(node.func, ast.Attribute):   # x.sum() — receiver
                kinds.append(self.expr_kind(node.func.value, env))
            return "traced" if "traced" in kinds else "static"
        if isinstance(node, ast.Subscript):
            return self.expr_kind(node.value, env)
        if isinstance(node, ast.Compare) \
                and all(isinstance(op, (ast.In, ast.NotIn))
                        for op in node.ops) \
                and isinstance(node.left, ast.Constant) \
                and isinstance(node.left.value, str):
            return "static"    # '"w3" in params' — pytree structure check
        if isinstance(node, (ast.BinOp, ast.BoolOp, ast.Compare,
                             ast.UnaryOp, ast.IfExp)):
            kinds = [self.expr_kind(c, env) for c in ast.iter_child_nodes(node)
                     if isinstance(c, ast.expr)]
            return "traced" if "traced" in kinds else "static"
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            kinds = [self.expr_kind(e, env) for e in node.elts]
            return "traced" if "traced" in kinds else "static"
        if isinstance(node, ast.Starred):
            return self.expr_kind(node.value, env)
        return "static"

    def _init_env(self, f: FuncInfo) -> dict[str, str]:
        env: dict[str, str] = {}
        if f.parent is not None and f.parent.traced \
                and f.parent.env is not None:
            env.update(f.parent.env)       # closures over a traced scope
        a = f.node.args
        for p in a.posonlyargs + a.args:
            if p.arg in f.static_params:
                env[p.arg] = "static"
            elif p.arg in f.param_kinds:
                env[p.arg] = f.param_kinds[p.arg]
            else:
                env[p.arg] = "traced"
        # keyword-only params are this repo's static-config convention
        # (kernel scaling/k_steps bound via functools.partial)
        for p in a.kwonlyargs:
            env[p.arg] = f.param_kinds.get(p.arg, "static")
        if a.vararg:
            env[a.vararg.arg] = "traced"
        return env

    def _taint_fixpoint(self, f: FuncInfo) -> dict[str, str]:
        env = self._init_env(f)
        own_body = f.node.body
        for _ in range(10):
            changed = False
            for node in ast.walk(f.node):
                inner = self.func_of(node)
                if inner is not f and node is not f.node:
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        continue
                if inner is not f:
                    continue
                tgt_val = None
                if isinstance(node, ast.Assign):
                    tgt_val = (node.targets, node.value)
                elif isinstance(node, ast.AnnAssign) and node.value:
                    tgt_val = ([node.target], node.value)
                elif isinstance(node, ast.AugAssign):
                    tgt_val = ([node.target], node.value)
                elif isinstance(node, ast.For):
                    kind = self.expr_kind(node.iter, env)
                    for n in target_names(node.target):
                        if env.get(n) != kind and kind == "traced":
                            env[n] = kind
                            changed = True
                    continue
                if tgt_val is None:
                    continue
                targets, value = tgt_val
                kind = self.expr_kind(value, env)
                for t in targets:
                    for n in target_names(t):
                        if kind == "traced" and env.get(n) != "traced":
                            env[n] = "traced"
                            changed = True
                        elif n not in env:
                            env[n] = kind
            if not changed:
                break
        del own_body
        return env

    def _propagate_taint(self) -> None:
        # pass 1: directly-seeded traced functions
        order = [f for f in self.functions if f.traced]
        for f in order:
            if f.traced_why in ("decorator", "callsite", "nested"):
                f.env = self._taint_fixpoint(f)
        # pass 2: propagated callees get param kinds from their call sites
        for _ in range(2):
            for f in order:
                if f.env is not None:
                    continue
                a = f.node.args
                pos = [p.arg for p in a.posonlyargs + a.args]
                kinds: dict[str, str] = {}
                for caller in order:
                    if caller.env is None:
                        continue
                    for call in self.calls(caller.node):
                        if not (isinstance(call.func, ast.Name)
                                and call.func.id == f.name):
                            continue
                        for i, arg in enumerate(call.args):
                            if i < len(pos):
                                k = self.expr_kind(arg, caller.env)
                                if k == "traced":
                                    kinds[pos[i]] = "traced"
                        for kw in call.keywords:
                            if kw.arg and self.expr_kind(
                                    kw.value, caller.env) == "traced":
                                kinds[kw.arg] = "traced"
                f.param_kinds = {p: kinds.get(p, "static") for p in pos}
                f.env = self._taint_fixpoint(f)

    # ---- assignment scanning (flow-ordered, for host-loop rules) -----------

    def assignments(self, f: FuncInfo) -> list[tuple[list[str], ast.AST,
                                                     ast.AST]]:
        """(bound names, rhs, stmt) for every binding inside f, source order,
        including for-targets (rhs = the iterable)."""
        out = []
        for node in ast.walk(f.node):
            if self.func_of(node) is not f:
                continue
            if isinstance(node, ast.Assign):
                names = [n for t in node.targets for n in target_names(t)]
                out.append((names, node.value, node))
            elif isinstance(node, ast.AnnAssign) and node.value:
                out.append((target_names(node.target), node.value, node))
            elif isinstance(node, ast.AugAssign):
                out.append((target_names(node.target), node.value, node))
            elif isinstance(node, ast.For):
                out.append((target_names(node.target), node.iter, node))
            elif isinstance(node, ast.withitem) and node.optional_vars:
                out.append((target_names(node.optional_vars),
                            node.context_expr, node))
        out.sort(key=lambda t: getattr(t[2], "lineno", 0))
        return out
