"""Shared AST helpers for firstlint rules.

Rules resolve names *canonically* (``np.asarray`` -> ``numpy.asarray``,
``jit`` imported from jax -> ``jax.jit``) via :class:`ImportMap`, and the
two hot-path rules share :class:`JitRegistry` — the per-module inventory
of which local functions are jitted (and with which ``donate_argnums``),
whether via decorator, ``jax.jit(f, ...)`` assignment, or a
``partial(...)`` wrapper (also inside a naming wrapper
``jax.jit(g("name", partial(f, ...)))``).
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Iterator


def dotted(node: ast.AST) -> str | None:
    """Render a Name/Attribute chain as ``a.b.c`` (None for anything else)."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class ImportMap:
    """alias -> canonical dotted module/object path for one module."""

    def __init__(self, tree: ast.Module):
        self.aliases: dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom) and node.module:
                for a in node.names:
                    self.aliases[a.asname or a.name] = \
                        f"{node.module}.{a.name}"

    def resolve(self, name: str | None) -> str | None:
        """Canonicalize a dotted name through the module's import aliases."""
        if name is None:
            return None
        head, _, rest = name.partition(".")
        base = self.aliases.get(head, head)
        return f"{base}.{rest}" if rest else base


def resolves_to(imports: ImportMap, node: ast.AST, *targets: str) -> bool:
    got = imports.resolve(dotted(node))
    return got is not None and got in targets


def is_self_attr(node: ast.AST, name: str | None = None) -> bool:
    return (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name) and node.value.id == "self"
            and (name is None or node.attr == name))


def call_key(func: ast.AST) -> str | None:
    """Bare key a call target is registered under: ``f(...)`` -> "f",
    ``self.f(...)`` / ``self.f[k](...)`` -> "f". None when unresolvable."""
    if isinstance(func, ast.Subscript):
        func = func.value
    if isinstance(func, ast.Name):
        return func.id
    if is_self_attr(func):
        return func.attr
    return None


def literal_argnums(node: ast.AST | None) -> frozenset[int] | None:
    """Evaluate a ``donate_argnums``-style literal; None if not static."""
    if node is None:
        return frozenset()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for el in node.elts:
            if not (isinstance(el, ast.Constant)
                    and isinstance(el.value, int)):
                return None
            out.add(el.value)
        return frozenset(out)
    return None


@dataclass
class JitTarget:
    """One jitted callable registered in a module."""
    key: str                     # name it is callable under ("_fused", "fn")
    func_name: str | None        # local function the jit wraps (if resolved)
    lambda_node: ast.Lambda | None
    donated: frozenset[int] | None   # None = donates, positions unknown
    node: ast.AST                # registration site (for diagnostics)


def _is_naming_call(node: ast.Call) -> bool:
    """``g("name", f)``: a wrapper that names the program jitted from f."""
    return (len(node.args) == 2 and not node.keywords
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str))


def _unwrap_partial(imports: ImportMap, node: ast.AST) -> ast.AST:
    """partial(f, ...) / functools.partial(f, ...) -> f, and a naming
    wrapper ``g("name", f)`` -> f (recursively)."""
    while isinstance(node, ast.Call) and node.args:
        if resolves_to(imports, node.func, "functools.partial"):
            node = node.args[0]
        elif _is_naming_call(node):
            node = node.args[1]
        else:
            break
    return node


def _jit_call_parts(imports: ImportMap, call: ast.Call):
    """For a ``jax.jit(target, ...)`` call, return (target_expr, donated)."""
    if not resolves_to(imports, call.func, "jax.jit"):
        return None
    donate = None
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            donate = kw.value
    donated = literal_argnums(donate)
    target = _unwrap_partial(imports, call.args[0]) if call.args else None
    return target, donated


class JitRegistry:
    """Per-module inventory of jitted callables.

    ``targets``: every registration found.  ``by_key``: callable key ->
    list of registrations (a dict-of-jits like ``self._fused[K]`` collects
    one per branch).  ``root_funcs``: names of local functions whose bodies
    execute under jit (the seed set for hot-path reachability).
    ``root_lambdas``: jitted inline lambdas.
    """

    def __init__(self, tree: ast.Module, imports: ImportMap):
        self.targets: list[JitTarget] = []
        self.by_key: dict[str, list[JitTarget]] = {}
        self.root_funcs: set[str] = set()
        self.root_lambdas: list[ast.Lambda] = []
        self._collect(tree, imports)

    def _add(self, t: JitTarget) -> None:
        self.targets.append(t)
        self.by_key.setdefault(t.key, []).append(t)
        if t.func_name:
            self.root_funcs.add(t.func_name)
        if t.lambda_node is not None:
            self.root_lambdas.append(t.lambda_node)

    def _collect(self, tree: ast.Module, imports: ImportMap) -> None:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    donated: frozenset[int] | None = frozenset()
                    if resolves_to(imports, dec, "jax.jit"):
                        pass
                    elif isinstance(dec, ast.Call):
                        if resolves_to(imports, dec.func, "jax.jit"):
                            donated = literal_argnums(next(
                                (kw.value for kw in dec.keywords
                                 if kw.arg == "donate_argnums"), None))
                        elif (resolves_to(imports, dec.func,
                                          "functools.partial")
                              and dec.args
                              and resolves_to(imports, dec.args[0],
                                              "jax.jit")):
                            donated = literal_argnums(next(
                                (kw.value for kw in dec.keywords
                                 if kw.arg == "donate_argnums"), None))
                        else:
                            continue
                    else:
                        continue
                    self._add(JitTarget(key=node.name, func_name=node.name,
                                        lambda_node=None, donated=donated,
                                        node=node))
                    break
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call):
                parts = _jit_call_parts(imports, node.value)
                if parts is None:
                    continue
                target, donated = parts
                for tgt in node.targets:
                    key = call_key(tgt)
                    if key is None:
                        continue
                    fn, lam = None, None
                    if isinstance(target, ast.Lambda):
                        lam = target
                    else:
                        fn = call_key(target) if not isinstance(
                            target, ast.Call) else None
                    self._add(JitTarget(key=key, func_name=fn,
                                        lambda_node=lam, donated=donated,
                                        node=node))

    def donated_at(self, key: str) -> frozenset[int] | None:
        """Argument positions donated for calls through ``key``.

        When several registrations share a key (per-K jit dicts), only the
        positions donated under EVERY registration are reported — a
        position donated on one branch but live on another cannot be
        checked statically without knowing which branch the call hits.
        Returns None when the key is unregistered or any registration has
        non-literal donate_argnums.
        """
        regs = self.by_key.get(key)
        if not regs:
            return None
        out: frozenset[int] | None = None
        for r in regs:
            if r.donated is None:
                return None
            out = r.donated if out is None else (out & r.donated)
        return out


def collect_functions(tree: ast.Module) -> dict[str, list[ast.FunctionDef]]:
    """Every (possibly nested) function/method in the module, by bare name."""
    out: dict[str, list[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


def called_keys(fn: ast.AST) -> Iterator[str]:
    """Bare keys of every call inside ``fn`` (names and self.X methods)."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            key = call_key(node.func)
            if key is not None:
                yield key


@dataclass
class HotSet:
    """Transitive closure of functions reachable from the module's jit
    roots through same-module calls (by bare name — conservative, but
    cross-module calls are out of scope for a per-module pass)."""
    funcs: dict[str, list[ast.FunctionDef]] = field(default_factory=dict)
    lambdas: list[ast.Lambda] = field(default_factory=list)

    def subtrees(self) -> Iterator[tuple[str, ast.AST]]:
        for name, defs in self.funcs.items():
            for d in defs:
                yield name, d
        for lam in self.lambdas:
            yield "<lambda>", lam


def hot_set(tree: ast.Module, imports: ImportMap,
            registry: JitRegistry | None = None) -> HotSet:
    registry = registry or JitRegistry(tree, imports)
    table = collect_functions(tree)
    hot = HotSet(lambdas=list(registry.root_lambdas))
    frontier = [n for n in registry.root_funcs if n in table]
    for lam in registry.root_lambdas:
        frontier.extend(k for k in called_keys(lam) if k in table)
    while frontier:
        name = frontier.pop()
        if name in hot.funcs:
            continue
        hot.funcs[name] = table[name]
        for d in table[name]:
            for key in called_keys(d):
                if key in table and key not in hot.funcs:
                    frontier.append(key)
    return hot
