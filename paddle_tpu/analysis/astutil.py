"""Shared AST helpers for the jaxlint rules (stdlib-only)."""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit", "_jax.jit"}
PARTIAL_NAMES = {"partial", "functools.partial"}


def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def attr_segments(node: ast.AST) -> List[str]:
    """All segments of an attribute chain, root first; [] if not a chain."""
    d = dotted(node)
    return d.split(".") if d else []


def last_attr(node: ast.Call) -> Optional[str]:
    """Final attribute name of the call target ('item' for x.y.item())."""
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    if isinstance(node.func, ast.Name):
        return node.func.id
    return None


def walk_function_body(fn: ast.AST,
                       into_nested: bool = True) -> Iterable[ast.AST]:
    """Walk a function body; optionally stop at nested function defs."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if not into_nested and isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def function_defs(tree: ast.AST) -> Dict[str, List[ast.FunctionDef]]:
    """Every function def in the module keyed by bare name."""
    out: Dict[str, List[ast.FunctionDef]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out.setdefault(node.name, []).append(node)
    return out


def partial_aliases(tree: ast.AST) -> Dict[str, Set[str]]:
    """`x = functools.partial(f, ...)` assignments anywhere: x -> {'f'}.

    A SET of targets per name: different functions commonly reuse one
    local alias (`kernel = partial(_gmm_kernel, ...)` in one builder,
    `kernel = partial(_tgmm_kernel, ...)` in another) and a last-wins
    dict would silently drop all but one kernel from analysis."""
    out: Dict[str, Set[str]] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            call = node.value
            if dotted(call.func) in PARTIAL_NAMES and call.args and \
                    isinstance(call.args[0], ast.Name):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        out.setdefault(tgt.id, set()).add(call.args[0].id)
    return out


def kernel_functions(tree: ast.AST) -> Set[ast.FunctionDef]:
    """Function defs that are Pallas kernel bodies: passed (directly, via
    a ``functools.partial`` alias, or as an inline partial) as the first
    argument of a ``pallas_call``, plus the module-level helpers those
    bodies call."""
    defs = function_defs(tree)
    aliases = partial_aliases(tree)
    kernels: Set[ast.FunctionDef] = set()

    def resolve(name: str) -> None:
        for target in aliases.get(name, {name}):
            for fn in defs.get(target, ()):
                kernels.add(fn)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d is None or d.split(".")[-1] != "pallas_call" or not node.args:
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Name):
            resolve(arg.id)
        elif isinstance(arg, ast.Call) and \
                dotted(arg.func) in PARTIAL_NAMES and arg.args and \
                isinstance(arg.args[0], ast.Name):
            resolve(arg.args[0].id)

    # module-level helpers a kernel body calls by bare name are kernel
    # code too (grouped_matmul's _gather_rows carried python-int
    # fori_loop bounds past JL001 that way)
    top = {n.name: n for n in getattr(tree, "body", ())
           if isinstance(n, ast.FunctionDef)}
    work = list(kernels)
    while work:
        for node in ast.walk(work.pop()):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Name):
                helper = top.get(node.func.id)
                if helper is not None and helper not in kernels:
                    kernels.add(helper)
                    work.append(helper)
    return kernels


def _jit_call_static_params(call: ast.Call,
                            fn: Optional[ast.FunctionDef]) -> Set[str]:
    """Static parameter names from static_argnums/static_argnames."""
    static: Set[str] = set()
    pos_names: List[str] = []
    if fn is not None:
        a = fn.args
        pos_names = [p.arg for p in a.posonlyargs + a.args]
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    static.add(n.value)
        elif kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int) \
                        and 0 <= n.value < len(pos_names):
                    static.add(pos_names[n.value])
    return static


def jitted_functions(tree: ast.AST) -> Dict[ast.FunctionDef, Set[str]]:
    """Function defs wrapped by jax.jit (decorator or call site), mapped
    to the set of their parameter names marked static."""
    defs = function_defs(tree)
    out: Dict[ast.FunctionDef, Set[str]] = {}

    def jit_call_of(call: ast.Call) -> bool:
        d = dotted(call.func)
        if d in JIT_NAMES:
            return True
        # partial(jax.jit, ...) used as a decorator factory
        if d in PARTIAL_NAMES and call.args and \
                dotted(call.args[0]) in JIT_NAMES:
            return True
        return False

    # decorator form
    for name, fns in defs.items():
        for fn in fns:
            for dec in fn.decorator_list:
                if (isinstance(dec, (ast.Name, ast.Attribute))
                        and dotted(dec) in JIT_NAMES):
                    out.setdefault(fn, set())
                elif isinstance(dec, ast.Call) and jit_call_of(dec):
                    out.setdefault(fn, set()).update(
                        _jit_call_static_params(dec, fn))

    # call-site form: jax.jit(fn_name, ...)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and dotted(node.func) in JIT_NAMES \
                and node.args and isinstance(node.args[0], ast.Name):
            for fn in defs.get(node.args[0].id, ()):
                out.setdefault(fn, set()).update(
                    _jit_call_static_params(node, fn))
    return out


def int_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant) and type(node.value) is int:
        return True
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, (ast.USub, ast.UAdd)):
        return int_literal(node.operand)
    return False


def literal_only(node: ast.AST) -> bool:
    """Constant, or a tuple/list of constants (incl. unary +-)."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp) and \
            isinstance(node.op, (ast.USub, ast.UAdd)):
        return literal_only(node.operand)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(literal_only(e) for e in node.elts)
    return False
