"""Shared AST machinery for the reprolint rules.

Everything here is plain :mod:`ast` — no jax import, no compilation — so
the rule engine stays a zero-FLOP static pass that can run in CI before
any accelerator exists.

The load-bearing abstraction is the **traced-context map**
(:func:`traced_functions`): the set of function/lambda nodes whose bodies
execute under a jax trace.  A function is traced when it is

* decorated with ``jax.jit`` / ``jit`` / ``partial(jax.jit, ...)`` (and
  the vmap/pmap/shard_map equivalents),
* passed by name as the first argument to a ``jax.jit(...)`` /
  ``jax.vmap(...)`` / ``shard_map(...)`` call anywhere in the module,
* a lambda appearing directly inside such a call, or
* lexically nested inside another traced function (tracing is
  transitive through closures).

Rules that care about *collective binding* rather than tracing use the
narrower :func:`shardmap_functions` (shard_map/pmap only) — a jitted body
does not bind axis names, a shard_mapped body does.
"""
from __future__ import annotations

import ast
from dataclasses import dataclass, field

# dotted callables that put their operand under a jax trace
JIT_WRAPPERS = {"jax.jit", "jit", "jax.pmap", "pmap"}
MAP_WRAPPERS = {"jax.vmap", "vmap", "jax.lax.map", "jax.checkpoint",
                "jax.remat", "jax.grad", "jax.value_and_grad",
                "jax.eval_shape", "jax.make_jaxpr"}
# wrappers that additionally BIND mesh axis names over their operand
AXIS_WRAPPERS = {"shard_map", "jax.shard_map", "jax.pmap", "pmap", "xmap"}
TRACE_WRAPPERS = JIT_WRAPPERS | MAP_WRAPPERS | AXIS_WRAPPERS


def parse_module(source: str, path: str = "<string>") -> ast.Module:
    """Parse ``source`` and annotate every node with ``.parent``."""
    tree = ast.parse(source, filename=path)
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child.parent = node  # type: ignore[attr-defined]
    tree.parent = None  # type: ignore[attr-defined]
    return tree


def dotted_name(node: ast.AST) -> str | None:
    """``jax.lax.psum`` from a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_target(call: ast.Call) -> str | None:
    """Dotted name of the called object, unwrapping ``partial(f, ...)``."""
    name = dotted_name(call.func)
    if name in ("functools.partial", "partial") and call.args:
        inner = dotted_name(call.args[0])
        return inner
    return name


def ancestors(node: ast.AST):
    cur = getattr(node, "parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "parent", None)


def enclosing_function(node: ast.AST):
    """Nearest enclosing FunctionDef/AsyncFunctionDef/Lambda, or None."""
    for anc in ancestors(node):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            return anc
    return None


def function_name(node: ast.AST) -> str:
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return node.name
    if isinstance(node, ast.Lambda):
        return "<lambda>"
    return "<module>"


def context_name(node: ast.AST) -> str:
    """Name of the function whose body contains ``node`` (for baseline
    fingerprints — stable across line-number drift)."""
    fn = enclosing_function(node)
    return function_name(fn) if fn is not None else "<module>"


def param_names(fn: ast.FunctionDef | ast.Lambda) -> list[str]:
    a = fn.args
    names = [p.arg for p in
             (*a.posonlyargs, *a.args, *a.kwonlyargs)]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def decorator_targets(fn: ast.FunctionDef) -> set[str]:
    """Dotted names of decorators, looking through ``partial(...)``."""
    out: set[str] = set()
    for dec in fn.decorator_list:
        if isinstance(dec, ast.Call):
            name = call_target(dec)
        else:
            name = dotted_name(dec)
        if name:
            out.add(name)
    return out


def _wrapped_names(tree: ast.Module, wrappers: set[str]) -> set[str]:
    """Names passed as the first argument to any wrapper call, e.g. the
    ``run`` in ``jax.jit(run)`` or ``shard_map(local, mesh=...)``."""
    out: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and call_target(node) in wrappers:
            if node.args and isinstance(node.args[0], ast.Name):
                out.add(node.args[0].id)
    return out


def _collect(tree: ast.Module, wrappers: set[str]) -> set[ast.AST]:
    """Function/Lambda nodes whose bodies run under any of ``wrappers``."""
    by_name = _wrapped_names(tree, wrappers)
    marked: set[ast.AST] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name in by_name or decorator_targets(node) & wrappers:
                marked.add(node)
        elif isinstance(node, ast.Lambda):
            parent = getattr(node, "parent", None)
            if isinstance(parent, ast.Call) and \
                    call_target(parent) in wrappers and \
                    parent.args and parent.args[0] is node:
                marked.add(node)
    # tracing is transitive: defs nested inside a marked function
    changed = True
    while changed:
        changed = False
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)) or node in marked:
                continue
            fn = enclosing_function(node)
            if fn is not None and fn in marked:
                marked.add(node)
                changed = True
    return marked


def traced_functions(tree: ast.Module) -> set[ast.AST]:
    """Function/Lambda nodes whose bodies execute under a jax trace."""
    return _collect(tree, TRACE_WRAPPERS)


def shardmap_functions(tree: ast.Module) -> set[ast.AST]:
    """Function/Lambda nodes whose bodies have mesh axis names bound
    (shard_map / pmap operands and their nested defs)."""
    return _collect(tree, AXIS_WRAPPERS)


def in_marked_context(node: ast.AST, marked: set[ast.AST]) -> bool:
    fn = enclosing_function(node)
    while fn is not None:
        if fn in marked:
            return True
        fn = enclosing_function(fn)
    return False


@dataclass
class JitSpec:
    """A name bound to a jitted callable with static argument info, e.g.
    ``g = jax.jit(f, static_argnums=(1,))`` — used by the RETRACE rule to
    check call sites of ``g`` for unhashable static operands."""
    name: str
    target: str | None          # wrapped function name, when identifiable
    static_argnums: tuple[int, ...] = ()
    static_argnames: tuple[str, ...] = ()
    node: ast.Call = field(default=None, repr=False)  # type: ignore


def _const_seq(node: ast.AST) -> tuple:
    """Constant tuple/list/str/int contents, else ()."""
    if isinstance(node, ast.Constant):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not isinstance(el, ast.Constant):
                return ()
            out.append(el.value)
        return tuple(out)
    return ()


def jit_call_statics(call: ast.Call) -> tuple[tuple[int, ...],
                                              tuple[str, ...]]:
    nums: tuple[int, ...] = ()
    names: tuple[str, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            nums = tuple(v for v in _const_seq(kw.value)
                         if isinstance(v, int))
        elif kw.arg == "static_argnames":
            names = tuple(v for v in _const_seq(kw.value)
                          if isinstance(v, str))
    return nums, names


def jitted_bindings(tree: ast.Module) -> dict[str, JitSpec]:
    """Map of ``name -> JitSpec`` for ``name = jax.jit(f, static_*=...)``
    assignments and ``@partial(jax.jit, static_*=...)`` decorated defs."""
    out: dict[str, JitSpec] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                call_target(node.value) in JIT_WRAPPERS:
            nums, names = jit_call_statics(node.value)
            target = (dotted_name(node.value.args[0])
                      if node.value.args else None)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out[tgt.id] = JitSpec(tgt.id, target, nums, names,
                                          node.value)
        elif isinstance(node, ast.FunctionDef):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and \
                        call_target(dec) in JIT_WRAPPERS:
                    nums, names = jit_call_statics(dec)
                    if nums or names:
                        out[node.name] = JitSpec(node.name, node.name,
                                                 nums, names, dec)
    return out


def subtree_mentions(node: ast.AST, roots: set[str]) -> bool:
    """True when any Name in the subtree has an id in ``roots`` (e.g. a
    ``jnp``-rooted expression inside a ``np.`` call)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in roots:
            return True
    return False
