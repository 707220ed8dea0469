"""Shared traced-context resolver — which functions in a file trace.

The trace/SPMD checks (E006 trace_checks.py, E007 spmd_checks.py) both
need the same answer: *which function bodies in this file run under a
JAX trace* — because the contract inside a traced body is inverted
from host code (host effects bake into the compile, Python branches on
array values raise or silently specialize, collectives must be
schedule-identical across ranks).

A function is traced when it flows into a trace entry point:

  * directly — ``jax.jit(f)``, ``lax.scan(body, ...)``,
    ``shard_map(f, ...)`` / ``shard_map_unchecked``, ``jax.vjp`` /
    ``grad`` / ``checkpoint`` / ``eval_shape`` / ``make_jaxpr`` /
    ``vmap``, ``lax.cond`` branches, ``lax.while_loop`` /
    ``fori_loop`` bodies;
  * as a decorator — ``@jax.jit``, ``@functools.partial(shard_map,
    mesh=...)`` (the collectives.py ``mesh_allreduce`` idiom);
  * through a builder — ``jax.jit(self._build_fwd(is_train))``: the
    builder's RETURNED closures are traced (the executor.py
    ``_build_fwd``/``_grad_core``/``_build_block_fn`` idiom), chased
    through local assignments (``fn = self._build_block_fn(...)``;
    ``jax.jit(fn)``);
  * transitively — a call inside a traced body to a function this file
    can resolve (nested def, module-level def, ``self._method``, a
    closure variable bound from a builder call) traces that callee too.

Resolution is the same names-level, within-one-file machinery the E001
engine checks use (default-arg bindings, assignment chasing), with the
same contract: anything unresolvable — a registry-dispatched
``op.fn``, a parameter-passed callable — is silently host-assumed.
mxlint never claims false certainty; the runtime halves (the schedule
verifier ``parallel/schedule_check.py`` and the retrace monitor
``telemetry.note_retrace``) cover the dynamic remainder.
"""
from __future__ import annotations

import ast

__all__ = ["traced_functions", "own_statements", "FN_NODES"]

FN_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

# trace entry points: callable attr/name -> tuple of traced arg slots
_ENTRY_SLOTS = {
    "jit": (0,), "vjp": (0,), "grad": (0,), "value_and_grad": (0,),
    "checkpoint": (0,), "remat": (0,), "eval_shape": (0,),
    "make_jaxpr": (0,), "vmap": (0,), "pmap": (0,), "named_call": (0,),
    "custom_vjp": (0,), "custom_jvp": (0,),
    "scan": (0,), "shard_map": (0,), "shard_map_unchecked": (0,),
    "while_loop": (0, 1), "fori_loop": (2,), "cond": (1, 2),
    "saved_residuals": (0,),
}


def _entry_name(fn):
    """The entry-point key of a call's callee (``jax.jit`` -> 'jit',
    ``lax.scan`` -> 'scan', bare ``shard_map`` -> itself), or None."""
    if isinstance(fn, ast.Attribute):
        name = fn.attr
    elif isinstance(fn, ast.Name):
        name = fn.id
    else:
        return None
    return name if name in _ENTRY_SLOTS else None


def _is_partial(fn):
    return (isinstance(fn, ast.Attribute) and fn.attr == "partial") or \
        (isinstance(fn, ast.Name) and fn.id == "partial")


def own_statements(fn):
    """Nodes of `fn`'s own scope — nested function BODIES excluded
    (they are their own traced/untraced question), the nested def node
    itself included (so calls can resolve to it)."""
    body = fn.body if isinstance(fn.body, list) else [fn.body]
    out = []
    stack = list(body)
    while stack:
        n = stack.pop()
        out.append(n)
        if isinstance(n, FN_NODES):
            continue
        stack.extend(ast.iter_child_nodes(n))
    return out


class _Resolver:
    """Within-one-file callable resolution (module docstring)."""

    _MAX_DEPTH = 8

    def __init__(self, ctx):
        self.ctx = ctx

    def _scopes_of(self, node):
        """Enclosing function scopes of `node`, innermost first, then
        the module — the search path for Name resolution."""
        return self.ctx.enclosing_functions(node) + [self.ctx.tree]

    @staticmethod
    def _scope_nodes(scope):
        """Nodes owned directly by `scope` — nested function bodies
        excluded (they are their own scope)."""
        if isinstance(scope, FN_NODES):
            return own_statements(scope)
        out = []
        stack = list(ast.iter_child_nodes(scope))
        while stack:
            n = stack.pop()
            out.append(n)
            if not isinstance(n, FN_NODES):
                stack.extend(ast.iter_child_nodes(n))
        return out

    def _defs_in_scope(self, scope, name):
        """FunctionDefs named `name` owned directly by `scope`."""
        return [n for n in self._scope_nodes(scope)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                and n.name == name]

    def _assigns_in_scope(self, scope, name):
        """Values assigned to `name` directly in `scope` (last wins is
        NOT modeled — all candidate values are chased; over-approx)."""
        out = []
        for n in self._scope_nodes(scope):
            if isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == name
                    for t in n.targets):
                out.append(n.value)
        return out

    def resolve(self, expr, at, depth=0, seen=None):
        """Function AST nodes the callable expression `expr` may denote
        (evaluated at node `at` for scope purposes).  Empty when not
        resolvable in this file."""
        if depth > self._MAX_DEPTH or expr is None:
            return []
        seen = seen if seen is not None else set()
        key = id(expr)
        if key in seen:
            return []
        seen.add(key)
        if isinstance(expr, ast.Lambda):
            return [expr]
        if isinstance(expr, ast.Name):
            out = []
            for scope in self._scopes_of(at):
                hits = self._defs_in_scope(scope, expr.id)
                out.extend(hits)
                for val in self._assigns_in_scope(scope, expr.id):
                    out.extend(self.resolve(val, at, depth + 1, seen))
                if out:
                    break  # innermost binding scope wins
            return out
        if isinstance(expr, ast.Attribute):
            # self._method -> method of the enclosing class
            if isinstance(expr.value, ast.Name) and expr.value.id == "self":
                cls = self.ctx.enclosing_class(at)
                if cls is not None:
                    return [n for n in cls.body
                            if isinstance(n, (ast.FunctionDef,
                                              ast.AsyncFunctionDef))
                            and n.name == expr.attr]
            return []
        if isinstance(expr, ast.Call):
            # a wrapper entry (jax.checkpoint(f), functools.partial(jit,
            # ...)) resolves to its traced-slot args; any other
            # resolvable callee resolves to the closures it RETURNS
            ename = _entry_name(expr.func)
            if ename is not None:
                out = []
                for slot in _ENTRY_SLOTS[ename]:
                    if slot < len(expr.args):
                        out.extend(self.resolve(expr.args[slot], at,
                                                depth + 1, seen))
                return out
            if _is_partial(expr.func) and expr.args:
                return self.resolve(expr.args[0], at, depth + 1, seen)
            out = []
            for callee in self.resolve(expr.func, at, depth + 1, seen):
                out.extend(self._returned_callables(callee, depth + 1, seen))
            return out
        return []

    def _returned_callables(self, fn, depth, seen):
        """Closures a builder function returns (``def _build(...):
        def f(...): ...; return f`` -> [f])."""
        if isinstance(fn, ast.Lambda):
            return []
        out = []
        for n in own_statements(fn):
            if isinstance(n, ast.Return) and n.value is not None:
                out.extend(self.resolve(n.value, fn.body[0], depth, seen))
        return out


def traced_functions(ctx):
    """``{fn_node: (entry_kind, entry_lineno)}`` for every function in
    the file whose body runs under a JAX trace.  Cached on the
    FileContext so E006 and E007 share one resolution pass."""
    cached = getattr(ctx, "_traced_fns", None)
    if cached is not None:
        return cached
    res = _Resolver(ctx)
    traced = {}
    work = []

    def _add(fns, kind, lineno):
        for fn in fns:
            if fn not in traced:
                traced[fn] = (kind, lineno)
                work.append(fn)

    # seeds: entry call sites + trace decorators
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            ename = _entry_name(node.func)
            if ename is None:
                continue
            for slot in _ENTRY_SLOTS[ename]:
                if slot < len(node.args):
                    _add(res.resolve(node.args[slot], node),
                         ename, node.lineno)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                ename = None
                if isinstance(dec, ast.Call):
                    if _is_partial(dec.func) and dec.args:
                        ename = _entry_name(dec.args[0])
                    else:
                        ename = _entry_name(dec.func)
                else:
                    ename = _entry_name(dec)
                if ename is not None:
                    _add([node], ename, dec.lineno)
    # transitive closure: calls inside a traced body trace their
    # resolvable callees too
    while work:
        fn = work.pop()
        kind, lineno = traced[fn]
        for n in own_statements(fn):
            if isinstance(n, ast.Call):
                _add(res.resolve(n.func, n), kind, lineno)
    ctx._traced_fns = traced
    return traced
