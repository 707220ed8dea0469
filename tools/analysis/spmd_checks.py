"""mxlint SPMD checks — collectives must be schedule-identical per rank.

A collective (``psum``, ``all_gather``, a barrier) completes only when
EVERY rank of the mesh axis reaches it.  A collective that is
control-dependent on a rank-varying value — ``process_index()``, the
launcher's ``MXTPU_PROCESS_ID`` export, ``axis_index`` — or on a
data-dependent Python branch is the static face of the deadlock class
the stall watchdog (obs/watchdog.py) diagnoses post-mortem: some ranks
enter the collective, the others never will, and the job hangs until
the watchdog's timeout.  This check rejects the program before it
runs; its runtime counterpart is the cross-rank collective-schedule
verifier (``parallel/schedule_check.py``, ``MXTPU_COLLECTIVE_CHECK=1``),
which catches the dynamically-divergent remainder static analysis
cannot see.

  * **E007** — inside a traced body (:mod:`.traced`), a collective
    call with an ancestor ``if``/``while`` whose condition reads a
    rank source (``process_index`` / ``axis_index`` / ``own_rank`` /
    an ``MXTPU_PROCESS_ID`` / ``DMLC_WORKER_ID`` env read — directly
    or through a local bound from one) or compares a traced value
    (every rank branches on ITS shard's data — ranks disagree).

Host-static ancestor conditions — ``if comm is not None:`` around the
bucketed psum, ``isinstance``/``hasattr`` version shims — are the
sanctioned shape and stay silent: every rank resolves them identically
at trace time.  ``for`` loops are static trip counts under trace and
never flagged.
"""
from __future__ import annotations

import ast

from .core import Finding, register
from .trace_checks import (_array_value_names, _is_static_test,
                           _value_compare_on_traced)
from .traced import traced_functions, own_statements

__all__ = ["CollectiveUnderRankControl"]

# collective entry points: lax primitives + the framework's wrappers
# (parallel/collectives.py, parallel/multihost.py)
_COLLECTIVE_NAMES = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "psum_scatter",
    "all_to_all", "ppermute", "pshuffle",
    "allreduce", "allgather", "reduce_scatter", "alltoall",
    "ring_permute", "barrier", "mesh_allreduce",
}
# rank sources: calls whose value differs per rank
_RANK_CALL_NAMES = {"process_index", "axis_index", "own_rank",
                    "process_id", "host_id", "node_rank"}
_RANK_ENV_VARS = {"MXTPU_PROCESS_ID", "DMLC_WORKER_ID",
                  "MXTPU_RECOVER_RANK", "MXTPU_DATA_HOST_INDEX"}


def _call_name(node):
    if not isinstance(node, ast.Call):
        return None
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _env_var_read(node):
    """String name of an environ read (`os.environ.get("X")`,
    `os.environ["X"]`, `os.getenv("X")`), or None."""
    def _is_environ(v):
        return (isinstance(v, ast.Attribute) and v.attr == "environ") \
            or (isinstance(v, ast.Name) and v.id == "environ")

    if isinstance(node, ast.Call):
        f = node.func
        is_get = (isinstance(f, ast.Attribute)
                  and (f.attr == "getenv"
                       or (f.attr == "get" and _is_environ(f.value)))) \
            or (isinstance(f, ast.Name) and f.id == "getenv")
        if is_get and node.args and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            return node.args[0].value
    elif isinstance(node, ast.Subscript) and _is_environ(node.value):
        sl = node.slice
        if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
            return sl.value
    return None


def _is_rank_expr(node):
    """Does this expression read a rank source directly?"""
    if _call_name(node) in _RANK_CALL_NAMES:
        return True
    env = _env_var_read(node)
    return env is not None and env in _RANK_ENV_VARS


def _rank_names(fn):
    """Locals carrying a rank-derived value: assigned from a rank
    source, or from an expression mentioning an existing rank name
    (``rank = jax.process_index(); me = rank % 2``)."""
    names = set()
    changed = True
    while changed:
        changed = False
        for n in own_statements(fn):
            if not isinstance(n, ast.Assign):
                continue
            v = n.value
            hit = any(_is_rank_expr(x) for x in ast.walk(v)) or any(
                isinstance(x, ast.Name) and x.id in names
                for x in ast.walk(v))
            if hit:
                for t in n.targets:
                    for x in ast.walk(t):
                        if isinstance(x, ast.Name) and x.id not in names:
                            names.add(x.id)
                            changed = True
    return names


def _test_is_rank_dependent(test, rank_names):
    for node in ast.walk(test):
        if _is_rank_expr(node):
            return True
        if isinstance(node, ast.Name) and node.id in rank_names \
                and isinstance(node.ctx, ast.Load):
            return True
    return False


@register
class CollectiveUnderRankControl:
    """E007: no collective may be control-dependent on rank-varying or
    data-dependent values inside a traced body (module docstring)."""

    id = "E007"
    title = ("collectives in traced code must not sit under rank-"
             "dependent or data-dependent Python control flow")

    def run(self, ctx):
        traced = traced_functions(ctx)
        for fn, (entry, entry_line) in traced.items():
            where = "traced body (%s at line %d)" % (entry, entry_line)
            anames = _array_value_names(fn)
            rnames = _rank_names(fn)
            seen = set()
            for n in own_statements(fn):
                cname = _call_name(n)
                if cname not in _COLLECTIVE_NAMES:
                    continue
                for anc in ctx.parent_chain(n):
                    if anc is fn:
                        break
                    if not isinstance(anc, (ast.If, ast.While, ast.IfExp)):
                        continue
                    if _is_static_test(anc.test):
                        continue
                    if _test_is_rank_dependent(anc.test, rnames):
                        key = (n.lineno, n.col_offset, "rank")
                        if key in seen:
                            continue
                        seen.add(key)
                        yield Finding(
                            "E007", ctx.path, n.lineno, n.col_offset,
                            "collective `%s` is control-dependent on a "
                            "rank-varying value (%s test at line %d) "
                            "inside a %s: ranks that branch the other "
                            "way never enter it — every peer blocks "
                            "until the stall watchdog fires.  Hoist "
                            "the branch out of the traced body, or "
                            "make every rank take the same path"
                            % (cname,
                               "while" if isinstance(anc, ast.While)
                               else "if", anc.test.lineno, where))
                        break
                    if _value_compare_on_traced(anc.test, anames) \
                            and not _is_static_test(anc.test):
                        key = (n.lineno, n.col_offset, "data")
                        if key in seen:
                            continue
                        seen.add(key)
                        yield Finding(
                            "E007", ctx.path, n.lineno, n.col_offset,
                            "collective `%s` sits under a data-"
                            "dependent Python branch (%s test at line "
                            "%d) inside a %s: each rank branches on "
                            "ITS shard's values, so the collective "
                            "schedules diverge (the deadlock class "
                            "MXTPU_COLLECTIVE_CHECK=1 verifies at "
                            "runtime) — use lax.cond with a psum'd "
                            "predicate so every rank agrees"
                            % (cname,
                               "while" if isinstance(anc, ast.While)
                               else "if", anc.test.lineno, where))
                        break
