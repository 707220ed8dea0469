"""mxlint telemetry checks — the zero-cost-when-disabled contract.

Every observability layer in the framework promises ~zero overhead
when off: the profiler via ``spans_active()`` and the metrics registry
via ``telemetry.enabled()``.  That promise only holds if HOT-path call
sites guard the recording call itself — the recording helpers do
early-return when disabled, but argument construction (string
formatting, ``time.time()`` pairs, byte-size sums) happens at the call
site, before the callee can bail.

  * **E004** — a recording call (``telemetry.inc/set_gauge/observe/
    flush``, ``profiler.record_span/record_counter``, the obs
    flight recorder's ``recorder.record``, and the memory census's
    ``memory.book/rebook`` — but NOT ``memory.unbook``, which must run
    unconditionally to balance a book made while telemetry was on)
    that is not guarded by the fast path.  Two guard shapes are
    recognized, the ones the codebase actually uses:

      - an enclosing ``if`` whose test reaches ``enabled()`` /
        ``spans_active()`` — directly, or through a local bound from
        one (``prof = profiler.spans_active()`` … ``if prof:``,
        including ``timed = prof or tel`` style combinations);
      - an early return: a prior statement in the same function of the
        form ``if not <guard>: return``.

Anything else — a guard smuggled through a container, an attribute, a
cross-function contract — is flagged; restructure to one of the two
shapes or allowlist with the justification that makes it safe.

``profiler.span(name, hist=..., **attrs)`` is NOT a recording call and
stays out of E004's set: its guards (``telemetry.enabled()``, the
chrome profiler's running flag, the JAX profiler's own level check)
live inside the primitive, and a context manager cannot sit under an
``if``.  The same discipline therefore binds its ARGUMENTS instead:
``name`` is a static string and ``attrs`` are values the caller already
holds (an int, a bucket, a tenant's name) — never a formatted string, a
``len()`` over a fresh list or a byte sum, which would be built and
thrown away whenever every sink is off.  Do not wrap a span in ``if
telemetry.enabled()``.
"""
from __future__ import annotations

import ast

from .core import Finding, register

__all__ = ["UnguardedTelemetryCall"]

# module-level handles the framework uses at instrumentation sites
# (recorder = the obs flight recorder, whose record() sits on the same
# hot dispatch paths and promises the same ~zero disabled cost;
# tracing = the request tracer, whose record/record_outcome/flow calls
# sit once per SERVED REQUEST — the serving tier's hottest sites;
# memory = the live-buffer census, whose book/rebook sit on every
# NDArray materialization)
_MODULE_NAMES = {"telemetry", "profiler", "recorder", "tracing",
                 "memory"}
# the recording entry points whose CALL must be guarded.  The census's
# ``memory.unbook`` is deliberately ABSENT: unbook must run whenever
# the matching book ran (holders remember the booked amount and
# release exactly it), and making it conditional on the CURRENT
# telemetry state would leak census bytes across an enabled->disabled
# flip mid-lifetime.  Its disabled cost is one dict-miss under a lock,
# paid only by holders that booked while telemetry was on.
_RECORDING_ATTRS = {"inc", "set_gauge", "observe", "observe_values",
                    "attach_value_histogram", "flush", "record_span",
                    "record_counter", "record", "record_outcome",
                    "record_event", "flow", "book", "rebook"}
# the fast-path predicates
_GUARD_ATTRS = {"enabled", "spans_active"}


def _is_guard_call(node):
    """``telemetry.enabled()`` / ``profiler.spans_active()`` (any base:
    the predicate name is unambiguous) or a bare ``spans_active()``."""
    if not isinstance(node, ast.Call):
        return False
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr in _GUARD_ATTRS
    return isinstance(fn, ast.Name) and fn.id in _GUARD_ATTRS


def _guard_names(fn_node):
    """Locals carrying a fast-path value: assigned from a guard call, or
    from a boolean combination of existing guard names (``timed = prof
    or tel``).  One pass in source order — the codebase assigns guards
    before use."""
    names = set()
    for n in ast.walk(fn_node):
        if not isinstance(n, ast.Assign):
            continue
        v = n.value
        derived = _is_guard_call(v) or (
            isinstance(v, ast.BoolOp) and v.values
            and all(isinstance(x, ast.Name) and x.id in names
                    or _is_guard_call(x) for x in v.values))
        if derived:
            for t in n.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
    return names


def _reaches_guard(test, guard_names):
    """Does this condition expression consult the fast path?"""
    for n in ast.walk(test):
        if _is_guard_call(n):
            return True
        if isinstance(n, ast.Name) and n.id in guard_names:
            return True
    return False


@register
class UnguardedTelemetryCall:
    """E004: recording calls must sit behind enabled()/spans_active()."""

    id = "E004"
    title = ("telemetry/profiler recording on hot paths must be guarded "
             "by the enabled()/spans_active() fast path")

    @staticmethod
    def _recording_calls(ctx):
        for n in ast.walk(ctx.tree):
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in _RECORDING_ATTRS
                    and isinstance(n.func.value, ast.Name)
                    and n.func.value.id in _MODULE_NAMES):
                yield n

    @staticmethod
    def _has_early_return_guard(fn_node, call, guard_names):
        """A prior ``if not <guard>: return`` at the TOP LEVEL of the
        same function body.  Strict on purpose: the If must be a direct
        child of the function (a guard nested in an unrelated branch
        guards nothing on the other paths) and its test must be the
        NEGATED fast path (``if enabled(): return`` is an inverted
        guard — the call below it runs exactly when telemetry is ON
        *off*, i.e. it guards nothing)."""
        for n in fn_node.body:
            if not isinstance(n, ast.If) or n.lineno >= call.lineno:
                continue
            t = n.test
            if not (isinstance(t, ast.UnaryOp) and isinstance(t.op, ast.Not)):
                continue
            if not _reaches_guard(t.operand, guard_names):
                continue
            if any(isinstance(s, ast.Return) for s in n.body):
                return True
        return False

    def run(self, ctx):
        for call in self._recording_calls(ctx):
            funcs = ctx.enclosing_functions(call)
            scope = funcs[0] if funcs else ctx.tree
            guards = _guard_names(scope)
            guarded = any(
                isinstance(anc, (ast.If, ast.IfExp))
                and _reaches_guard(anc.test, guards)
                for anc in ctx.parent_chain(call))
            if not guarded and funcs:
                guarded = self._has_early_return_guard(scope, call, guards)
            if guarded:
                continue
            yield Finding(
                "E004", ctx.path, call.lineno, call.col_offset,
                "`%s.%s(...)` is not behind the enabled()/spans_active() "
                "fast path: when telemetry/profiling is OFF this call "
                "still evaluates its arguments on the hot path — wrap it "
                "in `if %s:` (or early-return) so the disabled cost is "
                "one predicted branch"
                % (call.func.value.id, call.func.attr,
                   {"telemetry": "telemetry.enabled()",
                    "recorder": "recorder.enabled()",
                    "tracing": "tracing.enabled()",
                    "memory": "telemetry.enabled()"}.get(
                       call.func.value.id, "profiler.spans_active()")))
