"""Runtime lock-contract verifier — the dynamic half of mxlint E008/E009.

The static pass (tools/analysis/lock_checks.py) proves lock-order
consistency for the acquisition sites it can see in one file; this
module is the runtime teeth for everything it cannot: cross-module
nesting, callback-driven acquisition, and the production question
"which lock is everyone actually waiting on?".

Every subsystem declares its locks through the three factories here
instead of calling ``threading.Lock()`` directly::

    self._lock = locks.lock("serving.server")
    self._cv = locks.condition("serving.queue")          # own hidden lock
    self._work_cv = locks.condition("engine", self._lock)  # shared lock

With ``MXTPU_LOCK_CHECK`` unset (the default) the factories return the
plain ``threading`` primitives — zero overhead, byte-identical
behavior.  With ``MXTPU_LOCK_CHECK=1`` they return a
:class:`RecordingLock` that

* keeps a per-thread held-set and folds every held->acquired pair into
  a process-global lock ORDER graph;
* detects a cycle at edge-insertion time — i.e. BEFORE blocking on the
  lock that would complete the deadlock — and raises (or, under
  ``MXTPU_LOCK_CHECK_ACTION=dump``, records + prints) a
  :class:`DeadlockError` postmortem naming BOTH conflicting
  acquisition sites;
* books ``locks.wait_seconds.<name>`` / ``locks.hold_seconds.<name>``
  histograms and a ``locks.contended`` counter into the telemetry
  registry (E004-guarded), and emits a ``lock_wait.<name>`` span while
  the profiler runs, so contention renders beside the dispatch lanes
  in chrome traces and ``parse_log --telemetry``.

Deliberately NOT converted: the telemetry/profiler registry locks
themselves (a RecordingLock books telemetry, so instrumenting the
registry's own lock would recurse) — both are leaf locks by
construction, documented in docs/observability.md.

Chaos pin: tests/test_locks.py scripts an AB/BA deadlock that raises
in milliseconds with the check on and genuinely hangs with it off.
"""
from __future__ import annotations

import sys
import threading
import time

from . import config

__all__ = ["DeadlockError", "RecordingLock", "lock", "rlock", "condition",
           "enabled", "order_graph", "cycles", "violations", "held_names",
           "reset"]


class DeadlockError(RuntimeError):
    """A lock acquisition would close a cycle in the global order graph.

    ``sites`` carries the two conflicting acquisition sites:
    ``(this_site, prior_site)`` — where THIS thread is taking ``b``
    while holding ``a``, and where some earlier acquisition took ``a``
    (possibly transitively) while holding ``b``.
    """

    def __init__(self, msg, a=None, b=None, sites=()):
        super().__init__(msg)
        self.a = a
        self.b = b
        self.sites = tuple(sites)


def enabled():
    """True when MXTPU_LOCK_CHECK=1 — factories hand out RecordingLocks."""
    return bool(config.get("MXTPU_LOCK_CHECK"))


# ---------------------------------------------------------------------------
# process-global order graph
# ---------------------------------------------------------------------------

# raw leaf lock guarding the graph — NEVER a RecordingLock (recursion)
_STATE_LOCK = threading.Lock()
# name -> {successor_name: (outer_site, inner_site)} with first-seen sites;
# edge a->b means "b was acquired while a was held"
_EDGES = {}
# postmortems recorded instead of raised under MXTPU_LOCK_CHECK_ACTION=dump
_VIOLATIONS = []
_TLS = threading.local()


def _held():
    """This thread's held list: [(RecordingLock, site_str), ...]."""
    lst = getattr(_TLS, "held", None)
    if lst is None:
        lst = _TLS.held = []
    return lst


_SKIP_PREFIXES = tuple(s[:-1] if s.endswith("c") else s
                       for s in (__file__, threading.__file__))


def _site():
    """'file:line' of the acquiring frame — first caller outside this
    module and the threading internals.  Walks raw frames
    (sys._getframe) rather than traceback.extract_stack(): this runs
    on EVERY sentinel acquire, and extract_stack's per-frame linecache
    lookups would dominate the sentinel's cost (on the chip: not
    measured)."""
    f = sys._getframe(1)
    while f is not None:
        fname = f.f_code.co_filename
        if not fname.startswith(_SKIP_PREFIXES):
            return "%s:%d" % (fname, f.f_lineno)
        f = f.f_back
    return "<unknown>"


def _reaches(src, dst):
    """Path of names src -> ... -> dst in _EDGES, or None.  Caller holds
    _STATE_LOCK."""
    stack = [(src, (src,))]
    seen = set()
    while stack:
        cur, path = stack.pop()
        if cur == dst:
            return path
        if cur in seen:
            continue
        seen.add(cur)
        for nxt in _EDGES.get(cur, ()):
            stack.append((nxt, path + (nxt,)))
    return None


def _postmortem(holder_name, holder_site, taking_name, taking_site, path):
    """Render the two-sided DeadlockError message: this acquisition and
    the recorded reverse-path edge that closes the cycle."""
    first = _EDGES.get(path[0], {}).get(path[1], ("<unknown>", "<unknown>"))
    chain = " -> ".join(path)
    held = ", ".join("%r held since %s" % (lk.name, s) for lk, s in _held())
    return (
        "lock order violation: acquiring %r at %s while holding %r "
        "(acquired at %s), but the order graph already has %s — "
        "recorded when %r was taken under %r at %s (outer acquisition "
        "at %s).  This thread holds: [%s].  Consistent order or a "
        "`# mxlint: disable=E008 -- why` justification required."
        % (taking_name, taking_site, holder_name, holder_site, chain,
           path[1], path[0], first[1], first[0], held))


def _on_violation(msg, a, b, sites):
    action = config.get("MXTPU_LOCK_CHECK_ACTION")
    err = DeadlockError(msg, a=a, b=b, sites=sites)
    if action == "dump":
        with _STATE_LOCK:
            _VIOLATIONS.append(err)
        from . import telemetry
        if telemetry.enabled():
            telemetry.inc("locks.order_violations")
        print("MXTPU_LOCK_CHECK: %s" % msg, file=sys.stderr)
        return
    raise err


class RecordingLock:
    """Drop-in threading.Lock/RLock replacement that records ordering.

    Satisfies the full ``threading.Condition`` owner-lock protocol via
    the stdlib's documented fallbacks (plain ``acquire(0)`` probe for
    ``_is_owned``, release/acquire for the wait-side save/restore), so
    ``threading.Condition(RecordingLock(...))`` works unchanged.
    """

    def __init__(self, name, recursive=False):
        self.name = name
        self._recursive = recursive
        self._inner = threading.RLock() if recursive else threading.Lock()
        self._acquired_at = {}  # thread ident -> hold-start perf time

    # -- ordering ----------------------------------------------------------

    def _depths(self):
        d = getattr(_TLS, "depths", None)
        if d is None:
            d = _TLS.depths = {}
        return d

    def _record(self, site):
        """Fold (held -> self) edges into the global graph; raise/dump
        on a cycle BEFORE the caller blocks on the inner lock."""
        held = _held()
        if not held:
            return
        # lock-free fast path: edges only ever grow (reset() swaps the
        # whole dict), so if every held lock already has its (holder ->
        # self) edge recorded there is nothing to fold in — the common
        # steady-state acquire never touches _STATE_LOCK
        name = self.name
        for holder, _hs in held:
            if holder is not self and holder.name != name \
                    and name not in _EDGES.get(holder.name, ()):
                break
        else:
            return
        with _STATE_LOCK:
            pending = []
            for holder, holder_site in held:
                # same-name siblings (per-connection / per-replica locks
                # share one factory name) are ordering CLASSES, not
                # instances — nesting two is not self-deadlock evidence
                if holder is self or holder.name == self.name:
                    continue
                succ = _EDGES.setdefault(holder.name, {})
                if self.name not in succ:
                    path = _reaches(self.name, holder.name)
                    if path is not None:
                        msg = _postmortem(holder.name, holder_site,
                                          self.name, site, path)
                        sites = (site,
                                 _EDGES[path[0]].get(path[1],
                                                     ("?", "?"))[1])
                        pending.append((msg, holder.name, sites))
                        continue
                    succ[self.name] = (holder_site, site)
        for msg, holder_name, sites in pending:
            _on_violation(msg, a=holder_name, b=self.name, sites=sites)

    # -- lock protocol -----------------------------------------------------

    def acquire(self, blocking=True, timeout=-1):
        me = threading.get_ident()
        depths = self._depths()
        if self._recursive and depths.get(id(self), 0) > 0:
            got = self._inner.acquire(blocking, timeout)
            if got:
                depths[id(self)] += 1
            return got
        site = _site()
        self._record(site)
        t0 = time.perf_counter()
        got = self._inner.acquire(False)
        contended = not got
        if not got:
            from . import profiler, telemetry
            if telemetry.enabled():
                telemetry.inc("locks.contended")
            if not blocking:
                if telemetry.enabled():
                    telemetry.observe("locks.wait_seconds.%s" % self.name,
                                      time.perf_counter() - t0)
                return False
            got = self._inner.acquire(True, timeout)
        wait = time.perf_counter() - t0
        if contended:
            from . import profiler, telemetry
            if telemetry.enabled():
                telemetry.observe("locks.wait_seconds.%s" % self.name, wait)
            if profiler.spans_active():
                profiler.record_span("lock_wait.%s" % self.name,
                                     int((time.time() - wait) * 1e6),
                                     int(wait * 1e6), cat="lock")
        if got:
            depths[id(self)] = 1
            self._acquired_at[me] = time.perf_counter()
            _held().append((self, site))
        return got

    def release(self):
        me = threading.get_ident()
        depths = self._depths()
        if self._recursive and depths.get(id(self), 0) > 1:
            depths[id(self)] -= 1
            self._inner.release()
            return
        t_acq = self._acquired_at.pop(me, None)
        if t_acq is not None:
            from . import telemetry
            if telemetry.enabled():
                telemetry.observe("locks.hold_seconds.%s" % self.name,
                                  time.perf_counter() - t_acq)
        depths.pop(id(self), None)
        held = _held()
        for i in range(len(held) - 1, -1, -1):
            if held[i][0] is self:
                del held[i]
                break
        self._inner.release()

    # -- threading.Condition owner-lock protocol ---------------------------
    # Without these the stdlib falls back to an acquire(False) probe for
    # _is_owned, which a RecordingLock would mis-book as contention.

    def _is_owned(self):
        return self._depths().get(id(self), 0) > 0

    def _release_save(self):
        n = self._depths().get(id(self), 0)
        for _ in range(max(1, n)):
            self.release()
        return n

    def _acquire_restore(self, state):
        for _ in range(max(1, state)):
            self.acquire()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._inner.locked() if hasattr(self._inner, "locked") \
            else self._depths().get(id(self), 0) > 0

    def __repr__(self):
        return "<RecordingLock %r%s>" % (self.name,
                                         " (recursive)" if self._recursive
                                         else "")


# ---------------------------------------------------------------------------
# factories — THE declared lock sites call these (docs/static_analysis.md
# "lock naming convention": dotted subsystem.role names)
# ---------------------------------------------------------------------------

def lock(name):
    """A mutex named for telemetry/ordering; plain Lock when the check
    is off."""
    return RecordingLock(name) if enabled() else threading.Lock()


def rlock(name):
    """Reentrant variant of :func:`lock`."""
    return RecordingLock(name, recursive=True) if enabled() \
        else threading.RLock()


def condition(name, lock=None):
    """A condition variable; pass ``lock`` to share an existing
    factory-made lock (the engine's one-lock/two-conditions layout) —
    condition waits then count against that lock's name."""
    if lock is None and enabled():
        lock = RecordingLock(name)
    return threading.Condition(lock)


# ---------------------------------------------------------------------------
# introspection (tests, postmortem tooling)
# ---------------------------------------------------------------------------

def order_graph():
    """Copy of the global order graph:
    {name: {successor: (outer_site, inner_site)}}."""
    with _STATE_LOCK:
        return {a: dict(succ) for a, succ in _EDGES.items()}


def cycles():
    """Unordered lock pairs {a, b} that are mutually reachable in the
    order graph — each is a latent deadlock (empty list = clean run).
    Under ACTION=raise a cycle raises before its edge lands, so this
    reports cycles observed in dump mode or via racing edge inserts."""
    with _STATE_LOCK:
        out, seen = [], set()
        for a, succ in _EDGES.items():
            for b in succ:
                key = frozenset((a, b))
                if key in seen:
                    continue
                if _reaches(b, a):
                    seen.add(key)
                    out.append(sorted(key))
        return out


def violations():
    """DeadlockErrors recorded under MXTPU_LOCK_CHECK_ACTION=dump."""
    with _STATE_LOCK:
        return list(_VIOLATIONS)


def held_names():
    """Names of locks the CALLING thread currently holds (debugging)."""
    return [lk.name for lk, _ in _held()]


def reset():
    """Clear the order graph + recorded violations (tests; per-thread
    held-sets empty themselves as locks release)."""
    with _STATE_LOCK:
        _EDGES.clear()
        del _VIOLATIONS[:]
