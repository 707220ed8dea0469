"""Memory observability plane: footprints, census, budget, forensics.

The rest of ``obs/`` explains *time* — request traces decompose every
millisecond, the flight recorder attributes every stall.  This module
explains *bytes*, in four parts (docs/observability.md "Memory
observability"):

  1. **Per-program footprint accounting** — every compile-cache site
     (executor forward/serve/fused_step/fused_block/backward, lazy
     fusion, and through them the decode buckets) builds its executable
     via :func:`program` instead of a bare ``jax.jit``.  The wrapper
     compiles ahead-of-time on first call (``jit(f).lower(args)
     .compile()``) and harvests XLA's compiled memory analysis
     (argument/output/temp/alias bytes) into a queryable
     ProgramFootprint table (:func:`footprints`) and per-site
     ``mem.program_bytes.<site>`` gauges — "what does tenant T's
     bucket-64 program cost in HBM" is an API call.  The jit dispatch
     cache does NOT share AOT executables, so the wrapper dispatches
     the compiled object itself (one compile, not two) and keeps a
     small per-signature executable cache for bucket ping-pong.

  2. **Live-buffer census** — tag-attributed byte accounting threaded
     through the places bytes are born and die (NDArray payloads per
     device, KV rings per generative tenant, serve ping-pong slots,
     staged input blocks, checkpoint D2H blobs).  :func:`book` /
     :func:`unbook` keep ``mem.live_bytes.<tag>`` gauges (chrome
     counter lanes while profiling, like every gauge) and a
     high-watermark tracker that snapshots the top-K holders at each
     new peak.  Holders record what they booked and unbook exactly
     that, so the census stays balanced even when telemetry toggles
     mid-life.

  3. **Byte-budget admission** — :func:`admit` preflights a predicted
     footprint against :func:`budget_bytes` (``MXTPU_MEM_BUDGET_MB``,
     default = platform-queried device memory; unlimited when neither
     is known, the XLA:CPU case) and refuses with the
     predicted-vs-available numbers instead of OOMing mid-traffic.
     ModelServer/Router ``health()`` render :func:`health_section`.

  4. **OOM forensics** — allocation failures (RESOURCE_EXHAUSTED) at
     the wrapper's compile/dispatch boundaries write a
     write-then-rename ``memory_postmortem.r<rank>.json``
     (schema ``mxtpu-mem-postmortem-v1``, the watchdog artifact
     pattern) naming the failing program, the live census by tag, the
     top-K holders at the last peak, and recent flight-recorder
     events.  :func:`inject_oom` plants a synthetic failure for chaos
     tests.

E004 contract: :func:`book`/:func:`rebook` are recording calls — call
sites guard them behind ``telemetry.enabled()`` (mxlint enforces it).
:func:`unbook` is exempt: it must run unconditionally at death so a
holder booked while telemetry was on cannot leak census bytes when
telemetry is off at teardown (the booked-amount record makes it a
no-op for never-booked holders).
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time

from ..base import MXNetError

__all__ = [
    "Program", "program", "footprints", "program_bytes",
    "book", "unbook", "rebook", "live_bytes", "census", "peak",
    "set_census", "census_enabled",
    "budget_bytes", "headroom_bytes", "admit", "MemoryBudgetError",
    "health_section", "write_postmortem", "inject_oom", "InjectedOOM",
    "last_postmortem_path", "reset", "nbytes_of",
]

# the "new avals at an existing program" marker in the AOT executable's
# input check — the one TypeError that means "recompile", not "bug"
_SIG_MISMATCH = "Argument types differ"
# per-Program executable cache (signature -> compiled): covers a
# serving bucket ladder / reshape ping-pong; oldest-first eviction
# keeps footprint rows bounded (the predict._EXEC_CACHE_CAP discipline)
_SIG_CAP = 16
# holders snapshotted at each new census peak
_TOP_K = 8

_ROW_SEQ = itertools.count(1)


class MemoryBudgetError(MXNetError):
    """Admission refused: predicted footprint exceeds the byte budget."""


class InjectedOOM(RuntimeError):
    """Synthetic RESOURCE_EXHAUSTED planted by :func:`inject_oom` —
    str() carries the marker so it walks the real forensics path."""


def _is_oom(exc):
    s = "%s: %s" % (type(exc).__name__, exc)
    return ("RESOURCE_EXHAUSTED" in s or "Out of memory" in s
            or "out of memory" in s)


def nbytes_of(value):
    """Resident bytes of one array-like: ``nbytes`` when the object
    carries it (numpy, jax.Array), else shape x dtype — NDArray exposes
    shape/dtype but not nbytes, and admission predictions must not
    read zero for it."""
    n = getattr(value, "nbytes", None)
    if n is not None:
        return int(n)
    shape = getattr(value, "shape", None)
    if shape is None:
        return 0
    total = 1
    for d in shape:
        total *= int(d)
    import numpy as _np

    return total * _np.dtype(getattr(value, "dtype", _np.float32)).itemsize


# ----------------------------------------------------------------------
# live-buffer census
# ----------------------------------------------------------------------
# RLock on purpose: book/unbook allocate (gauge names, dict resizes),
# an allocation can trigger GC, and a collected NDArray's __del__
# unbooks — a plain Lock would deadlock on that re-entry
_CENSUS_LOCK = threading.RLock()
_LIVE = {}          # tag -> live bytes
_LIVE_TOTAL = 0
_PEAK = {"bytes": 0, "top": [], "wall_time": None}
_CENSUS_ON = True


def set_census(flag):
    """Arm/disarm the census in-process (tests; it starts armed).
    Returns the previous state."""
    global _CENSUS_ON
    prev = _CENSUS_ON
    _CENSUS_ON = bool(flag)
    return prev


def census_enabled():
    return _CENSUS_ON


def book(tag, nbytes):
    """Book `nbytes` live under `tag`.  Call sites guard with
    ``telemetry.enabled()`` (E004) and record the amount so the
    matching :func:`unbook` subtracts exactly what was booked."""
    _account(tag, int(nbytes))


def unbook(tag, nbytes):
    """Release `nbytes` from `tag` — runs UNGUARDED at death sites
    (see module docstring); a holder that never booked passes 0."""
    _account(tag, -int(nbytes))


def rebook(tag, old_nbytes, new_nbytes):
    """Payload swap at one holder: one locked delta instead of an
    unbook+book pair (the NDArray ``_set_data`` path)."""
    _account(tag, int(new_nbytes) - int(old_nbytes))


def _account(tag, delta):
    global _LIVE_TOTAL, _PEAK
    if not _CENSUS_ON or delta == 0:
        return
    with _CENSUS_LOCK:
        n = _LIVE.get(tag, 0) + delta
        _LIVE[tag] = n if n > 0 else 0
        _LIVE_TOTAL = total = max(0, _LIVE_TOTAL + delta)
        new_peak = total > _PEAK["bytes"]
        if new_peak:
            top = sorted(_LIVE.items(), key=lambda kv: -kv[1])[:_TOP_K]
            _PEAK = {"bytes": total, "top": top, "wall_time": time.time()}
        tag_bytes = _LIVE[tag]
    from .. import telemetry

    if telemetry.enabled():
        telemetry.set_gauge("mem.live_bytes.%s" % tag, tag_bytes)
        telemetry.set_gauge("mem.live_bytes", total)
        if new_peak:
            telemetry.set_gauge("mem.peak_bytes", total)
        budget = budget_bytes()
        if budget:
            telemetry.set_gauge(
                "mem.headroom_pct",
                100.0 * max(0, budget - total) / budget)


def live_bytes(tag=None):
    """Current live bytes — total, or one tag's."""
    with _CENSUS_LOCK:
        return _LIVE_TOTAL if tag is None else _LIVE.get(tag, 0)


def census():
    """Snapshot of the live census: {tag: bytes} (zeroed tags pruned)."""
    with _CENSUS_LOCK:
        return {t: n for t, n in _LIVE.items() if n > 0}


def peak():
    """High-watermark snapshot: {bytes, top: [[tag, bytes], ...],
    wall_time} captured at the last new census peak."""
    with _CENSUS_LOCK:
        return {"bytes": _PEAK["bytes"],
                "top": [list(kv) for kv in _PEAK["top"]],
                "wall_time": _PEAK["wall_time"]}


# ----------------------------------------------------------------------
# per-program footprint accounting
# ----------------------------------------------------------------------
_TABLE_LOCK = threading.Lock()
_FOOTPRINTS = {}    # row id -> footprint dict
_SITE_BYTES = {}    # site -> sum of peak_bytes over its rows
_INJECT = None      # site substring armed by inject_oom()


def _sig_of(args):
    """Hashable aval signature of a call's arguments (the per-Program
    executable cache key).  weak_type matters: the AOT input check
    distinguishes a python-scalar-traced aval from a strong np one."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(
        (tuple(getattr(x, "shape", ())),
         str(getattr(x, "dtype", type(x).__name__)),
         bool(getattr(x, "weak_type", False)))
        for x in leaves)


class Program:
    """A compile-cache entry that knows its memory footprint.

    Callable like the ``jax.jit`` object it replaces.  First call (per
    input signature) lowers + compiles ahead-of-time, harvests
    ``compiled.memory_analysis()`` into the ProgramFootprint table,
    then dispatches the compiled executable directly on every call
    (the jit dispatch cache does not share AOT executables — routing
    through it would compile twice).  Signature drift (reshape,
    bucket ping-pong) is handled by the executable cache; anything the
    AOT path cannot express falls back permanently to the plain
    ``jax.jit`` object, so the wrapper can never break a model that
    worked before it existed.  ``MXTPU_MEM_PROGRAMS=0`` forces the
    fallback from birth (the escape hatch)."""

    __slots__ = ("site", "key", "_jit", "_lock", "_current", "_compiled",
                 "_rows", "_fallback")

    def __init__(self, fn, site, key=None, donate_argnums=()):
        import jax

        self.site = site
        self.key = key
        self._jit = jax.jit(fn, donate_argnums=donate_argnums)
        self._lock = threading.Lock()
        self._current = None
        self._compiled = {}   # signature -> compiled executable
        self._rows = {}       # signature -> footprint row id
        self._fallback = (
            os.environ.get("MXTPU_MEM_PROGRAMS", "1") in ("0", ""))

    def __call__(self, *args):
        if self._fallback:
            return self._jit(*args)
        if _INJECT is not None and _INJECT in self.site:
            err = InjectedOOM(
                "RESOURCE_EXHAUSTED: injected allocation failure at %s"
                % self.site)
            self._forensics(err)
            raise err
        c = self._current
        if c is not None:
            try:
                return c(*args)
            except TypeError as e:
                if _SIG_MISMATCH not in str(e):
                    raise
                # new avals at this site (reshape / another bucket):
                # fall through to the signature cache
            except Exception as e:
                if _is_oom(e):
                    self._forensics(e)
                raise
        return self._call_slow(args)

    def hlo_text(self):
        """The optimised HLO of the executable this entry dispatched
        last — what the compiler did with the program, layouts, copies
        and ``input_output_alias`` included — or None before the first
        call and on the jit fallback."""
        c = self._current
        return None if c is None else c.as_text()

    def module_name(self):
        """The name of the executable this entry dispatched last, as its
        HLO module has it (``jit_<function>``) — what a device trace's
        ``XLA Modules`` line calls each of its runs, before the
        runtime's own fingerprint in brackets — or None before the first
        call and on the jit fallback.  Read from the compiled object:
        nothing is lowered or traced for it."""
        c = self._current
        if c is None:
            return None
        try:
            return c.runtime_executable().hlo_modules()[0].name
        except Exception:  # noqa: BLE001 — a name, never a failure
            return None

    def _call_slow(self, args):
        with self._lock:
            if self._fallback:
                c = None
            else:
                sig = _sig_of(args)
                c = self._compiled.get(sig)
                if c is None:
                    c = self._compile(args, sig)
        if c is None:
            return self._jit(*args)
        try:
            out = c(*args)
        except Exception as e:
            if _is_oom(e):
                self._forensics(e)
                raise
            if isinstance(e, TypeError) and _SIG_MISMATCH in str(e):
                # aval drift our signature cannot see (committed
                # shardings, dtype promotion corners): recompile once
                # for these exact arguments; a second failure is real
                with self._lock:
                    c = self._compile(args, sig, replace=True)
                if c is None:
                    return self._jit(*args)
                out = c(*args)
            else:
                raise
        self._current = c
        return out

    def _compile(self, args, sig, replace=False):
        """AOT lower+compile under self._lock; harvest the footprint.
        Returns None after arming the permanent jit fallback when the
        AOT path cannot express this call."""
        try:
            compiled = self._jit.lower(*args).compile()
        except Exception as e:
            if _is_oom(e):
                self._forensics(e)
                raise
            from .. import telemetry

            self._fallback = True
            self._current = None
            if telemetry.enabled():
                telemetry.inc("mem.program_fallbacks")
            return None
        if replace:
            self._drop_sig(sig)
        while len(self._compiled) >= _SIG_CAP:
            self._drop_sig(next(iter(self._compiled)))
        self._compiled[sig] = compiled
        self._harvest(compiled, sig)
        self._current = compiled
        return compiled

    def _harvest(self, compiled, sig):
        from .. import telemetry

        fp = {"site": self.site, "key": _short(self.key),
              "signature": _short(sig[1]),
              "argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
              "alias_bytes": 0, "generated_code_bytes": 0,
              "peak_bytes": 0}
        try:
            m = compiled.memory_analysis()
            fp["argument_bytes"] = int(m.argument_size_in_bytes)
            fp["output_bytes"] = int(m.output_size_in_bytes)
            fp["temp_bytes"] = int(m.temp_size_in_bytes)
            fp["alias_bytes"] = int(m.alias_size_in_bytes)
            fp["generated_code_bytes"] = int(m.generated_code_size_in_bytes)
            fp["peak_bytes"] = max(0, fp["argument_bytes"]
                                   + fp["output_bytes"] + fp["temp_bytes"]
                                   - fp["alias_bytes"])
        except Exception:
            pass  # a backend without the analysis still serves
        row = next(_ROW_SEQ)
        with _TABLE_LOCK:
            self._rows[sig] = row
            _FOOTPRINTS[row] = fp
            _SITE_BYTES[self.site] = (_SITE_BYTES.get(self.site, 0)
                                      + fp["peak_bytes"])
            site_bytes = _SITE_BYTES[self.site]
        if telemetry.enabled():
            telemetry.inc("mem.programs_compiled")
            telemetry.set_gauge("mem.program_bytes.%s" % self.site,
                                site_bytes)

    def _drop_sig(self, sig):
        self._compiled.pop(sig, None)
        row = self._rows.pop(sig, None)
        if row is not None:
            _release_rows([row], self.site)

    def footprint(self):
        """The most recently compiled signature's footprint row (a
        copy), or None before first compile / after fallback."""
        with self._lock, _TABLE_LOCK:
            for row in reversed(list(self._rows.values())):
                fp = _FOOTPRINTS.get(row)
                if fp is not None:
                    return dict(fp)
        return None

    def release(self):
        """Drop every compiled executable and remove this program's
        rows from the footprint table (eviction/close path)."""
        with self._lock:
            rows = list(self._rows.values())
            self._rows.clear()
            self._compiled.clear()
            self._current = None
        _release_rows(rows, self.site)

    def _forensics(self, err):
        write_postmortem(self.site, self.key, err,
                         program=self.footprint())


def _short(obj, limit=200):
    s = repr(obj)
    return s if len(s) <= limit else s[:limit] + "..."


def _release_rows(rows, site):
    from .. import telemetry

    freed = 0
    with _TABLE_LOCK:
        for row in rows:
            fp = _FOOTPRINTS.pop(row, None)
            if fp is not None:
                freed += fp["peak_bytes"]
        if site in _SITE_BYTES:
            _SITE_BYTES[site] = max(0, _SITE_BYTES[site] - freed)
            site_bytes = _SITE_BYTES[site]
        else:
            site_bytes = 0
    if rows and telemetry.enabled():
        telemetry.set_gauge("mem.program_bytes.%s" % site, site_bytes)


def program(fn, site, key=None, donate_argnums=()):
    """Build the compile-cache entry for `fn` at `site` (see
    :class:`Program`).  Drop-in for ``jax.jit(fn, donate_argnums=...)``
    at every executable-cache site."""
    return Program(fn, site, key=key, donate_argnums=donate_argnums)


def footprints(site=None):
    """The ProgramFootprint table (copies), newest last; `site` filters
    to one compile-cache site."""
    with _TABLE_LOCK:
        rows = [dict(fp) for _, fp in sorted(_FOOTPRINTS.items())]
    return rows if site is None else [f for f in rows if f["site"] == site]


def program_bytes(site=None):
    """Sum of registered programs' peak bytes — total or per site."""
    with _TABLE_LOCK:
        if site is not None:
            return _SITE_BYTES.get(site, 0)
        return sum(fp["peak_bytes"] for fp in _FOOTPRINTS.values())


def inject_oom(site_substr):
    """Arm (or with None disarm) a synthetic RESOURCE_EXHAUSTED at
    every :class:`Program` whose site contains `site_substr` — the
    chaos hook behind the injected-OOM test.  Returns the previous
    setting."""
    global _INJECT
    prev = _INJECT
    _INJECT = site_substr
    return prev


# ----------------------------------------------------------------------
# byte-budget admission
# ----------------------------------------------------------------------
_DEVICE_LIMIT = {}  # jax.Device -> bytes_limit or None (one query each)


def _device_limit(device=None):
    """``memory_stats()["bytes_limit"]`` of `device` (default: the device
    JAX computes on when nothing is placed) — None where the backend
    reports no limit (XLA:CPU)."""
    if device is None:
        from ..context import default_device

        device = default_device()
    if device not in _DEVICE_LIMIT:
        stats = device.memory_stats()
        _DEVICE_LIMIT[device] = (
            int(stats.get("bytes_limit", 0)) or None) if stats else None
    return _DEVICE_LIMIT[device]


def budget_bytes(device=None):
    """The admission budget: ``MXTPU_MEM_BUDGET_MB`` when set (> 0),
    else the memory limit of `device` — the device the tenant is bound
    to; default JAX's default device — as the platform reports it
    (None on XLA:CPU), else None = unlimited."""
    from .. import config

    mb = config.get("MXTPU_MEM_BUDGET_MB")
    if mb:
        return int(mb) << 20
    return _device_limit(device)


def headroom_bytes():
    """budget - live census bytes, or None when no budget is known."""
    budget = budget_bytes()
    if budget is None:
        return None
    return budget - live_bytes()


def admit(what, predicted_bytes, device=None):
    """Preflight `predicted_bytes` for `what` against the budget of
    `device` (the jax.Device the tenant is bound to): raise
    :class:`MemoryBudgetError` naming predicted vs available when it
    does not fit (the add_tenant gate — refuse at admission, not OOM
    mid-traffic).  Returns the predicted bytes for booking."""
    from .. import telemetry

    predicted = int(predicted_bytes)
    budget = budget_bytes(device)
    if budget is not None:
        live = live_bytes()
        if live + predicted > budget:
            if telemetry.enabled():
                telemetry.inc("mem.admission_refusals")
            raise MemoryBudgetError(
                "cannot admit %s: predicted footprint %.2f MB + %.2f MB "
                "already live exceeds the %.2f MB budget (headroom "
                "%.2f MB) — retire a tenant or raise MXTPU_MEM_BUDGET_MB"
                % (what, predicted / 2**20, live / 2**20, budget / 2**20,
                   max(0, budget - live) / 2**20))
    return predicted


def health_section(tenants=None):
    """The ``memory`` block of ModelServer.health() (rides the HEALTH_R
    frame to Router.health() unchanged): live/peak/budget/headroom plus
    per-tenant KV-ring bytes for the names in `tenants`.  Cheap by the
    health contract: census locks + dict reads, never the device."""
    live = census()
    total = sum(live.values())
    budget = budget_bytes()
    section = {
        "live_bytes": total,
        "peak_bytes": peak()["bytes"],
        "budget_bytes": budget,
        "headroom_bytes": None if budget is None else budget - total,
        "headroom_pct": (None if not budget
                         else 100.0 * max(0, budget - total) / budget),
        "program_bytes": program_bytes(),
        "by_tag": live,
        "tenants": {},
    }
    for t in (tenants or ()):
        kv = live.get("kv_ring.%s" % t)
        if kv:
            section["tenants"][t] = {"kv_ring_bytes": kv}
    return section


# ----------------------------------------------------------------------
# OOM forensics
# ----------------------------------------------------------------------
_LAST_POSTMORTEM = [None]


def last_postmortem_path():
    return _LAST_POSTMORTEM[0]


def _own_rank():
    try:
        return int(os.environ.get("MXTPU_PROCESS_ID", "0"))
    except ValueError:
        return 0


def write_postmortem(site, key, error, program=None):
    """Write ``MXTPU_OBS_DIR``/memory_postmortem.r<rank>.json (schema
    ``mxtpu-mem-postmortem-v1``, write-then-rename like the watchdog
    artifact): the failing program's footprint, the live census by
    tag, the top-K holders at the last peak, the full footprint table,
    and recent flight-recorder events.  Best-effort by contract — the
    original RESOURCE_EXHAUSTED must propagate whether or not the
    artifact lands.  Returns the path, or None."""
    from .. import telemetry
    from . import recorder

    rank = _own_rank()
    artifact = {
        "schema": "mxtpu-mem-postmortem-v1",
        "rank": rank,
        "wall_time": time.time(),
        "site": site,
        "key": _short(key),
        "error": _short(error, limit=2000),
        "program": program,
        "census": census(),
        "live_bytes": live_bytes(),
        "peak": peak(),
        "footprints": footprints(),
        "budget_bytes": budget_bytes(),
        "events": recorder.events(last_k=64) if recorder.enabled() else [],
    }
    if telemetry.enabled():
        telemetry.inc("mem.oom_postmortems")
    try:
        directory = os.environ.get("MXTPU_OBS_DIR", "") or "."
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory,
                            "memory_postmortem.r%d.json" % rank)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=1, default=str)
        os.replace(tmp, path)
    except OSError:
        return None
    _LAST_POSTMORTEM[0] = path
    return path


def reset():
    """Test helper: clear the census, the footprint table, the peak
    tracker, and any armed injection.  Live Program objects keep their
    executables but re-register footprints on their next compile."""
    global _LIVE_TOTAL, _PEAK, _INJECT
    with _CENSUS_LOCK:
        _LIVE.clear()
        _LIVE_TOTAL = 0
        _PEAK = {"bytes": 0, "top": [], "wall_time": None}
    with _TABLE_LOCK:
        _FOOTPRINTS.clear()
        _SITE_BYTES.clear()
    _INJECT = None
    _DEVICE_LIMIT.clear()
    _LAST_POSTMORTEM[0] = None
