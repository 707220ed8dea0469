"""mxlint (tools/analysis) — the static scheduling-contract gate.

Tier-1 on purpose: `test_repo_is_lint_clean` runs the full check suite
over mxnet_tpu/ exactly like `python -m tools.analysis mxnet_tpu`, so a
PR that introduces an undeclared engine dependency (E001), a sync call
inside an op (E002), a leaked Var (E003), or an undocumented env knob
(W103) fails CI here.  The rest unit-tests each check against synthetic
sources so the framework itself cannot silently rot.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.analysis import run_paths  # noqa: E402


def _lint_src(tmp_path, src, name="snippet.py", config_src=None):
    """Lint one synthetic file; a minimal mxnet_tpu/config.py can be
    provided so W103 has a registry to resolve against."""
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "config.py").write_text(config_src or "REGISTRY = []\n")
    p = pkg / name
    p.write_text(src)
    return run_paths([str(p)])


def _ids(findings):
    return [f.check_id for f in findings]


# ----------------------------------------------------------------------
# the repo gate
# ----------------------------------------------------------------------

def test_repo_is_lint_clean():
    """`python -m tools.analysis mxnet_tpu chip_smoke.py tools` must
    exit 0: every finding fixed or allowlisted with a justification
    (docs/static_analysis.md).  chip_smoke.py is in the sweep because
    it is the root script left that drives the framework on the chip;
    ISSUE 12 widened the target from tools/bandwidth + tools/launch.py
    to ALL of tools/ — the trace/SPMD checks (E006/E007) apply to the
    bandwidth tool's jit+psum probes and the new check modules
    themselves must hold their own gate."""
    findings, suppressed, errors = run_paths(
        [os.path.join(ROOT, "mxnet_tpu"),
         os.path.join(ROOT, "chip_smoke.py"),
         os.path.join(ROOT, "tools")])
    assert not errors, errors
    assert not findings, "\n".join(str(f) for f in findings)
    # the allowlist is in use and every entry carries its justification
    for f in suppressed:
        assert "[allowlisted:" in f.message


def test_repo_gate_sweeps_bandwidth_tool_and_launcher():
    """ISSUE 10 pin: the gate walk covers tools/bandwidth/ and
    tools/launch.py (iter_py_files resolves files and directories), so
    a future target-list edit cannot silently drop them."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "tools", "bandwidth"),
                           os.path.join(ROOT, "tools", "launch.py")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    assert os.path.join("tools", "bandwidth", "measure.py") in swept
    assert os.path.join("tools", "launch.py") in swept


def test_cli_runs_and_is_clean():
    import subprocess

    r = subprocess.run(
        [sys.executable, "-m", "tools.analysis", "mxnet_tpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr


def test_repo_gate_sweeps_the_serving_package():
    """The gate's directory walk must cover mxnet_tpu/serving/ — the
    batcher pushes engine callbacks and per-request telemetry, exactly
    the surfaces E001/E002/E004 exist for.  Pinned so a future repack
    (or an over-broad _SKIP_DIRS entry) cannot silently drop it."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    for mod in ("__init__", "request", "bucket", "session", "server"):
        assert os.path.join("mxnet_tpu", "serving", "%s.py" % mod) in swept


def test_repo_gate_sweeps_the_data_package():
    """Same pin for mxnet_tpu/data/ — the data service's consumer fetch
    rides engine ops and books per-batch telemetry (docs/data.md), so
    every E00x surface exists there too."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    for mod in ("__init__", "service", "worker", "iter", "shm"):
        assert os.path.join("mxnet_tpu", "data", "%s.py" % mod) in swept


def test_repo_gate_sweeps_the_router_package():
    """Same pin for mxnet_tpu/router/ (ISSUE 14) — the router books
    per-request telemetry on the resolve path and its poll/reader
    threads are exactly where a blocking sync would wedge the tier, so
    the E002/E004 surfaces exist there too."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    for mod in ("__init__", "wire", "agent", "policy", "router"):
        assert os.path.join("mxnet_tpu", "router", "%s.py" % mod) in swept


# ----------------------------------------------------------------------
# E001 — undeclared dependencies
# ----------------------------------------------------------------------

E001_UNDECLARED = """
def schedule(eng, a, b, out):
    def cb():
        out._set_data(a._raw() + b._raw())
    eng.push(cb, read_vars=[a._engine_var()], write_vars=[out._engine_var()])
"""


def test_e001_flags_undeclared_closure_read(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E001_UNDECLARED)
    assert _ids(findings) == ["E001"]
    assert "`b`" in findings[0].message


E001_DECLARED = """
def schedule(eng, arrs, out):
    read_vars = [g._engine_var() for g in arrs]

    def cb(_arrs=arrs, _out=out):
        acc = _arrs[0]._raw()
        for g in _arrs[1:]:
            acc = acc + g._raw()
        _out._set_data(acc)
    eng.push(cb, read_vars=read_vars, write_vars=[out._engine_var()])
"""


def test_e001_follows_default_bindings_and_loops(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E001_DECLARED)
    assert findings == []


E001_LIST_BUILD = """
def schedule(eng, k, stored, grads, key_var):
    ws = [key_var]
    ws.append(stored._engine_var())

    def cb(_stored=stored, _grads=grads):
        _stored._set_data(_grads[0]._raw())
    eng.push(cb, read_vars=[g._engine_var() for g in grads], write_vars=ws)
"""


def test_e001_follows_imperative_list_construction(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E001_LIST_BUILD)
    assert findings == []


E001_SELF_STORE = """
class KV:
    def push(self, eng, k, merged, key_var):
        def cb(_k=k, _merged=merged):
            self._store[_k] = _merged
        eng.push(cb, read_vars=[merged._engine_var()], write_vars=[key_var])
"""


def test_e001_flags_shared_container_write(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E001_SELF_STORE)
    assert _ids(findings) == ["E001"]
    assert "self._store" in findings[0].message


E001_STAGING_UNDECLARED = """
def stage_blocks(eng, source, staged, slot_var):
    def fetch():
        block = source._raw()
        staged._set_data(block)
    eng.push(fetch, read_vars=[source._engine_var()], write_vars=[slot_var])
"""


def test_e001_flags_undeclared_staging_buffer_write(tmp_path):
    """A staging-style callback (background H2D double buffering, the
    io.DeviceStagedIter shape) that writes its staging buffer without
    declaring it: the scheduler can't order the write against the
    consumer's read of the same buffer."""
    findings, _, _ = _lint_src(tmp_path, E001_STAGING_UNDECLARED)
    assert _ids(findings) == ["E001"]
    assert "`staged`" in findings[0].message


E001_STAGING_DECLARED = """
def stage_blocks(eng, source, staged, slot_var):
    def fetch(_src=source, _dst=staged):
        _dst._set_data(_src._raw())
    eng.push(fetch, read_vars=[source._engine_var()],
             write_vars=[slot_var, staged._engine_var()])
"""


def test_e001_staging_callback_with_declared_buffer_is_clean(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E001_STAGING_DECLARED)
    assert findings == []


E001_NON_ATOMIC = """
def schedule(eng, a, v):
    def cb():
        return a.asnumpy()
    eng.push(cb, write_vars=[v], atomic=False)
"""


def test_e001_e002_exempt_non_atomic_ops(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E001_NON_ATOMIC)
    assert findings == []


# ----------------------------------------------------------------------
# E002 — sync calls inside atomic callbacks
# ----------------------------------------------------------------------

E002_SYNC = """
def schedule(eng, a, v):
    def cb():
        a.wait_to_read()
        x = a.asnumpy()
        y = a.data + 1
    eng.push(cb, read_vars=[a._engine_var()], write_vars=[v])
"""


def test_e002_flags_sync_calls_and_data_reads(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_SYNC)
    got = _ids(findings)
    assert got.count("E002") == 3, findings
    assert any("`.data`" in f.message for f in findings)


def test_missing_path_is_an_error_not_a_clean_pass(tmp_path):
    findings, _, errors = run_paths([str(tmp_path / "no_such_dir")])
    assert findings == []
    assert len(errors) == 1 and "does not exist" in errors[0][1]


# a serving-batcher-shaped callback (serving/session.py dispatch): an
# ATOMIC readback op that syncs on its outputs instead of reading the
# raw payloads — exactly the deadlock shape E002 exists for (a blocked
# worker starves the pool that must run the fill it waits on).  The
# real pipeline pushes atomic=False (ThreadedIter convention); this
# corpus pins that E002 still fires if someone "tightens" it to atomic.
E002_SERVING_READBACK = """
def dispatch(eng, outs, reqs, slot_var):
    def readback(_outs=outs, _reqs=reqs):
        for o in _outs:
            o.wait_to_read()
        host = [o.asnumpy() for o in _outs]
        for i, r in enumerate(_reqs):
            r.future.set_result([h[i] for h in host])
    eng.push(readback, read_vars=[o._engine_var() for o in outs],
             write_vars=[slot_var])
"""


def test_e002_fires_on_atomic_serving_readback(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_SERVING_READBACK)
    got = _ids(findings)
    assert got.count("E002") == 2, findings  # wait_to_read + asnumpy
    assert any("wait_to_read" in f.message for f in findings)


E002_SERVING_NON_ATOMIC = """
def dispatch(eng, outs, reqs, slot_var):
    def readback(_outs=outs, _reqs=reqs):
        host = [o.asnumpy() for o in _outs]
        for i, r in enumerate(_reqs):
            r.future.set_result([h[i] for h in host])
    eng.push(readback, read_vars=[o._engine_var() for o in outs],
             write_vars=[slot_var], atomic=False)
"""


def test_e002_serving_readback_clean_when_non_atomic(tmp_path):
    """The shape the real pipeline uses: atomic=False keeps normal sync
    semantics, so the readback may block on payloads."""
    findings, _, _ = _lint_src(tmp_path, E002_SERVING_NON_ATOMIC)
    assert findings == []


# a data-service-consumer-shaped callback (data/iter.py _fetch runs as a
# ThreadedIter engine op): the fetch blocks on the worker's full queue
# and then SYNCS on a staged NDArray it built — fine under the
# ThreadedIter atomic=False convention, a pool-deadlock shape the moment
# someone "tightens" the push to atomic.  Corpus pins both sides.
E002_DATA_FETCH_ATOMIC = """
def schedule_fetch(eng, svc, staged, iter_var):
    def fetch(_svc=svc, _staged=staged):
        data, label, pad, meta = _svc.next_batch()
        out = _staged.put(data, label)
        out.wait_to_read()
        return out.asnumpy(), pad
    eng.push(fetch, write_vars=[iter_var])
"""

E002_DATA_FETCH_NON_ATOMIC = """
def schedule_fetch(eng, svc, staged, iter_var):
    def fetch(_svc=svc, _staged=staged):
        data, label, pad, meta = _svc.next_batch()
        out = _staged.put(data, label)
        out.wait_to_read()
        return out.asnumpy(), pad
    eng.push(fetch, write_vars=[iter_var], atomic=False)
"""


def test_e002_fires_on_atomic_data_fetch(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_DATA_FETCH_ATOMIC)
    got = _ids(findings)
    assert got.count("E002") == 2, findings  # wait_to_read + asnumpy
    findings, _, _ = _lint_src(tmp_path, E002_DATA_FETCH_NON_ATOMIC)
    assert findings == []


# a router-poll-shaped callback (ISSUE 14: the health-poll tick pushed
# as an engine op): the poll syncs on a staged health tensor inside an
# ATOMIC callback — on a worker the fence is a silent no-op and the
# "fresh" probe reads stale bytes, or the blocked worker starves the
# pool serving the very replica it polls.  The real router polls on a
# plain thread (no engine op at all); this corpus pins that E002 fires
# the moment someone routes the poll through an atomic push.
E002_ROUTER_POLL_ATOMIC = """
def schedule_poll(eng, replicas, staged, poll_var):
    def poll(_reps=replicas, _staged=staged):
        for rep in _reps:
            rep.probe_op(_staged)
        _staged.wait_to_read()
        depths = _staged.asnumpy()
        for rep, depth in zip(_reps, depths):
            rep.last_depth = float(depth)
    eng.push(poll, read_vars=[staged._engine_var()],
             write_vars=[poll_var])
"""

E002_ROUTER_POLL_NON_ATOMIC = """
def schedule_poll(eng, replicas, staged, poll_var):
    def poll(_reps=replicas, _staged=staged):
        for rep in _reps:
            rep.probe_op(_staged)
        _staged.wait_to_read()
        depths = _staged.asnumpy()
        for rep, depth in zip(_reps, depths):
            rep.last_depth = float(depth)
    eng.push(poll, read_vars=[staged._engine_var()],
             write_vars=[poll_var], atomic=False)
"""


def test_e002_fires_on_atomic_router_poll(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_ROUTER_POLL_ATOMIC)
    got = _ids(findings)
    assert got.count("E002") == 2, findings  # wait_to_read + asnumpy
    findings, _, _ = _lint_src(tmp_path, E002_ROUTER_POLL_NON_ATOMIC)
    assert findings == []


# a decode-loop-shaped callback (serving/decode.py decode_step: sample
# the packed logits, book tokens, retire sessions): the real loop runs
# SYNCHRONOUSLY on the batcher thread — reading logits back is its whole
# job — but routed through an ATOMIC engine push the readback becomes
# the canonical pool deadlock (the blocked worker starves the pool that
# must run the very decode program it waits on).  Corpus pins that E002
# fires the moment someone "pipelines" the decode tick onto the engine,
# and stays quiet under the atomic=False ThreadedIter convention.
E002_DECODE_STEP_ATOMIC = """
def schedule_decode(eng, logits, sessions, ring_var):
    def step(_logits=logits, _sessions=sessions):
        _logits.wait_to_read()
        host = _logits.asnumpy()
        for i, sess in enumerate(_sessions):
            sess.emit(int(host[i].argmax()))
    eng.push(step, read_vars=[logits._engine_var()],
             write_vars=[ring_var])
"""

E002_DECODE_STEP_NON_ATOMIC = """
def schedule_decode(eng, logits, sessions, ring_var):
    def step(_logits=logits, _sessions=sessions):
        _logits.wait_to_read()
        host = _logits.asnumpy()
        for i, sess in enumerate(_sessions):
            sess.emit(int(host[i].argmax()))
    eng.push(step, read_vars=[logits._engine_var()],
             write_vars=[ring_var], atomic=False)
"""


def test_e002_fires_on_atomic_decode_step(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_DECODE_STEP_ATOMIC)
    got = _ids(findings)
    assert got.count("E002") == 2, findings  # wait_to_read + asnumpy
    findings, _, _ = _lint_src(tmp_path, E002_DECODE_STEP_NON_ATOMIC)
    assert findings == []


# ----------------------------------------------------------------------
# E004 — telemetry/profiler recording must be behind the fast path
# ----------------------------------------------------------------------

E004_UNGUARDED = """
import time
from . import profiler, telemetry

def hot_loop(ops):
    for op in ops:
        t0 = time.time()
        op()
        telemetry.observe("engine.op_seconds", time.time() - t0)
        profiler.record_span("op", int(t0 * 1e6), 1)
"""


def test_e004_flags_unguarded_recording_calls(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_UNGUARDED)
    assert _ids(findings) == ["E004", "E004"]
    assert "telemetry.observe" in findings[0].message
    assert "profiler.record_span" in findings[1].message


E004_IF_GUARDED = """
import time
from . import profiler, telemetry

def hot_loop(ops):
    for op in ops:
        t0 = time.time()
        op()
        if telemetry.enabled():
            telemetry.observe("engine.op_seconds", time.time() - t0)
        if profiler.spans_active():
            profiler.record_span("op", int(t0 * 1e6), 1)
"""

E004_VAR_GUARDED = """
import time
from . import profiler, telemetry

def hot_loop(ops):
    prof = profiler.spans_active()
    tel = telemetry.enabled()
    timed = prof or tel
    for op in ops:
        t0 = time.time() if timed else 0.0
        op()
        if timed:
            t1 = time.time()
            if prof:
                profiler.record_span("op", int(t0 * 1e6), int(t1 - t0))
            if tel:
                telemetry.observe("engine.op_seconds", t1 - t0)
"""

E004_EARLY_RETURN = """
from . import telemetry

def note_dispatch(kind, elapsed):
    if not telemetry.enabled():
        return
    telemetry.inc("executor.train_dispatches")
    telemetry.observe("executor.dispatch_seconds." + kind, elapsed)
"""


def test_e004_accepts_the_three_guard_shapes(tmp_path):
    for src in (E004_IF_GUARDED, E004_VAR_GUARDED, E004_EARLY_RETURN):
        findings, _, _ = _lint_src(tmp_path, src)
        assert findings == [], findings


# the decode loop's own instrumentation (serving/decode.py decode_step
# books 2 counters, a histogram, and 3 gauges PER TOKEN-LEVEL STEP —
# the hottest serving path in the tree): unguarded, that is six
# registry locks per generated token.  The real loop guards with one
# `if telemetry.enabled():`; corpus pins both the violation and the
# shipped shape.
E004_DECODE_UNGUARDED = """
import time
from . import telemetry

def decode_step(active, run, bucket):
    t0 = time.monotonic()
    logits = run(active)
    dt = time.monotonic() - t0
    telemetry.inc("serving.decode.dispatches")
    telemetry.inc("serving.decode.tokens", len(active))
    telemetry.observe("serving.decode.step_seconds", dt)
    telemetry.set_gauge("serving.decode.batch_fill_ratio",
                        len(active) / bucket)
    return logits
"""

E004_DECODE_GUARDED = """
import time
from . import telemetry

def decode_step(active, run, bucket):
    t0 = time.monotonic()
    logits = run(active)
    dt = time.monotonic() - t0
    if telemetry.enabled():
        telemetry.inc("serving.decode.dispatches")
        telemetry.inc("serving.decode.tokens", len(active))
        telemetry.observe("serving.decode.step_seconds", dt)
        telemetry.set_gauge("serving.decode.batch_fill_ratio",
                            len(active) / bucket)
    return logits
"""


def test_e004_covers_the_decode_loop_shape(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_DECODE_UNGUARDED)
    assert _ids(findings).count("E004") == 4, findings
    findings, _, _ = _lint_src(tmp_path, E004_DECODE_GUARDED)
    assert findings == [], findings


# the live-buffer census (obs/memory.py): book/rebook sit on every
# NDArray materialization — the same guard contract as telemetry.
# unbook is deliberately EXEMPT: it must run whenever the matching
# book ran, whatever the CURRENT telemetry state, or an
# enabled->disabled flip mid-lifetime leaks census bytes forever.
E004_MEM_UNGUARDED = """
from .obs import memory

def materialize(holder, value):
    holder.payload = value
    memory.book("ndarray.cpu", value.nbytes)
    memory.rebook("ndarray.cpu", 0, value.nbytes)
"""

E004_MEM_GUARDED = """
from . import telemetry
from .obs import memory

def materialize(holder, value):
    holder.payload = value
    if telemetry.enabled():
        holder.booked = value.nbytes
        memory.book("ndarray.cpu", holder.booked)

def release(holder):
    # the balancing half runs UNGUARDED by design (exempt from E004)
    memory.unbook("ndarray.cpu", holder.booked)
    holder.booked = 0
"""


def test_e004_covers_census_booking_but_exempts_unbook(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_MEM_UNGUARDED)
    assert _ids(findings) == ["E004", "E004"], findings
    assert "memory.book" in findings[0].message
    assert "telemetry.enabled()" in findings[0].message
    assert "memory.rebook" in findings[1].message
    findings, _, _ = _lint_src(tmp_path, E004_MEM_GUARDED)
    assert findings == [], findings


E004_WRONG_GUARD = """
from . import telemetry

def hot(flag):
    if flag:  # not the fast path: arbitrary condition
        telemetry.inc("c")
"""

E004_INVERTED_GUARD = """
from . import telemetry

def hot():
    if telemetry.enabled():
        return  # inverted: the call below runs exactly when DISABLED
    telemetry.inc("c")
"""

E004_NESTED_GUARD = """
from . import telemetry

def hot(x):
    if x:
        if not telemetry.enabled():
            return
    telemetry.inc("c")  # unguarded when x is falsy
"""


def test_e004_arbitrary_condition_is_not_a_guard(tmp_path):
    for src in (E004_WRONG_GUARD, E004_INVERTED_GUARD, E004_NESTED_GUARD):
        findings, _, _ = _lint_src(tmp_path, src)
        assert _ids(findings) == ["E004"], (src, findings)


# a serving-batcher-shaped hot loop: per-request latency observation and
# queue-depth gauge inside the fill/readback path — the highest-rate
# instrumentation sites in the framework (once per REQUEST, not once per
# step), so an unguarded call here is exactly the regression E004 guards
# against
E004_SERVING_UNGUARDED = """
import time
from . import telemetry

def resolve_fill(reqs, host_outs, tenant):
    now = time.monotonic()
    for i, r in enumerate(reqs):
        r.future.set_result([h[i] for h in host_outs])
        telemetry.inc("serving.requests." + tenant)
        telemetry.observe("serving.request_seconds", now - r.arrival)
    telemetry.set_gauge("serving.queue_depth", 0)
"""

E004_SERVING_GUARDED = """
import time
from . import telemetry

def resolve_fill(reqs, host_outs, tenant):
    now = time.monotonic()
    tel = telemetry.enabled()
    for i, r in enumerate(reqs):
        r.future.set_result([h[i] for h in host_outs])
        if tel:
            telemetry.inc("serving.requests." + tenant)
            telemetry.observe("serving.request_seconds", now - r.arrival)
    if tel:
        telemetry.set_gauge("serving.queue_depth", 0)
"""


def test_e004_fires_on_unguarded_serving_batcher_telemetry(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_SERVING_UNGUARDED)
    assert _ids(findings) == ["E004", "E004", "E004"], findings
    findings, _, _ = _lint_src(tmp_path, E004_SERVING_GUARDED)
    assert findings == []


# a data-service-consumer-shaped hot loop (data/service.py next_batch
# booking worker stats once per BATCH): per-batch histogram + per-worker
# byte counter + two gauges — unguarded, that is four argument
# constructions per batch with telemetry off
E004_DATA_BOOK_UNGUARDED = """
from . import telemetry

def book(meta, occupancy, alive):
    telemetry.inc("data.batches_produced")
    telemetry.observe("data.decode_seconds", meta["decode_s"])
    telemetry.inc("data.worker_bytes.w%d" % meta["w"], meta["bytes"])
    telemetry.set_gauge("data.ring_occupancy", occupancy())
"""

E004_DATA_BOOK_GUARDED = """
from . import telemetry

def book(meta, occupancy, alive):
    if not telemetry.enabled():
        return
    telemetry.inc("data.batches_produced")
    telemetry.observe("data.decode_seconds", meta["decode_s"])
    telemetry.inc("data.worker_bytes.w%d" % meta["w"], meta["bytes"])
    telemetry.set_gauge("data.ring_occupancy", occupancy())
"""


def test_e004_fires_on_unguarded_data_service_booking(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_DATA_BOOK_UNGUARDED)
    assert _ids(findings) == ["E004"] * 4, findings
    findings, _, _ = _lint_src(tmp_path, E004_DATA_BOOK_GUARDED)
    assert findings == []


# a router-resolve-shaped hot path (ISSUE 14, router/router.py: once
# per ROUTED REQUEST — the tier's highest-rate instrumentation site,
# plus the death path's redispatch booking): the `router.*` namespace
# must ride the same enabled() fast path as every other layer.  Corpus
# pins both sides so the guard discipline survives refactors.
E004_ROUTER_UNGUARDED = """
import time
from . import telemetry

def resolve(flight, arrays, replay):
    flight.future.set_result(arrays)
    telemetry.inc("router.requests")
    telemetry.observe("router.route_seconds",
                      time.monotonic() - flight.t_submit)
    if replay:
        telemetry.inc("router.redispatches")
"""

E004_ROUTER_GUARDED = """
import time
from . import telemetry

def resolve(flight, arrays, replay):
    flight.future.set_result(arrays)
    if telemetry.enabled():
        telemetry.inc("router.requests")
        telemetry.observe("router.route_seconds",
                          time.monotonic() - flight.t_submit)
    if replay and telemetry.enabled():
        telemetry.inc("router.redispatches")
"""


def test_e004_fires_on_unguarded_router_telemetry(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_ROUTER_UNGUARDED)
    assert _ids(findings) == ["E004"] * 3, findings
    findings, _, _ = _lint_src(tmp_path, E004_ROUTER_GUARDED)
    assert findings == []


# ----------------------------------------------------------------------
# E005 — registered op kernels must not sync on operands (lazy fusion)
# ----------------------------------------------------------------------

def _lint_ops_src(tmp_path, src, name="snippet.py"):
    """Like _lint_src but under mxnet_tpu/ops/, where E005 applies."""
    pkg = tmp_path / "mxnet_tpu"
    ops = pkg / "ops"
    ops.mkdir(parents=True, exist_ok=True)
    (pkg / "config.py").write_text("REGISTRY = []\n")
    p = ops / name
    p.write_text(src)
    return run_paths([str(p)])


E005_DECORATED = """
from .registry import register

@register("bad_op", inputs=("data",))
def bad_op(data, **kw):
    host = data.asnumpy()
    return host + data.data
"""

E005_DIRECT_LAMBDA = """
from .registry import register

register("bad_scalar")(lambda data, scalar=1.0, **kw: data.wait_to_read())
"""

E005_FACTORY_LAMBDA = """
from .registry import register

def _reg_scalar(name, fn):
    register(name, inputs=("data",))(
        (lambda f: lambda data, scalar=1.0, **kw: f(data.data, scalar))(fn)
    )
"""

E005_CLEAN = """
import jax.numpy as jnp
from .registry import register

@register("good_op", inputs=("data",), lift_floats=True)
def good_op(data, scalar=1.0, **kw):
    return jnp.abs(data) * scalar

def helper(nd):
    # not a registered op: host access is fine here
    return nd.asnumpy()
"""


def test_e005_flags_sync_in_registered_ops(tmp_path):
    findings, _, _ = _lint_ops_src(tmp_path, E005_DECORATED)
    got = _ids(findings)
    assert got.count("E005") == 2, findings  # .asnumpy() AND .data
    assert any("`.asnumpy()`" in f.message for f in findings)
    assert any("`.data`" in f.message for f in findings)
    assert any("`bad_op`" in f.message for f in findings)


def test_e005_covers_direct_and_factory_registration(tmp_path):
    findings, _, _ = _lint_ops_src(tmp_path, E005_DIRECT_LAMBDA)
    assert _ids(findings) == ["E005"]
    assert "wait_to_read" in findings[0].message
    findings, _, _ = _lint_ops_src(tmp_path, E005_FACTORY_LAMBDA)
    assert _ids(findings) == ["E005"]


def test_e005_clean_kernel_and_non_ops_file(tmp_path):
    findings, _, _ = _lint_ops_src(tmp_path, E005_CLEAN)
    assert findings == []
    # the same sync-y source OUTSIDE mxnet_tpu/ops/ is out of scope
    findings, _, _ = _lint_src(tmp_path, E005_DECORATED)
    assert "E005" not in _ids(findings)


# ----------------------------------------------------------------------
# E003 — leaked Vars
# ----------------------------------------------------------------------

E003_LEAKS = """
def leak_discard(eng):
    eng.new_variable()

def leak_unused(eng):
    v = eng.new_variable()
    return 3

def fine(eng):
    v = eng.new_variable()
    eng.push(lambda: None, write_vars=[v])

def fine_closure(eng):
    v = eng.new_variable()

    def cb():
        return None
    eng.push(cb, write_vars=[v])
"""


def test_e003_flags_leaked_vars_only(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E003_LEAKS)
    assert _ids(findings) == ["E003", "E003"]
    assert findings[0].line < findings[1].line <= 7


# ----------------------------------------------------------------------
# W1xx — general checks
# ----------------------------------------------------------------------

W_GENERAL = """
def f(x=[]):
    try:
        return x
    except:
        pass
"""


def test_w101_and_w102(tmp_path):
    findings, _, _ = _lint_src(tmp_path, W_GENERAL)
    assert sorted(_ids(findings)) == ["W101", "W102"]


W103_CONFIG = """
EnvVar = None
REGISTRY = [EnvVar("MXNET_DOCUMENTED", str, "", "doc'd")]
ABSORBED = {"MXNET_ABSORBED": "xla"}
"""

W103_READS = """
import os
a = os.environ.get("MXNET_DOCUMENTED", "")
b = os.environ.get("MXNET_ABSORBED")
c = os.environ["MXTPU_SECRET_KNOB"]
d = os.environ.get("HOME")  # not a framework var: out of scope
"""


def test_w103_flags_only_undocumented_framework_vars(tmp_path):
    findings, _, _ = _lint_src(tmp_path, W103_READS, config_src=W103_CONFIG)
    assert _ids(findings) == ["W103"]
    assert "MXTPU_SECRET_KNOB" in findings[0].message


# the MFU-sink knobs (docs/perf.md "MFU sinks"): reads are W103 findings
# unless the registry declares them — pinned per knob so dropping a
# registration (or reading a knob the registry never gained) fails tier-1
SINK_KNOB_READS = """
import os
a = os.environ.get("MXTPU_BF16_WGRAD")
c = os.environ.get("MXNET_TPU_S2D_STEM")
"""

SINK_KNOB_CONFIG = """
EnvVar = None
REGISTRY = [EnvVar("MXTPU_BF16_WGRAD", int, 0, "bf16 wgrad"),
            EnvVar("MXNET_TPU_S2D_STEM", int, 0, "s2d stem fold")]
ABSORBED = {}
"""


def test_w103_sink_knobs_must_be_registered(tmp_path):
    findings, _, _ = _lint_src(tmp_path, SINK_KNOB_READS)
    assert _ids(findings) == ["W103", "W103"]
    hit = "\n".join(f.message for f in findings)
    for name in ("MXTPU_BF16_WGRAD", "MXNET_TPU_S2D_STEM"):
        assert name in hit


def test_w103_sink_knobs_clean_when_registered(tmp_path):
    findings, _, _ = _lint_src(tmp_path, SINK_KNOB_READS,
                               config_src=SINK_KNOB_CONFIG)
    assert findings == []


def test_sink_knobs_registered_in_real_config():
    """The real registry declares every MFU-sink knob (so the generated
    env_var.md documents them and W103 lets framework reads through)."""
    import ast

    cfg = os.path.join(ROOT, "mxnet_tpu", "config.py")
    with open(cfg, "rb") as f:
        tree = ast.parse(f.read().decode("utf-8"))
    names = {n.args[0].value for n in ast.walk(tree)
             if isinstance(n, ast.Call) and getattr(n.func, "id", "") == "EnvVar"
             and n.args and isinstance(n.args[0], ast.Constant)}
    for knob in ("MXTPU_BF16_WGRAD", "MXNET_TPU_S2D_STEM"):
        assert knob in names, knob


# ----------------------------------------------------------------------
# allowlist semantics
# ----------------------------------------------------------------------

ALLOW_TRAILING = """
def f(x={}):  # mxlint: disable=W101 -- read-only sentinel, never mutated
    return x
"""

ALLOW_STANDALONE = """
# mxlint: disable=W101 -- read-only sentinel, never mutated
def f(x={}):
    return x
"""

ALLOW_NO_REASON = """
def f(x={}):  # mxlint: disable=W101
    return x
"""


def test_allowlist_with_justification_suppresses(tmp_path):
    for src in (ALLOW_TRAILING, ALLOW_STANDALONE):
        findings, suppressed, _ = _lint_src(tmp_path, src)
        assert findings == []
        assert _ids(suppressed) == ["W101"]
        assert "never mutated" in suppressed[0].message


def test_allowlist_without_justification_is_inert_and_reported(tmp_path):
    findings, suppressed, _ = _lint_src(tmp_path, ALLOW_NO_REASON)
    assert sorted(_ids(findings)) == ["L001", "W101"]
    assert suppressed == []


def test_file_level_allowlist(tmp_path):
    src = ("# mxlint: disable-file=W102 -- exercising file-wide suppression\n"
           "try:\n    pass\nexcept:\n    pass\n"
           "try:\n    pass\nexcept:\n    pass\n")
    findings, suppressed, _ = _lint_src(tmp_path, src)
    assert findings == []
    assert _ids(suppressed) == ["W102", "W102"]


# ----------------------------------------------------------------------
# ISSUE 10 corpus — dist control-plane callbacks (parallel/dist.py /
# multi-process runtime shapes)
# ----------------------------------------------------------------------

# a dist_sync-shaped pushed comm callback: the worker pushes a per-key
# engine op that RPCs the parameter server and then SYNCS on the pulled
# array inside an atomic op — the pool-starvation shape E002 exists
# for (the blocked worker can occupy the thread the producing op
# needs).  The real control plane reads raw payloads (declared vars)
# or pushes atomic=False.
E002_DIST_PUSH_SYNC = """
def dist_push(eng, kv, key, grad, key_var):
    def rpc(_kv=kv, _key=key, _grad=grad):
        _grad.wait_to_read()
        _kv._rpc(0, 6, payload=_grad.asnumpy().tobytes())
    eng.push(rpc, read_vars=[grad._engine_var()], write_vars=[key_var])
"""

E002_DIST_PUSH_CLEAN = """
def dist_push(eng, kv, key, grad, key_var):
    def rpc(_kv=kv, _key=key, _grad=grad):
        _kv._rpc(0, 6, payload=_grad._raw().tobytes())
    eng.push(rpc, read_vars=[grad._engine_var()], write_vars=[key_var])
"""


def test_e002_fires_on_blocking_sync_in_dist_comm_callback(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_DIST_PUSH_SYNC)
    got = _ids(findings)
    assert got.count("E002") == 2, findings  # wait_to_read + asnumpy
    assert any("wait_to_read" in f.message for f in findings)


def test_e002_dist_comm_callback_clean_on_raw_payload(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_DIST_PUSH_CLEAN)
    assert findings == []


# the bucket hot path (executor.fused_update_block comm accounting):
# per-dispatch bucket-byte booking must sit behind telemetry.enabled()
# — E004's contract — or every dispatch pays the recording cost even
# with the registry off.
E004_BUCKET_HOT_PATH = """
from mxnet_tpu import telemetry


def dispatch_block(plan, k):
    telemetry.inc("comm.dispatches")
    telemetry.inc("comm.bytes_reduced", sum(plan) * k)
    for nb in plan:
        telemetry.observe("comm.bucket_bytes", nb)
"""

E004_BUCKET_HOT_PATH_GUARDED = """
from mxnet_tpu import telemetry


def dispatch_block(plan, k):
    if telemetry.enabled():
        telemetry.inc("comm.dispatches")
        telemetry.inc("comm.bytes_reduced", sum(plan) * k)
        for nb in plan:
            telemetry.observe("comm.bucket_bytes", nb)
"""


def test_e004_fires_on_unguarded_bucket_telemetry(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_BUCKET_HOT_PATH)
    assert _ids(findings).count("E004") == 3, findings


def test_e004_bucket_telemetry_clean_when_guarded(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_BUCKET_HOT_PATH_GUARDED)
    assert findings == []


def test_repo_gate_sweeps_the_obs_package():
    """ISSUE 11 pin: the gate walk covers mxnet_tpu/obs/ — the flight
    recorder's record() sits on the fused-dispatch hot path, so the
    E004 guard contract applies there exactly as to telemetry.
    tracing.py (ISSUE 15) joins the list: its record/flow calls sit
    once per SERVED REQUEST, the serving tier's hottest sites."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    for mod in ("__init__", "recorder", "watchdog", "aggregate",
                "tracing", "memory"):
        assert os.path.join("mxnet_tpu", "obs", "%s.py" % mod) in swept


# the flight-recorder hot path (executor fused dispatch bracket): an
# unguarded recorder.record() pays detail-string formatting and byte
# sums on EVERY dispatch even with the recorder off — the same E004
# contract as telemetry, with recorder.enabled() as the fast path.
E004_RECORDER_HOT_PATH = """
from mxnet_tpu.obs import recorder


def dispatch(seq, k, plan):
    recorder.record("dispatch", "enter", seq,
                    detail="block(K=%d,buckets=%d)" % (k, len(plan)),
                    nbytes=sum(plan) * k)
    run()
    recorder.record("dispatch", "exit", seq)
"""

E004_RECORDER_HOT_PATH_GUARDED = """
from mxnet_tpu.obs import recorder


def dispatch(seq, k, plan):
    rec = recorder.enabled()
    if rec:
        recorder.record("dispatch", "enter", seq,
                        detail="block(K=%d,buckets=%d)" % (k, len(plan)),
                        nbytes=sum(plan) * k)
    run()
    if rec:
        recorder.record("dispatch", "exit", seq)
"""


def test_e004_fires_on_unguarded_recorder_record(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_RECORDER_HOT_PATH)
    got = _ids(findings)
    assert got.count("E004") == 2, findings
    assert all("recorder.enabled()" in f.message for f in findings)


def test_e004_recorder_record_clean_when_guarded(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_RECORDER_HOT_PATH_GUARDED)
    assert findings == []


# the request-tracer hot path (ISSUE 15, serving/session.py dispatch +
# router/router.py resolve): tracing.record/record_outcome/flow run
# once per SERVED REQUEST — unguarded, every request pays monotonic
# stamps, segment dicts, and attr formatting even with tracing off
# (MXTPU_TRACE_SAMPLE=0), exactly the regression E004 exists for.
E004_TRACING_HOT_PATH = """
from mxnet_tpu.obs import tracing


def resolve_fill(reqs, t_stage0, t_staged, t_done, fill_sid):
    for r in reqs:
        tracing.record(r.trace, "h2d", t_stage0, t_staged, fill=fill_sid)
        tracing.record(r.trace, "compute", t_staged, t_done, fill=fill_sid)
        tracing.record_outcome(r.trace, "ok", r.arrival, t_done)
    tracing.flow(reqs[0].trace, "reply", "s", t_done)
"""

E004_TRACING_HOT_PATH_GUARDED = """
from mxnet_tpu.obs import tracing


def resolve_fill(reqs, t_stage0, t_staged, t_done, fill_sid):
    if not tracing.enabled():
        return
    for r in reqs:
        tracing.record(r.trace, "h2d", t_stage0, t_staged, fill=fill_sid)
        tracing.record(r.trace, "compute", t_staged, t_done, fill=fill_sid)
        tracing.record_outcome(r.trace, "ok", r.arrival, t_done)
    tracing.flow(reqs[0].trace, "reply", "s", t_done)
"""


def test_e004_fires_on_unguarded_tracing_record(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_TRACING_HOT_PATH)
    got = _ids(findings)
    assert got.count("E004") == 4, findings
    assert all("tracing.enabled()" in f.message for f in findings)


def test_e004_tracing_record_clean_when_guarded(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_TRACING_HOT_PATH_GUARDED)
    assert findings == []


# ----------------------------------------------------------------------
# E006 — tracer leaks / host effects in traced code (ISSUE 12)
# ----------------------------------------------------------------------

E006_CONCRETIZE = """
import jax
import jax.numpy as jnp
import numpy as np


def step(x):
    s = jnp.mean(x)
    v = float(s)
    h = np.asarray(x)
    return x * v + h.sum()


fn = jax.jit(step)
"""


def test_e006_flags_concretization_in_jitted_fn(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_CONCRETIZE)
    got = _ids(findings)
    assert got.count("E006") == 2, findings
    assert any("float()" in f.message for f in findings)
    assert any("np.asarray" in f.message for f in findings)


E006_BRANCH = """
import jax
import jax.numpy as jnp


def step(x):
    s = jnp.sum(x)
    if s > 0:
        x = x - 1.0
    while s < 10:
        x = x + 1.0
    return x


fn = jax.jit(step)
"""


def test_e006_flags_python_branch_on_traced_value(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_BRANCH)
    got = _ids(findings)
    assert got.count("E006") == 2, findings
    assert any("`if`" in f.message for f in findings)
    assert any("`while`" in f.message for f in findings)


# the ancestor-if NEGATIVE case: host-static conditions (is-None
# checks, isinstance shims, closure config, string mode switches) are
# how the executor's comm gate and the RNN cells are written — they
# resolve identically at trace time on every rank and must stay silent
E006_STATIC_BRANCHES_CLEAN = """
import jax
import jax.numpy as jnp


def build(comm, mode):
    def step(x, seed):
        rng = None
        if seed is not None:
            rng = jax.random.key(seed)
        if comm is not None:
            x = x * 2.0
        if mode == "lstm":
            x = jnp.tanh(x)
        if isinstance(x, tuple):
            x = x[0]
        n = 1
        for d in x.shape:
            n *= int(d)
        if n > 4:
            x = x + float(n)
        return x, rng

    return jax.jit(step)
"""


def test_e006_static_branches_and_shape_math_are_clean(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_STATIC_BRANCHES_CLEAN)
    assert findings == [], findings


E006_HOST_EFFECTS = """
import time
import jax
from . import telemetry


def make(outer_log):
    def step(x):
        t0 = time.time()
        telemetry.inc("steps")
        print("step!")
        outer_log.append(t0)
        return x

    return jax.jit(step)
"""


def test_e006_flags_host_effects_and_closure_mutation(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_HOST_EFFECTS)
    got = [f for f in findings if f.check_id == "E006"]
    msgs = "\n".join(f.message for f in got)
    assert len(got) == 4, findings
    assert "time.time()" in msgs
    assert "telemetry.inc" in msgs
    assert "print()" in msgs
    assert "outer_log" in msgs and "mutates" in msgs


# the gate-idiom NEGATIVE case: the sanctioned trace-time mode gauge
# (ops/nn.py _bf16_wgrad_active) — set_gauge behind the enabled()
# guard records WHICH numerics this compile uses, once per compile,
# by design
E006_MODE_GAUGE_CLEAN = """
import jax
from . import telemetry


def kernel(x):
    if telemetry.enabled():
        telemetry.set_gauge("ops.mode", 1)
    return x * 2.0


fn = jax.jit(kernel)
"""


def test_e006_guarded_trace_time_mode_gauge_is_clean(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_MODE_GAUGE_CLEAN)
    assert findings == [], findings


# the resolver follows the executor's builder idiom: jit applied to a
# BUILDER CALL traces the closure the builder returns — interprocedural
# through the assignment and the module-level helper it calls
E006_BUILDER_RESOLUTION = """
import jax
from . import telemetry


def _run_graph(vals):
    telemetry.inc("nodes")
    return vals


class Executor:
    def _build_fwd(self):
        def f(vals):
            return _run_graph(vals)

        return f

    def _fwd_fn(self):
        fn = self._build_fwd()
        return jax.jit(fn)
"""


def test_e006_resolves_through_builders_and_module_helpers(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_BUILDER_RESOLUTION)
    got = [f for f in findings if f.check_id == "E006"]
    assert len(got) == 1, findings
    assert "telemetry.inc" in got[0].message


E006_SCAN_DECORATOR = """
import functools
import jax
from jax import lax

from jax import shard_map


@functools.partial(shard_map, mesh=None, in_specs=(), out_specs=())
def _reduce(x):
    print("reducing")
    return lax.psum(x, "data")


def outer(xs):
    def body(carry, x):
        v = float(x)
        return carry + v, carry

    return lax.scan(body, 0.0, xs)
"""


def test_e006_covers_partial_shard_map_decorator_and_scan_body(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E006_SCAN_DECORATOR)
    got = [f for f in findings if f.check_id == "E006"]
    msgs = "\n".join(f.message for f in got)
    assert "print()" in msgs and "shard_map" in msgs
    assert "float()" in msgs and "scan" in msgs


# ----------------------------------------------------------------------
# E007 — collectives under rank-dependent control flow (ISSUE 12)
# ----------------------------------------------------------------------

E007_RANK_IF = """
import jax
from jax import lax


def body(x):
    if jax.process_index() == 0:
        x = lax.psum(x, "data")
    return x


fn = jax.jit(body)
"""

E007_RANK_LOCAL = """
import jax
import os
from jax import lax


def body(x):
    rank = int(os.environ.get("MXTPU_PROCESS_ID", "0"))
    me = rank % 2
    if me:
        x = lax.all_gather(x, "data")
    return x


fn = jax.jit(body)
"""


def test_e007_flags_collective_under_rank_branch(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E007_RANK_IF)
    got = [f for f in findings if f.check_id == "E007"]
    assert len(got) == 1, findings
    assert "psum" in got[0].message and "rank-varying" in got[0].message
    findings, _, _ = _lint_src(tmp_path, E007_RANK_LOCAL)
    got = [f for f in findings if f.check_id == "E007"]
    assert len(got) == 1, findings
    assert "all_gather" in got[0].message


E007_DATA_DEPENDENT = """
import jax
import jax.numpy as jnp
from jax import lax


def body(g):
    norm = jnp.linalg.norm(g)
    if norm > 1.0:
        g = lax.psum(g, "data")
    return g


fn = jax.jit(body)
"""


def test_e007_flags_collective_under_data_branch(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E007_DATA_DEPENDENT)
    got = [f for f in findings if f.check_id == "E007"]
    assert len(got) == 1, findings
    assert "data-" in got[0].message
    assert "MXTPU_COLLECTIVE_CHECK" in got[0].message


# the ancestor-if NEGATIVE case: a collective under host-static
# config — exactly the executor's comm-mode gate (`if comm is not
# None:` around bucketed_psum) — is the sanctioned shape: every rank
# resolves it identically at trace time
E007_HOST_CONFIG_CLEAN = """
import jax
from jax import lax


def build(comm, axes):
    def body(grads):
        if comm is not None:
            grads = lax.psum(grads, "data")
        for name in axes:
            grads = lax.psum(grads, name)
        return grads

    return jax.jit(body)
"""


def test_e007_host_config_gate_and_loops_are_clean(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E007_HOST_CONFIG_CLEAN)
    assert findings == [], findings


# ----------------------------------------------------------------------
# W104 — retrace hazards (ISSUE 12)
# ----------------------------------------------------------------------

W104_LIFT_BREAK = """
from .registry import register


@register("bad_scale", lift_floats=True)
def bad_scale(data, scalar=1.0, **kw):
    return data * float(scalar)
"""

W104_UNLIFTED = """
from .registry import register


@register("unlifted_scale", inputs=("data",))
def unlifted_scale(data, scalar=2.0, **kw):
    return data * scalar
"""

# the lifted-scalar NEGATIVE case: the _reg_scalar family shape —
# lift_floats + the tracer-admitting _scalarv coercion (and the
# static-embed idiom: a param NORMALIZED before use is a deliberate
# per-model symbolic attr, not churn)
W104_LIFTED_CLEAN = """
from .registry import register


def _scalarv(v):
    return v


@register("good_scale", lift_floats=True)
def good_scale(data, scalar=1.0, **kw):
    return data * _scalarv(scalar)


@register("static_embed", inputs=("data",))
def static_embed(data, eps=1e-5, **kw):
    eps = float(eps)
    return data + eps
"""


def test_w104_flags_lift_break_and_unlifted_scalar(tmp_path):
    findings, _, _ = _lint_ops_src(tmp_path, W104_LIFT_BREAK)
    got = [f for f in findings if f.check_id == "W104"]
    assert len(got) == 1 and "float()" in got[0].message, findings
    findings, _, _ = _lint_ops_src(tmp_path, W104_UNLIFTED)
    got = [f for f in findings if f.check_id == "W104"]
    assert len(got) == 1 and "lift_floats" in got[0].message, findings


def test_w104_lifted_and_static_embed_kernels_are_clean(tmp_path):
    findings, _, _ = _lint_ops_src(tmp_path, W104_LIFTED_CLEAN)
    assert [f for f in findings if f.check_id == "W104"] == [], findings
    # op registration patterns only apply under mxnet_tpu/ops/
    findings, _, _ = _lint_src(tmp_path, W104_UNLIFTED)
    assert "W104" not in _ids(findings)


W104_CACHE_KEY = """
class Exe:
    def get(self, k, shapes, lr):
        key = (k, [s for s in shapes], float(lr))
        if key not in self._jit_cache:
            self._jit_cache[key] = 1
        return self._jit_cache[key]
"""

W104_CACHE_KEY_CLEAN = """
class Exe:
    def get(self, k, shapes):
        key = (k, tuple(tuple(s) for s in shapes))
        if key not in self._jit_cache:
            self._jit_cache[key] = 1
        return self._jit_cache[key]
"""


def test_w104_flags_unstable_jit_cache_keys(tmp_path):
    findings, _, _ = _lint_src(tmp_path, W104_CACHE_KEY)
    got = [f for f in findings if f.check_id == "W104"]
    assert got, findings
    assert any("unhashable" in f.message for f in got)
    findings, _, _ = _lint_src(tmp_path, W104_CACHE_KEY_CLEAN)
    assert [f for f in findings if f.check_id == "W104"] == [], findings


# ----------------------------------------------------------------------
# JSON output + baseline gating + --stats (ISSUE 12 satellites)
# ----------------------------------------------------------------------

def _run_cli(args, cwd=None):
    import subprocess

    return subprocess.run(
        [sys.executable, "-m", "tools.analysis"] + args,
        cwd=cwd or ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, PYTHONPATH=ROOT + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))


def test_json_output_schema_is_stable(tmp_path):
    """The machine-readable contract CI scripts parse: stable top-level
    keys, per-finding keys, and an explicit justification on
    suppressed entries."""
    import json as _json

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text("REGISTRY = []\n")
    (pkg / "bad.py").write_text(
        "def f(x=[]):\n    return x\n\n\n"
        "def g(y={}):  # mxlint: disable=W101 -- sentinel, never mutated\n"
        "    return y\n")
    r = _run_cli(["--format", "json", str(pkg)])
    assert r.returncode == 1, r.stdout + r.stderr
    payload = _json.loads(r.stdout)
    assert payload["schema"] == "mxlint-v1"
    assert set(payload) == {"schema", "findings", "baselined",
                            "suppressed", "errors", "stats"}
    f = payload["findings"][0]
    assert set(f) == {"check", "path", "line", "col", "message"}
    assert f["check"] == "W101" and f["line"] == 1
    s = payload["suppressed"][0]
    assert set(s) == {"check", "path", "line", "col", "message",
                      "justification"}
    assert s["justification"] == "sentinel, never mutated"
    assert payload["stats"]["files"] == 2
    assert payload["errors"] == []


def test_baseline_write_then_compare_gates_only_new_findings(tmp_path):
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text("REGISTRY = []\n")
    (pkg / "bad.py").write_text("def f(x=[]):\n    return x\n")
    base = str(tmp_path / "baseline.json")
    # snapshot the existing finding -> compare exits 0 (baselined)
    r = _run_cli(["--write-baseline", base, str(pkg)])
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_cli(["--baseline", base, str(pkg)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "baselined" in r.stdout
    # a NEW finding in another file still fails the gate
    (pkg / "worse.py").write_text("def g(y={}):\n    return y\n")
    r = _run_cli(["--baseline", base, str(pkg)])
    assert r.returncode == 1, r.stdout + r.stderr
    assert "worse.py" in r.stdout
    # a garbage baseline is a usage error, never a silent un-gate
    (tmp_path / "junk.json").write_text("{}")
    r = _run_cli(["--baseline", str(tmp_path / "junk.json"), str(pkg)])
    assert r.returncode == 2, r.stdout + r.stderr


def test_committed_baseline_is_empty_and_schema_pinned():
    """ISSUE 12 acceptance: the committed baseline carries ZERO
    findings — the repo gate holds by fixes and justified allowlists,
    not by baselining debt."""
    import json as _json

    path = os.path.join(ROOT, "tools", "analysis", "baseline.json")
    payload = _json.load(open(path))
    assert payload["schema"] == "mxlint-baseline-v1"
    assert payload["findings"] == []


def test_each_file_is_parsed_exactly_once_per_run(tmp_path, monkeypatch):
    """ISSUE 12 satellite: one ast.parse per file, fanned out to every
    registered check — pinned by counting calls through the core parse
    hook.  config.py is both linted AND read by W103's registry
    resolution; the shared per-run cache keeps it at one parse."""
    import ast as _ast

    from tools.analysis import core

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text("REGISTRY = []\n")
    (pkg / "a.py").write_text("import os\n"
                              "x = os.environ.get('MXTPU_SOME_KNOB')\n")
    (pkg / "b.py").write_text("def f():\n    return 1\n")
    calls = []

    def counting_parse(text, filename="<unknown>", *a, **kw):
        calls.append(filename)
        return _ast.parse(text, filename, *a, **kw)

    monkeypatch.setattr(core, "_ast_parse", counting_parse)
    findings, _, errors = run_paths([str(pkg)])
    assert not errors
    assert _ids(findings) == ["W103"]  # W103 resolved the registry
    assert len(calls) == 3, calls
    assert len(set(calls)) == 3, calls


def test_stats_line_reports_files_findings_seconds(tmp_path):
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text("REGISTRY = []\n")
    r = _run_cli(["--stats", str(pkg)])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stats: files=1 findings=0" in r.stdout
    assert "seconds=" in r.stdout


def test_repo_gate_sweeps_the_quant_package():
    """ISSUE 13 pin: the gate walk covers mxnet_tpu/quant/ (calibration
    books telemetry and the transform runs trace-adjacent code — the
    E004/E006 surfaces) and the int8 kernels in ops/quant_ops.py."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    for mod in ("__init__", "calib", "transform"):
        assert os.path.join("mxnet_tpu", "quant", "%s.py" % mod) in swept
    assert os.path.join("mxnet_tpu", "ops", "quant_ops.py") in swept


E004_OBSERVE_VALUES_UNGUARDED = """
import numpy as np
from . import telemetry

def calib_sweep(acts):
    for a in acts:
        telemetry.observe_values("quant.calib.act", np.abs(a))
"""

E004_OBSERVE_VALUES_GUARDED = """
import numpy as np
from . import telemetry

def calib_sweep(acts):
    for a in acts:
        if telemetry.enabled():
            telemetry.observe_values("quant.calib.act", np.abs(a))
"""


def test_e004_covers_observe_values(tmp_path):
    """The value-range histogram recorder (telemetry.observe_values,
    ISSUE 13) is a recording call like observe: the E004 fast-path
    guard contract applies — notably to the array math feeding it."""
    findings, _, _ = _lint_src(tmp_path, E004_OBSERVE_VALUES_UNGUARDED)
    assert _ids(findings) == ["E004"]
    assert "telemetry.observe_values" in findings[0].message
    findings, _, _ = _lint_src(tmp_path, E004_OBSERVE_VALUES_GUARDED)
    assert findings == [], findings


# ----------------------------------------------------------------------
# ckpt subsystem surfaces (ISSUE 16)
# ----------------------------------------------------------------------

def test_repo_gate_sweeps_the_ckpt_package():
    """Same pin for mxnet_tpu/ckpt/ — the snapshot manager pushes the
    shard write as an engine callback and books ckpt.* telemetry on the
    training hot path, exactly the E002/E004 surfaces; pinned so a
    future repack cannot silently drop the new package from the gate."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    for mod in ("__init__", "atomic", "snapshot", "resume", "elastic"):
        assert os.path.join("mxnet_tpu", "ckpt", "%s.py" % mod) in swept


# a checkpoint-writer-shaped callback that captures D2H INSIDE an atomic
# engine op: the shard write would sync on device arrays from a worker
# the scheduler believes is non-blocking — the deadlock shape the real
# CheckpointManager avoids by capturing before the push (snapshot.py)
E002_CKPT_WRITE_ATOMIC = """
def snapshot(eng, params, var, path):
    def ckpt_write(_params=params, _path=path):
        blobs = [p.asnumpy() for p in _params]
        with open(_path, "wb") as f:
            for b in blobs:
                f.write(b.tobytes())
    eng.push(ckpt_write, read_vars=[p._engine_var() for p in params],
             write_vars=[var])
"""

E002_CKPT_WRITE_REAL = """
def snapshot(eng, blob, var, path, handoff):
    def ckpt_write(_blob=blob, _path=path, _q=handoff):
        try:
            with open(_path + ".tmp", "wb") as f:
                f.write(_blob)
            _q.put(None)
        except BaseException as e:
            _q.put(e)
    eng.push(ckpt_write, write_vars=[var], atomic=False,
             name="ckpt_write")
"""


def test_e002_fires_on_atomic_ckpt_write(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E002_CKPT_WRITE_ATOMIC)
    assert _ids(findings).count("E002") == 1, findings
    assert any("asnumpy" in f.message for f in findings)


def test_e002_ckpt_write_clean_when_captured_before_push(tmp_path):
    """The shape snapshot.py actually ships: the D2H capture and pickle
    happen on the trainer thread, the callback only writes bytes, and
    atomic=False keeps normal sync semantics with in-band errors."""
    findings, _, _ = _lint_src(tmp_path, E002_CKPT_WRITE_REAL)
    assert findings == [], findings


E004_CKPT_UNGUARDED = """
import time
from . import telemetry

def note_snapshot(step, nbytes, t0):
    telemetry.inc("ckpt.snapshots")
    telemetry.observe("ckpt.d2h_seconds", time.time() - t0)
    telemetry.set_gauge("ckpt.last_step", step)
"""

E004_CKPT_GUARDED = """
import time
from . import telemetry

def note_snapshot(step, nbytes, t0):
    if telemetry.enabled():
        telemetry.inc("ckpt.snapshots")
        telemetry.observe("ckpt.d2h_seconds", time.time() - t0)
        telemetry.set_gauge("ckpt.last_step", step)
"""


def test_e004_covers_ckpt_telemetry(tmp_path):
    """ckpt.* bookings ride note_dispatch on the training hot path: the
    fast-path guard contract applies to them like any other recorder."""
    findings, _, _ = _lint_src(tmp_path, E004_CKPT_UNGUARDED)
    assert _ids(findings).count("E004") >= 2, findings
    findings, _, _ = _lint_src(tmp_path, E004_CKPT_GUARDED)
    assert findings == [], findings


# ----------------------------------------------------------------------
# E008/E009 — the lock contracts (ISSUE 17, tools/analysis/lock_checks)
# ----------------------------------------------------------------------

E008_INCONSISTENT = """
import threading

class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def fwd(self):
        with self._a:
            with self._b:
                pass

    def rev(self):
        with self._b:
            with self._a:
                pass
"""

E008_CONSISTENT = """
import threading

class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def fwd(self):
        with self._a:
            with self._b:
                pass

    def also_fwd(self):
        with self._a:
            with self._b:
                pass
"""

E008_TRANSITIVE = """
import threading

class Pair:
    def __init__(self):
        self._a = threading.Lock()
        self._b = threading.Lock()

    def _take_b(self):
        with self._b:
            pass

    def fwd(self):
        with self._a:
            self._take_b()

    def rev(self):
        with self._b:
            with self._a:
                pass
"""


def test_e008_flags_inconsistent_lock_order(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E008_INCONSISTENT)
    assert _ids(findings) == ["E008"], findings
    assert "order" in findings[0].message


def test_e008_consistent_order_is_clean(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E008_CONSISTENT)
    assert findings == [], findings


def test_e008_follows_in_file_helper_calls(tmp_path):
    """The traced.py resolver: fwd() nests B under A only THROUGH
    _take_b(), and the pair must still be caught."""
    findings, _, _ = _lint_src(tmp_path, E008_TRANSITIVE)
    assert _ids(findings) == ["E008"], findings


E009_MIXED = """
import threading

class Srv:
    def __init__(self, sock, q):
        self._lock = threading.Lock()
        self._sock = sock
        self._q = q

    def bad_recv(self):
        with self._lock:
            return self._sock.recv(4)

    def bad_get(self):
        with self._lock:
            return self._q.get()

    def bad_sync(self, arr):
        with self._lock:
            arr.wait_to_read()

    def ok_get(self):
        with self._lock:
            return self._q.get(timeout=1.0)

    def ok_outside(self):
        data = self._sock.recv(4)
        with self._lock:
            return data
"""

E009_JUSTIFIED = """
import threading

class Srv:
    def __init__(self, sock):
        self._lock = threading.Lock()
        self._sock = sock

    def turn(self):
        with self._lock:
            # mxlint: disable=E009 -- the lock serializes socket turns
            return self._sock.recv(4)
"""


def test_e009_flags_blocking_calls_under_lock_only(tmp_path):
    """socket recv, timeout-less Queue.get and an engine sync under a
    held lock are each one E009; the timeout'd get and the recv
    OUTSIDE the lock are clean."""
    findings, _, _ = _lint_src(tmp_path, E009_MIXED)
    assert _ids(findings) == ["E009", "E009", "E009"], findings
    msgs = " ".join(f.message for f in findings)
    assert "recv" in msgs and "get" in msgs and "wait_to_read" in msgs


def test_e009_justified_site_is_suppressed_not_dropped(tmp_path):
    findings, suppressed, _ = _lint_src(tmp_path, E009_JUSTIFIED)
    assert findings == [], findings
    assert _ids(suppressed) == ["E009"]
    assert "serializes socket turns" in suppressed[0].message


W105_UNDISPOSED = """
import threading

def fire_and_forget(fn):
    worker = threading.Thread(target=fn)
    worker.start()
"""

W105_DISPOSED = """
import threading

def joined(fn):
    t = threading.Thread(target=fn)
    t.start()
    t.join()

def daemonized(fn):
    d = threading.Thread(target=fn, daemon=True)
    d.start()

def pooled(fns):
    pool = []
    for fn in fns:
        pool.append(threading.Thread(target=fn))
    for t in pool:
        t.start()
    for t in pool:
        t.join()
"""


def test_w105_flags_undisposed_thread(tmp_path):
    findings, _, _ = _lint_src(tmp_path, W105_UNDISPOSED)
    assert _ids(findings) == ["W105"], findings


def test_w105_join_daemon_and_pool_disposition_are_clean(tmp_path):
    findings, _, _ = _lint_src(tmp_path, W105_DISPOSED)
    assert findings == [], findings


def test_repo_gate_sweeps_locks_module():
    """ISSUE 17 pin: the gate walk covers mxnet_tpu/locks.py (the
    runtime sentinel the lock checks point at) and the check module
    itself, so a future target-list edit cannot silently drop them."""
    from tools.analysis.core import iter_py_files

    files = iter_py_files([os.path.join(ROOT, "mxnet_tpu"),
                           os.path.join(ROOT, "tools")])
    swept = {os.path.relpath(f, ROOT) for f in files}
    assert os.path.join("mxnet_tpu", "locks.py") in swept
    assert os.path.join("tools", "analysis", "lock_checks.py") in swept


# ----------------------------------------------------------------------
# --changed REF — the pre-push restricted run (ISSUE 17)
# ----------------------------------------------------------------------


def test_changed_paths_filters_suffix_scope_and_existence(tmp_path):
    """Unit pin on the plumbing: only .py names from the diff that
    still exist on disk AND fall under the requested paths survive;
    untracked files ride along via ls-files --others."""
    from tools.analysis.__main__ import changed_paths

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text("REGISTRY = []\n")
    (pkg / "a.py").write_text("x = 1\n")
    (pkg / "new.py").write_text("y = 2\n")
    (tmp_path / "outside.py").write_text("z = 3\n")

    def fake_run(cmd):
        if cmd[:2] == ["git", "diff"]:
            return "mxnet_tpu/a.py\nmxnet_tpu/deleted.py\noutside.py\nREADME.md\n"
        return "mxnet_tpu/new.py\n"

    got = changed_paths("HEAD", [str(pkg)], repo_root=str(tmp_path),
                        _run=fake_run)
    assert got == [str(pkg / "a.py"), str(pkg / "new.py")]


def _git(tmp_path, *argv):
    import subprocess

    r = subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t"]
                      + list(argv), cwd=str(tmp_path), capture_output=True,
                      text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_cli_changed_mode_restricts_to_the_diff(tmp_path):
    """End-to-end in a hermetic git repo: a committed file carries a
    REAL finding, a new uncommitted file is clean.  The full run fails
    on the committed finding; --changed HEAD lints only the new file
    and exits 0; with a fully-clean tree --changed prints the no-work
    message and still exits 0.  Both modes pinned."""
    import subprocess

    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "config.py").write_text("REGISTRY = []\n")
    (pkg / "dirty.py").write_text(W105_UNDISPOSED)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    (pkg / "fresh.py").write_text("x = 1\n")

    def cli(*argv):
        return subprocess.run(
            [sys.executable, "-m", "tools.analysis"] + list(argv),
            cwd=ROOT, capture_output=True, text=True, timeout=120)

    full = cli(str(pkg))
    assert full.returncode == 1, full.stdout + full.stderr
    assert "W105" in full.stdout

    changed = cli("--changed", "HEAD", str(pkg))
    assert changed.returncode == 0, changed.stdout + changed.stderr
    assert "W105" not in changed.stdout

    (pkg / "fresh.py").unlink()
    none = cli("--changed", "HEAD", str(pkg))
    assert none.returncode == 0, none.stdout + none.stderr
    assert "no changed python files" in none.stdout

    bad = cli("--changed", "no-such-ref", str(pkg))
    assert bad.returncode == 2, bad.stdout + bad.stderr


# a loop that books telemetry once per iteration of slow measured work
# is cheap next to that work, but the guard contract is uniform: the
# corpus pins the unguarded shape as a violation and the
# `if telemetry.enabled():` shape as clean.
E004_LOOP_UNGUARDED = """
from . import telemetry

def run_trials(trials, measure):
    best = {}
    for t, cand in enumerate(trials):
        delta = measure(cand)
        telemetry.inc("probe.trials")
        telemetry.set_gauge("probe.trial", t)
        telemetry.set_gauge("probe.kept", len(best))
    return best
"""

E004_LOOP_GUARDED = """
from . import telemetry

def run_trials(trials, measure):
    best = {}
    for t, cand in enumerate(trials):
        delta = measure(cand)
        if telemetry.enabled():
            telemetry.inc("probe.trials")
            telemetry.set_gauge("probe.trial", t)
            telemetry.set_gauge("probe.kept", len(best))
    return best
"""


def test_e004_covers_a_per_trial_booking_loop(tmp_path):
    findings, _, _ = _lint_src(tmp_path, E004_LOOP_UNGUARDED)
    assert _ids(findings).count("E004") == 3, findings
    findings, _, _ = _lint_src(tmp_path, E004_LOOP_GUARDED)
    assert findings == [], findings


# W103 reads a registration's NAME whatever else the row carries: rows
# with a trailing annotation read clean, an unregistered variable still
# fires.
ANNOTATED_CONFIG = """
EnvVar = None
Note = None
REGISTRY = [
    EnvVar("MXTPU_STEPS_PER_DISPATCH", int, 1, "fused K",
           Note(choices=(1, 2, 4, 8))),
    EnvVar("MXTPU_SERVE_WAIT_MS", float, 2.0, "fill wait",
           Note(lo=0.0, hi=20.0)),
]
ABSORBED = {}
"""

ANNOTATED_READS = """
import os
a = os.environ.get("MXTPU_STEPS_PER_DISPATCH", "1")
b = os.environ.get("MXTPU_SERVE_WAIT_MS")
c = os.environ.get("MXTPU_UNREGISTERED_SECRET")
"""


def test_w103_resolves_registry_rows_with_extra_fields(tmp_path):
    findings, _, _ = _lint_src(tmp_path, ANNOTATED_READS,
                               config_src=ANNOTATED_CONFIG)
    assert _ids(findings) == ["W103"]
    assert "MXTPU_UNREGISTERED_SECRET" in findings[0].message
