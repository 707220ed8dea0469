"""`profiler.span`: one pair of clock reads per host step feeding the
JAX profiler (an `mx:` event in the xplane), telemetry (`hist`) and the
chrome event list (`args.parent`, `attrs`) — at every host step of the
fit staging and step loop and of the batcher's decode step."""
import glob
import json
import statistics
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler, telemetry
from mxnet_tpu.serving.decode import GenerateRequest, GenerativeSession

from test_transformer_lm import _lm_and_params

# chrome `ts` against the xplane's `start_ns` moved onto the wall clock
# by an anchor span's `wall_ns`: medians of 1-5 us here and on the v5e
# host (PERF.md, PR 23).  A thread that loses the CPU between a span's
# clock read and its annotation is off by the time it was away (111 ms
# seen once in 2,000 on the chip's shared host), so the medians are held
# to the bound, not every span
CLOCK_TOLERANCE_NS = 100_000
SPANS_TRACED = 20

DECODE_LEGS = ["serving.decode.pack_seconds",
               "serving.decode.dispatch_seconds",
               "serving.decode.device_wait_seconds",
               "serving.decode.d2h_seconds",
               "serving.decode.emit_seconds"]
STAGE_LEGS = ["io.stage.fetch_seconds", "io.stage.readback_seconds",
              "io.stage.stack_seconds", "io.stage.put_seconds"]


def _chrome_events(tmp_path_factory, body):
    """Run `body` with the chrome profiler on; its "X" events."""
    fname = str(tmp_path_factory.mktemp("spans") / "profile.json")
    profiler.profiler_set_config(mode="all", filename=fname)
    profiler.profiler_set_state("run")
    try:
        body()
    finally:
        profiler.profiler_set_state("stop")
        profiler.dump_profile()
    with open(fname) as f:
        return [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]


@pytest.fixture
def fresh_telemetry():
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    yield
    telemetry.reset()
    telemetry.set_enabled(prev)


# ----------------------------------------------------------------------
# (a) the JAX profiler's sink, and the two clocks
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A few nested spans under a jax.profiler session at host level 1
    with the chrome profiler running: (xplane `mx:` events, chrome
    events), both keyed by name."""
    import jax
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    options.enable_hlo_proto = False

    def body():
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            for i in range(SPANS_TRACED):
                with profiler.span("t.outer", cat="test", block=i):
                    with profiler.span("t.inner", cat="test", k=2,
                                       pipe="p"):
                        sum(range(2000))
                    with profiler.span("t.tail", cat="test"):
                        sum(range(500))
        finally:
            jax.profiler.stop_trace()

    chrome = {}
    for e in _chrome_events(tmp_path_factory, body):
        chrome.setdefault(e["name"], []).append(e)
    path = sorted(glob.glob(trace_dir + "/plugins/profile/*/*.xplane.pb"))[-1]
    xplane = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mx:t."):
                    xplane.setdefault(ev.name[3:], []).append(
                        (int(ev.start_ns), int(ev.duration_ns), dict(ev.stats)))
    for evs in xplane.values():
        evs.sort()
    for evs in chrome.values():
        evs.sort(key=lambda e: e["args"]["id"])
    return xplane, chrome


@pytest.mark.parametrize("name,attrs", [
    ("t.outer", {"block": SPANS_TRACED - 1}),
    ("t.inner", {"k": 2, "pipe": "p"}),
    ("t.tail", {}),
])
def test_span_is_an_mx_event_of_the_host_plane_with_its_attrs(
        traced, name, attrs):
    xplane, _ = traced
    assert len(xplane[name]) == SPANS_TRACED
    stats = xplane[name][-1][2]
    for key, value in attrs.items():
        assert stats[key] == value
    # only a span with no parent on its thread carries the wall stamp
    assert ("wall_ns" in stats) == (name == "t.outer")


@pytest.mark.parametrize("child", ["t.inner", "t.tail"])
def test_span_nests_under_its_parent_in_the_xplane(traced, child):
    xplane, _ = traced
    for (ps, pd, _), (cs, cd, _) in zip(xplane["t.outer"], xplane[child]):
        assert ps <= cs and cs + cd <= ps + pd


@pytest.mark.parametrize("name", ["t.outer", "t.inner", "t.tail"])
def test_chrome_ts_and_dur_agree_with_the_xplane(traced, name):
    """The xplane counts from its session's start; a root span's
    `wall_ns` moves it onto the wall clock, and every span's chrome
    `ts`/`dur` must then agree with its xplane event."""
    xplane, chrome = traced
    # an annotation opens a little after its span's clock read, so every
    # anchor puts the epoch a little early: the latest is the closest
    epoch = max(stats["wall_ns"] - start_ns
                for start_ns, _, stats in xplane["t.outer"])
    starts, durs = [], []
    for (xs, xd, _), ev in zip(xplane[name], chrome[name]):
        starts.append(abs((epoch + xs) - ev["ts"] * 1000))
        # the annotation closes before the span's second clock read:
        # the chrome event contains the xplane's (chrome counts whole us)
        assert ev["dur"] * 1000 - xd >= -1000
        durs.append(ev["dur"] * 1000 - xd)
    assert len(starts) == SPANS_TRACED
    assert statistics.median(starts) < CLOCK_TOLERANCE_NS
    assert statistics.median(durs) < CLOCK_TOLERANCE_NS


# ----------------------------------------------------------------------
# (b) every histogram of the span tables, (d) parents and `block`
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def fit_run(tmp_path_factory):
    """Two K=2 blocks of `Module.fit` with a callback: (telemetry
    histograms, chrome events)."""
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    rng = np.random.RandomState(0)
    X = rng.rand(32, 10).astype(np.float32)
    y = rng.randint(0, 3, 32).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=8)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=3),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())

    def body():
        mod.fit(it, num_epoch=1, optimizer="sgd", steps_per_dispatch=2,
                batch_end_callback=lambda param: None)
        mx.waitall()

    events = _chrome_events(tmp_path_factory, body)
    hists = telemetry.snapshot()["histograms"]
    telemetry.reset()
    telemetry.set_enabled(prev)
    return hists, events


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One generation through `ModelServer` on a tiny TransformerLM
    tenant: (telemetry histograms, chrome events)."""
    prev = telemetry.set_enabled(True)
    telemetry.reset()
    lm, params = _lm_and_params()
    server = mx.serving.ModelServer({}, wait_ms=1.0)

    def body():
        try:
            server.add_generative_tenant("lm", lm, params, max_sessions=2,
                                         max_len=16, seq_buckets=[8])
            server.submit_generate("lm", [5, 9, 3],
                                   max_new_tokens=4).result(timeout=120)
        finally:
            server.close()

    events = _chrome_events(tmp_path_factory, body)
    hists = telemetry.snapshot()["histograms"]
    telemetry.reset()
    telemetry.set_enabled(prev)
    return hists, events


@pytest.mark.parametrize("hist", [
    "io.h2d_stage_seconds", *STAGE_LEGS, "io.consumer_wait_seconds",
    "executor.dispatch_seconds.block", "module.device_wait_seconds",
    "module.step_seconds"])
def test_a_fit_block_feeds_every_histogram_of_the_table(fit_run, hist):
    hists, _ = fit_run
    assert hists[hist]["count"] > 0 and hists[hist]["sum"] > 0


@pytest.mark.parametrize("hist", [
    "serving.decode.step_seconds", *DECODE_LEGS, "serving.prefill_seconds",
    "serving.loop.wait_seconds"])
def test_a_decode_step_feeds_every_histogram_of_the_table(served, hist):
    hists, _ = served
    assert hists[hist]["count"] > 0 and hists[hist]["sum"] > 0


@pytest.mark.parametrize("run,parent,children", [
    ("fit_run", "io.h2d_stage_seconds", STAGE_LEGS),
    ("fit_run", "module.step_seconds",
     ["executor.dispatch_seconds.block", "module.device_wait_seconds"]),
    ("served", "serving.decode.step_seconds", DECODE_LEGS),
])
def test_children_do_not_sum_past_their_parent(request, run, parent,
                                               children):
    hists, _ = request.getfixturevalue(run)
    assert sum(hists[c]["sum"] for c in children) <= hists[parent]["sum"]


def test_the_legs_of_a_decode_step_belong_to_two_steps(served):
    """`pack` and `dispatch` are the step's own; `device_wait`, `d2h`
    and `emit` are of the step dispatched one call before.  So the first
    step after idle feeds the first two alone (nothing is in flight
    before it but the prefill, which feeds none of these histograms),
    the last call before the drain — every token in flight is a
    session's last, nothing to pack — feeds the last three alone, and
    every step between feeds all five.  The fixture's one request of
    four tokens: a prefill, three dispatched steps, four calls."""
    hists, events = served
    assert hists["serving.decode.step_seconds"]["count"] == 4
    for leg in DECODE_LEGS:
        assert hists[leg]["count"] == 3
    assert hists["serving.prefill_seconds"]["count"] == 1
    by_id = {e["args"]["id"]: e for e in events if "id" in e.get("args", {})}
    steps = sorted((e for e in events if e["name"] == "serve.decode_step"),
                   key=lambda e: e["ts"])
    legs = [sorted(e["name"] for e in events
                   if e["args"].get("parent") == step["args"]["id"])
            for step in steps]
    ahead = ["decode.dispatch", "decode.pack"]
    behind = ["decode.d2h", "decode.device_wait", "decode.emit"]
    assert legs == [ahead, sorted(ahead + behind), sorted(ahead + behind),
                    behind]
    assert [s["args"]["n"] for s in steps] == [1, 1, 1, 0]
    # the prefill: dispatched at admission, read after the first step
    # was dispatched behind it
    (sent,) = [e for e in events if e["name"] == "serve.prefill_dispatch"]
    (read,) = [e for e in events if e["name"] == "serve.prefill"]
    assert sent["args"]["bucket"] == 8 and sent["args"]["prompt"] == 3
    assert sent["ts"] < steps[0]["ts"] < read["ts"] < steps[1]["ts"]
    assert by_id[read["args"]["id"]] is read


@pytest.mark.parametrize("run,child,parent", [
    ("fit_run", "io.stage.fetch", "io.stage"),
    ("fit_run", "io.stage.readback", "io.stage"),
    ("fit_run", "io.stage.stack", "io.stage"),
    ("fit_run", "io.stage.put", "io.stage"),
    ("fit_run", "fit.dispatch", "fit.block"),
    ("fit_run", "fit.device_wait", "fit.block"),
    ("served", "decode.pack", "serve.decode_step"),
    ("served", "decode.dispatch", ("serve.decode_step",
                                   "serve.prefill_dispatch")),
    ("served", "decode.device_wait", ("serve.decode_step", "serve.prefill")),
    ("served", "decode.d2h", ("serve.decode_step", "serve.prefill")),
    ("served", "decode.emit", ("serve.decode_step", "serve.prefill")),
])
def test_args_parent_is_the_span_that_was_open_on_the_thread(
        request, run, child, parent):
    _, events = request.getfixturevalue(run)
    parents = (parent,) if isinstance(parent, str) else parent
    by_id = {e["args"]["id"]: e for e in events
             if "id" in e.get("args", {})}
    kids = [e for e in events if e["name"] == child]
    assert kids
    for e in kids:
        up = by_id[e["args"]["parent"]]
        assert up["name"] in parents and up["tid"] == e["tid"]
        assert up["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= up["ts"] + up["dur"] + 1


@pytest.mark.parametrize("name", ["io.stage", "io.stage.fetch",
                                  "io.stage.readback", "io.stage.stack",
                                  "io.stage.put", "fit.dispatch",
                                  "fit.block"])
def test_block_links_the_staging_spans_to_the_dispatch_across_threads(
        fit_run, name):
    _, events = fit_run
    blocks = {e["args"]["block"] for e in events if e["name"] == name}
    # the staging op that found the epoch over took the next number
    assert blocks == ({1, 2, 3} if name in ("io.stage", "io.stage.fetch")
                      else {1, 2})


def test_span_names_are_static_and_what_varies_is_in_args(fit_run, served):
    for _, events in (fit_run, served):
        spans = [e for e in events if "id" in e.get("args", {})]
        assert spans
        for e in spans:
            assert "(" not in e["name"] and "%" not in e["name"]
    _, events = served
    step = [e for e in events if e["name"] == "serve.decode_step"][0]
    assert step["args"]["n"] == 1 and step["args"]["bucket"] == 1
    wait = [e for e in events if e["name"] == "serve.wait_work"][0]
    assert wait["args"]["parent"] == 0


# ----------------------------------------------------------------------
# (c) every sink off: nothing is booked
# ----------------------------------------------------------------------
@pytest.mark.parametrize("kwargs", [
    {}, {"hist": "test.span_seconds"},
    {"hist": "test.span_seconds", "block": 3, "pipe": "p"}])
def test_span_books_nothing_with_every_sink_off(fresh_telemetry, kwargs):
    assert not profiler.spans_active()
    telemetry.set_enabled(False)
    before = len(profiler._EVENTS)
    with profiler.span("t.off", cat="test", **kwargs) as sp:
        pass
    assert sp.seconds >= 0.0
    assert len(profiler._EVENTS) == before
    telemetry.set_enabled(True)
    assert telemetry.snapshot()["histograms"] == {}
    # and with telemetry on, the histogram alone is fed
    with profiler.span("t.on", cat="test", **kwargs):
        pass
    assert len(profiler._EVENTS) == before
    assert set(telemetry.snapshot()["histograms"]) == (
        {kwargs["hist"]} if "hist" in kwargs else set())


def test_a_body_that_raises_is_recorded_but_not_observed(fresh_telemetry):
    with pytest.raises(KeyError):
        with profiler.span("t.raises", hist="test.span_seconds"):
            raise KeyError("x")
    assert "test.span_seconds" not in telemetry.snapshot()["histograms"]
    # the per-thread stack unwound: the next span has no parent
    with profiler.span("t.after") as sp:
        pass
    assert sp.parent == 0


def test_parent_stacks_are_per_thread():
    seen = {}

    def other():
        with profiler.span("t.other") as sp:
            seen["parent"] = sp.parent

    with profiler.span("t.main") as main:
        t = threading.Thread(target=other)
        t.start()
        t.join()
        with profiler.span("t.child") as child:
            pass
    assert seen["parent"] == 0 and child.parent == main.id


# ----------------------------------------------------------------------
# (e) the run-ahead's own counter
# ----------------------------------------------------------------------
def test_runahead_steps_count_the_steps_dispatched_before_a_read(
        fresh_telemetry):
    """`serving.decode.runahead_steps` grows when a step is dispatched
    with a row whose token the host has not read — never past
    `serving.decode.dispatches`, and with two live sessions by every
    step: the first follows the unread prefills, each later one the
    unread step before it."""
    lm, params = _lm_and_params()
    gs = GenerativeSession("lm", lm, params, max_sessions=2, max_len=16,
                           seq_buckets=[8])
    try:
        reqs = [GenerateRequest("lm", [3, 4, 5][:2 + i], 60.0, 5 + i)
                for i in range(2)]
        assert gs.admit(reqs) == []
        while gs.active():
            gs.decode_step()
        ahead = telemetry.counter_value("serving.decode.runahead_steps")
        steps = telemetry.counter_value("serving.decode.dispatches")
        assert 0 < ahead <= steps
        # budgets 5 and 6: the prefill's token, then 5 dispatched steps
        assert (ahead, steps) == (5, 5)
        assert telemetry.counter_value("serving.decode.tokens") == 4 + 5
        assert telemetry.counter_value("serving.decode.dropped_rows") == 0
    finally:
        gs.close()


# ----------------------------------------------------------------------
# (f) KV positions reserved and used
# ----------------------------------------------------------------------
@pytest.mark.parametrize("sessions", [1, 2])
def test_kv_position_counters_grow_with_every_decode_step(
        fresh_telemetry, sessions):
    lm, params = _lm_and_params()
    gs = GenerativeSession("lm", lm, params, max_sessions=2, max_len=16,
                           seq_buckets=[8])
    try:
        reqs = [GenerateRequest("lm", [3, 4, 5][:2 + i], 60.0, 6)
                for i in range(sessions)]
        assert gs.admit(reqs) == []
        reserved = used = 0
        for step in range(3):
            fed = sum(s.fed for s in gs._active)
            gs.decode_step()
            r = telemetry.counter_value("kv.reserved_positions")
            u = telemetry.counter_value("kv.used_positions")
            # the live ring set and one placeholder set per program
            # built so far, (slots + 1) rows of max_len each
            programs = telemetry.counter_value(
                "serving.decode.bucket_programs")
            assert r - reserved == (1 + programs) * 3 * 16
            assert u - used == fed > 0
            assert u <= r
            reserved, used = r, u
    finally:
        gs.close()
