"""A stall names itself (serving/decode.py "A pass and its legs"): the
batcher adds every leg of a pass of `ModelServer._loop` up, holds each
to its limit — a host leg to `_STALL_FLOOR_S`, a fence to the floor plus
twice its program's own mean seen time — and a leg that stands still
books `serving.stalls`, feeds `serving.stall_seconds[.<leg>]` and leaves
ONE record: logged as `mx.stall {json}`, kept in `stats()`, counted in
`health()`, put into the flight recorder.  Decode flights are `flight`
enter/exit pairs there, so the stall watchdog names one that stands
open.

The device is made up as in test_spans.py: `_launch` really runs the
program (on the CPU) and hands back outputs whose fence SLEEPS — 2 ms a
flight, so every fence blocks past `_FENCE_FLOOR_S` and every flight is
seen, and as long as the test says for the flights it names.  The floor
is shrunk to 0.1 s so that a stall costs a test 0.3 s, not 0.5."""
import json
import logging
import os
import resource
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.obs import recorder
from mxnet_tpu.obs.watchdog import StallWatchdog
from mxnet_tpu.serving import decode

from test_transformer_lm import _lm_and_params

# tier-1 runs six workers on eight cores: a sound leg must stay under
# the floor on a loaded host too
FLOOR = 0.1    # the shrunk `_STALL_FLOOR_S`
FENCE = 0.002  # a sound flight's fence
SLOW = 0.3     # a fence, a callback or an admission that stands still

FLIGHT_FIELDS = {"seq", "program", "kind", "bucket", "rows",
                 "enqueued_to_ready_s", "ref_device_s"}
THREAD_FIELDS = {"thread_cpu_s", "process_cpu_s", "nvcsw", "nivcsw",
                 "majflt", "sampled_s"}
HOST_FIELDS = {"loadavg", "psi_cpu_some_avg10", "psi_mem_some_avg10",
               "psi_io_some_avg10", "compiling", "gc_collections",
               "bytes_in_use", "largest_free_block_bytes", "num_allocs"}
FIELDS = ({"leg", "seconds", "limit_s", "wall_time", "pass", "tenant",
           "next_wait_s", "next_seen"}
          | FLIGHT_FIELDS | THREAD_FIELDS | HOST_FIELDS)


class _SlowOut:
    """A program's first small output whose fence sleeps."""

    def __init__(self, real, seconds):
        self.real, self.seconds = real, seconds

    def block_until_ready(self):
        self.real.block_until_ready()
        if self.seconds:  # 0: the array is there, the fence does not block
            time.sleep(self.seconds)

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        return np.asarray(self.real)


class _Chip:
    """Wraps a session's `_launch`: flight `seq`'s fence sleeps
    `slow[seq]` seconds, every other `fence` — and a prefill bucket's
    `prefill_fence`."""

    def __init__(self, monkeypatch, gs, fence=FENCE, prefill_fence=None):
        self.slow = {}
        self.fence = fence
        self.prefill_fence = fence if prefill_fence is None else prefill_fence
        launch = gs._launch

        def slow_launch(exe, fn, state, data, slot, length, logits,
                        **riders):
            small, state = launch(exe, fn, state, data, slot, length, logits,
                                  **riders)
            base = self.prefill_fence if data.shape[1] > 1 else self.fence
            out = _SlowOut(small[0], self.slow.get(gs._seq, base))
            return (out,) + small[1:], state

        monkeypatch.setattr(gs, "_launch", slow_launch)


@pytest.fixture
def plane(monkeypatch):
    """Telemetry and the flight recorder on and empty, the floor
    shrunk."""
    prev_t, prev_r = telemetry.set_enabled(True), recorder.set_enabled(True)
    telemetry.reset()
    recorder.reset()
    monkeypatch.setattr(decode, "_STALL_FLOOR_S", FLOOR)
    yield
    telemetry.reset()
    recorder.reset()
    telemetry.set_enabled(prev_t)
    recorder.set_enabled(prev_r)


def _serve(monkeypatch, two_programs=True, **chip):
    lm, params = _lm_and_params(two_programs=two_programs)
    server = mx.serving.ModelServer({}, wait_ms=1.0)
    gs = server.add_generative_tenant("lm", lm, params, max_sessions=2,
                                      max_len=32, seq_buckets=[8])
    return server, gs, _Chip(monkeypatch, gs, **chip)


@pytest.fixture
def served(plane, monkeypatch):
    """A server over the tiny LM that keeps two programs (flight 1 of a
    lone request is its prefill, flights 2.. its steps, all of the
    one-row decode bucket) on the made-up chip."""
    server, gs, chip = _serve(monkeypatch)
    yield server, gs, chip
    server.close()


def _generate(server, n=10, prompt=(5, 9, 3), **kw):
    return server.submit_generate("lm", list(prompt), max_new_tokens=n,
                                  **kw).result(timeout=120)


def _stall_lines(caplog, expect=None):
    """The records logged so far; `expect`: wait for that many first (a
    request's future resolves inside the last landing's emit, before
    that pass has judged and logged)."""
    def lines():
        return [json.loads(r.getMessage()[len("mx.stall "):])
                for r in caplog.records
                if r.getMessage().startswith("mx.stall ")]

    deadline = time.monotonic() + 10
    while expect and len(lines()) < expect and time.monotonic() < deadline:
        time.sleep(0.005)
    return lines()


def _hist(name):
    h = telemetry.snapshot()["histograms"].get(name)
    return (0, 0.0) if h is None else (h["count"], h["sum"])


# ----------------------------------------------------------------------
# (a) a fence that sleeps past its limit
# ----------------------------------------------------------------------
def test_a_fence_that_stands_still_leaves_one_record(served, caplog):
    server, gs, chip = served
    chip.slow[6] = SLOW  # the fifth plain step: four were seen before it
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=10)
    assert telemetry.counter_value("serving.stalls") == 1
    count, total = _hist("serving.stall_seconds.device_wait")
    assert count == 1 and SLOW <= total < 4 * SLOW
    assert _hist("serving.stall_seconds") == (count, total)
    (rec,) = _stall_lines(caplog, 1)
    assert set(rec) == FIELDS
    assert rec["leg"] == "device_wait" and rec["tenant"] == "lm"
    assert rec["seconds"] == total and rec["seconds"] > rec["limit_s"]
    # the limit: the floor plus twice the program's mean seen time, which
    # is what the record says it was judged against
    assert rec["limit_s"] == pytest.approx(FLOOR + 2 * rec["ref_device_s"])
    assert FENCE <= rec["ref_device_s"] < FLOOR
    assert abs(rec["wall_time"] - time.time()) < 120
    assert rec["pass"] >= 6
    # the flight
    assert (rec["seq"], rec["kind"], rec["bucket"], rec["rows"]) == (
        6, "decode", 1, 1)
    assert rec["program"] == gs._buckets["decode", 1].program != ""
    assert rec["enqueued_to_ready_s"] >= rec["seconds"]
    # the thread slept: it was off the CPU for the leg, of its own will;
    # its last sample before the stall was at most 0.25 s and a pass old
    assert rec["seconds"] <= rec["sampled_s"] < rec["seconds"] + 1.0
    assert rec["thread_cpu_s"] < 0.5 * rec["seconds"]
    assert rec["process_cpu_s"] >= 0 and rec["nvcsw"] >= 1
    assert rec["nivcsw"] >= 0 and rec["majflt"] >= 0
    # finished at the next landing: flight 7's fence blocked its 2 ms
    assert FENCE <= rec["next_wait_s"] < SLOW
    assert rec["next_seen"] is True
    # the host, read at the stall
    assert len(rec["loadavg"]) == 3 and rec["compiling"] is False
    assert len(rec["gc_collections"]) == 3
    for key in ("psi_cpu_some_avg10", "psi_mem_some_avg10",
                "psi_io_some_avg10"):
        have = os.path.exists("/proc/pressure/" + key.split("_")[1]
                              .replace("mem", "memory"))
        assert (rec[key] is not None) == have
    # where an operator looks
    stats = server.stats()["generative"]["lm"]
    assert stats["stalls"] == [rec] and stats["stall_count"] == 1
    assert server.health()["stalls"] == 1
    (event,) = [e for e in recorder.events() if e["kind"] == "stall"]
    assert json.loads(event["detail"]) == rec and event["seq"] == rec["pass"]
    # the stalled flight does not feed the mean the next one is held to
    prog = gs._buckets["decode", 1]
    assert prog.seen_s / prog.seen_n < FLOOR


def test_the_next_fence_tells_a_late_host_from_a_late_device(served, caplog):
    """The flight after the stalled one is enqueued before the stalled
    fence: if it ran to its end during the stall its fence returns at
    once (`next_seen` false) — the chip went on, the host learned late."""
    server, gs, chip = served
    chip.slow[6] = SLOW
    chip.slow[7] = 0.0  # its array is there when the host comes for it
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=10)
    (rec,) = _stall_lines(caplog, 1)
    assert rec["seq"] == 6
    assert rec["next_wait_s"] < decode._FENCE_FLOOR_S * 50
    assert rec["next_seen"] is False


def test_a_stall_with_nothing_left_to_land_is_finished_without_a_fence(
        served, caplog):
    server, gs, chip = served
    chip.slow[7] = SLOW  # the last of 1 prefill + 6 steps
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=7)
    (rec,) = _stall_lines(caplog, 1)
    assert rec["seq"] == 7
    assert rec["next_wait_s"] is None and rec["next_seen"] is None
    assert server.stats()["generative"]["lm"]["stalls"] == [rec]


# ----------------------------------------------------------------------
# (b) the host's own legs
# ----------------------------------------------------------------------
def test_a_slow_callback_is_a_stall_of_the_emit_leg(served, caplog):
    server, gs, chip = served
    _generate(server, n=3)  # every program built: later passes are judged
    seen = []

    def on_token(token):
        seen.append(token)
        if len(seen) == 4:
            time.sleep(SLOW)

    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=8, on_token=on_token)
    (rec,) = _stall_lines(caplog, 1)
    assert rec["leg"] == "emit" and rec["limit_s"] == FLOOR
    assert SLOW <= rec["seconds"] < 4 * SLOW
    # the flight whose token was being emitted; its fence had returned
    assert rec["kind"] == "decode" and rec["seq"] is not None
    assert 0 < rec["enqueued_to_ready_s"] < FLOOR
    assert rec["next_wait_s"] is not None
    assert _hist("serving.stall_seconds.emit")[0] == 1
    assert _hist("serving.stall_seconds.device_wait")[0] == 0
    assert telemetry.counter_value("serving.stalls") == 1


def test_a_slow_admission_is_a_stall_of_the_rest(served, caplog,
                                                 monkeypatch):
    """Admission is under no leaf span: what it takes is the pass's
    `rest`, judged when the pass ends."""
    server, gs, chip = served
    _generate(server, n=3)
    admit = gs.admit

    def slow_admit(reqs):
        time.sleep(SLOW)
        return admit(reqs)

    monkeypatch.setattr(gs, "admit", slow_admit)
    before = _hist("serving.loop.unspanned_seconds")
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=4)
    (rec,) = _stall_lines(caplog, 1)
    assert rec["leg"] == "rest" and rec["limit_s"] == FLOOR
    assert SLOW <= rec["seconds"] < 4 * SLOW
    assert rec["seq"] is None and rec["program"] is None
    assert rec["thread_cpu_s"] < 0.5 * rec["seconds"]
    assert _hist("serving.stall_seconds.rest")[0] == 1
    # and it is in the histogram every pass feeds
    after = _hist("serving.loop.unspanned_seconds")
    assert after[1] - before[1] >= SLOW


def test_a_wait_for_work_that_outlasts_its_window_is_a_stall(
        served, caplog, monkeypatch):
    """With a session live the wait is bounded by the decode window
    (2 ms): one that lasts longer was not woken."""
    server, gs, chip = served
    _generate(server, n=3)
    next_work = server._queue.next_work
    calls = []

    def late(wait_s, max_batch, stopping, until=None):
        calls.append(until)
        if until is not None and len([u for u in calls if u]) == 4:
            time.sleep(SLOW)
        return next_work(wait_s, max_batch, stopping, until=until)

    monkeypatch.setattr(server._queue, "next_work", late)
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=8)
    (rec,) = _stall_lines(caplog, 1)
    assert rec["leg"] == "wait_work" and rec["seq"] is None
    assert _hist("serving.stall_seconds.wait_work")[0] == 1


def test_an_idle_servers_wait_is_never_judged(served, caplog):
    server, gs, chip = served
    _generate(server, n=3)
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        time.sleep(3 * FLOOR)  # nothing live: the loop waits for a put
        _generate(server, n=3)
    assert _stall_lines(caplog) == []
    assert telemetry.counter_value("serving.stalls") == 0


# ----------------------------------------------------------------------
# (c) what is never judged
# ----------------------------------------------------------------------
def test_a_program_with_fewer_than_three_seen_flights_is_not_judged(
        served, caplog):
    server, gs, chip = served
    chip.slow[1] = SLOW  # the prefill bucket's first flight
    chip.slow[3] = SLOW  # the decode bucket's second
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=6)
    assert _stall_lines(caplog) == []
    assert telemetry.counter_value("serving.stalls") == 0
    assert gs._buckets["decode", 1].fence_limit() is not None


@pytest.mark.parametrize("how", ["call", "warm", "drain"])
def test_synchronous_calls_warm_up_and_drain_are_never_judged(
        served, caplog, how):
    server, gs, chip = served
    _generate(server, n=6)  # both programs have a history now
    chip.fence = chip.prefill_fence = SLOW
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        if how == "call":
            exe, fn = gs._program(gs._prefill_pred, 1, 8, True)
            gs._run(exe, fn, np.zeros((1, 8), np.float32),
                    np.full((1,), gs._slots, np.float32),
                    np.ones((1,), np.float32))
        elif how == "warm":
            server.warmup()
        else:
            # a flight in the air at a no-drain close is landed by
            # `finish_all`: a shutdown's fence times and judges nothing
            fut = server.submit_generate("lm", [5, 9, 3], max_new_tokens=20)
            while not gs._flights:
                time.sleep(0.001)
            server.close(drain=False)
            assert fut.result(timeout=60).finish_reason == "closed"
    assert [r for r in _stall_lines(caplog)
            if how != "drain" or r["leg"] == "device_wait"
            and r["next_wait_s"] is None] == []
    if how != "drain":
        assert telemetry.counter_value("serving.stalls") == 0


def test_a_pass_that_builds_a_program_judges_none_of_its_host_legs(
        plane, monkeypatch, caplog):
    """A replica's warm-up traffic runs through the same loop: a bucket's
    first dispatch binds and compiles for longer than the floor, and is
    no stall."""
    monkeypatch.setattr(decode, "_STALL_FLOOR_S", 1e-4)
    server, gs, chip = _serve(monkeypatch, two_programs=False)
    try:
        with caplog.at_level(logging.WARNING, logger=decode.__name__):
            built = telemetry.counter_value("serving.decode.bucket_programs")
            fut = server.submit_generate("lm", [5, 9, 3], max_new_tokens=2)
            fut.result(timeout=120)
        assert (telemetry.counter_value("serving.decode.bucket_programs")
                > built)
        # every leg over 100 us in the passes that built: none recorded;
        # what is recorded comes from passes that built nothing
        first = min((r["pass"] for r in _stall_lines(caplog)), default=None)
        compiled = [r for r in _stall_lines(caplog)
                    if r["leg"] in ("pack", "dispatch") and r["seconds"] > 0.1]
        assert compiled == [] and (first is None or first > 1)
    finally:
        server.close()


def test_a_session_driven_by_hand_has_no_pass_and_judges_nothing(
        plane, monkeypatch, caplog):
    lm, params = _lm_and_params(two_programs=True)
    gs = decode.GenerativeSession("lm", lm, params, max_sessions=2,
                                  max_len=32, seq_buckets=[8])
    chip = _Chip(monkeypatch, gs)
    chip.slow[6] = SLOW
    req = decode.GenerateRequest("lm", [5, 9, 3], 60.0, 10)
    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        assert gs.admit([req]) == []
        while gs.active():
            gs.decode_step()
    gs.close()
    assert len(req.future.result(timeout=1).tokens) == 10
    assert _stall_lines(caplog) == [] and gs.stats()["stalls"] == []


# ----------------------------------------------------------------------
# (d) a long sound prefill
# ----------------------------------------------------------------------
def test_a_long_prefill_under_its_own_limit_is_no_stall(plane, monkeypatch,
                                                        caplog):
    """A fence is held to its PROGRAM's history: a prefill bucket whose
    flights take 0.1 s may take 0.2 (under the floor plus twice the
    mean), which a decode step may not."""
    server, gs, chip = _serve(monkeypatch, fence=0.0)
    try:
        # flights 1-3 build the programs; their fences do not block, so
        # no history starts from a flight that a compile stood behind
        _generate(server, n=3)
        chip.fence, chip.prefill_fence = FENCE, 0.1
        for _ in range(3):
            _generate(server, n=5)  # prefills are flights 4, 9, 14
        assert gs._buckets["prefill", 8].fence_limit() == pytest.approx(
            FLOOR + 2 * 0.1, rel=0.25)
        with caplog.at_level(logging.WARNING, logger=decode.__name__):
            chip.slow[19] = 0.2  # the fourth prefill
            _generate(server, n=5)
            time.sleep(0.05)
            assert _stall_lines(caplog) == []
            chip.slow[26] = 0.2  # a decode step of the fifth request
            _generate(server, n=5)
        (rec,) = _stall_lines(caplog, 1)
        assert (rec["leg"], rec["kind"], rec["seq"]) == (
            "device_wait", "decode", 26)
    finally:
        server.close()


# ----------------------------------------------------------------------
# (e) the legs and the rest add up to the pass
# ----------------------------------------------------------------------
def test_the_legs_and_the_rest_add_up_to_the_passes(plane, monkeypatch):
    """Over 200 passes: every leaf span's seconds, collected where the
    spans close, plus the sum of `serving.loop.unspanned_seconds` is the
    time from the first pass's start to the last one's end, and
    `serving.loop.passes` counts them."""
    server, gs, chip = _serve(monkeypatch, two_programs=False)
    legs, starts = [], []
    sent, landed, turn = gs._sent, gs._landed, decode.Pass.turn

    def spy_turn(pas, wait, live):
        # a pass ENDS where the next one's wait began
        starts.append(wait.end_ns - int(wait.seconds * 1e9))
        legs.append(wait.seconds)
        return turn(pas, wait, live)

    monkeypatch.setattr(decode.Pass, "turn", spy_turn)
    monkeypatch.setattr(gs, "_sent", lambda pack, span, flight: (
        legs.extend((pack.seconds, span.seconds)),
        sent(pack, span, flight))[1])
    monkeypatch.setattr(gs, "_landed", lambda flight, *spans: (
        legs.extend(span.seconds for span in spans),
        landed(flight, *spans))[1])
    try:
        for _ in range(8):
            _generate(server, n=28)
    finally:
        server.close()
    passes = telemetry.counter_value("serving.loop.passes")
    count, unspanned = _hist("serving.loop.unspanned_seconds")
    assert passes == count == len(starts) - 1 >= 200
    whole = (starts[-1] - starts[0]) * 1e-9
    # the last wait opened a pass that never ended: it is in no sum
    assert sum(legs[:-1]) + unspanned == pytest.approx(whole, rel=0.01)
    assert 0 < unspanned < whole
    assert server._pass.number == len(starts)


# ----------------------------------------------------------------------
# (f) decode flights in the flight recorder, and the watchdog over them
# ----------------------------------------------------------------------
def test_decode_flights_are_enter_exit_pairs_of_the_flight_recorder(served):
    server, gs, chip = served
    _generate(server, n=5)
    server.close()
    flights = [(e["phase"], e["seq"], e["detail"])
               for e in recorder.events() if e["kind"] == "flight"]
    # flight 1 the prefill, dispatched at admission and read after step 2
    # was dispatched behind it; then each step enters before its
    # predecessor exits: the loop runs one step ahead
    assert flights == [
        ("enter", 1, "lm prefill.8"), ("enter", 2, "lm decode.1"),
        ("exit", 1, ""), ("enter", 3, "lm decode.1"), ("exit", 2, ""),
        ("enter", 4, "lm decode.1"), ("exit", 3, ""),
        ("enter", 5, "lm decode.1"), ("exit", 4, ""), ("exit", 5, "")]
    assert recorder.open_spans() == []
    assert recorder.progress()["flight"] == {
        "entered": 5, "exited": 5, "last_entered_seq": 5,
        "last_exited_seq": 5}


def test_a_buckets_first_flight_is_a_compile_bracket_of_the_recorder(
        plane, monkeypatch):
    """A bucket's first dispatch compiles (or reads the compile cache for
    a second) with the flight before it in the air: the bracket holds the
    armed watchdog still, as the classic fill's does, and restarts the
    age of the flights behind it."""
    server, gs, chip = _serve(monkeypatch, two_programs=False)
    try:
        _generate(server, n=4)
        compiles = [(e["phase"], e["detail"]) for e in recorder.events()
                    if e["kind"] == "compile"]
        # the ladder's idle steps before the first mixed step, that
        # step's bucket, the first plain step's (an exit says no detail);
        # no bracket for the warm flights
        assert compiles == [
            ("enter", "lm decode ladder"), ("exit", ""),
            ("enter", "lm prefill.8"), ("exit", ""),
            ("enter", "lm decode.1"), ("exit", "")]
        assert not recorder.compiling()
        assert recorder.last_compile_exit() > 0
        before = len(compiles)
        _generate(server, n=4)
        assert len([e for e in recorder.events()
                    if e["kind"] == "compile"]) == before
    finally:
        server.close()
    assert recorder.open_spans() == []


@pytest.mark.parametrize("how", ["abandon", "fail"])
def test_no_flight_stands_open_after_a_shutdown_or_a_failed_step(
        served, how):
    server, gs, chip = served
    fut = server.submit_generate("lm", [5, 9, 3], max_new_tokens=20)
    while not gs._flights:
        time.sleep(0.001)
    if how == "abandon":
        server.close(drain=False)
        assert fut.result(timeout=60).finish_reason == "closed"
    else:
        gs._flights[-1].outs = (None,)  # its fence raises
        with pytest.raises(AttributeError):
            fut.result(timeout=60)
        server.close()
    assert recorder.open_spans() == []


def test_the_synchronous_call_path_records_no_flight(served):
    server, gs, chip = served
    server.warmup()
    assert [e for e in recorder.events() if e["kind"] == "flight"] == []


def test_the_watchdog_dumps_a_post_mortem_that_names_an_open_flight(
        served, tmp_path):
    server, gs, chip = served
    _generate(server, n=3)
    # the last flight of the next request (its prefill is flight 4): no
    # step runs ahead of it, whose own dump would replace this one's
    chip.slow[7] = 0.6
    wd = StallWatchdog(stall_seconds=0.05, artifact_dir=str(tmp_path),
                       poll_seconds=0.01)
    wd.start()
    try:
        _generate(server, n=4)
    finally:
        wd.stop()
        wd.join(timeout=5)
    with open(wd.artifact_path) as f:
        art = json.load(f)
    assert art["schema"] == "mxtpu-obs-postmortem-v1"
    stalled = {(s["kind"], s["seq"]): s for s in art["stalled"]}
    assert ("flight", 7) in stalled
    assert stalled["flight", 7]["detail"] == "lm decode.1"
    # where the batcher stood: in the fence of `_land`
    (stack,) = [v for k, v in art["stacks"].items() if "serve_batcher" in k]
    assert "_land" in stack and "block_until_ready" in stack
    assert any(e["kind"] == "flight" for e in art["events"])


def test_a_model_server_arms_the_watchdog_from_the_environment(
        monkeypatch, tmp_path):
    from mxnet_tpu import obs
    from mxnet_tpu.obs import watchdog

    monkeypatch.setenv("MXTPU_OBS_STALL_SECONDS", "30")
    monkeypatch.setenv("MXTPU_OBS_DIR", str(tmp_path))
    monkeypatch.setattr(obs, "_BOOTSTRAPPED", False)
    assert watchdog._WD is None
    server = mx.serving.ModelServer({})
    try:
        assert watchdog._WD is not None and watchdog._WD.is_alive()
        assert watchdog._WD.stall_seconds == 30.0
        assert watchdog._WD.artifact_dir == str(tmp_path)
    finally:
        server.close()
        watchdog.stop()


# ----------------------------------------------------------------------
# (g) what it costs with every sink off
# ----------------------------------------------------------------------
def test_with_both_planes_off_a_pass_makes_no_system_call(plane, monkeypatch,
                                                          caplog):
    calls = []
    thread_time_ns, getrusage = time.thread_time_ns, resource.getrusage
    monkeypatch.setattr(time, "thread_time_ns", lambda: (
        calls.append("thread_time_ns"), thread_time_ns())[1])
    monkeypatch.setattr(time, "process_time_ns", lambda: (
        calls.append("process_time_ns"), 0)[1])
    monkeypatch.setattr(resource, "getrusage", lambda who: (
        calls.append("getrusage"), getrusage(who))[1])
    server, gs, chip = _serve(monkeypatch)
    try:
        _generate(server, n=3)
        assert {"thread_time_ns", "process_time_ns", "getrusage"} <= set(
            calls)
        telemetry.set_enabled(False)
        recorder.set_enabled(False)
        time.sleep(0.05)  # the pass that was open when the planes went off
        del calls[:]
        chip.slow[9] = SLOW
        with caplog.at_level(logging.WARNING, logger=decode.__name__):
            _generate(server, n=8)
        assert calls == [] and _stall_lines(caplog) == []
        assert server._pass.on is False
        assert server.health()["stalls"] == 0
    finally:
        telemetry.set_enabled(True)
        recorder.set_enabled(True)
        server.close()
    assert telemetry.counter_value("serving.loop.passes") > 0


def test_the_thread_is_sampled_every_quarter_of_a_second_not_every_pass(
        served, monkeypatch):
    """Three system calls cost 18 us on the v5e's host: a pass whose
    start is less than `_SAMPLE_EVERY_NS` after the last sample takes
    none."""
    server, gs, chip = served
    _generate(server, n=3)
    calls, sample = [], decode._thread_sample
    monkeypatch.setattr(decode, "_thread_sample",
                        lambda: (calls.append(1), sample())[1])
    first, began = server._pass.number, time.monotonic()
    for _ in range(3):
        _generate(server, n=28)
    passes = server._pass.number - first
    elapsed = time.monotonic() - began
    assert passes >= 80
    assert 1 <= len(calls) <= elapsed * 1e9 / decode._SAMPLE_EVERY_NS + 2
    assert len(calls) < passes / 4


def test_with_telemetry_off_and_the_recorder_on_a_stall_is_still_recorded(
        served, caplog):
    """The counters are telemetry's; the record is the operator's.  With
    no device time booked no fence has a history, so it is a host leg
    that shows."""
    server, gs, chip = served
    _generate(server, n=3)
    telemetry.set_enabled(False)
    try:
        seen = []

        def on_token(token):
            seen.append(token)
            if len(seen) == 3:
                time.sleep(SLOW)

        with caplog.at_level(logging.WARNING, logger=decode.__name__):
            _generate(server, n=6, on_token=on_token)
    finally:
        telemetry.set_enabled(True)
    (rec,) = _stall_lines(caplog, 1)
    assert rec["leg"] == "emit"
    assert [e["kind"] for e in recorder.events()].count("stall") == 1
    assert telemetry.counter_value("serving.stalls") == 0


def test_the_two_occupancy_gauges_are_written_when_they_change(
        served, monkeypatch):
    """`_note_occupancy` ran twice a pass and took the registry's lock
    each time: now an admission and a retirement write, a pass does
    not."""
    server, gs, chip = served
    _generate(server, n=3)
    writes = []
    set_gauge = telemetry.set_gauge
    monkeypatch.setattr(telemetry, "set_gauge", lambda name, value: (
        writes.append((name, value)), set_gauge(name, value))[1])
    _generate(server, n=12)
    occupancy = [w for w in writes if w[0] in (
        "kv.slot_occupancy", "serving.decode.active_sessions")]
    assert occupancy == [
        ("kv.slot_occupancy", 0.5), ("serving.decode.active_sessions", 1),
        ("kv.slot_occupancy", 0.0), ("serving.decode.active_sessions", 0)]
    snap = telemetry.snapshot()["gauges"]
    assert snap["kv.slot_occupancy"] == 0.0
    assert snap["serving.decode.active_sessions"] == 0


def test_stats_keeps_the_last_sixteen_records_and_counts_them_all(
        served, caplog):
    server, gs, chip = served
    _generate(server, n=3)

    def on_token(token):
        time.sleep(FLOOR * 1.2)

    with caplog.at_level(logging.WARNING, logger=decode.__name__):
        _generate(server, n=19, on_token=on_token)
    lines = _stall_lines(caplog, 19)
    stats = server.stats()["generative"]["lm"]
    assert stats["stall_count"] == 19 == server.health()["stalls"]
    assert len(stats["stalls"]) == decode._STALLS_KEPT == 16
    assert stats["stalls"] == lines[-16:]
    assert telemetry.counter_value("serving.stalls") == 19


def test_the_batcher_thread_is_the_one_sampled(served):
    """`RUSAGE_THREAD` and `thread_time_ns` are the calling thread's:
    the pass samples on the batcher, whose CPU time a busy client thread
    does not grow."""
    server, gs, chip = served
    _generate(server, n=3)
    assert server._thread.name == "serve_batcher"
    stop = threading.Event()

    def burn():
        while not stop.is_set():
            sum(range(1000))

    burner = threading.Thread(target=burn, daemon=True)
    burner.start()
    try:
        time.sleep(0.05)
        before = server._pass.sample
        time.sleep(0.2)
        _generate(server, n=2)
        after = server._pass.sample
    finally:
        stop.set()
        burner.join()
    grown = decode._thread_growth(before, after)
    assert grown["process_cpu_s"] > grown["thread_cpu_s"] >= 0
