#!/usr/bin/env python3
"""What the batcher's pass ledger costs a pass, on this host.

    python3 tools/pass_cost.py [--passes 200000] [--record]

Times, with telemetry and the flight recorder on, what one pass of
``ModelServer._loop`` pays for being judged (serving/decode.py "A pass and
its legs") against the same loop without it: `Pass.turn` (one ``inc``, one
``observe``, and every `_SAMPLE_EVERY_NS` — each 62nd of these 4 ms
passes — the thread's three system calls), the comparison a leg (the two
of a dispatch and the three of a landing), and a flight's two flight-recorder
events.  Prints one JSON line, microseconds a pass; the parts are timed
on their own too (`thread_sample_us`: ONE sample, what a pass would pay
if it took one each), so that the sum can be read against the whole.  ``--record`` also times what
is paid once a stall — the record itself (the host's load and pressure,
the device's memory statistics, one JSON line, one log line) — and one
poll of the armed stall watchdog; it touches the default device, so on
the chip's host it needs the chip free.

No program runs here: the number is the host's, and is reported beside a
span's 2.45 us and a flight's device-time booking of 5.4 us (PERF.md)."""
import argparse
import collections
import json
import logging
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class _Span:
    """A closed span of 40 us that ended now-ish."""

    seconds = 40e-6

    def __init__(self):
        self.end_ns = time.perf_counter_ns()


class _Prog:
    kind, bucket, program, label = "decode", 8, "jit_f", "lm decode.8"
    seen_n, seen_s = 100, 0.48

    def fence_limit(self):
        from mxnet_tpu.serving import decode

        return decode._Bucket.fence_limit(self)


class _Flight:
    seq, riders, prog, enqueued_ns = 7, 8, _Prog(), 0


def _per_pass(fn, passes):
    """Microseconds a call of `fn`, the best of five rounds."""
    best = None
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(passes):
            fn()
        took = (time.perf_counter_ns() - t0) / passes * 1e-3
        best = took if best is None else min(best, took)
    return best


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--passes", type=int, default=200000)
    ap.add_argument("--record", action="store_true")
    args = ap.parse_args()

    from mxnet_tpu import telemetry
    from mxnet_tpu.obs import recorder
    from mxnet_tpu.serving import decode

    telemetry.set_enabled(True)
    recorder.set_enabled(True)

    class Session:
        """What `_sent` / `_landed` read of a session."""

        _stalls_open, _flights = (), ()
        _sent = decode.GenerativeSession._sent
        _landed = decode.GenerativeSession._landed

    session, pas = Session(), decode.Pass()
    session._pass = pas
    wait, leg, flight = _Span(), _Span(), _Flight()

    def judged():
        wait.end_ns += 4_000_000  # a pass of 4 ms
        pas.turn(wait, True)
        session._sent(leg, leg, flight)
        if recorder.enabled():
            recorder.record("flight", "enter", 7, detail="lm decode.8")
        if recorder.enabled():
            recorder.record("flight", "exit", 7)
        session._landed(flight, leg, leg, leg)

    def turn():
        wait.end_ns += 4_000_000
        pas.turn(wait, True)

    def legs():
        session._sent(leg, leg, flight)
        session._landed(flight, leg, leg, leg)

    def events():
        if recorder.enabled():
            recorder.record("flight", "enter", 7, detail="lm decode.8")
        if recorder.enabled():
            recorder.record("flight", "exit", 7)

    def booked():
        if telemetry.enabled():
            telemetry.inc("serving.loop.passes")
            telemetry.observe("serving.loop.unspanned_seconds", 2e-4)

    n = args.passes
    out = {"passes": n,
           "empty_loop_us": _per_pass(lambda: None, n),
           "pass_us": _per_pass(judged, n),
           "turn_us": _per_pass(turn, n),
           "legs_us": _per_pass(legs, n),
           "recorder_events_us": _per_pass(events, n),
           "thread_sample_us": _per_pass(decode._thread_sample, n),
           "inc_and_observe_us": _per_pass(booked, n)}
    out["added_us"] = out["pass_us"] - out["empty_loop_us"]
    if args.record:
        import jax

        from mxnet_tpu.obs.watchdog import StallWatchdog

        logging.getLogger(decode.__name__).addHandler(logging.NullHandler())
        logging.getLogger(decode.__name__).propagate = False

        class Stalled(Session):
            name = "lm"
            _device = jax.devices()[0]
            _gc_seen = [0, 0, 0]
            _stall_count = 0
            _stall = decode.GenerativeSession._stall
            _stall_record = decode.GenerativeSession._stall_record
            _finish_stalls = decode.GenerativeSession._finish_stalls
            _kind = staticmethod(lambda prog: prog.kind)

        stalled = Stalled()
        stalled._pass, stalled._stalls_open = pas, []
        stalled._stalls = collections.deque(maxlen=16)

        def record():
            stalled._stall("device_wait", 3.0, 0.26, flight, leg)
            stalled._finish_stalls(None, 0.004, True)

        out["stall_record_us"] = _per_pass(record, 200)
        assert len(stalled._stalls) == 16, "no record was made"
        out["device"] = jax.devices()[0].device_kind
        if recorder.enabled():  # two flights stand open, as in steady state
            recorder.record("flight", "enter", 8, detail="lm decode.8")
            recorder.record("flight", "enter", 9, detail="lm decode.8")
        wd = StallWatchdog(stall_seconds=3600.0)
        out["watchdog_poll_us"] = _per_pass(wd.check, 20000)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
