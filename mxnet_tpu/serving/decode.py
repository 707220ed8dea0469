"""Generative decode sessions — slot-based KV caching + token-level
continuous batching (ROADMAP item 2; docs/serving.md "Decode sessions
& continuous batching").

One :class:`GenerativeSession` is the generative analog of
:class:`~.session.TenantSession`: one autoregressive LM served under
one tenant name.  Where a TenantSession packs whole requests into one
forward, a GenerativeSession owns *sessions* — requests that live for
many decode iterations — and two program families:

* **prefill** — one prompt (batch 1, padded to a sequence-length
  bucket) runs through the full forward ONCE, writing each layer's
  per-head K/V block into the session's ring slot and emitting the
  first next-token logits from the prompt's true tail.  One dispatch,
  cache write included.
* **decode** — one token for EVERY active session, packed into a
  decode-batch bucket.  Slot index and length ride as traced operands
  (ops/attention.py `_cached_attention`), so each decode bucket
  compiles exactly ONCE and sessions join/leave between steps without
  recompiling — the vLLM slot discipline composed with the Orca
  iteration-level re-pack the batcher already does for classic
  tenants.
* **mixed step** (PR 46) — where every mixer kind of the model has a
  mixed form (``model.mixed_symbol(slots)``), the two in ONE program: it
  prefills one prompt into its slot as the prefill does AND advances
  ``max_sessions`` packed rows one token as a decode step does, the
  prompt's positions and the rows' tokens riding every dense product
  together, so an admission reads the weights once and not twice.  It
  takes the prefill programs' PLACE, bucket for bucket (no executor
  more: each binds a cache set of its own); rows with no session point
  at the scratch slot with length 0, and with none live it IS the
  prefill.  :meth:`admit` then only takes the slot and leaves the prompt
  PENDING; :meth:`decode_step` dispatches ONE program an iteration —
  the oldest pending prompt's mixed step with every live row packed
  into it, or, nothing pending, the decode bucket's step — so several
  prompts ride consecutive iterations and the sessions admitted earlier
  advance meanwhile.  A model with a mixer kind that has no mixed form
  (Mamba-2, latent attention) keeps the two programs, its prefill dispatched at
  admission as before: that is the only selection, made from what the
  model offers.

**State of more than one kind.**  What a session keeps on the device
between calls is whatever the MODEL's ``cache_spec(slots, max_len)``
states — an ordered ``{name: CacheEntry(kind, shape)}`` the session never
spells out: per attention layer two KV RINGS (kind ``"ring"``:
``max_sessions + 1`` pages of as many positions as the layer's kind
keeps — the session's ``max_len``, or a window layer's window, which a
session outgrows and then writes modulo — every length read off the
entry's own shape), per latent-attention layer ONE latent ring (kind
``"latent"``: a ring in every respect — pages of ``max_len`` positions,
masked by length, counted with the rings — of one row a position that
all heads share, with no second ring beside it), per state-space
layer a conv window and a recurrent STATE (kind ``"state"``: a fixed size
a slot, whatever the context).  Every entry is preallocated, threaded,
donated, booked and charged the same way; the kinds differ in what may
be stale.  A ring is masked by ``length``, so what a slot's previous
tenant or a padded prefill left beyond it is never read.  A recurrent
state has no mask: a prefill therefore computes the prompt's state from
an empty one, counts no position of the bucket's pad (``length`` rides
into the scan), and writes the WHOLE of the slot's window and state —
nothing of the previous tenant survives — and a decode step writes only
the slots of its rows.  Index ``max_sessions`` is the SCRATCH slot that
padded decode rows (and the warm-up's fills) point at: their ring writes
land on its position 0 one after the other and their state updates on
its one state, garbage nobody reads.

The entries thread FUNCTIONALLY through every program
call — caches in, updated caches out — which on TPU rides the serve
program's donated input tuple: a decode step writes one row a session
in place and each row's attention reads its own page where it lies
(ops/attention.py), so a step moves B pages, not the rings; on CPU,
which does not donate, it costs one buffer copy per step.  Donation
deletes the buffers passed in, so every call REPLACES the rings it was
given with the ones it got back; warm-up runs on throwaway rings of its
own and never touches the live ones.

**The loop runs one step ahead of the host.**  Step n+1 needs one thing
of step n, a token id a session, and that never leaves the device: every
program samples the greedy token of its logits and writes it at
``last_token[slot]``, a ``(max_sessions + 1,)`` vector threaded and
donated with the rings, and a decode row whose ``data`` is negative
reads its token from there (ops/attention.py ``_token_feed`` /
``_greedy_token``).  Rows are addressed by slot, so this survives any
re-pack, bucket change or admission.  A program call is therefore
DISPATCHED and left in flight (:class:`_Flight`); the host reads its
small outputs — the ``(B,)`` tokens and a routed model's ``moe_load``,
never the ``(B, vocab)`` logits — one call later.  :meth:`decode_step`
packs and dispatches step n+1 first, from what the host knows without
the token (who is live, their slots, how many positions each has fed:
retirement by budget or ring-full is a count, so a session whose token
in flight is its last is simply not packed), and only then fences on
step n, reads it and emits — one ``on_token`` a session a step.  A
prefill is dispatched by :meth:`admit` the same way and read after the
step that was in flight before it (a mixed step is dispatched by
:meth:`decode_step` in a plain step's place and read by the next call,
under ``serve.prefill``: it is a prefill for every reading with prefill
in its name — its bucket's positions, its whole device time, the host's
wait for it — and a decode dispatch of the live rows it carried for the
counters of steps, rows and tokens).  So the host's pack, dispatch and
emit, and the completion's way back to the host, pass under the device's
step instead of beside it.  EOS needs the value: a session that turns
out to have hit EOS has one row in flight, whose token is dropped
(``serving.decode.dropped_rows``) and whose K/V row lands in its own,
already freed slot at a position the slot's next tenant overwrites
before it attends that far.  A step with no predecessor (the first
after idle) is dispatched and read by the next call.

**A model that drafts** (``model.token_state`` gives ``last_token`` a
second axis: `TransformerLM` with `nextn`, a multi-token-prediction module
used as the model's own draft).  A decode step then runs TWO positions a
row — the last verified token and the draft of the one after it — and
yields ONE OR TWO tokens: the trunk's argmax after the verified token,
and, where the draft was that very token, the trunk's argmax after the
draft.  The tokens a request receives are the trunk's own greedy tokens
whatever the draft; nothing here accepts or forces one.  What changes
with it is what the host can know a step ahead.  A row's next POSITION
depends on whether the device accepted, so token, draft and position of
every slot live on the device (``last_token (3, slots + 1)``) and a row
packed while its last step is in flight (negative ``data``) takes all
three from there; ``_Session.fed`` is then a LOWER BOUND of the positions
cached — one a step dispatched, and one more for each step that is READ
and had accepted — exact once nothing of the session is in flight.
:meth:`_wants_row` decides by that bound: a session that MAY still lack
a token is packed, so one whose step in flight turns out to have emitted
its last token has a row in flight that is dropped, exactly as after an
EOS (its writes land in its own, freed slot at positions the next tenant's
prefill and steps overwrite before they read that far; a step's second
row may lie ONE position past ``prompt + budget``, which ``max_len``
must leave room for or the write is clamped onto the row before it — of
a session that has retired).  :meth:`_land` reads ``token (B, 3)`` =
``[count, first, second]`` and emits `count` tokens, trimmed to the
budget — a reply holds exactly its budget.  Counters tell rows, positions
and tokens apart: ``serving.decode.row_steps`` (rows read that emitted),
``serving.decode.tokens`` (tokens emitted by them),
``serving.mtp.drafts`` (rows read that carried a draft),
``serving.mtp.accepted`` (those whose second token was emitted),
``serving.mtp.dropped_rows`` (second positions computed and thrown away:
rejected, past a budget or the ring's end, or of a row dropped whole),
``mtp.bytes`` / ``mtp.step_bytes`` (the weights the module's part of a
step reads, and the whole step's: ``model.step_weight_bytes``).  There is
no mixed step for such a model.

**Device time, from the fences.**  The device runs the flights in the
order they were enqueued and :meth:`_land` fences on every one.  When
the host was BLOCKED at the fence of flight k-1 and of flight k (each
``decode.device_wait`` span outlasted ``_FENCE_FLOOR_S``: the array
was not there yet), the two exits are the two completions plus the same
way back to the host, and their difference is flight k's time on the
device, from ``max(ready(k-1), enqueued(k))``.  Such a flight is SEEN
and its interval goes into the histogram of its program
(``serving.device.decode_seconds`` / ``.prefill_seconds``, and the same
name with ``.<bucket>``); a flight whose own fence, or whose
predecessor's while it was queued behind it, did not block is counted
(``serving.device.flights``) and feeds nothing.  A flight enqueued
AFTER its predecessor's fence returned found the chip with nothing
queued: that gap is ``serving.device.starved_seconds`` if a session was
live through it — a lower bound, the fence's exit is late by the
completion's way to the host.  Two subtractions and one ``observe`` a
flight on clock readings the spans already took; no profiler session is
needed (docs/observability.md "Device time without a profiler").

**A pass and its legs.**  A PASS is one iteration of
``ModelServer._loop``: its wait for work, at most one admission, one
:meth:`decode_step` a live tenant.  Its LEGS are the leaf spans the
batcher's thread opens on the way — ``serve.wait_work``, ``decode.pack``
(a step's rows or a prompt's bucket), ``decode.dispatch``,
``decode.device_wait``, ``decode.d2h``, ``decode.emit``, whichever
flight they belong to: the step a call dispatches is one flight and the
step or prefill it lands another — and ``rest``: the pass's duration
less the leaves', the thread's time under NO span (admission, the
counters' own booking, the parents' bookkeeping).  Each leaf adds its
``seconds`` to the server's :class:`Pass` as it closes, and the pass ends
where the next ``serve.wait_work`` began (that span's ``end_ns`` less its
``seconds``): no clock is read for it.  Every pass books `rest`
(``serving.loop.unspanned_seconds``, ``serving.loop.passes``).

A leg is a STALL when it outlasts its limit: `_STALL_FLOOR_S` for a host
leg (``wait_work`` only while a session is live, its wait bounded by the
decode window), and for a fence the floor plus `_STALL_REF_X` times the
program's own mean SEEN device time (``_Bucket.seen_n`` / ``seen_s``, fed
where the histograms above are), once `_STALL_MIN_SEEN` of its flights
were seen.  A pass that built or compiled a program — a bucket's first
``_program`` or first flight, ``_build_ladder``'s idle steps (the two
that compile are ``compile`` brackets of the flight recorder too, which
hold the stall watchdog still), a classic fill — judges none of its
host legs, and the synchronous ``_call``, a
shutdown's landings and a session driven by hand (no pass) are never
judged: a replica's warm-up runs through this same loop and logs
nothing.  A stall books ``serving.stalls`` and
``serving.stall_seconds[.<leg>]`` and leaves one RECORD (:meth:`_stall`):
which leg of which pass, for how long against what limit; the flight —
``seq``, program, kind, bucket, rows, enqueue to ready, the mean it was
held to —; what the THREAD did up to the stall (CPU seconds, context
switches of its own will and against it, major faults, over `sampled_s`
seconds: sampled at a pass's start — the end of its ``serve.wait_work``
— every `_SAMPLE_EVERY_NS`, not every pass, and once more at the stall);
what the
HOST and the device said then (load, pressure, a compile, the
interpreter's collections, the allocator); and, filled at the NEXT
landing, how long that flight's fence blocked and whether it was seen —
step n+1 is enqueued before step n's fence, so a next fence that blocks
its own device time says flight n itself ended late, and one that
returns at once says the chip went on and the host learned late.  The
finished record is kept for :meth:`stats`, logged as ONE line ``mx.stall
{json}`` and put into the flight recorder (kind ``"stall"``), where every
flight is an enter/exit pair too (kind ``"flight"``): the stall watchdog,
armed, dumps every thread's stack while one stands open
(docs/observability.md "Reading a stall record").

Retirement (EOS, token budget, or ring-full) resolves the request's
future with a :class:`GenerateResult` and frees the slot under
admission control: prompts that arrive while all slots are busy wait
in the tenant queue and are re-offered every decode window.  The
server's close/drain contract extends to sessions: every future is
resolved when close() returns, with partial tokens and
``finish_reason='closed'`` on a no-drain shutdown — never lost; tokens
still in flight then are read first, they are computed already.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import logging
import os
import resource
import time

import numpy as _np

from ..base import MXNetError
from .. import locks, profiler, telemetry
from ..obs import recorder
from .bucket import bucket_ladder, choose_bucket
from .request import Request

__all__ = ["GenerativeSession", "GenerateRequest", "GenerateResult"]

# the histograms of the three legs in which a decode step is READ (device
# wait, read-back, emit), one `decode_step` call after the one whose
# `pack` and `dispatch` legs sent it.  A prefill and a synchronous `_run`
# record the same spans and feed no histogram
_LAND_HISTS = ("serving.decode.device_wait_seconds",
               "serving.decode.d2h_seconds",
               "serving.decode.emit_seconds")
_NO_HISTS = (None, None, None)
# a fence that lasted longer found its array NOT there, so its exit is
# the program's completion plus the completion's way to the host.  On
# the v5e a fence on an array that is there lasts 0.4 us (19 us the
# longest of 20,000) and the first fence on a finished program's output
# whose host copy was asked for 17 us (45 us the longest of 300):
# PERF.md section 6, PR 35
_FENCE_FLOOR_S = 100e-6
# what a flight that was NOT seen adds to the two counters a mean device
# time divides by (`serving.device.decode_seen`, `.prefill_positions`),
# where a seen one adds 1: a window that saw one flight in a thousand
# reads its mean 0.1% low, and a window that saw none divides 0 by
# something — a reader that takes a zero denominator for "no reading"
# (the benchmark's `ratio`) then reads 0, not nothing
_UNSEEN = 1e-6
# a leg of the batcher's pass that outlasts this stood still (module
# docstring, "A pass and its legs").  The slowest sound host leg on
# record is a 3 ms dispatch and the longest sound program a 1.0 s prefill
# (PERF.md section 7 (9)); the stalls it is there to name last 1.3-3.4 s
_STALL_FLOOR_S = 0.25
# a fence may wait its program's whole device time and no more (every
# flight before it has been fenced): the floor plus this many times the
# program's own mean SEEN time leaves a sound flight its scatter
_STALL_REF_X = 2.0
# a program is judged once this many of its flights were seen: a bucket's
# first calls have no history to be held to
_STALL_MIN_SEEN = 3
# stall records a session keeps for `stats()`; the log line and the
# flight recorder have every one
_STALLS_KEPT = 16
# the batcher's thread is sampled (`_thread_sample`) at the first pass
# that begins this long after the last sample, not every pass: on the
# v5e's host the three system calls cost 18 us together (0.4 us each on
# an ordinary Linux; PERF.md section 6, PR 50), and a stall of a quarter
# of a second or more is told as well from a baseline that old
_SAMPLE_EVERY_NS = 250_000_000


def _thread_sample():
    """What the calling thread has used so far: CPU ns of the thread and
    of the process, and the thread's `getrusage` (context switches,
    faults): three system calls, no more."""
    return (time.thread_time_ns(), time.process_time_ns(),
            resource.getrusage(resource.RUSAGE_THREAD))


def _thread_growth(old, new):
    """The fields of a stall record that say what the thread did between
    two `_thread_sample`s: on the CPU (`thread_cpu_s`), descheduled of
    its own will (`nvcsw`: it blocked) or against it (`nivcsw`: the host
    took the core), waiting for a page (`majflt`)."""
    (cpu0, proc0, ru0), (cpu1, proc1, ru1) = old, new
    return {"thread_cpu_s": (cpu1 - cpu0) * 1e-9,
            "process_cpu_s": (proc1 - proc0) * 1e-9,
            "nvcsw": ru1.ru_nvcsw - ru0.ru_nvcsw,
            "nivcsw": ru1.ru_nivcsw - ru0.ru_nivcsw,
            "majflt": ru1.ru_majflt - ru0.ru_majflt}


def _pressure(resource_name):
    """`some avg10` of ``/proc/pressure/<resource_name>``: the share of
    the last ten seconds in which some task of the HOST waited for it;
    None where the kernel keeps none."""
    try:
        with open("/proc/pressure/" + resource_name) as f:
            for line in f:
                if line.startswith("some"):
                    return float(line.split("avg10=")[1].split()[0])
    except (OSError, IndexError, ValueError):
        pass
    return None


def _host_reading(device, leg_seconds):
    """What is read of the host and the device AT a stall only: the load
    and the pressure the whole host is under, whether a compile was open
    or closed inside the leg, and what the device's allocator holds."""
    try:
        mem = device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — a backend without the statistics
        mem = {}
    return {"loadavg": list(os.getloadavg()),
            "psi_cpu_some_avg10": _pressure("cpu"),
            "psi_mem_some_avg10": _pressure("memory"),
            "psi_io_some_avg10": _pressure("io"),
            "compiling": bool(
                recorder.compiling() or recorder.last_compile_exit()
                > time.monotonic() - leg_seconds),
            "bytes_in_use": mem.get("bytes_in_use"),
            "largest_free_block_bytes": mem.get("largest_free_block_bytes"),
            "num_allocs": mem.get("num_allocs")}


class Pass:
    """One iteration of ``ModelServer._loop`` as the sum of its legs
    (module docstring, "A pass and its legs"): the server owns one and
    hands it to every generative session it registers; a session driven
    by hand has none and judges nothing.

    `number`: passes begun; `on`: whether this pass is kept at all
    (telemetry or the flight recorder is on); `start_ns`: when its
    ``serve.wait_work`` began; `leaves`: seconds its leaf spans have added
    so far; `unjudged`: it built or compiled a program, or ran a classic
    fill (whose program is under no leg): none of its host legs is a
    stall; `owner`: the session whose leg closed last, which takes a
    stall of the loop's own two legs (``wait_work``, ``rest``);
    `sample`, `sampled_ns`: the thread's last `_thread_sample` and when
    it was taken — at a pass's start, at most `_SAMPLE_EVERY_NS` before
    this one's."""

    __slots__ = ("number", "on", "start_ns", "leaves", "unjudged", "owner",
                 "sample", "sampled_ns")

    def __init__(self):
        self.number = 0
        self.on = False
        self.start_ns = 0
        self.leaves = 0.0
        self.unjudged = False
        self.owner = None
        self.sample = None
        self.sampled_ns = 0

    def growth(self, now_ns):
        """What the thread did since the last sample (`_thread_growth`),
        over how many seconds (`sampled_s`), sampled NOW, at `now_ns` on
        the spans' clock: the sample is the new baseline."""
        old, since = self.sample, self.sampled_ns
        self.sample, self.sampled_ns = _thread_sample(), now_ns
        grown = _thread_growth(old, self.sample)
        grown["sampled_s"] = (now_ns - since) * 1e-9
        return grown

    def turn(self, wait, live):
        """``serve.wait_work`` has closed: the pass before it ended where
        this span began (no clock is read: `end_ns` less `seconds`), so
        book what of it lay under no span, judge that and — `live`: a
        session was mid-generation, the wait was bounded by the decode
        window — the wait itself, and begin the next pass with the wait as
        its first leg."""
        counted = telemetry.enabled()
        if not (counted or recorder.enabled()):
            self.on = False
            return
        seconds, end_ns = wait.seconds, wait.end_ns
        start_ns = end_ns - int(seconds * 1e9)
        owner = self.owner
        if self.on:
            rest = (start_ns - self.start_ns) * 1e-9 - self.leaves
            if counted:
                telemetry.inc("serving.loop.passes")
                telemetry.observe("serving.loop.unspanned_seconds", rest)
            if owner is not None:
                grown = None
                if rest > _STALL_FLOOR_S and not self.unjudged:
                    grown = self.growth(end_ns)
                    owner._stall("rest", rest, _STALL_FLOOR_S, growth=grown)
                if live and seconds > _STALL_FLOOR_S:
                    # the wait lies between the two samples as the rest
                    owner._stall("wait_work", seconds, _STALL_FLOOR_S,
                                 growth=grown or self.growth(end_ns),
                                 number=self.number + 1)
                if owner._stalls_open and not owner._flights:
                    owner._finish_stalls()  # nothing in flight to land next
        else:
            self.on = True
            self.sampled_ns = end_ns - _SAMPLE_EVERY_NS
        if end_ns - self.sampled_ns >= _SAMPLE_EVERY_NS:
            self.sample, self.sampled_ns = _thread_sample(), end_ns
        self.number += 1
        self.start_ns = start_ns
        self.leaves = seconds
        self.unjudged = False


def device_interval(last, sent_ns, ready_ns, blocked):
    """Flight k's time on the device from two fences (module docstring).
    `last`: ``(ready_ns, blocked, ...)`` of the flight landed before it,
    or None; `sent_ns`: when k was enqueued; `ready_ns`, `blocked`: when
    its own fence returned and whether it blocked.  Returns ``(start_ns,
    seen, gap_ns)``: k ran from `start_ns` to `ready_ns`, which is its
    device time if `seen`; `gap_ns` > 0 is how long the chip had nothing
    queued before k reached it (0: k was queued behind its predecessor,
    or has none)."""
    if last is None:
        return sent_ns, blocked, 0
    if sent_ns > last[0]:
        return sent_ns, blocked, sent_ns - last[0]
    return last[0], blocked and last[1], 0


class GenerateResult:
    """What a ``submit_generate`` future resolves to.

    ``tokens``: int32 numpy array of the GENERATED tokens (prompt
    excluded, EOS included when hit); ``finish_reason``: ``'eos'`` |
    ``'length'`` (token budget or KV ring exhausted) | ``'closed'``
    (server shut down no-drain mid-generation — tokens are the partial
    prefix); ``prompt_len``: tokens consumed by prefill."""

    __slots__ = ("tokens", "finish_reason", "prompt_len")

    def __init__(self, tokens, finish_reason, prompt_len):
        self.tokens = _np.asarray(tokens, dtype=_np.int32)
        self.finish_reason = str(finish_reason)
        self.prompt_len = int(prompt_len)

    def __repr__(self):
        return ("GenerateResult(tokens=%s, finish_reason=%r, prompt_len=%d)"
                % (self.tokens.tolist(), self.finish_reason,
                   self.prompt_len))


class GenerateRequest(Request):
    """One queued generation request: the prompt snapshot plus the
    per-request decode policy.  Rides the same RequestQueue (deadline
    at dequeue, admission control, fairness) as classic requests."""

    __slots__ = ("max_new_tokens", "eos_id", "on_token")

    def __init__(self, tenant, tokens, timeout_s, max_new_tokens,
                 eos_id=None, on_token=None, trace=None, slo=None):
        tokens = _np.asarray(tokens, dtype=_np.int32).reshape(-1)
        Request.__init__(self, tenant, {"data": tokens}, timeout_s,
                         trace=trace, slo=slo)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = None if eos_id is None else int(eos_id)
        self.on_token = on_token


class _Session:
    """One ACTIVE decode session (slot held, prefill dispatched)."""

    __slots__ = ("req", "slot", "prompt_len", "generated", "fed", "retired")

    def __init__(self, req, slot, prompt_len):
        self.req = req
        self.slot = slot
        self.prompt_len = prompt_len
        self.generated = []  # the tokens the host has read
        # positions the dispatched programs have written into the ring
        # == tokens fed through the model, read back or not
        self.fed = prompt_len
        self.retired = False

    def sampled(self):
        """Tokens sampled on the device so far: the prefill's and one a
        decode row, whether or not the host has read them."""
        return self.fed - self.prompt_len + 1


class _Bucket:
    """What a flight says of the bucket program it runs: `kind`
    (``"decode"`` | ``"prefill"``), `bucket` (rows | positions),
    `program` (the executable's name in a device trace's ``XLA
    Modules`` line; ``""`` until its first flight has compiled it),
    `hists`, the two histograms its device time goes into, `label`, what
    the flight recorder says of its flights (``<tenant> <kind>.<bucket>``),
    `experts`, a routed model's ``expert_plan`` of the tokens a call
    computes (None for any other model), and `seen_n` / `seen_s`, how many
    of its flights were seen and their device seconds: the mean a fence on
    it is held to.  Built once a bucket program: a call formats no name."""

    __slots__ = ("kind", "bucket", "program", "hists", "label", "experts",
                 "seen_n", "seen_s")

    def __init__(self, kind, bucket, tenant="", experts=None):
        self.kind = kind
        self.bucket = bucket
        self.experts = experts
        self.program = ""
        hist = "serving.device.%s_seconds" % kind
        self.hists = (hist, "%s.%d" % (hist, bucket))
        self.label = "%s %s.%d" % (tenant, kind, bucket)
        self.seen_n = 0
        self.seen_s = 0.0

    def fence_limit(self):
        """How long a fence on this program may block before it is a
        stall; None while too few of its flights were seen to say."""
        if self.seen_n < _STALL_MIN_SEEN:
            return None
        return _STALL_FLOOR_S + _STALL_REF_X * self.seen_s / self.seen_n


class _Flight:
    """One dispatched program call whose outputs the host has not read:
    `outs` are the device's ``token (B,)`` and the outputs after it,
    `rows` the sessions of its tokens in order — a prefill's one session,
    a step's packed rows, a mixed step's prompt and then its riders —,
    `riders` how many of them are decode rows, `prog` the :class:`_Bucket`
    of its program, `seq` its number among the session's flights and
    `enqueued_ns` the end of its ``decode.dispatch`` span."""

    __slots__ = ("outs", "rows", "riders", "prog", "seq", "enqueued_ns")

    def __init__(self, outs, rows, riders, prog, seq, enqueued_ns):
        self.outs = outs
        self.rows = rows
        self.riders = riders
        self.prog = prog
        self.seq = seq
        self.enqueued_ns = enqueued_ns


class GenerativeSession:
    """One generative LM tenant (module docstring).

    `model` is duck-typed (models/transformer_lm.py TransformerLM is
    the zoo instance): attribute ``max_len`` and methods
    ``prefill_symbol()`` / ``decode_symbol()`` / ``cache_spec(slots,
    max_len)`` (ordered name -> entry with ``kind`` ``"ring"`` |
    ``"latent"`` | ``"state"``, ``shape`` and ``nbytes``: every buffer the session
    keeps on the device).  Both graphs take ``data``, ``slot``,
    ``length``, the spec's entries and ``last_token (slots + 1,)`` and
    return ``[logits, entries..., last_token, token (B,),
    extra_outputs()...]``.
    `params` maps parameter name -> array (a training checkpoint's
    arg+aux dicts merged; a model with ``stored_params(params)`` is asked
    for them as its graphs take them).  `max_sessions` is the number of cache
    slots (the cap on concurrent sessions), `max_len` a slot's ring
    length in tokens (clamped to the model's positional table),
    `max_decode_tokens` the budget of a request that names none."""

    is_generative = True

    def __init__(self, name, model, params, ctx=None, max_sessions=8,
                 max_len=256, max_decode_tokens=64, eos_id=None,
                 seq_buckets=None):
        from ..predict import Predictor

        self.name = name
        self._model = model
        self._slots = int(max_sessions)
        self._max_len = min(int(max_len), int(model.max_len))
        self._budget_default = int(max_decode_tokens)
        self._eos_default = None if eos_id is None else int(eos_id)
        # the model owns what is cached and in which shape; the +1 is the
        # scratch slot padded decode rows point at
        self._spec = dict(model.cache_spec(self._slots + 1, self._max_len))
        # each slot's last token — or, of a model that DRAFTS, its last
        # token, draft and position (module docstring)
        token_state = getattr(model, "token_state", None)
        self._token_shape = (tuple(token_state(self._slots + 1))
                             if token_state is not None
                             else (self._slots + 1,))
        self._drafts = len(self._token_shape) == 2
        self._step_bytes = (getattr(model, "step_weight_bytes", None)
                            if self._drafts else None)
        self._cache_bytes = sum(e.nbytes for e in self._spec.values())
        self._state_bytes = sum(e.nbytes for e in self._spec.values()
                                if e.kind == "state")
        # every ring's own positions a page (a window layer's are fewer
        # than `max_len`); counters of positions are means over the rings
        # (a latent layer's one ring counts as any other: a page of
        # positions, read by blocks up to the one that holds `length`)
        ring_entries = [e for e in self._spec.values()
                        if e.kind in ("ring", "latent")]
        rings = [e.shape for e in ring_entries]
        self._ring_lens = _np.asarray([r[3] for r in rings], _np.int64)
        self._has_ring = bool(rings)
        # the platform the programs are lowered for: what the layer kinds'
        # counters and the rings' blocks are asked with
        from ..context import current_context
        from ..ops.attention import decode_block

        self._device = (ctx or current_context()).jax_device()
        self._platform = self._device.platform
        # positions of each ring's page that one step of the decode
        # program's attention reads at a time, where it stops at the
        # block that holds `length`; the whole page where it reads whole
        # pages (the CPU)
        blocks = [decode_block(e.shape, self._platform, latent=True)
                  if e.kind == "latent"
                  else decode_block(e.shape, self._platform)
                  for e in ring_entries]
        self._ring_blocks = _np.asarray(
            [b or r[3] for b, r in zip(blocks, rings)], _np.int64)
        # a page's positions in the rings the decode program reads
        # through the TPU's kernel (those with a block), summed
        self._kernel_positions = sum(
            r[3] for b, r in zip(blocks, rings) if b)
        # a routed model's programs end with tokens per (layer, expert)
        self._reports_moe_load = "moe_load" in tuple(
            getattr(model, "extra_outputs", tuple)())
        # whose last column is then the zero-compute experts' pairs
        self._zero_experts = bool(getattr(model, "zero_experts", 0))
        # and whose experts may be of two matrices (`moe.ungated_pairs`)
        self._ungated = not getattr(model, "expert_gated", True)
        # counters the model's layer kinds declare for a program call
        self._call_counters = getattr(model, "call_counters", None)
        # every call threads the cache entries, then each slot's last
        # token; a mixed step's riders come between the prompt's operands
        # and the entries
        self._input_names = (["data", "slot", "length", "row_data",
                              "row_slot", "row_length"] + list(self._spec)
                             + ["last_token"])
        # a model whose mixer kinds all have a mixed form prefills through
        # the mixed step: that program takes the prefill's place
        mixed = getattr(model, "mixed_symbol", None)
        mixed = mixed(self._slots) if mixed is not None else None
        self._mixed = mixed is not None
        self._laddered = False  # `_build_ladder` has run
        graphs = {True: mixed or model.prefill_symbol(),
                  False: model.decode_symbol()}
        # a graph takes the inputs it uses: the decode step of a model
        # with neither a ring nor a position table has no use for `length`
        self._wire = {
            prefill: [n for n in self._input_names
                      if n in set(graph.list_arguments())]
            for prefill, graph in graphs.items()}
        # sequence-length ladder for prefill; decode-batch ladder for
        # the packed step — both compile-once through the predictors'
        # signature caches
        self._seq_ladder = (sorted(int(b) for b in seq_buckets)
                            if seq_buckets else
                            bucket_ladder(self._max_len, ""))
        self._decode_ladder = bucket_ladder(self._slots, "")
        # a model may STORE an array otherwise than a checkpoint has it (a
        # routed layer's expert stacks in whole lane tiles); what is
        # stored already passes as the object it is
        params = getattr(model, "stored_params", dict)(params)
        self._prefill_pred = Predictor(
            graphs[True], dict(params),
            self._shapes(1, self._seq_ladder[0], prefill=True), ctx=ctx)
        self._decode_pred = Predictor(
            graphs[False], dict(params),
            self._shapes(self._decode_ladder[0], 1, prefill=False),
            ctx=ctx)
        # the device-resident state, threaded through every call
        self._state = self._fresh_state()
        self._free = list(range(self._slots))  # LIFO slot pool
        # admitted (slot held) and waiting to ride a mixed step, in order
        self._pending = collections.deque()
        self._active = []
        self._flights = []  # dispatched and unread, oldest first
        self._seq = 0  # flights dispatched: a flight's `seq`
        # (ready_ns, blocked, live) of the flight landed last: when its
        # fence returned, whether that fence blocked, whether a session
        # was active once it had emitted; None when no flight's device
        # time can start from it (nothing landed, or a synchronous call
        # or a drain came between)
        self._last_fence = None
        # the server's pass, whose legs this session's spans are (None: a
        # session driven by hand); stall records that wait for the next
        # landing, the last `_STALLS_KEPT` finished ones, and their count
        self._pass = None
        self._stalls_open = []
        self._stalls = collections.deque(maxlen=_STALLS_KEPT)
        self._stall_count = 0
        # collections of the interpreter, by generation, at the last
        # stall: a record says how many came since
        self._gc_seen = [g["collections"] for g in gc.get_stats()]
        # the two gauges `_note_occupancy` wrote last
        self._occupancy = None
        self._prog_lock = locks.lock("serving.decode_progs")
        self._programs = {}
        self._placeholders = {}  # the one zero-filled set `_program` keeps
        self._buckets = {}  # (kind, bucket) -> _Bucket, beside _programs
        self._tokens_done = 0
        self._closed = False
        # book the state in the live-buffer census: nbytes is constant
        # for the session's lifetime (numpy seeds become device arrays
        # of the same shape/dtype), so book once and unbook at close()
        self._mem_booked = 0
        if telemetry.enabled():
            from ..obs import memory

            self._mem_booked = self._cache_bytes + self._state[-1].nbytes
            memory.book("kv_ring.%s" % name, self._mem_booked)
            telemetry.set_gauge("kv.ring_bytes", self._mem_booked)
            telemetry.set_gauge("kv.slot_occupancy", 0.0)
            telemetry.set_gauge("serving.decode.active_sessions", 0)

    # ------------------------------------------------------------------
    # the TenantSession surface the server drives
    # ------------------------------------------------------------------
    def _shapes(self, batch, seq, prefill):
        shp = {"data": (batch, seq), "slot": (batch,),
               "length": (batch,), "row_data": (self._slots, 1),
               "row_slot": (self._slots,), "row_length": (self._slots,)}
        shp.update({n: e.shape for n, e in self._spec.items()})
        shp["last_token"] = self._token_shape
        return {n: shp[n] for n in self._wire[bool(prefill)]}

    def _fresh_state(self, on_device=False):
        """Zeroed cache entries and last-token vector, in wire order: on
        the host (the live set: it reaches the device with the first
        call that takes it, after the warm-up's set is gone, so the
        device never holds both beside the bucket programs' placeholder
        set, `_program` — OLMoE's prefill had no room for the two before
        the placeholders were one set), or `on_device` (the
        warm-up's: gigabytes need not cross the host link to be
        zeros)."""
        shapes = [e.shape for e in self._spec.values()]
        shapes.append(self._token_shape)
        if not on_device:
            return [_np.zeros(shape, _np.float32) for shape in shapes]
        import jax.numpy as jnp

        device = self._decode_pred._ctx.jax_device()
        return [jnp.zeros(shape, jnp.float32, device=device)
                for shape in shapes]

    def validate(self, inputs):
        """A classic submit() against a generative tenant is a client
        bug — fail it at its own caller, like any validation error."""
        raise MXNetError(
            "tenant %r is generative: use submit_generate(tenant, "
            "tokens, ...) — plain submit() has no decode policy to "
            "ride on" % self.name)

    def validate_generate(self, tokens, max_new_tokens):
        """Bounds-check one generate request at submit() time."""
        n = int(_np.asarray(tokens).reshape(-1).shape[0])
        if n < 1:
            raise MXNetError("generate request for tenant %r has an "
                             "empty prompt" % self.name)
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1, got %d"
                             % max_new_tokens)
        if n + max_new_tokens > self._max_len:
            raise MXNetError(
                "generate request for tenant %r needs %d prompt + %d "
                "new tokens > the %d-token KV ring "
                "(the tenant's max_len, clamped to the model's "
                "max_len) — shorten the prompt or the budget"
                % (self.name, n, max_new_tokens, self._max_len))

    def free_slots(self):
        return len(self._free)

    def active(self):
        """Sessions mid-generation — or, once the last of them has hit
        EOS, the flight that still holds its dropped row; or prompts
        admitted that wait for their step: truthy while
        :meth:`decode_step` has anything left to do."""
        return (len(self._active) or len(self._flights)
                or len(self._pending))

    def budget_for(self, max_new_tokens):
        return (self._budget_default if max_new_tokens is None
                else int(max_new_tokens))

    def eos_for(self, eos_id):
        return self._eos_default if eos_id is None else int(eos_id)

    def _program(self, pred, batch, seq, prefill):
        """(executor, fn) for one (prefill-T | decode-B) bucket; the
        session pins executors like TenantSession does, so
        compile-once-per-bucket survives predictor-cache eviction."""
        key = ("prefill", seq) if prefill else ("decode", batch)
        with self._prog_lock:
            exe = self._programs.get(key)
            if exe is None:
                exe = self._programs[key] = pred.executor_for(
                    self._shapes(batch, seq, prefill))
                # an executor binds a zero-filled array for each of its
                # inputs, the cache entries among them, which no call
                # reads (the live set is a call operand): all bucket
                # programs hold ONE such set between them, the first
                # bound, not one each
                for name in (*self._spec, "last_token"):
                    if name in exe.arg_dict:
                        exe.arg_dict[name] = self._placeholders.setdefault(
                            name, exe.arg_dict[name])
                self._buckets[key] = _Bucket(
                    *key, tenant=self.name,
                    experts=self._expert_plan(batch, seq, prefill))
                self._unjudged()  # this pass binds, and soon compiles
                if telemetry.enabled():
                    telemetry.inc("serving.decode.bucket_programs")
            fn = exe.serve_program(self._wire[bool(prefill)])
        return exe, fn

    def _expert_plan(self, batch, seq, prefill):
        """A routed model's ``expert_plan`` of the tokens ONE call of a
        bucket program computes: a prefill bucket's positions and, where
        its program is the mixed step, every slot's row beside them; a
        decode bucket's rows — a drafting model's rows twice."""
        if not self._reports_moe_load:
            return None
        each = 2 if self._drafts else 1
        return self._model.expert_plan(
            seq + each * self._slots * bool(self._mixed) if prefill
            else each * batch, platform=self._platform)

    def warm(self, buckets=None):
        """Compile-and-run every prefill sequence bucket and decode
        batch bucket with dummy fills (ModelServer.warmup calls this;
        `buckets` — the server's BATCH ladder — is ignored: generative
        programs bucket by sequence length and session count).  The
        fills thread throwaway state: a re-warm beside live traffic must
        neither donate nor overwrite the rings the batcher holds."""
        state = self._fresh_state(on_device=True)
        n = 0
        for t in self._seq_ladder:
            exe, fn = self._program(self._prefill_pred, 1, t, True)
            _, state, _ = self._call(
                exe, fn, state, _np.zeros((1, t), _np.float32),
                _np.full((1,), self._slots, _np.float32),
                _np.ones((1,), _np.float32))
            n += 1
        for b in self._decode_ladder:
            state = self._idle_step(b, state)
            n += 1
        return n

    def _idle_step(self, bucket, state):
        """The `bucket`-row decode program once on `state`, every row
        idle (the scratch slot, length 0): the updated state."""
        exe, fn = self._program(self._decode_pred, bucket, 1, False)
        return self._call(exe, fn, state, *self._pack((), bucket))[1]

    def _launch(self, exe, fn, state, data, slot, length, logits,
                riders=None):
        """Queue one program call threading `state` through and ask for
        the copies of its small outputs behind it (a copy asked for only
        after a fence costs one more host round trip a call): returns
        (those outputs still on the device — the logits if `logits`,
        the tokens, a routed model's `moe_load` — and the updated
        state).  `riders`: a mixed step's ``(row_data, row_slot,
        row_length)``; without them its rows idle.  The state passed in
        is donated on device backends — the caller keeps only what comes
        back."""
        names = [n for n in self._input_names if n in exe.arg_dict]
        other_vals, aux_vals = exe.serve_args(names)
        if riders is None:  # a mixed step with none to serve: all idle
            riders = self._pack((), self._slots) if "row_data" in names \
                else (None,) * 3
        wire = dict(zip(self._input_names,
                        [data, slot, length, *riders, *state]))
        outs = fn(tuple(wire[n] for n in names), other_vals, aux_vals,
                  _np.uint32(0))
        n_state = len(state)
        small = tuple(outs[1 + n_state:])
        if logits:
            small = (outs[0],) + small
        for o in small:
            o.copy_to_host_async()
        return small, list(outs[1:1 + n_state])

    def _call(self, exe, fn, state, data, slot, length, riders=None):
        """One SYNCHRONOUS program call on `state`: returns (host
        logits, updated state, host outputs after the token).  What the
        warm-up and `_run` use; the batcher's own calls stay in flight
        (`_dispatch` / `_land`).  It times no program, and no flight's
        device time starts from a fence before it."""
        with profiler.span("decode.dispatch", cat="serving"):
            small, state = self._launch(exe, fn, state, data, slot, length,
                                        logits=True, riders=riders)
        # the fence np.asarray would perform anyway, made explicit so
        # that waiting for the device and copying are two numbers
        with profiler.span("decode.device_wait", cat="serving"):
            small[0].block_until_ready()
        with profiler.span("decode.d2h", cat="serving"):
            logits, _token, *extra = (_np.asarray(o) for o in small)
        self._last_fence = None
        if "row_data" in exe.arg_dict and riders is None:
            # a mixed step with idle rows IS the prefill: the prompt's
            # row comes first, the scratch rows' logits are nobody's
            logits = logits[:1]
        return logits, state, extra

    def _run(self, exe, fn, data, slot, length, riders=None):
        """One LIVE program call, start to end: the session's state goes
        in, the updated state replaces it; returns the host logits
        ``(B, vocab)`` — of a prefill bucket's program, mixed or not, the
        prompt's ``(1, vocab)``; of a mixed step handed `riders`
        (``(row_data, row_slot, row_length)``, as `_pack` makes them) the
        prompt's and then every packed row's, ``(1 + slots, vocab)``.
        The path of whoever needs logits and not tokens (the benchmark's
        reference check, chip_smoke.py), for a batcher that is idle."""
        logits, self._state, extra = self._call(
            exe, fn, self._state, data, slot, length, riders)
        if self._reports_moe_load:
            with self._prog_lock:
                key = next(k for k, e in self._programs.items() if e is exe)
            self._book_moe_load(extra[0], self._buckets[key].experts,
                                self._zero_experts, self._ungated)
        return logits

    def _dispatch(self, exe, fn, data, slot, length, rows, prog, pack,
                  hist=None, riders=None):
        """Queue one call of the bucket program `prog` on the live state
        and leave it in flight: nothing here waits for the device.
        `rows`: the sessions of its tokens, a prefill bucket's prompt
        first; `pack`: the closed ``decode.pack`` span that made its
        operands; `riders`: a mixed step's ``(row_data, row_slot,
        row_length)``."""
        self._seq = seq = self._seq + 1
        live = len(rows) - (prog.kind == "prefill")
        sent = profiler.span("decode.dispatch", cat="serving", hist=hist,
                             seq=seq, program=prog.program,
                             kind=self._kind(prog), bucket=prog.bucket,
                             rows=live)
        if prog.program:
            with sent:
                outs, self._state = self._launch(
                    exe, fn, self._state, data, slot, length, logits=False,
                    riders=riders)
        else:
            # a bucket's first flight compiles its program, or reads it
            # back from the compile cache for a second
            with self._building(prog.label), sent:
                outs, self._state = self._launch(
                    exe, fn, self._state, data, slot, length, logits=False,
                    riders=riders)
            # read once, off the compiled object the first call has just
            # made: beside that compile, never in a warmed bucket's path
            prog.program = fn.module_name() or ""
        flight = _Flight(outs, rows, live, prog, seq, sent.end_ns)
        self._flights.append(flight)
        if recorder.enabled():
            # open until `_land` has fenced on it: what the stall
            # watchdog's post-mortem names (obs/watchdog.py)
            recorder.record("flight", "enter", seq, detail=prog.label)
        self._sent(pack, sent, flight)

    def _kind(self, prog):
        """What a span says the bucket program `prog` is: ``"decode"``,
        ``"prefill"``, or ``"mixed"`` where a prefill bucket's program
        is the mixed step."""
        return "mixed" if self._mixed and prog.kind == "prefill" \
            else prog.kind

    def _land(self, flight, hists=_NO_HISTS, book=True):
        """Fence on one flight, read its tokens and emit them, one a
        row (a drafting model's: one or two, and the count is returned) —
        but for a row whose session has retired since (it hit EOS
        while the row was in flight): that token is dropped.  Then, if
        `book`, book the flight's device time from the fence (module
        docstring); a shutdown's landing (not `book`) is no leg of a
        pass either."""
        try:
            with profiler.span("decode.device_wait", cat="serving",
                               hist=hists[0], seq=flight.seq,
                               program=flight.prog.program) as wait:
                flight.outs[0].block_until_ready()
        finally:
            if recorder.enabled():
                recorder.record("flight", "exit", flight.seq)
        with profiler.span("decode.d2h", cat="serving",
                           hist=hists[1]) as read:
            token, *extra = (_np.asarray(o) for o in flight.outs)
        if self._reports_moe_load:
            self._book_moe_load(extra[0], flight.prog.experts,
                                self._zero_experts, self._ungated)
        if self._drafts:
            return self._land_drafted(flight, token, extra, wait, read,
                                      hists[2], book)
        with profiler.span("decode.emit", cat="serving",
                           hist=hists[2]) as emit:
            live = [(i, sess, int(t))
                    for i, (sess, t) in enumerate(zip(flight.rows, token))
                    if not sess.retired]
            for _, sess, t in live:
                self._emit(sess, t)
        self._close_landing(flight, wait, read, emit, book, len(live), sum(
            1 for i, _, _ in live if i >= len(flight.rows) - flight.riders))

    def _land_drafted(self, flight, token, extra, wait, read, hist, book):
        """`_land`'s second half for a model that drafts: ``token (rows,
        3)`` = ``[count, first, second]``.  Each live row emits its
        `count` tokens as far as its budget goes (`_emit` retires at the
        budget; what follows is dropped) and a step's row whose two were
        both emitted has cached one position more than its dispatch
        counted."""
        step = flight.prog.kind == "decode"
        live = decoded = accepted = 0
        with profiler.span("decode.emit", cat="serving", hist=hist) as emit:
            for sess, (count, *tokens) in zip(flight.rows, token):
                if sess.retired:
                    continue
                live += 1
                before = len(sess.generated)
                for t in tokens[:int(count)]:
                    self._emit(sess, int(t))
                    if sess.retired:
                        break
                emitted = len(sess.generated) - before
                decoded += emitted * step
                if emitted > 1:   # the draft's position is cached too
                    sess.fed += 1
                    accepted += 1
        if step and telemetry.enabled():
            drafts = len(flight.rows)
            telemetry.inc("serving.decode.row_steps", live)
            telemetry.inc("serving.mtp.drafts", drafts)
            telemetry.inc("serving.mtp.accepted", accepted)
            telemetry.inc("serving.mtp.dropped_rows", drafts - accepted)
            if self._step_bytes is not None:
                for name, n in self._step_bytes(
                        extra[0] if self._reports_moe_load else None).items():
                    telemetry.inc(name, n)
        if step and recorder.enabled():
            # a point event beside the flight's bracket: what it emitted
            recorder.record("emitted", "exit", flight.seq,
                            detail="rows=%d live=%d accepted=%d tokens=%d"
                            % (len(flight.rows), live, accepted, decoded))
        self._close_landing(flight, wait, read, emit, book, live, decoded)
        return decoded

    def _close_landing(self, flight, wait, read, emit, book, live, decoded):
        """What follows a landing's emit: the stall records its fence
        finishes, the tokens and dropped rows it adds (`live` of its rows
        were read, `decoded` tokens came from decode rows), its device
        time."""
        # the stall records this landing's fence finishes
        waiting = None
        if self._stalls_open:
            waiting, self._stalls_open = self._stalls_open, []
        stalled = book and self._landed(flight, wait, read, emit)
        dropped = len(flight.rows) - live
        # (a session's first token is its prefill's, not a decode token:
        # the decode rows are the flight's last `riders`)
        if decoded:
            self._tokens_done += decoded
            if telemetry.enabled():
                telemetry.inc("serving.decode.tokens", decoded)
        if dropped and telemetry.enabled():
            telemetry.inc("serving.decode.dropped_rows", dropped)
        # after the emit: whether a session is live through the gap to
        # the next flight is known once this one's rows have retired
        seen = None
        if book:
            seen = self._book_device(flight, wait.end_ns,
                                     wait.seconds > _FENCE_FLOOR_S,
                                     feed=not stalled)
        else:
            self._last_fence = None
        if waiting:
            self._finish_stalls(waiting, wait.seconds, seen)

    def _book_device(self, flight, ready_ns, blocked, feed=True):
        """Flight k's time on the device from its fence and the one
        before it (module docstring): `ready_ns` the end of its
        ``decode.device_wait`` span, `blocked` whether that span
        outlasted the floor.  Returns whether the flight was seen (None
        with telemetry off); `feed`: a seen flight also feeds the mean
        its program's fences are held to — not one whose own fence was a
        stall."""
        if not telemetry.enabled():
            self._last_fence = None
            return None
        last = self._last_fence
        self._last_fence = (ready_ns, blocked, bool(self._active))
        start, seen, gap = device_interval(last, flight.enqueued_ns,
                                           ready_ns, blocked)
        telemetry.inc("serving.device.flights")
        if gap and last[2]:  # a session was live through the gap
            telemetry.observe("serving.device.starved_seconds", gap * 1e-9)
        prog = flight.prog
        if seen:
            seconds = (ready_ns - start) * 1e-9
            telemetry.inc("serving.device.seen_flights")
            for hist in prog.hists:
                telemetry.observe(hist, seconds)
            if feed:
                prog.seen_n += 1
                prog.seen_s += seconds
        # what the two means divide by: steps, and a prefill's positions
        # (so that microseconds a position divide like by like)
        weight = 1 if seen else _UNSEEN
        if prog.kind == "prefill":
            telemetry.inc("serving.device.prefill_positions",
                          weight * prog.bucket)
        else:
            telemetry.inc("serving.device.decode_seen", weight)
        return seen

    # ------------------------------------------------------------------
    # a pass, its legs, and a leg that stands still
    # ------------------------------------------------------------------
    def _unjudged(self):
        """This pass builds or compiles a program: none of its host legs
        is a stall (the stall watchdog's own rule)."""
        if self._pass is not None:
            self._pass.unjudged = True

    @contextlib.contextmanager
    def _building(self, what):
        """The batcher compiles `what` (a bucket's first flight, the
        ladder's idle steps) with flights in the air: the pass is not
        judged, and a ``compile`` bracket of the flight recorder holds
        the stall watchdog still meanwhile and restarts the age of the
        flights that stood open behind it."""
        self._unjudged()
        seq = (recorder.record("compile", "enter", detail=what)
               if recorder.enabled() else None)
        try:
            yield
        finally:
            if seq is not None and recorder.enabled():
                recorder.record("compile", "exit", seq)

    def _sent(self, pack, sent, flight):
        """The two legs in which `flight` was dispatched have closed, its
        ``decode.pack`` and ``decode.dispatch``: add them to the pass,
        and call one a stall if it outlasted the floor in a pass that
        built nothing."""
        pas = self._pass
        if pas is None or not pas.on:
            return
        pas.leaves += pack.seconds + sent.seconds
        pas.owner = self
        if not pas.unjudged:
            if pack.seconds > _STALL_FLOOR_S:
                self._stall("pack", pack.seconds, _STALL_FLOOR_S, flight)
            if sent.seconds > _STALL_FLOOR_S:
                self._stall("dispatch", sent.seconds, _STALL_FLOOR_S, flight)

    def _landed(self, flight, wait, read, emit):
        """The same for the three legs in which `flight` was landed: its
        ``decode.device_wait``, held to its program's own history
        (`_Bucket.fence_limit`), and ``decode.d2h`` and ``decode.emit``.
        Returns whether the fence stood still."""
        pas = self._pass
        if pas is None or not pas.on:
            return False
        pas.leaves += wait.seconds + read.seconds + emit.seconds
        pas.owner = self
        stalled = False
        if wait.seconds > _STALL_FLOOR_S:  # no fence's limit is under it
            limit = flight.prog.fence_limit()
            stalled = limit is not None and wait.seconds > limit
            if stalled:
                self._stall("device_wait", wait.seconds, limit, flight, wait)
        if not pas.unjudged:
            if read.seconds > _STALL_FLOOR_S:
                self._stall("d2h", read.seconds, _STALL_FLOOR_S, flight, wait)
            if emit.seconds > _STALL_FLOOR_S:
                self._stall("emit", emit.seconds, _STALL_FLOOR_S, flight,
                            wait)
        return stalled

    def _stall(self, leg, seconds, limit, flight=None, fence=None,
               growth=None, number=None):
        """Book a leg that stood still and open its record (docs/
        observability.md "Reading a stall record"): which leg of which
        pass for how long against what limit, the flight it fenced on or
        dispatched (`fence`: its closed ``decode.device_wait`` span), what
        the thread did since its last sample (`growth`, or sampled now),
        and what is read of the host and the device now.
        The record is finished, logged and kept at the NEXT landing,
        whose fence says whether the device or the host was late."""
        if telemetry.enabled():
            telemetry.inc("serving.stalls")
            telemetry.observe("serving.stall_seconds", seconds)
            telemetry.observe("serving.stall_seconds." + leg, seconds)
        self._stall_count += 1
        try:
            self._stalls_open.append(self._stall_record(
                leg, seconds, limit, flight, fence, growth, number))
        except Exception:  # noqa: BLE001 — a record never fails a step
            logging.getLogger(__name__).warning(
                "tenant %r: no record of a %.3f s stall of leg %s",
                self.name, seconds, leg, exc_info=True)

    def _stall_record(self, leg, seconds, limit, flight, fence, growth,
                      number):
        """The record `_stall` opens."""
        pas = self._pass
        if growth is None:
            growth = pas.growth(time.perf_counter_ns())
        collections_now = [g["collections"] for g in gc.get_stats()]
        rec = {"leg": leg, "seconds": seconds, "limit_s": limit,
               "wall_time": time.time(), "tenant": self.name,
               "pass": pas.number if number is None else number,
               "seq": None, "program": None, "kind": None, "bucket": None,
               "rows": None, "enqueued_to_ready_s": None,
               "ref_device_s": None}
        if flight is not None:
            prog = flight.prog
            rec.update(
                seq=flight.seq, program=prog.program, kind=self._kind(prog),
                bucket=prog.bucket, rows=flight.riders,
                enqueued_to_ready_s=(
                    None if fence is None
                    else (fence.end_ns - flight.enqueued_ns) * 1e-9),
                ref_device_s=(prog.seen_s / prog.seen_n if prog.seen_n
                              else None))
        rec.update(growth)
        rec.update(next_wait_s=None, next_seen=None)
        rec.update(_host_reading(self._device, seconds))
        rec["gc_collections"] = [now - seen for now, seen in
                                 zip(collections_now, self._gc_seen)]
        self._gc_seen = collections_now
        return rec

    def _finish_stalls(self, records=None, next_wait_s=None,
                       next_seen=None):
        """Finish `records` (all that are open, by default) with the
        fence of the flight landed after them — how long it blocked and
        whether it was seen; None where nothing was left to land —, keep
        them for `stats()`, log each as one ``mx.stall {json}`` line and
        put it into the flight recorder."""
        if records is None:
            records, self._stalls_open = self._stalls_open, []
        for rec in records:
            rec["next_wait_s"] = next_wait_s
            rec["next_seen"] = next_seen
            line = json.dumps(rec, default=str)
            self._stalls.append(rec)
            logging.getLogger(__name__).warning("mx.stall %s", line)
            if recorder.enabled():
                # a point event: an exit that no enter opened
                recorder.record("stall", "exit", rec["pass"], detail=line)

    def _per_ring(self, total):
        """`total`, a sum over the rings, as the mean a ring: a whole
        number wherever every ring has one length."""
        mean, rest = divmod(int(total), len(self._ring_lens))
        return total / len(self._ring_lens) if rest else mean

    def _book_call(self, **call):
        """The counters the model's layer kinds add for one program call
        (``call_counters(positions=...)`` of a prefill bucket, ``(rows=
        ..., lengths=..., ...)`` of a decode step), told the platform its
        programs are lowered for."""
        if telemetry.enabled() and self._call_counters is not None:
            for name, n in self._call_counters(platform=self._platform,
                                               **call).items():
                telemetry.inc(name, n)

    @staticmethod
    def _book_moe_load(load, plan, zero=False, ungated=False):
        """The `moe.*` counters of one program call from its `moe_load
        (layers, experts)` output — behind them, where the model has
        zero-compute experts (`zero`), ONE column of the pairs that chose
        one, `moe.zero_pairs` (padded rows included, as `moe.pairs` has
        them), which no other counter takes for an expert —: token-expert
        pairs computed (`moe.ungated_pairs` too where the model's experts
        are of two matrices, `ungated`; padded
        rows included — the device computed them), experts that got at
        least one token, expert slots offered, and the fullest expert's
        tokens, each summed over the layers.  And from the program's
        `plan` (``expert_plan``: pairs a layer, pieces, a pass's rows,
        which kernels take them) the rows the expert layers
        gathered, `moe.pair_rows`: every pair's row where a layer gathers
        them all; where it walks the held pairs alone
        (`moe.compact_calls`, a layer) a pass's rows times the passes the
        load filled (`moe.passes`) — a call in pieces as if its held pairs
        lay evenly over them.  The same rows are `moe.kernel_rows` where
        the program's segment matmuls are the TPU's kernel
        (`parallel.moe.kernel_tiles`), `moe.fused_rows` where the
        kernel's calls fetch and place their own rows (`.fused_tile`),
        `moe.placed_rows` where a pass's return is ours (`.return_tiles`)."""
        if telemetry.enabled():
            if zero:
                telemetry.inc("moe.zero_pairs", int(load[..., -1].sum()))
                load = load[..., :-1]
            telemetry.inc("moe.pairs", int(load.sum()))
            if ungated:
                telemetry.inc("moe.ungated_pairs", int(load.sum()))
            telemetry.inc("moe.experts_hit", int((load > 0).sum()))
            telemetry.inc("moe.expert_slots", int(load.size))
            telemetry.inc("moe.max_load", int(load.max(axis=-1).sum()))
            pairs, pieces, rows = plan[:3]
            gathered = pairs * len(load)
            if rows:
                passes = pieces * int(_np.ceil(
                    load.sum(axis=-1) / (pieces * rows)).sum())
                telemetry.inc("moe.compact_calls", len(load))
                telemetry.inc("moe.passes", passes)
                gathered = passes * rows
            telemetry.inc("moe.pair_rows", gathered)
            # (`placed` only where the layer walks passes: their rows)
            for name, taken in zip(("kernel", "fused", "placed"), plan[3:]):
                if taken:
                    telemetry.inc("moe.%s_rows" % name, gathered)

    # ------------------------------------------------------------------
    # admission: prefill newly-arrived prompts into free slots
    # ------------------------------------------------------------------
    def admit(self, reqs):
        """Give each request a free slot; returns the requests that found
        NONE (the server re-queues them at the front — admission control,
        not failure).  Where the prefill program is the mixed step, the
        prompt is left PENDING: it rides the next :meth:`decode_step`'s
        dispatch, one prompt an iteration, and its first token is read by
        the iteration after.  Where the model keeps two programs its
        prefill is dispatched here, and one that cannot be fails ITS
        request only."""
        leftovers = []
        for req in reqs:
            if self._closed or not self._free:
                leftovers.append(req)
                continue
            n = req.inputs["data"].reshape(-1).shape[0]
            sess = _Session(req, self._free.pop(), n)
            if self._mixed:
                self._pending.append(sess)
                continue
            try:
                self._prefill(sess, ())
            except BaseException as e:  # noqa: BLE001
                req.fail(e)
        self._note_occupancy()
        return leftovers

    def _prefill(self, sess, rows):
        """Dispatch the prefill bucket program of `sess`'s prompt — with
        `rows`, the live sessions that decode on, packed into it where it
        is the mixed step — and book it.  A dispatch that fails gives
        the slot back and raises."""
        req, n = sess.req, sess.prompt_len
        bucket = choose_bucket(self._seq_ladder, n)
        riders, live = None, len(rows)
        with profiler.span("serve.prefill_dispatch", cat="serving",
                           bucket=bucket, prompt=n):
            req.service_at = time.monotonic()
            try:
                # the same work for a prompt as for a step's rows
                with profiler.span("decode.pack", cat="serving") as pack:
                    exe, fn = self._program(self._prefill_pred, 1, bucket,
                                            True)
                    data = _np.zeros((1, bucket), _np.float32)
                    data[0, :n] = req.inputs["data"].reshape(-1)
                    if self._mixed:
                        riders = self._pack(rows, self._slots)
                self._dispatch(
                    exe, fn, data, _np.full((1,), sess.slot, _np.float32),
                    _np.full((1,), n, _np.float32), [sess, *rows],
                    self._buckets["prefill", bucket], pack, riders=riders)
            except BaseException:
                self._free.append(sess.slot)
                raise
            self._active.append(sess)
        for rider in rows:
            rider.fed += 1
        if telemetry.enabled():
            telemetry.inc("serving.decode.sessions")
            # how often the mixed step engages: prompts whose program
            # carried a live row, and the rows carried (booked by 0 too,
            # so that a window's ratio reads 0 and not nothing)
            telemetry.inc("serving.prefill.mixed", int(live > 0))
            telemetry.inc("serving.prefill.rider_rows", live)
            # positions the prefill program computed, and those of them
            # past the prompt's end: computed and thrown away
            telemetry.inc("serving.prefill.bucket_positions", bucket)
            telemetry.inc("serving.prefill.pad_positions", bucket - n)
            if live:
                # a decode dispatch of its live rows, in a program that
                # computes all its rows beside the bucket's positions
                self._book_step(riders, live, self._slots,
                                positions=bucket)
            else:
                self._book_call(positions=bucket,
                                computed=self._slots * self._mixed)

    def _note_occupancy(self):
        if not telemetry.enabled():
            return
        # the two change at an admission or a retirement, not a pass
        now = (self._slots - len(self._free), len(self._active))
        if now == self._occupancy:
            return
        self._occupancy = now
        telemetry.set_gauge("kv.slot_occupancy", now[0] / self._slots)
        telemetry.set_gauge("serving.decode.active_sessions", now[1])

    # ------------------------------------------------------------------
    # the decode iteration
    # ------------------------------------------------------------------
    def _wants_row(self, sess):
        """Whether `sess` decodes on after the tokens sampled so far —
        a count: the budget and the ring's end need no token's value."""
        sampled = sess.sampled()
        return (sampled < sess.req.max_new_tokens
                and sess.prompt_len + sampled < self._max_len)

    def decode_step(self):
        """One token-level iteration, a step ahead of the host: DISPATCH
        one program — the mixed step of the oldest pending prompt's
        bucket with every session that decodes on packed into it, or,
        nothing pending, those sessions re-packed into the smallest
        decode bucket — then land what was in flight before it: the
        previous step (fence, read, emit, retire), mixed or not, and,
        where the model keeps two programs, the prefills admitted since.
        Returns the rows dispatched (0 when the call only landed, or
        found nothing to do)."""
        landing, self._flights = self._flights, []
        rows = [s for s in self._active if self._wants_row(s)]
        prompt = self._pending.popleft() if self._pending else None
        if (prompt is not None or self._drafts) and not self._laddered:
            # (a drafting model too: its rows retire one OR two tokens a
            # step, so a warm-up of staggered budgets need not pass
            # through every count of live rows)
            self._build_ladder()
        # at most one decode step is in flight, and it is the oldest:
        # this call lands all it finds and leaves the one it dispatches
        step = (landing.pop(0)
                if landing and landing[0].prog.kind == "decode" else None)
        n = len(rows)
        if prompt is not None:
            bucket = self._slots
        else:
            bucket = choose_bucket(self._decode_ladder, n) if n else 0
        if prompt is not None or rows or step is not None:
            # `seq`: the flight this call dispatches, `landed`: the one
            # it reads (0: none).  The histograms of the step and its
            # legs are the period of PURE steps: a call that reads a
            # mixed step feeds `serving.prefill_seconds` below instead
            pure = not any(self._kind(f.prog) == "mixed" for f in landing)
            # a drafting model's step also says how many rows carry a
            # draft and, once the step before is read, what that emitted
            drafted = {"drafted": n, "emitted": 0} if self._drafts else {}
            with profiler.span(
                    "serve.decode_step", cat="serving",
                    hist="serving.decode.step_seconds" if pure else None,
                    bucket=bucket, rows=n,
                    program="decode" if prompt is None else "mixed",
                    seq=self._seq + 1 if rows or prompt is not None else 0,
                    landed=step.seq if step is not None else 0,
                    **drafted) as whole:
                if prompt is not None:
                    prompt = self._ride(prompt, rows)
                if prompt is None and rows:
                    self._dispatch_step(rows, bucket, timed=pure)
                if step is not None:
                    emitted = self._land(step, _LAND_HISTS)
                    if self._drafts:
                        whole.attrs["emitted"] = emitted
        for flight in landing:
            with profiler.span("serve.prefill", cat="serving",
                               hist="serving.prefill_seconds",
                               bucket=flight.prog.bucket, seq=flight.seq,
                               program=flight.prog.program):
                self._land(flight)
        self._note_occupancy()
        if self._stalls_open and not self._flights:
            self._finish_stalls()  # nothing is in flight to land next
        return n

    def _build_ladder(self):
        """Before the first mixed step (and a drafting model's first
        step): build every decode bucket program
        not built yet and run it once, its rows idle, on the live state
        (a step of garbage on the scratch slot; no second set of rings as
        `warm` threads).  With two programs a burst of admissions prefills
        first and then steps with every slot live, so a tenant's first
        requests passed through every decode bucket from the top; with
        mixed steps the earlier sessions advance while the later prompts
        prefill and may have retired before the slots are ever full —
        the bucket would compile, for seconds, under live sessions, the
        first time they are."""
        self._laddered = True
        # its idle steps are under no leg of the pass
        with self._building("%s decode ladder" % self.name):
            for b in self._decode_ladder:
                if ("decode", b) not in self._programs:
                    self._state = self._idle_step(b, self._state)

    def _ride(self, prompt, rows):
        """Dispatch the mixed step of `prompt` with `rows` riding it;
        returns `prompt`, or None where that could not be dispatched:
        it fails ITS request only, and the rows are owed a plain step."""
        try:
            self._prefill(prompt, rows)
        except Exception as e:  # noqa: BLE001 — the request's failure
            prompt.req.fail(e)
            return None
        return prompt

    def _pack(self, rows, bucket):
        """``(data, slot, length)`` of `rows` packed into `bucket` program
        rows, the rest at the scratch slot with length 0.  A row whose
        last token the host has not read yet says so with a negative
        `data`, and the program takes the token from
        ``last_token[slot]``."""
        data = _np.zeros((bucket, 1), _np.float32)
        slot = _np.full((bucket,), self._slots, _np.float32)  # scratch
        length = _np.zeros((bucket,), _np.float32)
        for i, sess in enumerate(rows):
            unread = sess.sampled() > len(sess.generated)
            data[i, 0] = -1.0 if unread else sess.generated[-1]
            slot[i] = sess.slot
            length[i] = sess.fed
        return data, slot, length

    def _dispatch_step(self, rows, bucket, timed=True):
        """Pack `rows` into the `bucket`-row decode program and dispatch
        it; `timed`: the two legs feed their histograms."""
        with profiler.span(
                "decode.pack", cat="serving",
                hist="serving.decode.pack_seconds" if timed else None
                ) as pack:
            exe, fn = self._program(self._decode_pred, bucket, 1, False)
            packed = self._pack(rows, bucket)
        self._dispatch(
            exe, fn, *packed, rows, self._buckets["decode", bucket], pack,
            hist="serving.decode.dispatch_seconds" if timed else None)
        for sess in rows:
            sess.fed += 1
        self._book_step(packed, len(rows), bucket)

    def _book_step(self, packed, n, computed, positions=0):
        """The counters of one decode dispatch: `n` real rows `packed`
        (``_pack``'s arrays) in a program of `computed` rows — and, a
        mixed step, `positions` of a prompt's bucket beside them."""
        if not telemetry.enabled():
            return
        data, _, length = packed
        telemetry.inc("serving.decode.dispatches")
        if (data[:n] < 0).any():
            telemetry.inc("serving.decode.runahead_steps")
        # the cache sets bound on the device: the live one plus the ONE
        # zero-filled placeholder set the bucket programs' executors
        # share (`_program`).  All their bytes, and the part that is
        # recurrent state
        sets = 1 + bool(self._programs)
        telemetry.inc("cache.reserved_bytes", sets * self._cache_bytes)
        telemetry.inc("cache.state_bytes", sets * self._state_bytes)
        pages = sets * (self._slots + 1)
        filled = length[:n].astype(_np.int64)
        # a drafting step runs two positions a row, the second one on
        # (`filled` is the host's lower bound where the step before is in
        # flight)
        each = 2 if self._drafts else 1
        lengths = [int(f) + k for k in range(each) for f in filled]
        self._book_call(positions=positions, rows=each * n, lengths=lengths,
                        computed=each * computed, pages=pages,
                        max_len=self._max_len)
        if self._has_ring:
            # position-steps, each the mean over the rings (whose
            # lengths differ where the model has window layers): over
            # a window their ratio is the mean reserved over used.
            # Reserved are a ring's pages times its length, in every
            # bound set; used are the positions the packed sessions
            # had filled — at most a ring's own length — when the
            # step was packed.  A model with no ring has neither
            lens, blks = self._ring_lens[:, None], self._ring_blocks[:, None]
            whole = int(lens.sum())
            telemetry.inc("kv.reserved_positions",
                          self._per_ring(pages * whole))
            telemetry.inc("kv.used_positions", self._per_ring(
                _np.minimum(filled, lens).sum()))
            # what the dispatched program's attention reads of the
            # packed rows' pages: all of each, or the blocks up to
            # the one that holds `length` — every block of a ring
            # that has wrapped (ops/attention.py)
            read = _np.minimum((filled // blks + 1) * blks, lens)
            telemetry.inc("kv.page_positions", self._per_ring(n * whole))
            telemetry.inc("kv.kernel_positions",
                          self._per_ring(n * self._kernel_positions))
            telemetry.inc("kv.skipped_positions",
                          self._per_ring(n * whole - read.sum()))

    def _emit(self, sess, token):
        """Book one sampled token; retire on EOS / budget / ring-full."""
        sess.generated.append(token)
        req = sess.req
        if req.on_token is not None:
            try:
                req.on_token(token)
            except BaseException:  # noqa: BLE001 — foreign code
                pass  # a client callback must never kill the batcher
        eos = self.eos_for(req.eos_id)
        if eos is not None and token == eos:
            self._retire(sess, "eos")
        elif len(sess.generated) >= req.max_new_tokens:
            self._retire(sess, "length")
        elif sess.prompt_len + len(sess.generated) >= self._max_len:
            self._retire(sess, "length")

    def _retire(self, sess, reason):
        """Resolve the session's future and free its slot — mid-window
        retirement is the normal path (sessions leave between decode
        steps; the next step simply re-packs without them)."""
        sess.retired = True
        if sess in self._active:
            self._active.remove(sess)
        self._free.append(sess.slot)
        if telemetry.enabled():
            telemetry.inc("serving.decode.retired")
            telemetry.inc("serving.decode.retired.%s" % reason)
            self._note_occupancy()
        sess.req.fulfil(GenerateResult(sess.generated, reason,
                                       sess.prompt_len))

    def finish_all(self, reason="closed"):
        """Retire every active session NOW with its partial tokens —
        the close(drain=False) path.  Zero lost futures, by
        construction.  Tokens in flight are computed already and are
        the sessions' (an admitted session has its first token); a
        flight that cannot be read is dropped."""
        landing, self._flights = self._flights, []
        try:
            while landing:
                # a shutdown's fences time no program
                self._land(landing[0], book=False)
                del landing[0]
        except Exception:  # noqa: BLE001 — the futures come first
            logging.getLogger(__name__).warning(
                "tenant %r: a program call in flight at shutdown could "
                "not be read; its tokens are dropped", self.name,
                exc_info=True)
            self._forget(landing[1:])
        self._finish_stalls()
        pending, self._pending = self._pending, collections.deque()
        for sess in [*self._active, *pending]:
            self._retire(sess, reason)

    def fail_active(self, exc):
        """A decode step blew up mid-flight: the packed step serves
        every active session, so all of them share the failure.  Fail
        their futures and free the slots — the tenant keeps accepting
        new prompts (a request-level error, not a server-level one)."""
        self._forget(self._flights)
        self._flights = []
        self._last_fence = None
        self._finish_stalls()
        pending, self._pending = self._pending, collections.deque()
        for sess in [*self._active, *pending]:
            self._free.append(sess.slot)
            sess.req.fail(exc)
        self._active = []
        if telemetry.enabled():
            self._note_occupancy()

    @staticmethod
    def _forget(flights):
        """Close the flight recorder's brackets of `flights` that will
        never be landed: none may stand open for the stall watchdog to
        find."""
        if recorder.enabled():
            for flight in flights:
                recorder.record("flight", "exit", flight.seq)

    def stats(self):
        """`stalls`: the last `_STALLS_KEPT` finished stall records,
        oldest first; `stall_count`: every stall so far."""
        return {"active_sessions": len(self._active),
                "free_slots": len(self._free),
                "max_sessions": self._slots,
                "max_len": self._max_len,
                "tokens_decoded": self._tokens_done,
                "stall_count": self._stall_count,
                "stalls": list(self._stalls)}

    def drain(self):
        """The batcher thread lands its own flights (the decode loop IS
        the pipeline) — nothing to fence from outside; no flight's
        device time starts from a fence before a drain."""
        self._last_fence = None

    def close(self):
        self._closed = True
        self.finish_all("closed")
        self._programs.clear()
        self._placeholders.clear()
        booked, self._mem_booked = getattr(self, "_mem_booked", 0), 0
        if booked:
            from ..obs import memory

            memory.unbook("kv_ring.%s" % self.name, booked)
