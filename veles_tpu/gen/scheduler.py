"""Continuous-batching scheduler: iteration-level admission over the
engine's slots.

The Orca/vLLM scheduling insight applied to the slot engine: instead
of forming a batch and padding every member to the slowest sequence,
requests are admitted into open KV-cache slots at EVERY decode
iteration and evicted the moment they finish, so the decode program's
fixed ``max_slots`` rows stay as full as the arrival process allows.
Throughput per decode dispatch is proportional to fill — the
``-m slow`` gate in ``tests/test_gen.py`` measures the continuous
scheduler against :func:`static_generate` (the pad-to-slowest
baseline, same compiled programs) on a mixed-length workload.

One scheduler thread owns the engine; ``submit`` only touches the
bounded queue (:class:`veles_tpu.serve.batcher.QueueFull` on
overflow — the HTTP layer's 503 path, same as the request/response
batcher).  Tokens stream per request through ``on_token`` callbacks
the moment the device returns them; the request future resolves with
the full greedy token list at eviction.

Against a PAGED engine (``veles_tpu.gen.paged``) the same loop gains
three moves: admission is priced by the pool's ACTUAL headroom
(``engine.can_admit`` — FIFO, no overtaking the head), a chunked
prefill feeds exactly one chunk per step so co-resident decodes keep
their cadence during long admissions, and pool exhaustion preempts
the YOUNGEST sequence — pages freed, request requeued at the front
with its tokens-so-far; greedy decode of the prefix replays the
stream, so the preempted request's final token list is byte-identical
to an uncontended run.
"""

import collections
import threading
import time
from concurrent.futures import Future

import numpy

from veles_tpu import trace
from veles_tpu.logger import Logger
from veles_tpu.metrics import LatencyHistogram
from veles_tpu.obs import context as obs_context
from veles_tpu.serve.batcher import QueueFull


class GenRequest(object):
    __slots__ = ("tokens", "max_new_tokens", "future", "on_token",
                 "submitted", "first_token_at", "generated", "slot",
                 "finish_reason", "admit_seq", "preemptions", "ctx",
                 "queued_at", "admitted_at", "export_pages", "export",
                 "rid", "req")

    def __init__(self, tokens, max_new_tokens, on_token=None,
                 ctx=None, export_pages=False, rid=None):
        self.tokens = tokens
        self.max_new_tokens = int(max_new_tokens)
        self.future = Future()
        self.on_token = on_token
        self.submitted = time.perf_counter()
        self.first_token_at = None
        self.generated = []
        self.slot = None
        self.finish_reason = None
        #: fleet prefill role: export the slot's KV pages into
        #: :attr:`export` at finish, BEFORE the slot is released —
        #: with ``max_new_tokens=1`` this turns a request into a
        #: prefill job whose result is a shippable page payload
        self.export_pages = bool(export_pages)
        self.export = None
        #: fleet request id (opaque) — correlates the frontend's
        #: exactly-once delivery across prefill/decode roles
        self.rid = rid
        #: admission stamp — preemption evicts the YOUNGEST (largest)
        self.admit_seq = -1
        self.preemptions = 0
        #: distributed-trace context captured at submit (None when
        #: tracing is off) — every span of this request's waterfall
        #: carries its ids across the thread handoff
        self.ctx = ctx
        #: start of the CURRENT queue residence (submit, then each
        #: preemption requeue) — the queue_wait phase span's begin
        self.queued_at = self.submitted
        self.admitted_at = None
        #: small integer the scheduler gives at ``submit_request``:
        #: the spans of one request share it in a profiler trace
        self.req = -1

    def span_args(self, args=None):
        """``args`` tagged with this request's trace identity (the
        dict unchanged when untraced)."""
        if self.ctx is None:
            return args
        return self.ctx.span_args(args)

    def prefix(self):
        """The tokens a (re-)admission must prefill: the prompt plus
        everything generated before a preemption.  Greedy decode of
        the prefix reproduces the stream, so requeueing is lossless."""
        if not self.generated:
            return self.tokens
        return numpy.concatenate([
            numpy.asarray(self.tokens, numpy.int32),
            numpy.asarray(self.generated, numpy.int32)])


def finish_reason(engine, n_generated, max_new_tokens, token, slot,
                  slot_len=None):
    """The ONE finish predicate continuous and static batching share
    (divergent semantics here would break the parity gate): ``"eos"``
    when the engine's eos token was produced, ``"length"`` at the
    request's token budget or a full KV slot (the sequence is out of
    cache road even under its budget), else ``None``.  ``slot_len``
    overrides the engine's live counter — a speculative verify
    advances the slot by the whole accepted span before its tokens
    are emitted one by one, so intermediate emits pass the length AS
    OF that token to keep the predicate bitwise-plain-decode."""
    if engine.eos_id is not None and token == engine.eos_id:
        return "eos"
    if n_generated >= max_new_tokens:
        return "length"
    if slot_len is None:
        slot_len = engine.slot_len[slot]
    if slot_len >= engine.max_seq:
        return "length"
    return None


class GenerativeScheduler(Logger):
    """Continuous batcher over ONE :class:`~veles_tpu.gen.engine
    .GenerativeEngine`.

    Drive it either manually (``step()`` / ``run_until_idle()`` — the
    deterministic test/bench mode) or with the background worker
    (``start()`` — the serving mode; ``generate()`` then blocks on the
    future).  Both modes execute the identical admission/decode/evict
    sequence.
    """

    def __init__(self, engine, metrics=None, name="default",
                 max_queue=256, **kwargs):
        super(GenerativeScheduler, self).__init__(**kwargs)
        self.engine = engine
        self.name = name
        self.max_queue = int(max_queue)
        self.metrics = metrics
        self._queue = collections.deque()
        self._active = {}            # slot -> decoding GenRequest
        self._prefilling = {}        # slot -> chunk-admitting request
        #: (payload, GenRequest) pairs awaiting page adoption — the
        #: fleet decode role's admission lane (veles_tpu.fleet)
        self._handoff = collections.deque()
        self._cond = threading.Condition()
        self._stopped = False
        self._drain_future = None
        self._thread = None
        # counters the /metrics gauges read (single worker writes)
        self.admitted_total = 0
        self.finished_total = 0
        self.tokens_total = 0
        self.shed_total = 0
        self.decode_steps = 0
        self.decode_slot_steps = 0   # active rows summed over steps
        #: tokens decoded by a step that first ran one prefill or chunk
        #: (or two and more): with ``tokens_total``, the share of token
        #: gaps that waited behind a prefill
        self.decode_tokens_stalled = 0
        self.decode_tokens_multi_stalled = 0
        self._admit_counter = 0
        self._req_counter = 0        # GenRequest.req, under _cond
        #: submit → first streamed token (the prefill turnaround +
        #: queue wait): the latency generative SLOs are written against
        self.ttft = LatencyHistogram()
        if metrics is not None:
            self._register_gauges(metrics)

    # -- metrics -----------------------------------------------------------
    def _register_gauges(self, metrics):
        label = '{model="%s"}' % self.name
        metrics.register_gauge("gen_queue_depth" + label,
                               lambda: len(self._queue))
        metrics.register_gauge("gen_slot_occupancy" + label,
                               self.engine.occupancy)
        metrics.register_gauge("gen_admitted_total" + label,
                               lambda: self.admitted_total)
        metrics.register_gauge("gen_tokens_total" + label,
                               lambda: self.tokens_total)
        metrics.register_gauge("gen_batch_fill" + label,
                               self.batch_fill)
        metrics.register_gauge("gen_decode_tokens_stalled_total" + label,
                               lambda: self.decode_tokens_stalled)
        metrics.register_gauge(
            "gen_decode_tokens_multi_stalled_total" + label,
            lambda: self.decode_tokens_multi_stalled)
        metrics.register_gauge(
            "gen_ttft_p99_ms" + label,
            lambda: round(self.ttft.percentile(99) * 1e3, 3))
        # the block-pool surface: preemptions + bytes-per-sequence in
        # every kv mode, pool fill only where a pool exists
        metrics.register_gauge(
            "gen_preemptions_total" + label,
            lambda: self.engine.preemptions_total)
        metrics.register_gauge(
            "gen_hbm_per_request_bytes" + label,
            self.engine.hbm_per_request_bytes)
        if getattr(self.engine, "kv_mode", "contiguous") == "paged":
            metrics.register_gauge(
                "gen_blocks_total" + label,
                lambda: self.engine.blocks_total)
            metrics.register_gauge(
                "gen_blocks_free" + label,
                lambda: self.engine.blocks_free)
        if getattr(self.engine, "prefix_cache", False):
            metrics.register_gauge(
                "gen_prefix_hit_rate" + label,
                lambda: round(self.engine.prefix_hit_rate(), 4))
        if getattr(self.engine, "speculative", None):
            metrics.register_gauge(
                "gen_spec_accept_rate" + label,
                lambda: round(self.engine.spec_accept_rate(), 4))
            metrics.register_gauge(
                "gen_spec_tokens_per_dispatch" + label,
                lambda: round(
                    self.engine.spec_tokens_per_dispatch(), 4))
        metrics.register_histogram("gen_ttft_seconds", self.ttft,
                                   "submit -> first generated token",
                                   labels={"model": self.name})

    def _unregister_gauges(self, metrics):
        label = '{model="%s"}' % self.name
        gauges = ["gen_queue_depth", "gen_slot_occupancy",
                  "gen_admitted_total", "gen_tokens_total",
                  "gen_batch_fill", "gen_decode_tokens_stalled_total",
                  "gen_decode_tokens_multi_stalled_total",
                  "gen_ttft_p99_ms", "gen_preemptions_total",
                  "gen_hbm_per_request_bytes"]
        if getattr(self.engine, "kv_mode", "contiguous") == "paged":
            gauges += ["gen_blocks_total", "gen_blocks_free"]
        if getattr(self.engine, "prefix_cache", False):
            gauges += ["gen_prefix_hit_rate"]
        if getattr(self.engine, "speculative", None):
            gauges += ["gen_spec_accept_rate",
                       "gen_spec_tokens_per_dispatch"]
        for gauge in gauges:
            metrics.unregister_gauge(gauge + label)
        metrics.unregister_histogram("gen_ttft_seconds",
                                     labels={"model": self.name})

    #: decode-step cadence of the telemetry-bus "serve" snapshots
    WATCH_EVERY = 32

    def watch_snapshot(self):
        """The compact serving digest published onto the telemetry
        bus every :data:`WATCH_EVERY` decode steps (and readable any
        time): queue/slot pressure, throughput counters, TTFT."""
        return {
            "model": self.name,
            "queue_depth": len(self._queue),
            "active": len(self._active),
            "prefilling": len(self._prefilling),
            "batch_fill": round(self.batch_fill(), 4),
            "admitted_total": self.admitted_total,
            "finished_total": self.finished_total,
            "tokens_total": self.tokens_total,
            "decode_tokens_stalled": self.decode_tokens_stalled,
            "decode_tokens_multi_stalled":
                self.decode_tokens_multi_stalled,
            "preemptions_total": self.engine.preemptions_total,
            "ttft_p99_ms": round(self.ttft.percentile(99) * 1e3, 3),
        }

    def batch_fill(self):
        """Mean decode-row utilisation: active slots served per decode
        dispatch over the engine's slot capacity."""
        if not self.decode_steps:
            return 0.0
        return self.decode_slot_steps / float(
            self.decode_steps * self.engine.max_slots)

    def queue_depth(self):
        return len(self._queue)

    def active_requests(self):
        return len(self._active) + len(self._prefilling)

    # -- client side -------------------------------------------------------
    def submit(self, tokens, max_new_tokens=16, on_token=None):
        """Enqueue one prompt; returns a Future resolving to the full
        greedy token list.  Sheds with :class:`QueueFull` at capacity
        and rejects unservable prompts with ``ValueError`` at the
        door (a queued request must never fail at admission time)."""
        tokens = numpy.ascontiguousarray(tokens, numpy.int32).ravel()
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(tokens) < 1:
            raise ValueError("empty prompt")
        request = GenRequest(tokens, max_new_tokens, on_token,
                             ctx=obs_context.current())
        return self.submit_request(request)

    def submit_request(self, request):
        """Enqueue a pre-built :class:`GenRequest` — the fleet's
        drain-replay path (and what :meth:`submit` rides).  Validation
        is written against the request's prefix and REMAINING budget,
        which for a fresh request equals the classic prompt +
        ``max_new_tokens`` check and for a replayed one admits exactly
        the streams the original admission admitted (the prefix grew
        by what the budget shrank)."""
        prefix_len = len(request.prefix())
        remaining = request.max_new_tokens - len(request.generated)
        if remaining < 1:
            raise ValueError(
                "request has no remaining token budget (%d generated "
                "of %d) — finished streams are not replayable"
                % (len(request.generated), request.max_new_tokens))
        self.engine.check_prompt(prefix_len)  # raises when oversized
        if prefix_len + remaining - 1 >= self.engine.max_seq:
            raise ValueError(
                "prompt %d + max_new_tokens %d exceeds the engine's "
                "max_seq %d KV slot" % (prefix_len, remaining,
                                        self.engine.max_seq))
        with self._cond:
            if self._stopped:
                raise RuntimeError("scheduler is stopped")
            if len(self._queue) >= self.max_queue:
                self.shed_total += 1
                if self.metrics is not None:
                    self.metrics.record_shed()
                raise QueueFull(
                    "generation queue full (%d requests, limit %d)"
                    % (len(self._queue), self.max_queue))
            self._req_counter += 1
            request.req = self._req_counter
            self._queue.append(request)
            self._cond.notify()
        if trace.enabled():
            trace.instant("gen", "enqueue",
                          request.span_args(
                              {"prompt": len(request.tokens),
                               "max_new": request.max_new_tokens,
                               "resumed": bool(request.generated)}),
                          role="server")
        return request.future

    def submit_handoff(self, payload, request):
        """Enqueue a shipped page payload for adoption — the fleet
        decode role's admission lane.  The request continues exactly
        where the prefill role left it: the payload's first token is
        emitted on adoption and decode takes over, no recompute.
        Handoffs admit ahead of the prompt queue (their prefill is
        already paid for)."""
        if int(payload["n"]) != len(request.prefix()):
            raise ValueError(
                "payload carries %d tokens but the request's prefix "
                "is %d" % (int(payload["n"]), len(request.prefix())))
        with self._cond:
            if self._stopped:
                raise RuntimeError("scheduler is stopped")
            self._handoff.append((payload, request))
            self._cond.notify()
        return request.future

    def handoff_depth(self):
        return len(self._handoff)

    def drain(self, timeout=30.0):
        """Evict EVERY live request — queued, pending handoff,
        prefilling, and decoding — and return the list of
        :class:`GenRequest` objects for replay on a surviving replica
        (futures untouched, tokens-so-far kept: resubmitting each via
        :meth:`submit_request` continues the streams losslessly, the
        preemption mechanism applied across engines).  Runs on the
        worker thread when one is live (the engine is single-owner);
        synchronously otherwise."""
        with self._cond:
            if self._thread is None or self._stopped:
                return self._drain_now()
            future = self._drain_future = Future()
            self._cond.notify()
        return future.result(timeout)

    def _drain_now(self):
        """The drain body — MUST run on the thread that owns the
        engine."""
        evicted = []
        for slot in sorted(set(self._prefilling) | set(self._active)):
            request = self._prefilling.pop(slot, None) \
                or self._active.pop(slot, None)
            try:
                self.engine.release_slot(slot)
            except Exception:
                pass
            request.slot = None
            request.queued_at = time.perf_counter()
            evicted.append(request)
        with self._cond:
            evicted.extend(r for _, r in self._handoff)
            self._handoff.clear()
            evicted.extend(self._queue)
            self._queue.clear()
        if trace.enabled():
            trace.instant("gen", "drain",
                          {"model": self.name,
                           "requests": len(evicted)}, role="server")
        return evicted

    def generate(self, tokens, max_new_tokens=16, timeout=120.0,
                 on_token=None):
        """Blocking convenience: ``submit`` + result.  Without the
        worker thread the caller's own thread pumps the loop."""
        future = self.submit(tokens, max_new_tokens, on_token)
        if self._thread is not None:
            return future.result(timeout)
        deadline = time.perf_counter() + timeout
        while not future.done():
            if self.step() == 0 and not future.done():
                raise RuntimeError("scheduler idle with an unresolved "
                                   "request — engine wedged?")
            if time.perf_counter() > deadline:
                raise TimeoutError("generation exceeded %.1fs"
                                   % timeout)
        return future.result(0)

    # -- the scheduling iteration ------------------------------------------
    def _emit(self, request, token, slot_len=None):
        request.generated.append(int(token))
        if request.first_token_at is None:
            request.first_token_at = time.perf_counter()
            self.ttft.record(request.first_token_at
                             - request.submitted)
            if trace.enabled() and request.admitted_at is not None:
                # the prefill phase of this request's waterfall:
                # admission → first token (whole-bucket dispatch or
                # the chunked cadence, whichever ran)
                trace.complete(
                    "gen", "prefill_phase",
                    int(request.admitted_at * 1e9),
                    int((request.first_token_at
                         - request.admitted_at) * 1e9),
                    request.span_args({"slot": request.slot,
                                       "prompt": len(request.tokens)}),
                    role="server")
        self.tokens_total += 1
        if request.on_token is not None:
            try:
                request.on_token(int(token))
            except Exception:
                self.exception("on_token callback failed; detaching "
                               "the stream (the future still resolves)")
                request.on_token = None
        reason = finish_reason(self.engine, len(request.generated),
                               request.max_new_tokens, int(token),
                               request.slot, slot_len=slot_len)
        if reason is not None:
            self._finish(request, reason)

    def _finish(self, request, reason):
        request.finish_reason = reason
        if request.export_pages:
            # fleet prefill role: package the slot's KV pages before
            # they go back to the pool — the job result the handoff
            # ships (a failure leaves export=None; the fleet master
            # re-runs the prefill rather than losing the request)
            try:
                request.export = self.engine.export_slot(request.slot)
                # ride the token stream + prompt length along so the
                # adopting engine's prefix cache can copy-on-adopt the
                # shared pages (prompt pages only — decode-written KV
                # never becomes shareable prefix)
                n = int(request.export["n"])
                stream = numpy.asarray(request.prefix(), numpy.int32)
                request.export["tokens"] = stream[:n]
                request.export["prompt_n"] = min(
                    len(request.tokens), n)
            except Exception:
                self.exception("page export failed; the fleet will "
                               "re-run this prefill")
        self.engine.release_slot(request.slot)
        self._active.pop(request.slot, None)
        self.finished_total += 1
        if trace.enabled():
            now = time.perf_counter()
            trace.instant("gen", "evict",
                          request.span_args(
                              {"slot": request.slot, "reason": reason,
                               "tokens": len(request.generated)}),
                          role="server")
            if request.first_token_at is not None \
                    and now > request.first_token_at:
                # the decode phase: first token → eviction
                trace.complete(
                    "gen", "decode_phase",
                    int(request.first_token_at * 1e9),
                    int((now - request.first_token_at) * 1e9),
                    request.span_args({"slot": request.slot,
                                       "tokens":
                                       len(request.generated)}),
                    role="server")
            # the whole request: submit → resolution (encloses the
            # queue_wait / prefill_phase / decode_phase spans)
            trace.complete(
                "gen", "request", int(request.submitted * 1e9),
                int((now - request.submitted) * 1e9),
                request.span_args({"reason": reason,
                                   "tokens": len(request.generated),
                                   "preemptions":
                                   request.preemptions}),
                role="server")
        request.future.set_result(list(request.generated))

    def _preempt(self, request):
        """Pool-exhaustion eviction of the YOUNGEST sequence: free its
        slot + pages, requeue it at the queue FRONT with its
        tokens-so-far (greedy decode of the prefix reproduces the
        stream — lossless), deterministically."""
        slot = request.slot
        self.engine.preempt(slot)
        self._active.pop(slot, None)
        self._prefilling.pop(slot, None)
        request.slot = None
        request.preemptions += 1
        request.queued_at = time.perf_counter()
        if trace.enabled():
            trace.instant("gen", "preempt",
                          request.span_args(
                              {"slot": slot,
                               "generated": len(request.generated)}),
                          role="server")
        with self._cond:
            self._queue.appendleft(request)

    def _spec_decode(self):
        """One speculative draft-then-verify round over the active
        set: collect proposals per slot, run the engine's single
        verify dispatch, then emit each slot's accepted span ONE
        token at a time through the shared finish predicate — the
        emitted stream (and where it stops) is bitwise what plain
        decode would have produced, just cheaper per token.  Returns
        the number of tokens emitted."""
        proposals = {}
        for slot, request in self._active.items():
            if self.engine.slot_len[slot] >= self.engine.max_seq:
                continue
            proposals[slot] = self.engine.propose(request.prefix())
        result = self.engine.spec_decode_step(proposals)
        if result is None:
            return 0
        emitted = 0
        self.decode_steps += 1
        self.decode_slot_steps += len(result)
        for slot, tokens in sorted(result.items()):
            request = self._active.get(slot)
            if request is None:
                continue
            final_len = int(self.engine.slot_len[slot])
            for j, token in enumerate(tokens):
                # the slot length AS OF this token: the engine already
                # advanced by the whole accepted span
                effective = final_len - (len(tokens) - 1 - j)
                self._emit(request, token, slot_len=effective)
                emitted += 1
                if request.finish_reason is not None:
                    # eos/length mid-span: plain decode would have
                    # stopped here too; the rest of the span is the
                    # rejected-future tail and must not be emitted
                    break
        return emitted

    def step(self):
        """One iteration: admit while the engine has REAL headroom
        (slots, and pool pages in paged mode), feed at most one chunk
        per pending chunked prefill, preempt the youngest sequence on
        pool exhaustion, then one decode dispatch over the active set.
        Returns the amount of work done — tokens emitted plus chunks
        fed (0 = idle).  The step's span carries ``prefills``, the
        prefill and chunk dispatches its decode waited behind, and
        ``decoded``, the tokens that decode emitted."""
        with trace.span("gen", "step", role="server") as span:
            emitted, prefills, decoded = self._step()
            span.set_metadata(prefills=prefills, decoded=decoded)
        return emitted

    def _step(self):
        """``(emitted, prefills, decoded)`` of one iteration."""
        emitted = 0
        decode_steps_before = self.decode_steps
        drain = None
        with self._cond:
            if self._drain_future is not None:
                drain, self._drain_future = self._drain_future, None
        if drain is not None:
            # a drain request from another thread: evict everything on
            # THIS thread (the engine's owner) and hand the requests
            # back for replay
            try:
                drain.set_result(self._drain_now())
            except Exception as exc:  # noqa: BLE001 - report, don't wedge
                drain.set_exception(exc)
            return 1, 0, 0           # progress, not idle
        prefill_calls = self.engine.prefill_calls
        # adopt shipped pages first: their prefill is already paid
        # for, so a waiting handoff beats a queued prompt to the pool
        while True:
            with self._cond:
                if not self._handoff:
                    break
                payload, request = self._handoff[0]
                if not self.engine.can_admit(int(payload["n"])):
                    break
                self._handoff.popleft()
            try:
                with obs_context.activate(request.ctx):
                    slot, token = self.engine.adopt_sequence(payload)
            except Exception as exc:  # noqa: BLE001 - per-request
                self.exception("page adoption failed; failing the "
                               "request")
                if not request.future.done():
                    request.future.set_exception(exc)
                continue
            request.slot = slot
            request.admitted_at = time.perf_counter()
            self._admit_counter += 1
            request.admit_seq = self._admit_counter
            self.admitted_total += 1
            if trace.enabled():
                trace.instant("gen", "adopt",
                              request.span_args(
                                  {"slot": slot,
                                   "prompt": len(request.tokens),
                                   "pages": len(payload["k"])}),
                              role="server")
            self._active[slot] = request
            self._emit(request, token)   # may evict immediately
            emitted += 1
        while True:
            # pop-and-admit one at a time: every admission updates the
            # slot free list AND the pool headroom before the next
            # request is priced, so co-admissions can never jointly
            # overflow what can_admit approved individually
            with self._cond:
                if not self._queue:
                    break
                head = self._queue[0]
                # pass the tokens so prefix-cache hits (and evictable
                # cache-only pages) count toward the pricing
                if not self.engine.can_admit(len(head.prefix()),
                                             head.prefix()):
                    break          # FIFO: no overtaking the head
                request = self._queue.popleft()
            try:
                # activate the request's trace context so the
                # engine's own dispatch spans (prefill /
                # prefill_chunk) carry its identity
                with obs_context.activate(request.ctx), trace.span(
                        "gen", "admit",
                        {"req": request.req,
                         "prompt": len(request.tokens),
                         "queue_wait_us": int(1e6 * (
                             time.perf_counter() - request.queued_at))},
                        role="server"):
                    slot, token = self.engine.admit(request.prefix())
            except Exception as exc:  # noqa: BLE001 - per-request
                # a failed admission must fail THIS request's future —
                # it already left the queue, so nobody else will; the
                # next queued request still gets its attempt
                self.exception("admission failed; failing the request")
                if not request.future.done():
                    request.future.set_exception(exc)
                continue
            request.slot = slot
            request.admitted_at = time.perf_counter()
            self._admit_counter += 1
            request.admit_seq = self._admit_counter
            self.admitted_total += 1
            if trace.enabled():
                trace.instant("gen", "admit",
                              request.span_args(
                                  {"slot": slot,
                                   "prompt": len(request.tokens),
                                   "resumed":
                                   bool(request.generated)}),
                              role="server")
                # the queue-wait phase: (re-)enqueue → admission
                trace.complete(
                    "gen", "queue_wait",
                    int(request.queued_at * 1e9),
                    int((request.admitted_at
                         - request.queued_at) * 1e9),
                    request.span_args({"slot": slot,
                                       "resumed":
                                       bool(request.generated)}),
                    role="server")
            if token is None:
                self._prefilling[slot] = request
            else:
                self._active[slot] = request
                self._emit(request, token)   # may evict immediately
                emitted += 1
        # chunked-prefill cadence: ONE chunk per pending prompt per
        # step — co-resident decodes below never wait for a whole
        # admission
        for slot in sorted(self._prefilling):
            request = self._prefilling[slot]
            try:
                with obs_context.activate(request.ctx):
                    token = self.engine.prefill_step(slot)
            except Exception as exc:  # noqa: BLE001 - per-request
                self.exception("prefill chunk failed; failing the "
                               "request")
                del self._prefilling[slot]
                try:
                    self.engine.release_slot(slot)
                except Exception:
                    pass
                if not request.future.done():
                    request.future.set_exception(exc)
                continue
            emitted += 1                     # progress, not idle
            if token is not None:
                del self._prefilling[slot]
                self._active[slot] = request
                self._emit(request, token)
        prefills = self.engine.prefill_calls - prefill_calls
        # safety net for the max_seq edge: a saturated slot decodes
        # nothing — route it through the SHARED finish predicate (both
        # kv modes) instead of crashing the batch
        for slot, request in list(self._active.items()):
            if self.engine.slot_len[slot] >= self.engine.max_seq:
                last = request.generated[-1] if request.generated \
                    else int(self.engine.slot_token[slot])
                reason = finish_reason(
                    self.engine, len(request.generated),
                    request.max_new_tokens, last, slot) or "length"
                self._finish(request, reason)
        # pool exhaustion: preempt the youngest decoding sequence
        # until the next decode step's pages fit
        while self.engine.decode_block_deficit() > 0:
            victims = [r for r in self._active.values()]
            if not victims:
                raise RuntimeError(
                    "block pool deficit with no preemptible sequence "
                    "— pool smaller than one step's working set")
            self._preempt(max(victims, key=lambda r: r.admit_seq))
            emitted += 1                     # progress, not idle
        decoded = 0
        if self._active:
            if getattr(self.engine, "proposer", None) is not None:
                decoded = self._spec_decode()
            else:
                result = self.engine.decode_step()
                if result is not None:
                    out, active = result
                    self.decode_steps += 1
                    n_active = int(active.sum())
                    self.decode_slot_steps += n_active
                    # token bookkeeping, on_token callbacks, finishes
                    with trace.span("gen", "emit", {"n": n_active},
                                    role="server"):
                        for slot, request in list(
                                self._active.items()):
                            if active[slot]:
                                self._emit(request, out[slot])
                                decoded += 1
        if prefills and decoded:
            self.decode_tokens_stalled += decoded
            if prefills > 1:
                self.decode_tokens_multi_stalled += decoded
        from veles_tpu import watch
        if watch.enabled() \
                and self.decode_steps != decode_steps_before \
                and self.decode_steps % self.WATCH_EVERY == 0:
            # periodic serving snapshot onto the telemetry bus, only
            # when a decode step actually advanced onto the cadence
            # (prefill-only pumps must not republish every call) —
            # NOBLOCK publish, so a dead dashboard never costs a
            # decode step
            watch.publish("serve", self.watch_snapshot())
        return emitted + decoded, prefills, decoded

    def run_until_idle(self, max_steps=100000):
        """Pump until queue and slots drain (manual mode)."""
        steps = 0
        while self._queue or self._active or self._prefilling \
                or self._handoff:
            if self.step() == 0:
                break
            steps += 1
            if steps > max_steps:
                raise RuntimeError("run_until_idle exceeded %d steps"
                                   % max_steps)
        return steps

    # -- worker mode -------------------------------------------------------
    def start(self):
        """Run the scheduling loop on a background thread (serving
        mode).  Returns self."""
        if self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._worker,
                                        daemon=True,
                                        name="gen-scheduler-%s"
                                             % self.name)
        self._thread.start()
        return self

    def _worker(self):
        while True:
            with self._cond:
                if self._stopped:
                    return
                if not self._queue and not self._active \
                        and not self._prefilling and not self._handoff \
                        and self._drain_future is None:
                    with trace.span("gen", "idle", role="server"):
                        self._cond.wait(0.05)
                    if self._stopped:
                        return
            try:
                self.step()
            except Exception:
                # fail the inhabitants rather than silently wedging
                self.exception("scheduler step failed; failing active "
                               "requests")
                occupants = list(self._active.items()) \
                    + list(self._prefilling.items())
                self._active.clear()
                self._prefilling.clear()
                for slot, request in occupants:
                    try:
                        self.engine.release_slot(slot)
                    except Exception:
                        pass
                    if not request.future.done():
                        request.future.set_exception(
                            RuntimeError("generation failed mid-"
                                         "stream"))

    def stop(self, drain=True):
        """Stop the worker; ``drain=True`` finishes queued + active
        work first (bounded by the workload, not time)."""
        if self._thread is not None and drain:
            # let the worker empty the pipeline
            while True:
                with self._cond:
                    idle = not self._queue and not self._active \
                        and not self._prefilling and not self._handoff
                if idle:
                    break
                time.sleep(0.005)
        with self._cond:
            self._stopped = True
            leftovers = list(self._queue)
            self._queue.clear()
            leftovers += [r for _, r in self._handoff]
            self._handoff.clear()
            self._cond.notify_all()
        for request in leftovers:
            if not request.future.done():
                request.future.set_exception(
                    RuntimeError("scheduler stopped"))
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        # whatever still occupies a slot (drain=False, or a request
        # that slipped into the drain race between queue-pop and
        # admission) fails LOUDLY now — a pending future against a
        # stopped scheduler would otherwise block its client for the
        # full request timeout
        for slot, request in (list(self._active.items())
                              + list(self._prefilling.items())):
            self._active.pop(slot, None)
            self._prefilling.pop(slot, None)
            try:
                self.engine.release_slot(slot)
            except Exception:
                pass
            if not request.future.done():
                request.future.set_exception(
                    RuntimeError("scheduler stopped mid-stream"))
        if self.metrics is not None:
            self._unregister_gauges(self.metrics)

    def describe(self):
        return {
            "queue_depth": len(self._queue),
            "active_requests": self.active_requests(),
            "admitted_total": self.admitted_total,
            "finished_total": self.finished_total,
            "tokens_total": self.tokens_total,
            "shed_total": self.shed_total,
            "batch_fill": round(self.batch_fill(), 4),
            "ttft_p99_ms": round(self.ttft.percentile(99) * 1e3, 3),
        }


def static_generate(engine, requests):
    """The pad-to-slowest baseline the continuous scheduler is gated
    against: admit ``engine.max_slots`` requests, decode until EVERY
    member finishes (idle slots keep burning decode rows), only then
    admit the next group.  Same compiled programs, same finish
    predicate — the only variable is iteration-level admission.
    Returns ``(token_lists, decode_steps)``."""
    results = [None] * len(requests)
    steps = 0
    i = 0
    while i < len(requests):
        group = []
        while i < len(requests) and len(group) < engine.max_slots:
            tokens, max_new = requests[i]
            slot, tok = engine.prefill(tokens)
            generated = [int(tok)]
            entry = {"slot": slot, "index": i, "generated": generated,
                     "max_new": int(max_new)}
            reason = finish_reason(engine, 1, int(max_new), int(tok),
                                   slot)
            if reason is not None:
                engine.release_slot(slot)
                results[i] = generated
            else:
                group.append(entry)
            i += 1
        while group:
            out, active = engine.decode_step()
            steps += 1
            still = []
            for entry in group:
                slot = entry["slot"]
                if not active[slot]:
                    still.append(entry)
                    continue
                tok = int(out[slot])
                entry["generated"].append(tok)
                reason = finish_reason(engine, len(entry["generated"]),
                                       entry["max_new"], tok, slot)
                if reason is not None:
                    engine.release_slot(slot)
                    results[entry["index"]] = entry["generated"]
                else:
                    still.append(entry)
            group = still
    return results, steps
