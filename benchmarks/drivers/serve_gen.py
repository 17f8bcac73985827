"""Driver of the generative serving path: ``ModelRegistry
.deploy_generative`` (preflight, warm-up, the scheduler's own worker
thread) and then ``scheduler.submit(tokens, max_new_tokens, on_token)``
from this driver's arrival loop, as ``chip_smoke.py``'s
``_serve_session`` proved it on the chip, without the HTTP transport.

Open loop: requests are submitted when they are DUE (the traffic file's
schedule), whatever the system's state, and every latency is taken from
the due time.  A fixed lead-in of the same traffic runs
before the window opens, so the window sees a filled engine.
"""

import threading
import time

import numpy

from benchmarks import end_to_end, traffic


def program_config(config):
    """The configuration in ``samples/transformer.py``'s keys."""
    if config["n_inner"] % config["n_embd"]:
        raise ValueError("n_inner must be a multiple of n_embd")
    return {"vocab": config["vocab_size"], "dim": config["n_embd"],
            "heads": config["n_head"], "layers": config["n_layer"],
            "mlp_ratio": config["n_inner"] // config["n_embd"],
            "seq_len": config["n_positions"]}


class Run(object):
    def __init__(self, ctx, reference):
        self.ctx = ctx
        self.reference = reference
        self.obs = None
        self.engine = self.registry = None

    def _deploy(self):
        import jax
        import jax.numpy as jnp
        from veles_tpu.gen import GenerativeEngine, TransformerGenModel
        from veles_tpu.samples import transformer
        from veles_tpu.serve import ModelRegistry

        config = self.ctx.config
        pcfg = program_config(config)
        dtype = jnp.dtype(config["dtype"])
        self.params = self.reference.init_params(config, self.ctx.seed,
                                                 dtype)
        want = jax.tree.map(lambda s: s.shape,
                            transformer.param_shapes(pcfg))
        have = jax.tree.map(lambda a: a.shape, self.params)
        if want != have:
            raise RuntimeError("the reference's parameter layout is not "
                               "the program's: %r vs %r" % (have, want))
        model = TransformerGenModel(pcfg, compute_dtype=dtype.type)
        eng = config["engine"]
        self.engine = GenerativeEngine(
            model, params=self.params, max_slots=eng["max_slots"],
            max_seq=eng["max_seq"],
            prefill_buckets=tuple(eng["prefill_buckets"]),
            kv=eng.get("kv", "contiguous"), seed=0)
        self.registry = ModelRegistry()
        deployed = self.registry.deploy_generative(
            "lm", self.engine,
            scheduler_config=dict(config.get("scheduler", {})))
        self.scheduler = deployed.scheduler

    def _counters(self):
        return {"t": time.perf_counter(),
                "decode_calls": self.engine.decode_calls,
                "prefill_calls": self.engine.prefill_calls,
                "compile_count": self.engine.compile_count,
                "decode_steps": self.scheduler.decode_steps,
                "decode_slot_steps": self.scheduler.decode_slot_steps,
                "tokens_total": self.scheduler.tokens_total,
                "preemptions_total": self.engine.preemptions_total,
                "queue_depth": self.scheduler.queue_depth()}

    def _submit(self, record):
        times = record["times"]

        def on_token(_token, append=times.append,
                     clock=time.perf_counter):
            append(clock())

        record["submitted"] = time.perf_counter()
        try:
            record["future"] = self.scheduler.submit(
                record["tokens"], record["max_new_tokens"],
                on_token=on_token)
        except Exception as exc:  # noqa: BLE001 - shed or refused: failed
            record["error"] = "%s: %s" % (type(exc).__name__, exc)

    def run(self):
        ctx, params, config = self.ctx, self.ctx.params, self.ctx.config
        tracer = ctx.tracer
        self._deploy()
        engine, scheduler = self.engine, self.scheduler
        spec = params["traffic_spec"]
        lead_in = float(params["lead_in_s"])
        requests = traffic.generate(spec, config["vocab_size"], ctx.seed,
                                    lead_in, ctx.seconds)
        records = [dict(r, times=[], future=None, error=None)
                   for r in requests]
        self.records = records

        # run every compiled program once before any timing: one request
        # per prefill bucket, two tokens each (prefill + one decode)
        rng = numpy.random.default_rng([ctx.seed, 2])
        warm = []
        for bucket in engine.prefill_buckets:
            n = min(bucket, int(spec["prompt_len"]["max"]))
            warm.append(scheduler.submit(
                rng.integers(0, config["vocab_size"], n), 2))
        for future in warm:
            future.result(600)
        tracer.wrap(scheduler, "step", "scheduler_step")
        tracer.wrap(engine, "admit", "prefill_dispatch")
        tracer.wrap(engine, "decode_step", "decode_dispatch")

        t_start = time.perf_counter()
        t_open = t_start + lead_in
        t_close = t_open + ctx.seconds
        marks = {}
        late = []

        def wait_until(when):
            while True:
                left = when - time.perf_counter()
                if left <= 0:
                    return
                time.sleep(min(left, 0.05))

        def pass_marks(now_due):
            """Read counters (and start/stop the trace) at the window's
            edges, as the arrival loop passes them."""
            if "open" not in marks and now_due >= t_open:
                wait_until(t_open)
                tracer.start()
                marks["open"] = self._counters()
            if tracer.running and "trace_stop" not in marks and \
                    now_due >= marks["open"]["t"] + ctx.trace_seconds:
                wait_until(marks["open"]["t"] + ctx.trace_seconds)
                marks["trace_stop"] = self._counters()
                stopper = threading.Thread(target=tracer.stop)
                stopper.start()
                marks["stopper"] = stopper

        for record in records:
            due = t_start + record["due"]
            pass_marks(due)
            with tracer.span("wait_for_arrival"):
                wait_until(due)
            self._submit(record)
            late.append(record["submitted"] - due)
        pass_marks(t_close)
        wait_until(t_close)
        marks["close"] = self._counters()
        if "stopper" in marks:
            marks["stopper"].join()
        tracer.stop()

        # wait for every request, a minute past the close if need be:
        # an answer that comes late is late, not wrong
        deadline = t_close + 60.0
        for record in records:
            future = record["future"]
            if future is None:
                continue
            try:
                record["served"] = future.result(
                    max(deadline - time.perf_counter(), 0.0))
            except Exception as exc:  # noqa: BLE001 - counted as failed
                record["error"] = "%s: %s" % (type(exc).__name__, exc)
        t_drained = time.perf_counter()

        due_in = [r for r in records
                  if t_open <= t_start + r["due"] < t_close]
        ttft, unanswered = [], 0
        for record in due_in:
            due = t_start + record["due"]
            served = record.get("served")
            if served is None or \
                    len(served) != record["max_new_tokens"]:
                unanswered += 1
            first = record["times"][0] if record["times"] else t_drained
            ttft.append(first - due)
        events = []             # (time, prompt length, index of token)
        gaps = []
        for record in records:
            n = len(record["tokens"])
            times = record["times"]
            for j, t in enumerate(times):
                events.append((t, n, j))
                if j and t_open <= t < t_close:
                    gaps.append(t - times[j - 1])
        in_window = sum(1 for t, _n, _j in events if t_open <= t < t_close)
        opened, closed = marks["open"], marks["close"]
        steps = closed["decode_steps"] - opened["decode_steps"]
        self.obs = {
            "t_open": t_open, "t_close": t_close,
            "window_s": t_close - t_open,
            "attempted": len(due_in), "failed": unanswered,
            "ttft_s": ttft, "gaps_s": gaps, "tokens_in_window": in_window,
            "token_events": events,
            "compiles_in_window":
                closed["compile_count"] - opened["compile_count"],
            "counters": {
                "batch_fill_slots": (
                    (closed["decode_slot_steps"]
                     - opened["decode_slot_steps"]) / steps
                    if steps else None),
                "decode_steps": steps,
                "prefill_calls":
                    closed["prefill_calls"] - opened["prefill_calls"],
                "preemptions":
                    closed["preemptions_total"]
                    - opened["preemptions_total"],
                "queue_depth_at_close": closed["queue_depth"]},
            "generator_late_s": {"max": max(late), "mean":
                                 sum(late) / len(late)},
            "drain_s": t_drained - t_close,
        }
        if tracer.done:
            stop = marks["trace_stop"]
            steps = stop["decode_steps"] - opened["decode_steps"]
            self.obs["traced"] = {
                "lo": opened["t"], "hi": stop["t"],
                "batch_fill_slots": (
                    (stop["decode_slot_steps"]
                     - opened["decode_slot_steps"]) / steps
                    if steps else None)}
        ctx.log("generator late: max %.6f s, mean %.6f s; drain %.3f s; "
                "queue at close %d"
                % (max(late), sum(late) / len(late),
                   t_drained - t_close, closed["queue_depth"]))
        # first-token time from the DUE time: too few requests in a
        # window for a bounded metric (PERF.md section 2), so a log line
        ordered = sorted(ttft)
        ctx.log("first token after due, %d requests: median %.1f ms, "
                "second largest %.1f ms, largest %.1f ms"
                % (len(ordered), 1e3 * ordered[len(ordered) // 2],
                   1e3 * ordered[-2] if len(ordered) > 1 else 0.0,
                   1e3 * ordered[-1]))
        if gaps:
            slowest = sorted(gaps, reverse=True)[
                :end_to_end.ceil_pct(len(gaps), 2)]
            ctx.log("token gaps, n = %d: p95 %.3f ms, p97 %.3f ms; the "
                    "slowest 2%%, k = %d, mean %.3f ms; %.2f%% above 40 "
                    "ms, %.2f%% above 100 ms"
                    % (len(gaps), end_to_end.gap_p95_ms(self.obs),
                       end_to_end.gap_p97_ms(self.obs), len(slowest),
                       1e3 * sum(slowest) / len(slowest),
                       100.0 * sum(g > 0.040 for g in gaps) / len(gaps),
                       100.0 * sum(g > 0.100 for g in gaps) / len(gaps)))
        self.due_in = due_in
        return self.obs

    def release(self):
        """Stop the scheduler and free the engine's cache; the weights
        stay (they are the benchmark's own, and the reference's)."""
        self.registry.undeploy("lm")
        self.engine = self.registry = self.scheduler = None

    def sample(self):
        """Requests the window finished, drawn from the seed, the
        longest among them."""
        done = [r for r in self.due_in
                if r.get("served") is not None and r["served"]]
        if not done:
            return []
        count = int(self.ctx.params["verify_sample"])
        longest = max(done, key=lambda r: len(r["tokens"])
                      + len(r["served"]))
        rng = numpy.random.default_rng([self.ctx.seed, 3])
        picks = [done[i] for i in rng.permutation(len(done))[:count]]
        if not any(p is longest for p in picks):
            picks[-1] = longest
        return picks

    def _gaps_of(self, record, served, quant=None):
        spec = self.ctx.params["traffic_spec"]
        max_rows = int(spec["output_len"]["max"])
        return self.reference.served_gaps(
            self.params, record["tokens"], served,
            int(spec["prompt_len"]["max"]) + max_rows, max_rows, quant)

    def gaps(self, quant=None):
        """The widest gap over the sample, and how many tokens of how
        many requests it covered."""
        sample = self.sample()
        widest, tokens = 0.0, 0
        for record in sample:
            gaps = self._gaps_of(record, record["served"], quant)
            widest = max(widest, float(gaps.max()))
            tokens += len(gaps)
        return widest, tokens, len(sample)

    def controls(self):
        """The reference in the precision below the configuration's (the
        gap of the token IT puts first), and a served token altered
        where it is produced."""
        out = {"fp8": {"logit_gap": self.gaps("fp8")[0]}}
        sample = self.sample()
        if sample:
            served = list(sample[0]["served"])
            middle = len(served) // 2
            served[middle] = (served[middle] + 1) \
                % self.ctx.config["vocab_size"]
            out["altered_token"] = {"logit_gap": float(
                self._gaps_of(sample[0], served).max())}
        return out

    def verify(self):
        widest, tokens, requests = self.gaps()
        self.ctx.log("compared %d served tokens of %d requests"
                     % (tokens, requests))
        return {"logit_gap": widest if tokens else float("nan"),
                "unanswered": self.obs["failed"],
                "compiles_in_window": self.obs["compiles_in_window"]}
