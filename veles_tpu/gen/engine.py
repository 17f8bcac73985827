"""GenerativeEngine: AOT prefill buckets + ONE decode program over a
slot-major, device-resident KV cache.

The generative counterpart of :class:`veles_tpu.serve.engine
.InferenceEngine` and the same compile discipline: a *small set* of
prefill programs (one per prompt-length bucket) plus exactly one
fixed-shape decode-step program are lowered and compiled up front
(:meth:`warmup`), so steady-state serving — any interleaving of
admissions and decode iterations — never triggers XLA.  The recompile
sentinel holds the engine to it exactly like serve buckets: a compile
after ``warmup()`` is flagged.

The KV cache is ``{"k", "v"}: [layers, slots, max_seq, heads,
head_dim]`` device arrays, donated through every program call (the
cache never round-trips to host, and XLA updates it in place), and
registered in the HBM ledger under the ``kv`` category reserved since
the PR 6 residency work — ``wf.perf_report()`` / ``/metrics`` show the
cache's exact footprint next to params/dataset/staging.

``root.common.gen.kv = "paged"`` (or ``kv="paged"``) swaps the
slot-major cache for the shared block pool of
:mod:`veles_tpu.gen.paged` — ``[layers, num_blocks, block_size,
heads, head_dim]`` plus per-slot block tables — with the SAME program
discipline: the block append is fused into the one fixed-shape decode
program (tables ride in as an input), per-bucket prefills scatter
whole pages, and ``root.common.gen.prefill_chunk = C`` replaces the
bucket prefills with ONE chunk program fed at the decode cadence so
co-resident streams stop stalling behind whole-prompt admissions.
Pool exhaustion surfaces as :class:`~veles_tpu.gen.paged
.PoolExhausted`; the scheduler answers with deterministic
youngest-first preemption (lossless — the requeued prefix replays
bitwise under greedy decode).

Tensor parallelism is declarative (``parallel/tp.py`` rules): given a
mesh with a ``model`` axis, block weights shard column→row, the KV
cache shards over heads, and the SAME traced functions compile to a
pjit'd program — no mesh (or a 1-sized model axis) falls back to
single-device compilation transparently.
"""

import itertools
import threading
import time

import numpy

from veles_tpu import prof, trace
from veles_tpu.obs import context as obs_context
from veles_tpu.logger import Logger

#: per-process engine sequence for performance-ledger entry names
_GEN_SEQ = itertools.count()


def _round_up(x, mult):
    return (x + mult - 1) // mult * mult


def _power_of_two_buckets(lo, hi):
    buckets, b = [], lo
    while b < hi:
        buckets.append(b)
        b *= 2
    buckets.append(hi)
    return tuple(buckets)


#: registry of small draft models for model-based speculation —
#: ``root.common.gen.speculative = <name>`` selects an entry; the
#: int8 deploy of the served model is the natural candidate
DRAFT_MODELS = {}


def register_draft_model(name, model, params=None):
    """Register a small GenModel as a speculative-decode proposer.
    ``params`` (host tree) defaults to ``model.init_params(seed=0)``
    at engine construction.  Returns ``model`` (chainable)."""
    DRAFT_MODELS[str(name)] = (model, params)
    return model


class NGramProposer(object):
    """Prompt-lookup drafting (training-free): propose the ``k``
    tokens that FOLLOWED the most recent earlier occurrence of the
    stream's longest matching suffix n-gram.  Pure host work, fully
    deterministic, and strongest exactly where speculation pays —
    repetitive/agentic streams re-deriving their own context.  A bad
    proposal costs nothing but speed: the target verifies every
    draft, so the output stream is bitwise plain greedy decode."""

    name = "ngram"

    def __init__(self, max_ngram=3, min_ngram=1):
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)

    def propose(self, stream, k):
        toks = [int(t) for t in stream]
        n = len(toks)
        for g in range(min(self.max_ngram, n - 1),
                       self.min_ngram - 1, -1):
            suffix = toks[n - g:]
            for start in range(n - g - 1, -1, -1):
                if toks[start:start + g] == suffix:
                    # copy forward through the VIRTUAL stream (the
                    # draft extends it), so an overlapping match near
                    # the end — a constant or short-period tail, the
                    # best case — still yields k tokens, not the one
                    # or two left before the stream ends
                    cont, p = [], start + g
                    for _ in range(int(k)):
                        cont.append(toks[p] if p < n
                                    else cont[p - n])
                        p += 1
                    return cont
        return []


class DraftModelProposer(object):
    """Model-based drafting: ``k`` sequential greedy steps of a
    REGISTERED small model over a fixed recent-token window — ONE
    cache-less fixed-shape program compiled at warmup, so drafting is
    stateless and preemption/handoff can never desynchronize a draft
    cache.  Draft quality only affects tokens-per-dispatch; the
    target's verify program owns correctness."""

    def __init__(self, engine, name, model, params):
        self.engine = engine
        self.name = str(name)
        self.model = model
        #: draft context window — bounded so the draft forward stays
        #: cheap relative to the target verify it feeds
        self.window = int(min(32, model.seq_limit))
        if params is None:
            params = model.init_params(seed=0)
        self.params = engine._jax.device_put(params)

    def propose(self, stream, k):
        exe, entry = self.engine._draft_executable()
        jnp = self.engine._jax.numpy
        toks = [int(t) for t in stream]
        out = []
        tic = time.perf_counter_ns()
        for _ in range(int(k)):
            win = toks[-self.window:]
            padded = numpy.zeros(self.window, numpy.int32)
            padded[:len(win)] = win
            tok = int(exe(self.params, jnp.asarray(padded[None]),
                          jnp.int32(len(win))))
            out.append(tok)
            toks.append(tok)
        prof.ledger.record_dispatch(
            entry, time.perf_counter_ns() - tic, items=len(out))
        return out


class GenerativeEngine(Logger):
    """Slot-based generative inference over a protocol model
    (:mod:`veles_tpu.gen.model`).

    Host-side slot bookkeeping (lengths, last tokens, free list) lives
    here; the scheduler (:mod:`veles_tpu.gen.scheduler`) decides WHEN
    to admit and evict.  All device state is functional: every program
    returns the successor cache and the engine swaps the reference, so
    a failed dispatch can never leave a half-written cache visible.

    Greedy sampling (argmax) happens inside the compiled programs —
    tokens come back as int32 scalars, never logits, so a decode step
    moves ``slots * 4`` bytes D2H and the parity gate is a bitwise
    token comparison.
    """

    def __init__(self, model, params=None, *, max_slots=4,
                 max_seq=None, prefill_buckets=None, mesh=None,
                 eos_id=None, seed=0, kv=None, block_size=None,
                 num_blocks=None, prefill_chunk=None,
                 prefix_cache=None, speculative=None, draft_k=None,
                 **kwargs):
        super(GenerativeEngine, self).__init__(**kwargs)
        import jax

        from veles_tpu.config import root
        self._jax = jax
        self.model = model
        self.max_slots = int(max_slots)
        if self.max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        self.max_seq = int(max_seq or model.seq_limit)
        if self.max_seq < 2 or self.max_seq > model.seq_limit:
            raise ValueError(
                "max_seq %d out of range (2..%d, the model's "
                "positional table)" % (self.max_seq, model.seq_limit))

        # KV layout mode: worst-case contiguous slots (PR 8) or the
        # shared block pool (veles_tpu.gen.paged)
        gen_cfg = root.common.gen
        self.kv_mode = str(kv or gen_cfg.get("kv", "contiguous"))
        if self.kv_mode not in ("contiguous", "paged"):
            raise ValueError(
                "root.common.gen.kv must be 'contiguous' or 'paged', "
                "got %r" % self.kv_mode)
        chunk = prefill_chunk if prefill_chunk is not None \
            else gen_cfg.get("prefill_chunk", None)
        self.prefill_chunk = int(chunk) if chunk else None
        if self.prefill_chunk is not None and self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")

        pc = prefix_cache if prefix_cache is not None \
            else gen_cfg.get("prefix_cache", "off")
        if pc in (True, "on"):
            self.prefix_cache = True
        elif pc in (False, None, "off"):
            self.prefix_cache = False
        else:
            raise ValueError(
                "root.common.gen.prefix_cache must be 'on' or 'off', "
                "got %r" % (pc,))
        spec = speculative if speculative is not None \
            else gen_cfg.get("speculative", "off")
        if spec in (False, None, "off"):
            spec = None
        self.speculative = None if spec is None else str(spec)
        dk = draft_k if draft_k is not None \
            else gen_cfg.get("draft_k", 4)
        self.draft_k = int(dk)
        if self.speculative is not None \
                and not 1 <= self.draft_k <= 7:
            raise ValueError(
                "draft_k must be 1..7 (the K+1 verify query rows ride "
                "one 8-sublane tile), got %d" % self.draft_k)

        #: a model that keeps recurrent state beside (or instead of)
        #: keys and values: nothing that assumes K/V pages may hold it
        self.recurrent = bool(getattr(model, "recurrent_state", False))
        if self.recurrent:
            for mode, on in (("kv='paged'", self.kv_mode == "paged"),
                             ("prefix_cache", self.prefix_cache),
                             ("prefill_chunk",
                              self.prefill_chunk is not None),
                             ("speculative", self.speculative is not None)):
                if on:
                    raise ValueError(self._no_recurrent(mode))
        #: a model some of whose layers keep only their last
        #: ``window`` positions (rings in the contiguous tree): nothing
        #: that assumes each layer keeps each position may hold it
        self.window = int(getattr(model, "window_rows", 0) or 0)
        if self.window:
            for mode, on in (("kv='paged'", self.kv_mode == "paged"),
                             ("prefix_cache", self.prefix_cache),
                             ("speculative", self.speculative is not None)):
                if on:
                    raise ValueError(self._no_window(mode))
            rows = min(self.window, self.max_seq)
            if self.prefill_chunk is None or rows % self.prefill_chunk:
                raise ValueError(
                    "%s takes its prompts by chunks only, and a chunk's "
                    "rows are one run of a window layer's %d: "
                    "prefill_chunk %r must divide it"
                    % (type(model).__name__, rows, self.prefill_chunk))
        if self.prefix_cache and self.kv_mode != "paged":
            raise ValueError(
                "prefix_cache requires kv='paged' — the contiguous "
                "engine has no shareable pages")

        self._pool = None
        self.block_size = None
        self.num_blocks = None
        if self.kv_mode == "paged":
            from veles_tpu.gen.paged import BlockPool
            self.block_size = int(block_size
                                  or gen_cfg.get("block_size", 16))
            if self.block_size < 1:
                raise ValueError("block_size must be >= 1")
            if self.max_seq % self.block_size:
                # the gathered [max_blocks*BS] view must equal the
                # contiguous [max_seq] layout EXACTLY, or the parity
                # gate degrades from bitwise to approximate
                raise ValueError(
                    "max_seq %d is not a multiple of block_size %d — "
                    "the paged gather could not mirror the contiguous "
                    "cache bitwise" % (self.max_seq, self.block_size))
            max_blocks = self.max_seq // self.block_size
            self.num_blocks = int(
                num_blocks or self.max_slots * max_blocks + 1)
            self._pool = BlockPool(self.max_slots, max_blocks,
                                   self.num_blocks, self.block_size)
            if self.prefill_chunk is not None:
                self.prefill_chunk = _round_up(self.prefill_chunk,
                                               self.block_size)
        self._prefix = None
        if self.prefix_cache:
            from veles_tpu.gen.prefix import PrefixCache
            self._prefix = PrefixCache(self._pool)
        if self.prefill_chunk is not None \
                and self.max_seq % self.prefill_chunk:
            # the final chunk of a near-max_seq prompt pads to a full
            # chunk; a non-divisor would spill that padded write past
            # the cache (clamped dynamic_update_slice = silent
            # corruption) and break the paged chunk program's fixed
            # chunk_ids shape
            raise ValueError(
                "prefill_chunk %d must divide max_seq %d"
                % (self.prefill_chunk, self.max_seq))

        buckets = tuple(sorted(set(
            int(b) for b in (prefill_buckets
                             or _power_of_two_buckets(
                                 min(8, self.max_seq), self.max_seq)))))
        if self._pool is not None:
            # bucket shapes scatter whole pages — round each up to the
            # page size (the padded tail routes to the trash block)
            buckets = tuple(sorted(set(
                _round_up(b, self.block_size) for b in buckets)))
        self.prefill_buckets = buckets
        if (self.prefill_buckets[0] < 1
                or self.prefill_buckets[-1] > self.max_seq):
            raise ValueError(
                "prefill buckets %s must lie in 1..max_seq=%d"
                % (self.prefill_buckets, self.max_seq))
        self.eos_id = None if eos_id is None else int(eos_id)
        # a mesh without a >1 model axis IS the single-device path
        self.mesh = mesh if (mesh is not None and
                             mesh.shape.get("model", 1) > 1) else None
        if self.mesh is not None and \
                model.heads % self.mesh.shape["model"]:
            raise ValueError(
                "model axis %d does not divide %d heads"
                % (self.mesh.shape["model"], model.heads))

        if params is None:
            params = model.init_params(seed=seed)
        from veles_tpu.quant import tree_is_quantized
        #: "int8" when the params tree carries veles_tpu.quant pairs
        #: (constructor-injected or via quantize_int8()); None = float
        self.quantized = "int8" if tree_is_quantized(params) else None
        if self.quantized and self.mesh is not None:
            raise ValueError(
                "int8-quantized params cannot shard over a model-axis "
                "mesh yet — serve the quantized deploy replicated (or "
                "keep the TP deploy float)")
        self._shardings = self._build_shardings()
        if self._pool is not None:
            cache = model.init_paged_cache(self.num_blocks,
                                           self.block_size)
        else:
            cache = model.init_cache(self.max_slots, self.max_seq)
        if self._shardings is None:
            self._params = jax.device_put(params)
            self._cache = cache
        else:
            p_sh, c_sh = self._shardings[:2]
            self._params = jax.device_put(params, p_sh)
            self._cache = jax.tree.map(
                lambda a, s: jax.device_put(a, s), cache, c_sh)
        #: the cache's exact footprint (pool bytes in paged mode),
        #: held in the HBM ledger's kv category for the engine's
        #: lifetime
        if self._pool is not None:
            self.kv_cache_bytes = model.paged_cache_nbytes(
                self.num_blocks, self.block_size)
        else:
            self.kv_cache_bytes = model.cache_nbytes(self.max_slots,
                                                     self.max_seq)
        #: the part of ``kv_cache_bytes`` (the WHOLE cache tree) that is
        #: recurrent state and not keys and values: fixed a slot,
        #: whatever the sequence's length
        self.state_cache_bytes = model.recurrent_nbytes(self.max_slots) \
            if self.recurrent else 0
        from veles_tpu.memory import Watcher
        Watcher.track(self.kv_cache_bytes, "kv", owner=self)
        self._kv_tracked = True
        #: the params' ACTUAL device footprint (int8 leaves count one
        #: byte) held in the HBM ledger's params category — the line
        #: the ≤0.35× int8-vs-bf16 acceptance gate reads
        from veles_tpu.quant import tree_nbytes
        self.params_nbytes = tree_nbytes(self._params)
        Watcher.track(self.params_nbytes, "params")
        self._params_tracked = True
        self._ledger_gen = Watcher.generation

        # host slot bookkeeping (single scheduler thread)
        self.slot_len = numpy.zeros(self.max_slots, numpy.int32)
        self.slot_token = numpy.zeros(self.max_slots, numpy.int32)
        self.slot_active = numpy.zeros(self.max_slots, bool)
        self._free = list(range(self.max_slots))
        #: slot -> in-flight chunked-prefill state
        self._chunking = {}
        #: slot -> occupant's distributed-trace id (None untraced) —
        #: stamped at admission from the ambient obs context so the
        #: shared decode dispatch span can name which requests each
        #: device call served
        self.slot_trace = [None] * self.max_slots

        #: the speculative proposer (None = plain decode): n-gram
        #: prompt lookup, or a registered small draft model
        self.proposer = None
        if self.speculative == "ngram":
            self.proposer = NGramProposer()
        elif self.speculative is not None:
            entry = DRAFT_MODELS.get(self.speculative)
            if entry is None:
                raise ValueError(
                    "speculative=%r names no registered draft model "
                    "(see register_draft_model) and is not 'ngram'"
                    % self.speculative)
            draft_model, draft_params = entry
            if int(draft_model.vocab) != int(model.vocab):
                self.warning(
                    "draft model %r vocab %d != target vocab %d — "
                    "proposals index a different token space, so "
                    "acceptance will collapse to zero (V-S01 flags "
                    "this at preflight)", self.speculative,
                    draft_model.vocab, model.vocab)
            self.proposer = DraftModelProposer(
                self, self.speculative, draft_model, draft_params)

        self._prefill_exe = {}
        self._chunk_exe = None
        self._decode_exe = None
        self._verify_exe = None
        self._draft_exe = None
        self._page_out_exe = None
        self._page_in_exe = None
        self._compile_lock = threading.Lock()
        self.compile_count = 0
        self.decode_calls = 0
        self.prefill_calls = 0
        self.preemptions_total = 0
        self.exports_total = 0
        self.adoptions_total = 0
        # prefix-cache admission accounting (hit rate = shared/total)
        self.prefix_pages_total = 0
        self.prefix_shared_pages_total = 0
        # speculative-decode accounting
        self.spec_dispatches = 0
        self.spec_drafted_total = 0
        self.spec_accepted_total = 0
        self.spec_tokens_total = 0
        #: what the model's programs count behind their tokens
        #: (``model.counters``, e.g. the expert layers' routed pairs):
        #: program kind -> name -> running value; a name ending in
        #: ``_max`` keeps the largest reading, the others add up
        self._counter_names = tuple(getattr(model, "counters", ()))
        self.counters = {kind: dict.fromkeys(self._counter_names, 0)
                         for kind in ("prefill", "decode")}
        if self.window:
            #: counted on the host a decode step, from the live slots'
            #: lengths: the cache rows the step's queries see in the
            #: window layers (a window at most, each) and in the others
            self.counters["host"] = {"kv_rows_window": 0,
                                     "kv_rows_full": 0}
            kinds = [rows < self.max_seq
                     for rows in model.layer_rows(self.max_seq)]
            self._window_layers = sum(kinds)
            self._full_layers = len(kinds) - sum(kinds)
        self._warmed = False
        self.prof_name = "gen%d" % next(_GEN_SEQ)
        self._prof_entries = {}

    def _no_recurrent(self, mode):
        return ("%s cannot hold the recurrent state of %s: a state is "
                "not a run of K/V pages that can be shared, split or "
                "replayed (serve it with kv='contiguous')"
                % (mode, type(self.model).__name__))

    def _no_window(self, mode):
        return ("%s cannot hold the window layers of %s: a layer that "
                "keeps only its last %d positions has no page for every "
                "position, to share, verify, replay or ship (serve it "
                "with kv='contiguous' and prefill_chunk)"
                % (mode, type(self.model).__name__, self.window))

    def _count(self, kind, out, n):
        """``out``: what a program of a counting model returned, on the
        host: ``n`` tokens, then ``model.counters``.  Adds the counters
        to ``self.counters[kind]`` and returns the tokens."""
        totals = self.counters[kind]
        for name, value in zip(self._counter_names, out[n:]):
            totals[name] = max(totals[name], int(value)) \
                if name.endswith("_max") else totals[name] + int(value)
        return out[:n]

    # -- sharding ----------------------------------------------------------
    def _build_shardings(self):
        if self.mesh is None:
            return None
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = self.mesh

        def named(spec_tree):
            return jax.tree.map(
                lambda s: NamedSharding(mesh, s), spec_tree,
                is_leaf=lambda x: isinstance(x, P))

        cache_spec = self.model.paged_cache_spec() \
            if self._pool is not None else self.model.cache_spec()
        return (named(self.model.param_specs()),
                named(cache_spec),
                NamedSharding(mesh, P()))

    # -- compilation -------------------------------------------------------
    def _struct_of(self, tree):
        jax = self._jax
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)

    def _compile(self, fn, args, kind, name, flops):
        """Lower + AOT-compile ``fn`` at ``args``' shapes (cache
        donated), register the ledger entry with the model's ANALYTIC
        flops (the layer scan makes ``cost_analysis`` depth-blind),
        and flag any post-warmup compile as a steady-state recompile —
        the serve-bucket discipline."""
        jax = self._jax
        with self._compile_lock:
            jit_kwargs = {"donate_argnums": (1,)}
            if self._shardings is not None:
                p_sh, c_sh, repl = self._shardings
                extra = tuple(repl for _ in range(len(args) - 2))
                jit_kwargs["in_shardings"] = (p_sh, c_sh) + extra
                jit_kwargs["out_shardings"] = (c_sh, repl)
            span_args = {"program": name, "engine": self.prof_name}
            with trace.span("serve", "compile_gen", span_args,
                            role="server"):
                jitted = jax.jit(fn, **jit_kwargs)
                exe = jitted.lower(*self._struct_of(args)).compile()
                cost, new_args = prof.span_cost_args(
                    exe, span_args, peak_dtype=self.quantized)
                cost["flops"] = float(flops)
                new_args["flops"] = float(flops)
                span_args.update(new_args)
                if self._warmed:
                    span_args["recompile"] = True
            self.compile_count += 1
            entry = self._prof_entries.get((kind, name))
            if entry is None:
                entry = self._prof_entries[(kind, name)] = \
                    prof.ledger.entry(kind,
                                      "%s[%s]" % (self.prof_name, name))
            if self.quantized:
                # honest MFU denominator: the chip's int8 rate, not
                # the bf16 table (backends.PEAK_INT8_OPS)
                entry.peak_dtype = self.quantized
            prof.ledger.record_compile(entry, cost=cost,
                                       steady=self._warmed)
            self.debug("compiled %s (compile #%d)", name,
                       self.compile_count)
            if self._warmed:
                prof.flag_recompile(
                    "gen:%s:%s" % (self.prof_name, name), None, None,
                    logger=self,
                    detail="%s compiled after warmup() — generative "
                           "steady state must reuse the AOT programs"
                           % name)
        return exe, entry

    def _compile_aux(self, fn, args, kind, name, donate=()):
        """AOT-compile an auxiliary (non-forward) program — the page
        I/O pair — under the same ledger/recompile discipline as
        :meth:`_compile` but with CALLER-CHOSEN donation: ``page_out``
        reads the live cache and must NOT donate it (donation would
        invalidate the resident buffers), while ``page_in`` rewrites
        it and donates like every forward program."""
        jax = self._jax
        with self._compile_lock:
            span_args = {"program": name, "engine": self.prof_name}
            with trace.span("serve", "compile_gen", span_args,
                            role="server"):
                jitted = jax.jit(fn, donate_argnums=tuple(donate))
                exe = jitted.lower(*self._struct_of(args)).compile()
                cost, new_args = prof.span_cost_args(
                    exe, span_args, peak_dtype=self.quantized)
                span_args.update(new_args)
                if self._warmed:
                    span_args["recompile"] = True
            self.compile_count += 1
            entry = self._prof_entries.get((kind, name))
            if entry is None:
                entry = self._prof_entries[(kind, name)] = \
                    prof.ledger.entry(kind,
                                      "%s[%s]" % (self.prof_name, name))
            prof.ledger.record_compile(entry, cost=cost,
                                       steady=self._warmed)
            self.debug("compiled %s (compile #%d)", name,
                       self.compile_count)
            if self._warmed:
                prof.flag_recompile(
                    "gen:%s:%s" % (self.prof_name, name), None, None,
                    logger=self,
                    detail="%s compiled after warmup() — generative "
                           "steady state must reuse the AOT programs"
                           % name)
        return exe, entry

    def _page_out_executable(self):
        """The page EXPORT program: copy one pool page's K/V out of
        the live cache — fixed shape, cache NOT donated."""
        if self._page_out_exe is None:
            jnp = self._jax.numpy

            def page_out(cache, bid):
                return cache["k"][:, bid], cache["v"][:, bid]

            self._page_out_exe = self._compile_aux(
                page_out, (self._cache, jnp.int32(0)),
                "handoff", "page_out")
        return self._page_out_exe

    def _page_in_executable(self):
        """The page ADOPT program: write one shipped page's K/V into
        a freshly allocated pool page (cache donated — in-place)."""
        if self._page_in_exe is None:
            jnp = self._jax.numpy
            k = self._cache["k"]
            page = jnp.zeros((k.shape[0],) + k.shape[2:], k.dtype)

            def page_in(cache, k, v, bid):
                return {"k": cache["k"].at[:, bid].set(k),
                        "v": cache["v"].at[:, bid].set(v)}

            self._page_in_exe = self._compile_aux(
                page_in, (self._cache, page, page, jnp.int32(0)),
                "handoff", "page_in", donate=(0,))
        return self._page_in_exe

    def _prefill_executable(self, bucket):
        exe = self._prefill_exe.get(bucket)
        if exe is None:
            jnp = self._jax.numpy
            if self._pool is not None:
                args = (self._params, self._cache,
                        jnp.zeros((1, bucket), jnp.int32),
                        jnp.zeros((bucket // self.block_size,),
                                  jnp.int32),
                        jnp.int32(1))
                fn = self.model.paged_prefill
            else:
                args = (self._params, self._cache,
                        jnp.zeros((1, bucket), jnp.int32),
                        jnp.int32(0), jnp.int32(1))
                fn = self.model.prefill
            exe = self._prefill_exe[bucket] = self._compile(
                fn, args, "prefill", "p%d" % bucket,
                self.model.prefill_flops(bucket))
        return exe

    def _chunk_executable(self):
        """The ONE fixed-shape chunked-prefill program (per kv mode):
        any prompt length feeds through it chunk by chunk, so chunked
        admission adds exactly one compile to warmup regardless of the
        prompt distribution."""
        if self._chunk_exe is None:
            jnp = self._jax.numpy
            chunk = self.prefill_chunk
            if self._pool is not None:
                args = (self._params, self._cache,
                        jnp.zeros((1, chunk), jnp.int32),
                        jnp.zeros((chunk // self.block_size,),
                                  jnp.int32),
                        jnp.zeros((self._pool.max_blocks,), jnp.int32),
                        jnp.int32(0), jnp.int32(1))
                fn = self.model.paged_prefill_chunk
            else:
                args = (self._params, self._cache,
                        jnp.zeros((1, chunk), jnp.int32),
                        jnp.int32(0), jnp.int32(0), jnp.int32(1))
                fn = self.model.prefill_chunk
            self._chunk_exe = self._compile(
                fn, args, "prefill", "chunk%d" % chunk,
                self.model.prefill_chunk_flops(chunk, self.max_seq))
        return self._chunk_exe

    def _decode_executable(self):
        if self._decode_exe is None:
            jnp = self._jax.numpy
            slots = self.max_slots
            if self._pool is not None:
                args = (self._params, self._cache,
                        jnp.zeros((slots, self._pool.max_blocks),
                                  jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), bool))
                fn = self.model.paged_decode
            else:
                args = (self._params, self._cache,
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), bool))
                fn = self.model.decode
            self._decode_exe = self._compile(
                fn, args, "decode", "decode",
                self.model.decode_flops(slots, self.max_seq))
        return self._decode_exe

    def _verify_executable(self):
        """The ONE fixed-shape speculative-verify program: every
        slot's pending token + up to ``draft_k`` drafts scored in one
        dispatch (per-slot real draft counts ride in as data, so
        partial/empty drafts never change the shape)."""
        if self._verify_exe is None:
            jnp = self._jax.numpy
            slots = self.max_slots
            kp1 = self.draft_k + 1
            if self._pool is not None:
                args = (self._params, self._cache,
                        jnp.zeros((slots, self._pool.max_blocks),
                                  jnp.int32),
                        jnp.zeros((slots, kp1), jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), bool))
                fn = self.model.paged_verify
            else:
                args = (self._params, self._cache,
                        jnp.zeros((slots, kp1), jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), jnp.int32),
                        jnp.zeros((slots,), bool))
                fn = self.model.verify
            self._verify_exe = self._compile(
                fn, args, "decode", "verify%d" % self.draft_k,
                self.model.verify_flops(slots, self.draft_k,
                                        self.max_seq))
        return self._verify_exe

    def _draft_executable(self):
        """The ONE fixed-shape draft program (model-based proposer
        only): a cache-less windowed forward of the registered draft
        model returning its greedy next token — called ``draft_k``
        times per slot per drafting round."""
        if self._draft_exe is None:
            jnp = self._jax.numpy
            proposer = self.proposer
            model = proposer.model
            window = proposer.window

            def draft_next(params, tokens, length):
                h = params["embed"][tokens] + params["pos"][:window]
                h = model._forward_cacheless(params, h)
                return model._greedy_at(params, h, length - 1)

            self._draft_exe = self._compile_aux(
                draft_next,
                (proposer.params,
                 jnp.zeros((1, window), jnp.int32), jnp.int32(1)),
                "draft", "draft_w%d" % window)
        return self._draft_exe

    def quantize_int8(self, calibration_tokens=None, tol=None):
        """Quantize the served params in place (per-output-channel
        symmetric int8, :func:`veles_tpu.quant.quantize_gen_params`)
        — the ``deploy_generative(..., quantize="int8")`` hook.  Must
        run BEFORE :meth:`warmup` so every program compiles against
        the quantized tree exactly once (the recompile sentinel's
        zero-steady-state contract).  ``calibration_tokens`` arms the
        drift gate: relative logit drift beyond ``tol`` (default
        :data:`veles_tpu.quant.DRIFT_TOL`) raises a typed
        :class:`~veles_tpu.quant.QuantizationError` naming the worst
        block weight.  Returns self (chainable)."""
        from veles_tpu import quant
        if self._warmed or self.compile_count:
            raise RuntimeError(
                "quantize_int8 must run before warmup()/any compile — "
                "a post-warmup dtype flip would recompile every "
                "program in steady state")
        if self.mesh is not None:
            raise ValueError(
                "int8-quantized params cannot shard over a model-axis "
                "mesh yet — serve the quantized deploy replicated")
        if self.quantized:
            return self
        import jax
        host = jax.tree.map(numpy.asarray, self._params)
        qparams = quant.quantize_gen_params(
            self.model, host, calibration_tokens=calibration_tokens,
            tol=quant.DRIFT_TOL if tol is None else tol)
        self._params = jax.device_put(qparams)
        self.quantized = "int8"
        # re-price the ledger hold from the new (int8) leaves
        from veles_tpu.memory import Watcher
        if (getattr(self, "_params_tracked", False)
                and getattr(self, "_ledger_gen", 0)
                == Watcher.generation):
            Watcher.untrack(self.params_nbytes, "params")
        self.params_nbytes = quant.tree_nbytes(self._params)
        Watcher.track(self.params_nbytes, "params")
        self._params_tracked = True
        self._ledger_gen = Watcher.generation
        self.info("quantized params to int8 (%d bytes resident)",
                  self.params_nbytes)
        return self

    def warmup(self):
        """AOT-compile the decode step and every admission program —
        the per-bucket prefills, plus the one chunk program when
        chunked prefill is on; afterwards ANY compile is a flagged
        steady-state recompile.  Returns self (chainable)."""
        self._decode_executable()
        if self.prefill_chunk is not None:
            self._chunk_executable()
        else:
            for bucket in self.prefill_buckets:
                self._prefill_executable(bucket)
        if self.proposer is not None:
            self._verify_executable()
            if isinstance(self.proposer, DraftModelProposer):
                self._draft_executable()
        self._warmed = True
        return self

    def warm_handoff(self):
        """AOT-compile the page export/adopt pair — the fleet handoff
        programs.  Call alongside :meth:`warmup` (before serving) on
        every role that ships or receives pages, or the first handoff
        trips the steady-state recompile sentinel.  Paged mode only;
        the handoff does not shard.  Returns self (chainable)."""
        if self._pool is None:
            raise ValueError(
                "page handoff requires kv='paged' — the contiguous "
                "engine has no pages to ship")
        if self.mesh is not None:
            raise ValueError(
                "page handoff does not cross a model-axis mesh yet — "
                "run fleet roles replicated")
        self._page_out_executable()
        self._page_in_executable()
        return self

    # -- slot accounting ---------------------------------------------------
    def bucket_for(self, n):
        for b in self.prefill_buckets:
            if b >= n:
                return b
        raise ValueError(
            "prompt of %d tokens exceeds the largest prefill bucket "
            "%d" % (n, self.prefill_buckets[-1]))

    @property
    def free_slots(self):
        return len(self._free)

    def active_slots(self):
        return int(self.slot_active.sum())

    def prefilling_slots(self):
        return len(self._chunking)

    def occupancy(self):
        return (self.active_slots() + len(self._chunking)) \
            / float(self.max_slots)

    def _validate_prompt_len(self, n):
        """The TWO guards every admission path shares (scheduler door
        check, whole-bucket prefill, chunked admit) — single-sourced
        so they can never diverge."""
        n = int(n)
        if n < 1:
            raise ValueError("empty prompt")
        if n >= self.max_seq:
            raise ValueError(
                "prompt of %d tokens leaves no room to generate "
                "(max_seq %d)" % (n, self.max_seq))
        return n

    def check_prompt(self, n):
        """Raise ``ValueError`` when a prompt of ``n`` tokens can
        never be admitted — the scheduler's door check, shared by
        every kv/prefill mode."""
        n = self._validate_prompt_len(n)
        if self._pool is not None and \
                self._pool.blocks_for(n) > self._pool.blocks_total:
            raise ValueError(
                "prompt of %d tokens needs %d pages but the pool has "
                "%d" % (n, self._pool.blocks_for(n),
                        self._pool.blocks_total))
        if self.prefill_chunk is None:
            self.bucket_for(n)          # raises when over the buckets

    def _appends_needed(self):
        """Pages the CURRENT residents' next decode step will claim
        (slots whose write position sits on a page boundary)."""
        if self._pool is None:
            return 0
        return sum(
            1 for slot in range(self.max_slots)
            if self.slot_active[slot]
            and self.slot_len[slot] < self.max_seq
            and self._pool.needs_append(slot, int(self.slot_len[slot])))

    def _prefix_tag(self, n):
        """Program-identity tag for prefix-cache entries: pages are
        only shared between prefills the SAME compiled program wrote,
        because XLA's reduction order is shape-dependent and a
        cross-program page could differ in the last ulp — which a
        co-resident's greedy argmax could amplify into a divergent
        stream.  Chunked engines have one chunk program (full
        sharing); whole-bucket engines tag by bucket."""
        if self.prefill_chunk is not None:
            return "chunk%d" % self.prefill_chunk
        return "b%d" % self.bucket_for(n)

    def _shared_usable(self, bids):
        """Matched prefix pages an admission may actually adopt:
        chunked prefill skips WHOLE chunks, so the adopted span
        rounds down to a chunk boundary (whole-bucket mode adopts
        every matched page — the prefix compute replays but its page
        writes are trash-routed)."""
        if self.prefill_chunk is not None:
            per = self.prefill_chunk // self.block_size
            return bids[:len(bids) // per * per]
        return bids

    def can_admit(self, n, tokens=None):
        """True when a prompt (or preempted prefix) of ``n`` tokens is
        admissible RIGHT NOW: a free slot, and — in paged mode — the
        pool holding its pages ON TOP of the pages the residents'
        next decode step claims.  Pricing admission under that
        reservation keeps a tight pool from admit-preempt thrashing:
        without it the head request's pages are immediately taken
        back by the residents' appends, the youngest (= that head)
        is preempted, re-admitted next step, and the cycle re-runs
        its whole prefill once per resident token.

        With the prefix cache on, pass ``tokens`` to price only the
        UNSHARED suffix (cache hits cost no fresh pages) and to count
        cache-only pages the LRU reclaimer would evict on demand as
        headroom — a pool full of idle cached prefixes must not
        refuse admissions it can serve."""
        if not self._free:
            return False
        if self._pool is not None:
            n = int(n)
            need = self._pool.blocks_for(n)
            reclaimable = 0
            if self._prefix is not None:
                reclaimable = self._prefix.reclaimable()
                if tokens is not None:
                    bids = self._shared_usable(
                        self._prefix.match(tokens,
                                           self._prefix_tag(n)))
                    need -= len(bids)
                    # matched pages are adopted, not evicted — they
                    # stop being reclaimable the moment we admit
                    reclaimable -= sum(
                        1 for bid in bids
                        if self._pool.refcount(bid) == 1)
                    reclaimable = max(0, reclaimable)
            if n % self.block_size == 0:
                # a prefix filling its pages exactly appends a fresh
                # page on its FIRST decode step — price it now, or
                # that admission is the next preemption victim
                need += 1
            return (need + self._appends_needed()
                    <= self._pool.blocks_free + reclaimable)
        return True

    def release_slot(self, slot):
        if slot in self._chunking:
            # a chunked prefill abandoned mid-flight (scheduler stop
            # or preemption): drop the chunk state with the pages
            del self._chunking[slot]
        elif not self.slot_active[slot]:
            raise ValueError("slot %d is not active" % slot)
        self.slot_active[slot] = False
        self.slot_len[slot] = 0
        self.slot_trace[slot] = None
        if self._pool is not None:
            self._pool.release(slot)
        # keep admission deterministic: the free list stays sorted so
        # the same request mix always lands in the same slots
        import bisect
        bisect.insort(self._free, slot)

    def preempt(self, slot):
        """Pool-exhaustion eviction: free the slot AND its pages
        without finishing the request — the scheduler requeues the
        sequence's tokens-so-far and greedy decode reproduces the
        stream, so preemption is lossless."""
        if self.recurrent:
            raise ValueError(self._no_recurrent("preempt's replay"))
        if self.window:
            raise ValueError(self._no_window("preempt's replay"))
        if not self.slot_active[slot] and slot not in self._chunking:
            raise ValueError("slot %d is not occupied" % slot)
        self.release_slot(slot)
        self.preemptions_total += 1

    def decode_block_deficit(self):
        """How many pages the NEXT decode step needs beyond the free
        list — the scheduler preempts until this reaches zero.  Always
        0 in contiguous mode (capacity was reserved at admission)."""
        if self._pool is None:
            return 0
        return max(0, self._appends_needed() - self._pool.blocks_free)

    # -- serving -----------------------------------------------------------
    def prefill(self, tokens):
        """Admit one prompt into a free slot with ONE whole-bucket
        dispatch: returns ``(slot, first_token)``.  Raises
        ``RuntimeError`` when no slot is free (the scheduler checks
        ``free_slots`` first), :class:`~veles_tpu.gen.paged
        .PoolExhausted` when the pool cannot hold the prompt's pages,
        and ``ValueError`` on an unservable prompt."""
        jnp = self._jax.numpy
        tokens = numpy.ascontiguousarray(tokens,
                                         numpy.int32).ravel()
        n = self._validate_prompt_len(len(tokens))
        bucket = self.bucket_for(n)
        if not self._free:
            raise RuntimeError("no free slot (all %d busy)"
                               % self.max_slots)
        slot = self._free.pop(0)
        shared, tag = [], None
        if self._pool is not None:
            if self._prefix is not None:
                tag = self._prefix_tag(n)
                shared = self._shared_usable(
                    self._prefix.match(tokens, tag))
            try:
                ids = self._pool.admit(slot, n, shared=shared)
            except Exception:
                import bisect
                bisect.insort(self._free, slot)
                raise
            block_ids = numpy.zeros(bucket // self.block_size,
                                    numpy.int32)
            block_ids[:len(ids)] = ids
            if shared:
                # NEVER rewrite a shared page: its resident K/V came
                # from the same program on the same prefix, but THIS
                # dispatch's copy would overwrite what a co-resident
                # slot is reading mid-flight — route those page
                # writes to the trash block instead (the in-dispatch
                # attention reads the chunk itself, not the cache, so
                # the returned token is unchanged)
                block_ids[:len(shared)] = self._pool.TRASH
            if self._prefix is not None:
                self.prefix_pages_total += len(ids)
                self.prefix_shared_pages_total += len(shared)
        padded = numpy.zeros(bucket, numpy.int32)
        padded[:n] = tokens
        exe, entry = self._prefill_executable(bucket)
        self.prefill_calls += 1
        self.slot_trace[slot] = obs_context.current_trace_id()
        with trace.span("gen", "prefill",
                        obs_context.tag(
                            {"bucket": bucket, "slot": slot, "len": n,
                             "engine": self.prof_name}), role="server"):
            tic = time.perf_counter_ns()
            with trace.span("gen", "prefill_dispatch"):
                if self._pool is not None:
                    self._cache, tok = exe(
                        self._params, self._cache,
                        jnp.asarray(padded[None]),
                        jnp.asarray(block_ids), jnp.int32(n))
                else:
                    self._cache, tok = exe(
                        self._params, self._cache,
                        jnp.asarray(padded[None]),
                        jnp.int32(slot), jnp.int32(n))
            with trace.span("gen", "prefill_fetch"):
                if self._counter_names:
                    tok = self._count("prefill", numpy.asarray(tok), 1)[0]
                tok = int(tok)
            prof.ledger.record_dispatch(
                entry, time.perf_counter_ns() - tic, items=n)
        self.slot_len[slot] = n
        self.slot_token[slot] = tok
        self.slot_active[slot] = True
        if self._prefix is not None:
            # register every FULL prompt page now that its K/V is
            # resident (full pages are immutable: decode writes start
            # at position n, always a later page)
            m = n // self.block_size
            if m:
                self._prefix.insert(tokens[:m * self.block_size],
                                    self._pool.owned(slot)[:m], tag)
        return slot, tok

    def admit(self, tokens):
        """The mode-agnostic admission door: whole-prompt engines
        prefill in one dispatch and return ``(slot, first_token)``;
        chunked engines claim the slot (and, paged, ALL the prompt's
        pages — deterministic up-front pricing) and return ``(slot,
        None)`` — the scheduler then pumps :meth:`prefill_step` once
        per decode cadence until the first token arrives."""
        if self.prefill_chunk is None:
            return self.prefill(tokens)
        tokens = numpy.ascontiguousarray(tokens,
                                         numpy.int32).ravel()
        n = self._validate_prompt_len(len(tokens))
        if not self._free:
            raise RuntimeError("no free slot (all %d busy)"
                               % self.max_slots)
        slot = self._free.pop(0)
        start0, shared, tag = 0, [], None
        if self._pool is not None:
            if self._prefix is not None:
                tag = self._prefix_tag(n)
                shared = self._shared_usable(
                    self._prefix.match(tokens, tag))
                # chunked prefill SKIPS the shared prefix outright —
                # chunks begin at the first unshared page (a chunk
                # boundary, keeping every program shape fixed), so a
                # hit saves the prefix's compute, not just its HBM
                start0 = len(shared) * self.block_size
            try:
                self._pool.admit(slot, n, shared=shared)
            except Exception:
                import bisect
                bisect.insort(self._free, slot)
                raise
            if self._prefix is not None:
                self.prefix_pages_total += self._pool.blocks_for(n)
                self.prefix_shared_pages_total += len(shared)
        chunk = self.prefill_chunk
        padded = numpy.zeros(start0 + _round_up(n - start0, chunk),
                             numpy.int32)
        padded[:n] = tokens
        self._chunking[slot] = {"tokens": padded, "n": n,
                                "done": start0, "tag": tag}
        self.slot_trace[slot] = obs_context.current_trace_id()
        return slot, None

    def prefill_step(self, slot):
        """Feed ONE chunk of the slot's pending prompt (fixed-shape
        program, decode-step cadence).  Returns the first generated
        token when the prompt completes, else ``None``."""
        jnp = self._jax.numpy
        state = self._chunking[slot]
        chunk = self.prefill_chunk
        start = state["done"]
        chunk_len = min(chunk, state["n"] - start)
        tokens = state["tokens"][start:start + chunk]
        exe, entry = self._chunk_executable()
        self.prefill_calls += 1
        with trace.span("gen", "prefill_chunk",
                        obs_context.tag(
                            {"slot": slot, "start": start,
                             "len": chunk_len,
                             "engine": self.prof_name}),
                        role="server"):
            tic = time.perf_counter_ns()
            if self._pool is not None:
                first = start // self.block_size
                chunk_ids = self._pool.tables[
                    slot, first:first + chunk // self.block_size]
                self._cache, tok = exe(
                    self._params, self._cache,
                    jnp.asarray(tokens[None]),
                    jnp.asarray(numpy.ascontiguousarray(chunk_ids)),
                    jnp.asarray(self._pool.tables[slot]),
                    jnp.int32(start), jnp.int32(chunk_len))
            else:
                self._cache, tok = exe(
                    self._params, self._cache,
                    jnp.asarray(tokens[None]), jnp.int32(slot),
                    jnp.int32(start), jnp.int32(chunk_len))
            if self._counter_names:
                # the counters ride behind every chunk's token, and the
                # fetch is a wait for the chunk: a prompt's LAST chunk
                # has to wait (its token feeds the next decode step), so
                # every chunk does, and a live slot's token waits the
                # same time behind any of them (left on the device until
                # the last chunk, the others' steps came out 2 ms
                # shorter: two levels in the tail of the gaps)
                with trace.span("gen", "prefill_fetch"):
                    tok = self._count("prefill", numpy.asarray(tok), 1)[0]
            prof.ledger.record_dispatch(
                entry, time.perf_counter_ns() - tic, items=chunk_len)
        state["done"] = start + chunk_len
        if state["done"] < state["n"]:
            return None
        del self._chunking[slot]
        tok = int(tok)
        n = state["n"]
        self.slot_len[slot] = n
        self.slot_token[slot] = tok
        self.slot_active[slot] = True
        if self._prefix is not None:
            m = n // self.block_size
            if m:
                self._prefix.insert(
                    state["tokens"][:m * self.block_size],
                    self._pool.owned(slot)[:m],
                    state.get("tag") or self._prefix_tag(n))
        return tok

    def decode_step(self):
        """ONE fixed-shape decode iteration over every slot.  Returns
        ``(tokens, active)`` host arrays — ``tokens[slot]`` is only
        meaningful where ``active[slot]`` — or ``None`` when nothing
        can decode (no device call).  Slots parked at ``max_seq`` are
        EXCLUDED from the dispatch rather than raising: the scheduler
        routes them through the shared ``finish_reason`` predicate and
        evicts, in both kv modes."""
        if not self.slot_active.any():
            return None
        jnp = self._jax.numpy
        active = self.slot_active & (self.slot_len < self.max_seq)
        if not active.any():
            return None
        n_active = int(active.sum())
        decode_args = {"active": n_active, "engine": self.prof_name}
        if trace.enabled():
            # which requests this shared dispatch decoded — the decode
            # half of every co-resident's waterfall, one span (plain
            # loop: max_slots is small and this runs per decode step)
            traces = sorted({t for s, t in enumerate(self.slot_trace)
                             if t is not None and active[s]})
            if traces:
                decode_args["traces"] = traces
        with trace.span("gen", "decode", decode_args, role="server"):
            with trace.span("gen", "decode_prepare"):
                if self._pool is not None:
                    # fused block append, host half: make sure every
                    # decoding row owns the page its write position
                    # lands in (raises PoolExhausted — the scheduler
                    # preempts first via decode_block_deficit, so this
                    # only fires on direct use)
                    for slot in numpy.nonzero(active)[0]:
                        self._pool.append(int(slot),
                                          int(self.slot_len[slot]))
                positions = numpy.where(active, self.slot_len, 0
                                        ).astype(numpy.int32)
                toks = numpy.where(active, self.slot_token, 0
                                   ).astype(numpy.int32)
                exe, entry = self._decode_executable()
            self.decode_calls += 1
            tic = time.perf_counter_ns()
            with trace.span("gen", "decode_dispatch"):
                if self._pool is not None:
                    self._cache, out = exe(
                        self._params, self._cache,
                        jnp.asarray(self._pool.tables),
                        jnp.asarray(toks), jnp.asarray(positions),
                        jnp.asarray(active))
                else:
                    self._cache, out = exe(
                        self._params, self._cache, jnp.asarray(toks),
                        jnp.asarray(positions), jnp.asarray(active))
            with trace.span("gen", "decode_fetch"):
                out = numpy.asarray(out)
                if self._counter_names:
                    out = self._count("decode", out, self.max_slots)
            prof.ledger.record_dispatch(
                entry, time.perf_counter_ns() - tic, items=n_active)
        self.slot_len[active] += 1
        self.slot_token[active] = out[active]
        if self.window:
            seen = self.slot_len[active].astype(numpy.int64)
            host = self.counters["host"]
            host["kv_rows_window"] += self._window_layers * int(
                numpy.minimum(seen, self.window).sum())
            host["kv_rows_full"] += self._full_layers * int(seen.sum())
        return out, active

    # -- speculative decode (draft K, verify in one dispatch) --------------
    def propose(self, stream):
        """Draft up to ``draft_k`` continuation tokens for one slot's
        full token stream (prompt + generated, last element = the
        slot's pending token) via the configured proposer.  Empty
        list = that slot degrades to plain decode this round."""
        if self.proposer is None:
            return []
        return list(self.proposer.propose(
            stream, self.draft_k))[:self.draft_k]

    def spec_decode_step(self, proposals):
        """ONE draft-then-verify iteration over every decoding slot:
        ``proposals`` maps slot -> proposed draft tokens (each at
        most ``draft_k``; missing or empty entries degrade that slot
        to plain decode).  All slots verify in the ONE fixed-shape
        AOT program; greedy acceptance emits, per slot, the drafted
        prefix that matched the target's own greedy choices plus the
        target's first divergent token — ``a + 1`` tokens that are
        BITWISE the plain-decode stream, just earned in one dispatch.
        Returns ``{slot: [tokens...]}`` (None when nothing decodes).
        Draft spans shrink per-slot against ``max_seq`` and the
        pool's headroom (after the residents' plain-decode appends
        are reserved), so speculation never triggers a preemption
        plain decode would not have."""
        if self.proposer is None:
            raise RuntimeError("speculative decode is off "
                               "(root.common.gen.speculative)")
        if not self.slot_active.any():
            return None
        active = self.slot_active & (self.slot_len < self.max_seq)
        if not active.any():
            return None
        jnp = self._jax.numpy
        kp1 = self.draft_k + 1
        tokens = numpy.zeros((self.max_slots, kp1), numpy.int32)
        drafts = numpy.zeros(self.max_slots, numpy.int32)
        tokens[:, 0] = numpy.where(active, self.slot_token, 0)
        order = [int(s) for s in numpy.nonzero(active)[0]]
        budget = None
        if self._pool is not None:
            # reserve what PLAIN decode would claim for every slot
            # first (the scheduler's preemption loop priced exactly
            # that); drafts only spend what remains
            base = 0
            for slot in order:
                base += max(0, int(self.slot_len[slot])
                            // self.block_size + 1
                            - len(self._pool.owned(slot)))
            budget = self._pool.blocks_free - base
        for slot in order:
            p = int(self.slot_len[slot])
            prop = list(proposals.get(slot, ()))[:self.draft_k]
            # the span p..p+D writes D+1 positions; keep them all
            # inside the slot's max_seq road
            cap = self.max_seq - p - 1
            if len(prop) > cap:
                prop = prop[:max(0, cap)]
            if self._pool is not None:
                while True:
                    extra = ((p + len(prop)) // self.block_size
                             - p // self.block_size)
                    if extra <= budget or not prop:
                        break
                    prop.pop()
                budget -= extra
            drafts[slot] = len(prop)
            tokens[slot, 1:1 + len(prop)] = prop
            self.spec_drafted_total += len(prop)
        if self._pool is not None:
            # host half of the fused append, draft-span sized: every
            # page that positions p..p+D land in must exist before
            # the dispatch scatters into it
            for slot in order:
                last = int(self.slot_len[slot]) + int(drafts[slot])
                while len(self._pool.owned(slot)) \
                        * self.block_size <= last:
                    self._pool.append(
                        slot, len(self._pool.owned(slot))
                        * self.block_size)
        positions = numpy.where(active, self.slot_len, 0
                                ).astype(numpy.int32)
        exe, entry = self._verify_executable()
        self.decode_calls += 1
        self.spec_dispatches += 1
        span_args = {"active": len(order), "engine": self.prof_name,
                     "draft_k": self.draft_k}
        with trace.span("gen", "spec_verify", span_args,
                        role="server"):
            tic = time.perf_counter_ns()
            if self._pool is not None:
                self._cache, out = exe(
                    self._params, self._cache,
                    jnp.asarray(self._pool.tables),
                    jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(drafts), jnp.asarray(active))
            else:
                self._cache, out = exe(
                    self._params, self._cache,
                    jnp.asarray(tokens), jnp.asarray(positions),
                    jnp.asarray(drafts), jnp.asarray(active))
            out = numpy.asarray(out)
            prof.ledger.record_dispatch(
                entry, time.perf_counter_ns() - tic,
                items=len(order))
        results = {}
        for slot in order:
            d = int(drafts[slot])
            a = 0
            while a < d and tokens[slot, a + 1] == out[slot, a]:
                a += 1
            emitted = [int(t) for t in out[slot, :a + 1]]
            self.slot_len[slot] += a + 1
            self.slot_token[slot] = emitted[-1]
            if self._pool is not None:
                # the rejected tail's pages go back (stale K/V beyond
                # the new length is masked by every read; the PAGES
                # must not leak)
                self._pool.truncate(slot, int(self.slot_len[slot]))
            self.spec_accepted_total += a
            self.spec_tokens_total += a + 1
            results[slot] = emitted
        return results

    # -- fleet page handoff ------------------------------------------------
    def export_slot(self, slot):
        """Package an active slot's KV pages for the fleet handoff:
        host copies of every owned page (position order, straight off
        the sorted-free-list allocation) plus the slot's decode state.
        The payload is engine-agnostic — any paged engine with the
        same model config and ``block_size`` can adopt it and the
        token stream stays bitwise-identical, because decode gathers
        K/V through the block table and masks past ``n``.  The slot
        itself is NOT released (the caller decides)."""
        if self.recurrent:
            raise ValueError(self._no_recurrent("export_slot"))
        if self.window:
            raise ValueError(self._no_window("export_slot"))
        if self._pool is None:
            raise ValueError("page export requires kv='paged'")
        if not self.slot_active[slot]:
            raise ValueError("slot %d is not active" % slot)
        jnp = self._jax.numpy
        exe, entry = self._page_out_executable()
        ids = self._pool.owned(slot)
        ks, vs = [], []
        with trace.span("gen", "page_out",
                        obs_context.tag(
                            {"slot": slot, "pages": len(ids),
                             "engine": self.prof_name}), role="server"):
            tic = time.perf_counter_ns()
            for bid in ids:
                k, v = exe(self._cache, jnp.int32(bid))
                ks.append(numpy.asarray(k))
                vs.append(numpy.asarray(v))
            prof.ledger.record_dispatch(
                entry, time.perf_counter_ns() - tic, items=len(ids))
        self.exports_total += 1
        return {"n": int(self.slot_len[slot]),
                "token": int(self.slot_token[slot]),
                "block_size": self.block_size,
                "k": numpy.stack(ks), "v": numpy.stack(vs)}

    def adopt_sequence(self, payload):
        """Admit a shipped sequence WITHOUT recomputing its prefill:
        allocate pages off the sorted free list (deterministic, same
        as any admission), write each shipped page in with the
        donated fixed-shape ``page_in`` program, and install the slot
        state so the next :meth:`decode_step` continues the stream.
        Callers gate on :meth:`can_admit` with the payload's ``n`` —
        the pricing is identical to a fresh admission.  Returns
        ``(slot, first_token)`` like :meth:`prefill`."""
        if self.recurrent:
            raise ValueError(self._no_recurrent("adopt_sequence"))
        if self.window:
            raise ValueError(self._no_window("adopt_sequence"))
        if self._pool is None:
            raise ValueError("page adoption requires kv='paged'")
        n = self._validate_prompt_len(int(payload["n"]))
        if int(payload["block_size"]) != self.block_size:
            raise ValueError(
                "shipped pages use block_size %d, this engine uses "
                "%d — fleet roles must agree"
                % (int(payload["block_size"]), self.block_size))
        k_pages = numpy.asarray(payload["k"])
        v_pages = numpy.asarray(payload["v"])
        need = self._pool.blocks_for(n)
        if len(k_pages) != need or len(v_pages) != need:
            raise ValueError(
                "payload holds %d/%d pages but %d tokens need %d"
                % (len(k_pages), len(v_pages), n, need))
        if not self._free:
            raise RuntimeError("no free slot (all %d busy)"
                               % self.max_slots)
        jnp = self._jax.numpy
        exe, entry = self._page_in_executable()
        slot = self._free.pop(0)
        # copy-on-adopt: pages the prefix cache already holds for this
        # token stream are adopted by REFERENCE — only the unshared
        # tail ships through page_in
        shared, tag, ptokens = [], None, payload.get("tokens")
        prompt_n = int(payload.get("prompt_n", 0))
        if self._prefix is not None and ptokens is not None \
                and prompt_n:
            ptokens = numpy.ascontiguousarray(
                ptokens, numpy.int32).ravel()
            tag = self._prefix_tag(prompt_n)
            shared = self._shared_usable(
                self._prefix.match(ptokens[:prompt_n], tag))
        try:
            ids = self._pool.admit(slot, n, shared=shared)
        except Exception:
            import bisect
            bisect.insort(self._free, slot)
            raise
        self.prefix_pages_total += len(ids)
        self.prefix_shared_pages_total += len(shared)
        with trace.span("gen", "page_in",
                        obs_context.tag(
                            {"slot": slot, "pages": len(ids), "len": n,
                             "engine": self.prof_name}), role="server"):
            tic = time.perf_counter_ns()
            for i, bid in enumerate(ids):
                if i < len(shared):
                    continue
                self._cache = exe(self._cache,
                                  jnp.asarray(k_pages[i]),
                                  jnp.asarray(v_pages[i]),
                                  jnp.int32(bid))
            prof.ledger.record_dispatch(
                entry, time.perf_counter_ns() - tic,
                items=len(ids) - len(shared))
        self.slot_len[slot] = n
        self.slot_token[slot] = int(payload["token"])
        self.slot_active[slot] = True
        self.slot_trace[slot] = obs_context.current_trace_id()
        self.adoptions_total += 1
        # register only the PROMPT's full pages: decode-written KV
        # came from a different program than prefill and must never
        # become shareable prefix
        if self._prefix is not None and ptokens is not None \
                and prompt_n:
            m = prompt_n // self.block_size
            if m:
                self._prefix.insert(ptokens[:m * self.block_size],
                                    ids[:m], tag)
        return slot, int(payload["token"])

    # -- lifecycle / introspection -----------------------------------------
    @property
    def blocks_total(self):
        return self._pool.blocks_total if self._pool else 0

    @property
    def blocks_free(self):
        return self._pool.blocks_free if self._pool else 0

    def hbm_per_request_bytes(self):
        """HBM actually held per in-flight sequence — the capacity
        metric the long-tail bench and /metrics report: the KV share
        (contiguous mode reserves a full ``max_seq`` slice per slot
        at admission, and with it the slot's recurrent state where the
        model keeps one; paged mode pays only for the pages in use)
        PLUS the shared params footprint amortized over the occupants —
        so an int8 deploy's 4× params shrink is visible to the PR 12
        SLO samplers, not just to ``describe()``."""
        occupants = self.active_slots() + len(self._chunking)
        if not occupants:
            return 0
        if self._pool is not None:
            per_block = self.kv_cache_bytes // self.num_blocks
            blocks = self._pool.blocks_used
            if self._prefix is not None:
                # pages ONLY the cache holds are speculative capacity,
                # not per-request cost (a shared page is counted once
                # by blocks_used already)
                blocks -= self._prefix.cache_only_pages()
            kv = blocks * per_block // occupants
        else:
            kv = self.kv_cache_bytes // self.max_slots
        return kv + self.params_nbytes // occupants

    def prefix_hit_rate(self):
        """Fraction of admitted pages served from the prefix cache
        instead of prefill compute (0.0 with the cache off)."""
        if not self.prefix_pages_total:
            return 0.0
        return self.prefix_shared_pages_total \
            / float(self.prefix_pages_total)

    def spec_accept_rate(self):
        """Fraction of drafted tokens the verify dispatch accepted."""
        if not self.spec_drafted_total:
            return 0.0
        return self.spec_accepted_total \
            / float(self.spec_drafted_total)

    def spec_tokens_per_dispatch(self):
        """Tokens emitted per speculative verify dispatch — 1.0 is
        plain-decode parity, anything above is the speedup lever."""
        if not self.spec_dispatches:
            return 0.0
        return self.spec_tokens_total / float(self.spec_dispatches)

    def describe(self):
        info = {
            "model": type(self.model).__name__,
            "max_slots": self.max_slots,
            "max_seq": self.max_seq,
            "prefill_buckets": list(self.prefill_buckets),
            "kv_cache_bytes": self.kv_cache_bytes,
            "kv_bytes_per_slot": None if self._pool is not None else (
                self.kv_cache_bytes - self.state_cache_bytes)
            // self.max_slots,
            "state_bytes_per_slot":
                self.state_cache_bytes // self.max_slots,
            "kv": self.kv_mode,
            "quantize": self.quantized,
            "params_bytes": self.params_nbytes,
            "prefill_chunk": self.prefill_chunk,
            "sharded": self.mesh is not None,
            "compile_count": self.compile_count,
            "active_slots": self.active_slots(),
            "prefilling_slots": len(self._chunking),
            "decode_calls": self.decode_calls,
            "prefill_calls": self.prefill_calls,
            "preemptions_total": self.preemptions_total,
            "exports_total": self.exports_total,
            "adoptions_total": self.adoptions_total,
            "hbm_per_request_bytes": self.hbm_per_request_bytes(),
            "prefix_cache": "on" if self.prefix_cache else "off",
            "speculative": self.speculative or "off",
        }
        if self.speculative:
            info["draft_k"] = self.draft_k
            info["spec_dispatches"] = self.spec_dispatches
            info["spec_drafted_total"] = self.spec_drafted_total
            info["spec_accepted_total"] = self.spec_accepted_total
            info["spec_accept_rate"] = round(
                self.spec_accept_rate(), 4)
            info["spec_tokens_per_dispatch"] = round(
                self.spec_tokens_per_dispatch(), 4)
        if self._prefix is not None:
            info["prefix_hit_rate"] = round(self.prefix_hit_rate(), 4)
            info.update(self._prefix.describe())
        if self._pool is not None:
            info.update(self._pool.describe())
        return info

    def close(self):
        """Release the KV cache (and its ledger hold).  Idempotent."""
        from veles_tpu.memory import Watcher
        # releases are generation-guarded like Vector's: a
        # Watcher.reset() since the holds were taken already wiped
        # them, and re-releasing would drive the ledger negative
        stale = getattr(self, "_ledger_gen", 0) != Watcher.generation
        if getattr(self, "_kv_tracked", False):
            if not stale:
                Watcher.untrack(self.kv_cache_bytes, "kv", owner=self)
            self._kv_tracked = False
        if getattr(self, "_params_tracked", False):
            if not stale:
                Watcher.untrack(self.params_nbytes, "params")
            self._params_tracked = False
        self._cache = None
        self._prefill_exe = {}
        self._chunk_exe = None
        self._decode_exe = None
        self._page_out_exe = None
        self._page_in_exe = None
        self._verify_exe = None
        self._draft_exe = None
