"""WindowMoEGenModel: sliding-window and full attention layers in one
stack, a parallel block and a chip's share of a gated mixture of
experts, behind the protocol :class:`~veles_tpu.gen.engine
.GenerativeEngine` already calls (:mod:`veles_tpu.gen.model`).

Parameters are :mod:`veles_tpu.samples.window_moe_lm`'s: a list of
per-layer dicts, the kind of each a letter of ``cfg["pattern"]``.  Every
layer is ``x <- x + Attn(u) + FFN(u)`` with ONE ``u = LayerNorm(x)``
(no bias); grouped-query attention, ``heads / kv_heads`` query heads a
KV head:

- ``W``: rotary positions on ``q`` and ``k`` (interleaved pairs ``(2i,
  2i + 1)``, angle ``pos * theta^(-2i / head_dim)``, the whole head);
  position ``t`` sees keys ``t - window + 1 .. t``;
- ``F``: no positions of any kind; position ``t`` sees ``0 .. t``.

``FFN`` is :mod:`veles_tpu.gen.experts`' share of gated experts plus
``shared_experts`` of the same form that run on every token and are
averaged.  A final LayerNorm and the embedding as the head.

The cache is a TREE, one ``{"k", "v"}`` a layer, rows ``[slots, rows,
kv_heads * head_dim]``: a ``W`` layer keeps a RING of ``rows =
min(window, max_seq)`` (position ``p`` lives in row ``p mod rows``), an
``F`` layer ``max_seq`` rows.  Which position a row holds follows from
the slot's length alone, so validity comes from positions and never
from zeroing: a slot admitted after a longer request does not see the
old rows, and admission IS the reset.

There is no whole-prompt program: a prompt enters by CHUNKS
(:meth:`prefill_chunk`; ``rows`` must be a multiple of the chunk, so a
chunk's rows are one run of the ring), and a chunk attends the ring AS
IT WAS plus its own keys before it writes them (its rows overwrite
positions that its first queries still see).  On the TPU a chunk's
attention is ONE kernel a layer that reads the cache in place
(:func:`veles_tpu.ops.attention.ring_chunk_attention`) and visits a
window's worth of rows whatever the depth, so that a chunk costs the
same wherever it lies in its prompt; elsewhere, and in a decode step,
the cache is visited by blocks with a running softmax, and only the
blocks that hold a live position (a chunk: its slot's; a decode step:
every slot's first block, then level by level the further blocks of the
slots that reach them), so what is read grows with the context and not
with ``max_seq``.

A layer that keeps a window only cannot be paged, shared by prefix,
verified k tokens at once, replayed or shipped as pages: the model
declares ``window_rows`` and the engine refuses those modes by name.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.gen import experts
from veles_tpu.gen.experts import COUNTERS
from veles_tpu.ops import attention, on_tpu
from veles_tpu.samples import window_moe_lm

F32 = jnp.float32
NEG = -1e30


def _layernorm(x, g, eps, out):
    x = x.astype(F32)
    x = x - x.mean(-1, keepdims=True)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return (x * g.astype(F32)).astype(out)


def rope(x, positions, theta):
    """``x [T, heads, head_dim]`` rotated by ``positions [T]``: the
    pairs ``(2i, 2i + 1)`` by ``pos * theta^(-2i / head_dim)``, on the
    float32 values."""
    dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    angle = positions.astype(F32)[:, None, None] \
        * jnp.repeat(inv, 2)[None, None, :]
    even = (jnp.arange(dh) % 2 == 0)[None, None, :]
    xf = x.astype(F32)
    # (a, b) -> (-b, a) within each pair
    turned = jnp.where(even, -jnp.roll(xf, -1, axis=-1),
                       jnp.roll(xf, 1, axis=-1))
    return (xf * jnp.cos(angle) + turned * jnp.sin(angle)).astype(x.dtype)


class WindowMoEGenModel(object):
    """The ``cohere2_moe`` family behind the generative protocol.
    ``compute_dtype`` defaults to float32 (the CPU parity tests);
    serving deployments on the TPU pass ``jnp.bfloat16``: the type of
    the matrix products' operands and of the cache.  The residual
    stream, the norms, the router WITH its input and the softmax are
    float32 whatever it is: a router that reads a rounded input puts a
    near-tie the other way, and a token that loses a held expert is the
    largest error a served token shows against the reference."""

    causal = True
    counters = COUNTERS
    #: a decode step visits a further block of the full layer slot by
    #: slot while at most this share of the slots reaches it, and for
    #: every slot at once above (a visit of one slot's block costs about
    #: a twelfth of the visit of all 24: PERF.md section 6, PR 34)
    level_share = 0.5

    def __init__(self, cfg, compute_dtype=None, dense_tokens=256,
                 use_pallas=None):
        self.cfg = dict(cfg)
        self.vocab = int(cfg["vocab"])
        self.dim = int(cfg["dim"])
        self.pattern = str(cfg["pattern"])
        self.heads = int(cfg["heads"])
        self.kv_heads = int(cfg["kv_heads"])
        self.head_dim = int(cfg["head_dim"])
        if self.heads % self.kv_heads:
            raise ValueError("%d query heads do not divide over %d KV "
                             "heads" % (self.heads, self.kv_heads))
        self.seq_limit = int(cfg["seq_len"])
        #: what a ``W`` layer keeps; the engine reads it to refuse every
        #: mode that assumes each layer keeps each position
        self.window_rows = int(cfg["window"])
        self.theta = float(cfg["rope_theta"])
        self.router_width = int(cfg["router_width"])
        self.held = int(cfg["experts_held"])
        self.held_from = int(cfg.get("held_from", 0))
        if self.held_from + self.held > self.router_width:
            raise ValueError(
                "experts %d..%d are not among the router's %d"
                % (self.held_from, self.held_from + self.held,
                   self.router_width))
        self.top_k = int(cfg["top_k"])
        self.shared = int(cfg["shared_experts"])
        self.eps = float(cfg["norm_eps"])
        self.logit_scale = float(cfg.get("logit_scale", 1.0))
        self.compute_dtype = compute_dtype or jnp.float32
        #: up to this many rows ALL go through the held experts that a
        #: valid row chose; above, the pairs are sorted by expert
        self.dense_tokens = int(dense_tokens)
        #: the grouped product's and the chunk attention's kernels:
        #: None lets the platform decide
        self.use_pallas = use_pallas

    # -- params / cache ----------------------------------------------------
    def init_params(self, seed=0):
        return window_moe_lm.init_params(self.cfg, seed=seed)

    def layer_rows(self, max_seq):
        """Rows of each layer's cache: the ring's, or every position."""
        return [min(self.window_rows, int(max_seq)) if kind == "W"
                else int(max_seq) for kind in self.pattern]

    def cache_shape(self, slots, max_seq):
        """One entry a layer: name -> (shape, dtype)."""
        cd = jnp.dtype(self.compute_dtype)
        width = self.kv_heads * self.head_dim
        return [{name: ((int(slots), rows, width), cd)
                 for name in ("k", "v")}
                for rows in self.layer_rows(max_seq)]

    def init_cache(self, slots, max_seq):
        return {"layers": [
            {name: jnp.zeros(shape, dtype)
             for name, (shape, dtype) in layer.items()}
            for layer in self.cache_shape(slots, max_seq)]}

    def cache_nbytes(self, slots, max_seq):
        return sum(int(numpy.prod(shape)) * dtype.itemsize
                   for layer in self.cache_shape(slots, max_seq)
                   for shape, dtype in layer.values())

    def param_specs(self):
        """Replicated: this model is divided over chips by its experts
        and its layers, not over a ``model`` axis."""
        from jax.sharding import PartitionSpec as P
        return jax.tree.map(lambda _leaf: P(),
                            window_moe_lm.param_shapes(self.cfg))

    def cache_spec(self):
        from jax.sharding import PartitionSpec as P
        return {"layers": [{name: P() for name in layer}
                           for layer in self.cache_shape(1, 1)]}

    # -- the block's two halves --------------------------------------------
    def _project(self, p, u, kind, positions):
        """``u [T, d]`` -> ``(q [T, kv_heads, heads / kv_heads,
        head_dim], k [T, kv_heads * head_dim], v)``, ``q`` and ``k``
        rotated where the layer has positions."""
        cd = self.compute_dtype
        q, k, v = (jnp.einsum("td,dhx->thx", u, p[name].astype(cd),
                              preferred_element_type=F32).astype(cd)
                   for name in ("wq", "wk", "wv"))
        if kind == "W":
            q = rope(q, positions, self.theta)
            k = rope(k, positions, self.theta)
        g = self.kv_heads
        T = u.shape[0]
        return (q.reshape(T, g, self.heads // g, self.head_dim),
                k.reshape(T, -1), v.reshape(T, -1))

    def _attn_out(self, p, att):
        """``att [T, kv_heads, heads / kv_heads, head_dim]`` float32 ->
        ``[T, d]`` float32."""
        cd = self.compute_dtype
        att = att.reshape(att.shape[0], self.heads, self.head_dim)
        return jnp.einsum("thx,hxd->td", att.astype(cd),
                          p["wo"].astype(cd), preferred_element_type=F32)

    def _visit(self, carry, q, kb, vb, visible, spec):
        """One block of keys into the running softmax of every query.
        ``q [..., kv_heads, r, head_dim]``, ``kb``/``vb`` ``[..., S,
        kv_heads * head_dim]``, ``visible`` broadcast against the
        scores ``[..., r, S]`` of one KV head; ``carry`` = ``(m, l,
        acc)`` stacked over the KV heads; ``spec`` the two einsums of one
        head.  A slice of lanes a head."""
        m, l, acc = carry
        dh = self.head_dim
        score, mix = spec
        out = []
        for g in range(self.kv_heads):
            lanes = slice(g * dh, (g + 1) * dh)
            s = jnp.einsum(score, q[:, g], kb[..., lanes],
                           preferred_element_type=F32) / math.sqrt(dh)
            s = jnp.where(visible, s, NEG)
            m_new = jnp.maximum(m[g], s.max(-1))
            alpha = jnp.exp(m[g] - m_new)
            # a row that has seen nothing yet keeps m = NEG: exp(0)
            w = jnp.where(visible, jnp.exp(s - m_new[..., None]), 0.0)
            out.append((m_new, l[g] * alpha + w.sum(-1),
                        acc[g] * alpha[..., None] + jnp.einsum(
                            mix, w.astype(vb.dtype), vb[..., lanes],
                            preferred_element_type=F32)))
        return tuple(jnp.stack(part) for part in zip(*out))

    def _attend_chunk(self, q, k, v, state, slot, start, window):
        """The chunk's queries ``q [C, g, r, x]`` at positions ``start
        + i`` against the slot's rows AS THEY ARE and the chunk's own
        ``k``/``v``: the kernel on the TPU; elsewhere blocks of C rows,
        the live ones only, with a running softmax.  ``window``: how
        many positions a query sees, None = all."""
        C, G, R, dh = q.shape
        rows = state["k"].shape[1]
        pallas = self.use_pallas if self.use_pallas is not None \
            else on_tpu()
        if pallas:
            from veles_tpu.config import root
            return attention.ring_chunk_attention(
                q, k, v, state["k"], state["v"], slot, start, window,
                floor_rows=min(self.window_rows, rows),
                interpret=bool(root.common.engine.get("interpret", False)))
        t = start + jnp.arange(C)
        spec = ("trx,sx->rts", "rts,sx->rtx")

        def visible(p):
            seen = (p[None, :] >= 0) & (p[None, :] <= t[:, None])
            if window is not None:
                seen &= (t[:, None] - p[None, :]) < window
            return seen[None]

        def block(j, carry):
            kb, vb = (jax.lax.dynamic_slice(
                c, (slot, j * C, 0), (1, C, c.shape[2]))[0]
                for c in (state["k"], state["v"]))
            r = j * C + jnp.arange(C)
            # the last position before the chunk that lives in row r
            p = start - 1 - jnp.mod(start - 1 - r, rows)
            return self._visit(carry, q, kb, vb, visible(p), spec)

        carry = (jnp.full((G, R, C), NEG, F32), jnp.zeros((G, R, C), F32),
                 jnp.zeros((G, R, C, dh), F32))
        carry = jax.lax.fori_loop(0, jnp.minimum(start, rows) // C, block,
                                  carry)
        _m, l, acc = self._visit(carry, q, k, v, visible(t), spec)
        return jnp.moveaxis(acc / l[..., None], 2, 0)      # [C, g, r, x]

    def _attend_decode(self, q, state, positions, active):
        """One query a slot, ``q [slots, g, r, x]`` at ``positions``,
        against the slot's rows AFTER this step's write, by blocks of
        ``window_rows`` rows (a ring is one): the first block of every
        slot in one visit, then level by level the further blocks of
        the slots that reach them, so that what a step reads past the
        first block is the live slots' own rows and not the longest
        slot's length for all."""
        slots, G, R, dh = q.shape
        rows = state["k"].shape[1]
        size = self.window_rows if rows % self.window_rows == 0 else rows
        spec = ("bqx,bsx->bqs", "bqs,bsx->bqx")

        def visit(carry, q, positions, slot, j, count):
            """Block ``j`` of ``count`` slots from ``slot`` on."""
            kb, vb = (jax.lax.dynamic_slice(
                c, (slot, j * size, 0), (count, size, c.shape[2]))
                for c in (state["k"], state["v"]))
            r = j * size + jnp.arange(size)
            # the last position up to the query's that lives in row r
            p = positions[:, None] - jnp.mod(positions[:, None] - r[None],
                                             rows)
            return self._visit(carry, q, kb, vb, (p >= 0)[:, None, :],
                               spec)

        carry = visit((jnp.full((G, slots, R), NEG, F32),
                       jnp.zeros((G, slots, R), F32),
                       jnp.zeros((G, slots, R, dh), F32)),
                      q, positions, 0, 0, slots)
        if size < rows:
            # further blocks: level j is block j of the slots that reach
            # it, those that reach furthest first in ``order``
            more = jnp.where(active, positions // size, 0)
            order = jnp.argsort(-more)

            def level(j, carry):
                reach = (more >= j).sum()

                def pair(i, carry):
                    slot = order[i]
                    one = tuple(jax.lax.dynamic_slice_in_dim(
                        part, slot, 1, axis=1) for part in carry)
                    one = visit(
                        one, jax.lax.dynamic_slice_in_dim(q, slot, 1),
                        jax.lax.dynamic_slice_in_dim(positions, slot, 1),
                        slot, j, 1)
                    return tuple(jax.lax.dynamic_update_slice_in_dim(
                        part, new, slot, axis=1)
                        for part, new in zip(carry, one))

                # few slots reach the level: theirs one by one; most
                # do: the level of every slot in one visit (a slot that
                # does not reach it sees none of its rows)
                return jax.lax.cond(
                    reach > self.level_share * slots,
                    lambda carry: visit(carry, q, positions, 0, j, slots),
                    lambda carry: jax.lax.fori_loop(0, reach, pair, carry),
                    carry)

            carry = jax.lax.fori_loop(1, more.max() + 1, level, carry)
        _m, l, acc = carry
        return jnp.moveaxis(acc / l[..., None], 0, 1)   # [slots, g, r, x]

    def _ffn(self, p, u, valid):
        """``u [T, d]`` -> ``(FFN(u) [T, d] float32, counters)``;
        ``valid [T]`` says which rows are real tokens (the counters
        leave the others out)."""
        cd = self.compute_dtype
        with jax.named_scope("veles.wmoe.moe.router"):
            local, g = experts.route(u, p["router"], self.top_k,
                                     self.held_from, self.held)
            u = u.astype(cd)
            loads = experts.load_counts(local, valid, self.held,
                                        self.top_k)
        with jax.named_scope("veles.wmoe.moe.experts"):
            mixed, counts = experts.mix(
                "gated_silu", p, u, local, g, valid, loads, self.held,
                self.top_k, self.dense_tokens, cd, self.use_pallas)
        with jax.named_scope("veles.wmoe.moe.shared"):
            # the shared experts side by side: one wide gated product,
            # their average folded into the sum over (expert, width)
            gate, up = (jnp.einsum("td,sdf->tsf", u, p[name].astype(cd),
                                   preferred_element_type=F32)
                        for name in ("sg", "su"))
            hidden = (jax.nn.silu(gate) * up).astype(cd)
            shared = jnp.einsum("tsf,sfd->td", hidden, p["sd"].astype(cd),
                                preferred_element_type=F32) / self.shared
        return mixed + shared, counts

    def head_logits(self, params, x):
        """``x [rows, d]`` -> float32 logits over the rows of the
        vocabulary held here (the embedding is the head)."""
        cd = self.compute_dtype
        with jax.named_scope("veles.wmoe.readout"):
            x = _layernorm(x, params["norm_f"], self.eps, cd)
            return jnp.einsum("bd,vd->bv", x, params["embed"].astype(cd),
                              preferred_element_type=F32) * self.logit_scale

    def _greedy(self, params, x, total):
        """One greedy token a row of ``x``, then the counters."""
        logits = self.head_logits(params, x)
        with jax.named_scope("veles.wmoe.readout"):
            return jnp.concatenate(
                [jnp.argmax(logits, axis=-1).astype(jnp.int32), total])

    # -- the protocol's two programs ---------------------------------------
    def _stack(self, params, cache, x, valid, attend):
        """The layers in their published order over ``x [T, d]``:
        ``attend(kind, p, state, u)`` gives ``(state', Attn(u))``.
        Returns ``(cache', x, counters)``.  The loop is unrolled (the
        layers' caches are not alike) and has no scope of its own."""
        total = jnp.zeros(len(COUNTERS), jnp.int32)
        states = []
        for kind, p, state in zip(self.pattern, params["layers"],
                                  cache["layers"]):
            u = _layernorm(x, p["norm"], self.eps, F32)
            state, att = attend(kind, p, state, u.astype(self.compute_dtype))
            ffn, counts = self._ffn(p, u, valid)
            x = x + att + ffn
            total = experts.merge(total, counts)
            states.append(state)
        return {"layers": states}, x, total

    def chunk_hidden(self, params, cache, tokens, slot, start, chunk_len):
        """:meth:`prefill_chunk` up to the head: ``(cache', the
        residual stream of every row [C, d], counters)``."""
        C = tokens.shape[1]
        for rows in (layer["k"].shape[1] for layer in cache["layers"]):
            if rows % C:
                raise ValueError(
                    "a layer's %d cache rows are no multiple of the chunk "
                    "of %d: a chunk's rows must be one run of the ring"
                    % (rows, C))
        with jax.named_scope("veles.wmoe.embed"):
            x = params["embed"][tokens[0]].astype(F32)
        positions = start + jnp.arange(C)
        valid = jnp.arange(C) < chunk_len

        def attend(kind, p, state, u):
            scope = "veles.wmoe.attn.window" if kind == "W" \
                else "veles.wmoe.attn.full"
            with jax.named_scope(scope):
                q, k, v = self._project(p, u, kind, positions)
                att = self._attend_chunk(
                    q, k, v, state, slot, start,
                    self.window_rows if kind == "W" else None)
                att = self._attn_out(p, att)
            with jax.named_scope("veles.wmoe.attn.kv_write"):
                row = jnp.mod(start, state["k"].shape[1])

                def put(c, new):    # the real rows of the chunk alone
                    was = jax.lax.dynamic_slice(
                        c, (slot, row, 0), (1, C, c.shape[2]))
                    new = jnp.where(valid[None, :, None],
                                    new.astype(c.dtype)[None], was)
                    return jax.lax.dynamic_update_slice(c, new,
                                                        (slot, row, 0))
                state = {"k": put(state["k"], k), "v": put(state["v"], v)}
            return state, att

        return self._stack(params, cache, x, valid, attend)

    def prefill_chunk(self, params, cache, tokens, slot, start,
                      chunk_len):
        """ONE chunk of a prompt: tokens (1, C) int32 (zero-padded past
        ``chunk_len`` on the final chunk) at positions ``start ..``
        (``start`` a multiple of C) -> ``(cache', [next token,
        *COUNTERS])``; the token is the greedy continuation and is
        meaningful on the final chunk only.  Padded rows write
        nothing."""
        cache, x, total = self.chunk_hidden(params, cache, tokens, slot,
                                            start, chunk_len)
        last = jax.lax.dynamic_slice_in_dim(x, chunk_len - 1, 1, axis=0)
        return cache, self._greedy(params, last, total)

    def decode_hidden(self, params, cache, tokens, positions, active):
        """:meth:`decode` up to the head: ``(cache', the residual
        stream [slots, d], counters)``."""
        slots = tokens.shape[0]
        idx = jnp.arange(slots)
        with jax.named_scope("veles.wmoe.embed"):
            x = params["embed"][tokens].astype(F32)

        def attend(kind, p, state, u):
            scope = "veles.wmoe.attn.window" if kind == "W" \
                else "veles.wmoe.attn.full"
            with jax.named_scope(scope):
                q, k, v = self._project(p, u, kind, positions)
            with jax.named_scope("veles.wmoe.attn.kv_write"):
                row = jnp.mod(positions, state["k"].shape[1])

                def put(c, new):    # one row a slot; inactive: as it was
                    return c.at[idx, row].set(
                        jnp.where(active[:, None], new.astype(c.dtype),
                                  c[idx, row]))
                state = {"k": put(state["k"], k), "v": put(state["v"], v)}
            with jax.named_scope(scope):
                att = self._attn_out(p, self._attend_decode(
                    q, state, positions, active))
            return state, att

        return self._stack(params, cache, x, active, attend)

    def decode(self, params, cache, tokens, positions, active):
        """ONE decode step over every slot -> ``(cache', [slots tokens,
        *COUNTERS])``.  Inactive slots ride along computing garbage
        (they choose no expert); none of their rows moves."""
        cache, x, total = self.decode_hidden(params, cache, tokens,
                                             positions, active)
        return cache, self._greedy(params, x, total)

    def logits(self, params, tokens, chunk):
        """float32 logits ``[T, vocab]`` of ONE sequence fed by chunks
        of ``chunk`` through a cache of its own: what the parity tests
        compare with the reference."""
        tokens = numpy.asarray(tokens, numpy.int32).ravel()
        T = len(tokens)
        padded = numpy.zeros(-(-T // chunk) * chunk, numpy.int32)
        padded[:T] = tokens
        cache = self.init_cache(1, len(padded))
        out = []
        for start in range(0, len(padded), chunk):
            cache, x, _total = self.chunk_hidden(
                params, cache, jnp.asarray(padded[None,
                                                  start:start + chunk]),
                0, start, min(chunk, T - start))
            out.append(self.head_logits(params, x))
        return jnp.concatenate(out)[:T]

    # -- analytic flops (the ledger's; matrix work only) -------------------
    def _per_token_flops(self, attended):
        """One token through the stack: every dense product, its
        ``top_k`` experts' share held here, attention over ``attended``
        positions (a window layer over its window at most)."""
        d, f = self.dim, self.cfg["expert_width"]
        project = 4 * d * (self.heads + self.kv_heads) * self.head_dim
        expert = 6 * d * f
        ffn = 2 * d * self.router_width + self.shared * expert \
            + expert * self.top_k * self.held / float(self.router_width)
        total = 0.0
        for kind in self.pattern:
            seen = min(attended, self.window_rows) if kind == "W" \
                else attended
            total += project + ffn + 4 * self.heads * self.head_dim * seen
        return total

    def prefill_chunk_flops(self, chunk, max_seq):
        """One chunk, counted at the ``max_seq / 2`` mean extent
        (``start`` is traced) + one readout."""
        return chunk * self._per_token_flops(max_seq / 2.0) \
            + 2.0 * self.dim * self.vocab

    def decode_flops(self, slots, max_seq):
        return slots * (self._per_token_flops(float(max_seq))
                        + 2.0 * self.dim * self.vocab)
