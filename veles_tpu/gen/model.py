"""Generative model protocol + the decoder-only transformer adapter.

The :class:`GenerativeEngine` compiles exactly two program families —
per-bucket **prefill** and ONE fixed-shape **decode step** — against a
model object exposing this protocol:

- ``causal`` (bool), ``vocab``, ``seq_limit`` attributes;
- ``init_params(seed)`` → host param pytree;
- ``init_cache(slots, max_seq)`` → slot-major KV cache pytree
  (``{"k": [L, slots, S, h, dh], "v": ...}``);
- ``prefill(params, cache, tokens, slot, length)`` → ``(cache',
  next_token)`` — run the prompt through the stack, write its K/V
  into cache slot ``slot``, return the greedy next token;
- ``decode(params, cache, tokens, positions)`` → ``(cache',
  next_tokens)`` — ONE autoregressive step over every slot at once.

Both functions must be jit-traceable with ``slot``/``length``/
``positions`` as traced int32 values (fixed shapes → the engine's
zero-steady-state-compile guarantee) and **row-independent across
slots**: slot ``i``'s outputs may depend only on slot ``i``'s query
and its valid cache prefix.  That independence is what makes
continuous batching bit-exact against sequential decode (the parity
gate in ``tests/test_gen.py``); :func:`veles_tpu.ops.attention
.decode_attention` provides it for the attention read.

The PAGED half of the protocol (``veles_tpu.gen.paged``) mirrors the
same four entry points over a shared block pool —
``init_paged_cache(num_blocks, block_size)`` (``{"k", "v"}:
[L, num_blocks, BS, h, dh]``), ``paged_prefill`` / ``paged_decode``
(block-id scatter + table-gathered read, the append fused into the
decode program), and the chunked-prefill pair ``prefill_chunk`` /
``paged_prefill_chunk`` that feeds one fixed-shape chunk per decode
cadence.  ``decode``/``paged_decode`` additionally take an ``active``
mask: inactive slots' ride-along K/V writes are routed to a no-op
(contiguous) or the trash block (paged), because a chunked prefill in
flight owns its slot's cache while the slot is still decode-inactive.

:class:`TransformerGenModel` adapts the :mod:`veles_tpu.samples
.transformer` parameter layout (stacked blocks, tied readout) so the
LM the platform trains is the LM it serves.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.ops.attention import (chunk_attention, decode_attention,
                                     flash_attention,
                                     paged_decode_attention,
                                     paged_verify_attention,
                                     verify_attention)


def _layernorm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


class TransformerGenModel(object):
    """Decoder-only transformer (``samples/transformer.py`` params)
    with a slot-major KV cache.

    ``compute_dtype`` defaults to float32 — bit-exact greedy decode on
    CPU and the parity tests' substrate; serving deployments on TPU
    pass ``jnp.bfloat16``.  ``use_pallas`` forces the attention
    backend (None = auto: Pallas on TPU, dense jnp elsewhere) — one
    resolution at construction so every compiled program agrees.
    """

    causal = True

    def __init__(self, cfg, compute_dtype=None, use_pallas=None):
        self.cfg = dict(cfg)
        self.vocab = int(cfg["vocab"])
        self.dim = int(cfg["dim"])
        self.heads = int(cfg["heads"])
        self.layers = int(cfg["layers"])
        if self.dim % self.heads:
            raise ValueError("dim %d not divisible by heads %d"
                             % (self.dim, self.heads))
        self.head_dim = self.dim // self.heads
        self.seq_limit = int(cfg["seq_len"])
        self.compute_dtype = compute_dtype or jnp.float32
        self.use_pallas = use_pallas

    # -- params / cache ----------------------------------------------------
    def init_params(self, seed=0):
        from veles_tpu.samples.transformer import init_params
        return init_params(self.cfg, seed=seed)

    def cache_shape(self, slots, max_seq):
        return (self.layers, int(slots), int(max_seq), self.heads,
                self.head_dim)

    def init_cache(self, slots, max_seq, dtype=None):
        shape = self.cache_shape(slots, max_seq)
        dtype = dtype or self.compute_dtype
        return {"k": jnp.zeros(shape, dtype),
                "v": jnp.zeros(shape, dtype)}

    def cache_nbytes(self, slots, max_seq, dtype=None):
        shape = self.cache_shape(slots, max_seq)
        itemsize = jnp.dtype(dtype or self.compute_dtype).itemsize
        return 2 * int(numpy.prod(shape)) * itemsize

    # -- paged cache (shared block pool + per-slot block tables) -----------
    def paged_cache_shape(self, num_blocks, block_size):
        return (self.layers, int(num_blocks), int(block_size),
                self.heads, self.head_dim)

    def init_paged_cache(self, num_blocks, block_size, dtype=None):
        shape = self.paged_cache_shape(num_blocks, block_size)
        dtype = dtype or self.compute_dtype
        return {"k": jnp.zeros(shape, dtype),
                "v": jnp.zeros(shape, dtype)}

    def paged_cache_nbytes(self, num_blocks, block_size, dtype=None):
        shape = self.paged_cache_shape(num_blocks, block_size)
        itemsize = jnp.dtype(dtype or self.compute_dtype).itemsize
        return 2 * int(numpy.prod(shape)) * itemsize

    # -- sharding rules (tensor parallelism over the model axis) -----------
    def param_specs(self):
        """PartitionSpec pytree: Megatron column→row pairs for the
        block weights (same rules the training side's
        ``transformer.param_specs`` applies), embed/pos/norms
        replicated."""
        from jax.sharding import PartitionSpec as P
        from veles_tpu.parallel.tp import column_parallel, shard_dim
        rules = {
            "wqkv": shard_dim(5, 3),     # heads: column-parallel qkv
            "wo": shard_dim(4, 1),       # heads in: row-parallel
            "w1": column_parallel(3),
            "b1": column_parallel(2),
            "w2": shard_dim(3, 1),       # hidden in: row-parallel
        }

        def walk(tree):
            return {key: walk(leaf) if isinstance(leaf, dict)
                    else rules.get(key, P())
                    for key, leaf in tree.items()}

        return walk(self.init_params(seed=0))

    def cache_spec(self):
        """KV cache sharded over heads (dim 3 of [L, slots, S, h, dh])
        — each model shard owns its heads' cache, matching the
        column-parallel qkv that produces them (no resharding between
        projection and cache write)."""
        from jax.sharding import PartitionSpec as P
        spec = P(None, None, None, "model", None)
        return {"k": spec, "v": spec}

    def paged_cache_spec(self):
        """The block pool shards over heads exactly like the slot-major
        cache — dim 3 of [L, num_blocks, BS, h, dh] — so each model
        shard owns its heads' pages and the block tables stay
        replicated host-mirrorable int32."""
        from jax.sharding import PartitionSpec as P
        spec = P(None, None, None, "model", None)
        return {"k": spec, "v": spec}

    # -- forwards ----------------------------------------------------------
    def _attend_prefill(self, q, k, v):
        # the existing flash path: Pallas kernel on TPU (q_offset=0
        # start-aligned causal mask), XLA-fused fallback elsewhere —
        # resolved once via use_pallas so recompiles can't flip it
        return flash_attention(q, k, v, True, None, None,
                               self.use_pallas)

    def _qmm(self, x2, qw, nc, bias=None, activation=None):
        """One int8 block matmul over a quantized ``{"q", "scale"}``
        leaf: the leaf's first ``nc`` axes are the contraction (K),
        the rest flatten into output channels (N) — so the per-layer
        slices of every stacked block weight reduce to the ONE 2D
        :func:`veles_tpu.ops.qgemm.qmatmul` kernel (int8 weights
        DMA'd as stored, dequant fused into the epilogue)."""
        from veles_tpu.ops.qgemm import qmatmul
        q = qw["q"]
        k = 1
        for dim in q.shape[:nc]:
            k *= int(dim)
        return qmatmul(x2, q.reshape(k, -1), qw["scale"].reshape(-1),
                       bias, activation, use_pallas=self.use_pallas,
                       out_dtype=x2.dtype)

    def _mlp(self, h, blk):
        """The block's MLP half (second layernorm included), before
        the residual add."""
        cd = self.compute_dtype
        b_, s_ = h.shape[0], h.shape[1]
        x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        w1_q = isinstance(blk["w1"], dict)
        w2_q = isinstance(blk["w2"], dict)
        if w1_q or w2_q:
            # bias + gelu fused into the up-projection epilogue,
            # bias into the down-projection's — the whole MLP is
            # two quantized dispatches.  The halves branch
            # independently so the calibration blame probe (one
            # key quantized at a time) traces cleanly.
            x2 = x.reshape(b_ * s_, -1).astype(cd)
            if w1_q:
                up_act = self._qmm(x2, blk["w1"], 1,
                                   bias=blk["b1"].astype(cd),
                                   activation="gelu")
            else:
                up_act = jax.nn.gelu(
                    x2 @ blk["w1"].astype(cd)
                    + blk["b1"].astype(cd))
            if w2_q:
                down = self._qmm(up_act, blk["w2"], 1,
                                 bias=blk["b2"].astype(cd))
            else:
                down = (up_act @ blk["w2"].astype(cd)
                        + blk["b2"].astype(cd))
            down = down.reshape(b_, s_, -1)
        else:
            up = (x.astype(cd) @ blk["w1"].astype(cd)
                  + blk["b1"].astype(cd))
            down = (jax.nn.gelu(up) @ blk["w2"].astype(cd)
                    + blk["b2"].astype(cd))
        return down

    def _run_layers(self, params, cache, h, kv_hook):
        """Loop over the block stack with the ONE shared layer body,
        the KV cache riding the loop as its CARRY: the same
        ``[L, ...]`` buffers enter and leave, so with the engine's
        donation the compiled program aliases cache-in to cache-out
        and a layer writes only the rows it owns, in place.
        ``kv_hook(i, cache, q, k, v) -> (cache', att)`` is the only
        thing the eight entry points differ in: ``i`` is the layer's
        (traced) index into the cache's leading axis, ``cache`` the
        WHOLE arrays (or None for a forward that keeps none) — where
        this layer's K/V land (slot slice, page scatter, chunk window)
        and what the attention reads after the write (the chunk
        itself, layer ``i``'s masked cache, its table-gathered pool).
        A hook never writes a whole layer's slab back.  One body means
        a layer-math change can never desynchronize the
        paged==contiguous parity pair — and the int8 deploy rides the
        same body: a quantized block weight (``veles_tpu.quant`` pair,
        detected per leaf at trace time) routes its matmul through
        :meth:`_qmm` while the float path stays byte-identical, so
        EVERY entry point (prefill, decode, paged, chunked) serves
        quantized without its own fork.
        Returns ``(h_final_normed, cache')``."""
        cd = self.compute_dtype

        def layer(carry, xs):
            h, cache = carry
            blk, i = xs
            b_, s_ = h.shape[0], h.shape[1]
            with jax.named_scope("veles.gpt.qkv"):
                x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
                if isinstance(blk["wqkv"], dict):
                    qkv = self._qmm(
                        x.reshape(b_ * s_, -1).astype(cd),
                        blk["wqkv"], 1).reshape(
                            b_, s_, 3, self.heads, self.head_dim)
                else:
                    qkv = jnp.einsum("bsd,dchx->bschx", x.astype(cd),
                                     blk["wqkv"].astype(cd))
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            cache, att = kv_hook(i, cache, q, k, v)
            with jax.named_scope("veles.gpt.proj"):
                if isinstance(blk["wo"], dict):
                    proj = self._qmm(
                        att.reshape(b_ * s_, -1).astype(cd),
                        blk["wo"], 2).reshape(b_, s_, -1)
                else:
                    proj = jnp.einsum("bshx,hxd->bsd", att.astype(cd),
                                      blk["wo"].astype(cd))
                h = h + proj.astype(h.dtype)
            with jax.named_scope("veles.gpt.mlp"):
                h = h + self._mlp(h, blk).astype(h.dtype)
            return (h, cache), None

        # the loop itself gets NO scope.  The cache's writes sit under
        # veles.gpt.kv_write and its reads under veles.gpt.attn, so
        # what no name covers is the loop's bookkeeping alone — and a
        # whole-cache movement that comes back shows there first
        # (engine.decode_unscoped_ms; docs/observability.md)
        (h, cache), _ = jax.lax.scan(
            layer, (h, cache),
            (params["blocks"], jnp.arange(self.layers)))
        with jax.named_scope("veles.gpt.readout"):
            h = _layernorm(h, params["lnf_g"], params["lnf_b"])
        return h, cache

    def _forward_cacheless(self, params, h):
        """The block stack over ``h`` (1, S, d) with causal
        self-attention and NO cache (the loop's carry holds ``h``
        alone) — the calibration probe's and the draft proposer's
        forward."""
        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.attn"):
                return cache, self._attend_prefill(q, k, v)

        return self._run_layers(params, None, h, kv_hook)[0]

    def calibration_logits(self, params, tokens):
        """Last-position logits of ONE prompt through the same shared
        ``_run_layers`` body the engine serves from — the float-vs-
        int8 calibration probe (:func:`veles_tpu.quant
        .quantize_gen_params` gates relative drift on it).  No cache
        is kept."""
        tokens = jnp.asarray(tokens, jnp.int32).reshape(1, -1)
        s = tokens.shape[1]
        cd = self.compute_dtype
        embed = jnp.asarray(params["embed"])
        with jax.named_scope("veles.gpt.embed"):
            h = embed[tokens] + jnp.asarray(params["pos"])[:s]

        h = self._forward_cacheless(params, h)
        with jax.named_scope("veles.gpt.readout"):
            return jnp.einsum("d,vd->v", h[0, -1].astype(cd),
                              embed.astype(cd)).astype(jnp.float32)

    @jax.named_scope("veles.gpt.readout")
    def _greedy_at(self, params, h, index):
        """h (1, S, d) -> the greedy token of row ``index`` (traced)
        through the tied readout."""
        cd = self.compute_dtype
        last = jax.lax.dynamic_slice_in_dim(h[0], index, 1,
                                            axis=0)[0]
        logits = jnp.einsum("d,vd->v", last.astype(cd),
                            params["embed"].astype(cd)
                            ).astype(jnp.float32)
        return jnp.argmax(logits).astype(jnp.int32)

    @jax.named_scope("veles.gpt.readout")
    def _greedy_rows(self, params, h):
        """h (slots, 1, d) -> one greedy token per row."""
        cd = self.compute_dtype
        logits = jnp.einsum("bd,vd->bv", h[:, 0].astype(cd),
                            params["embed"].astype(cd)
                            ).astype(jnp.float32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    @jax.named_scope("veles.gpt.readout")
    def _greedy_grid(self, params, h):
        """h (slots, K+1, d) -> the greedy token of EVERY row — the
        verify step's readout.  Per-(slot, row) the contraction is the
        same tied-readout einsum as :meth:`_greedy_rows`, so row 0's
        argmax is the plain decode token."""
        cd = self.compute_dtype
        logits = jnp.einsum("bsd,vd->bsv", h.astype(cd),
                            params["embed"].astype(cd)
                            ).astype(jnp.float32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def prefill(self, params, cache, tokens, slot, length):
        """tokens (1, bucket) int32 (zero-padded past ``length``),
        ``slot``/``length`` traced int32 scalars → (cache', greedy
        next token).  The causal mask makes the padded tail invisible
        to position ``length - 1``, so the bucket shape never leaks
        into the returned token; the tail's garbage K/V lands in the
        cache but stays masked (and is progressively overwritten) by
        the decode step's length mask."""
        bucket = tokens.shape[1]
        with jax.named_scope("veles.gpt.embed"):
            h = params["embed"][tokens] + params["pos"][:bucket]

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.attn"):
                att = self._attend_prefill(q, k, v)
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):       # [i, slot, :bucket] <- the prompt
                    return jax.lax.dynamic_update_slice(
                        c, new.astype(c.dtype)[None], (i, slot, 0, 0, 0))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_at(params, h, length - 1)

    def decode(self, params, cache, tokens, positions, active):
        """ONE decode step over every slot: tokens (slots,) int32 (each
        slot's last token), positions (slots,) int32 (the cache index
        this step writes = the slot's current length), active (slots,)
        bool.  Inactive slots ride along at position 0 computing
        garbage that the scheduler discards — the program shape never
        changes with occupancy — but their KV WRITE is masked to a
        no-op: a chunked prefill in flight owns its slot's cache row
        while the slot is still decode-inactive, so an unmasked
        ride-along write would corrupt position 0 of a live prompt."""
        slots = tokens.shape[0]
        with jax.named_scope("veles.gpt.embed"):
            h = (params["embed"][tokens]
                 + params["pos"][positions])[:, None, :]   # (slots, 1, d)
        idx = jnp.arange(slots)
        keep = active[:, None, None]

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):       # one row a slot, inactive: as it was
                    return c.at[i, idx, positions].set(
                        jnp.where(keep, new[:, 0].astype(c.dtype),
                                  c[i, idx, positions]))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            with jax.named_scope("veles.gpt.attn"):
                att = decode_attention(q, cache["k"][i], cache["v"][i],
                                       positions + 1,
                                       use_pallas=self.use_pallas)
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_rows(params, h)

    # -- paged forwards (block-pool cache, veles_tpu.gen.paged) ------------
    def paged_prefill(self, params, cache, tokens, block_ids, length):
        """Whole-prompt prefill into a PAGED pool: tokens (1, bucket)
        int32 (bucket a multiple of block_size), block_ids
        (bucket // block_size,) int32 — the prompt's allocated blocks
        in position order, entries past its allocation pointing at
        the trash block 0 so the bucket's garbage tail can never land
        in another sequence's pages.  Same forward as :meth:`prefill`;
        only the KV landing differs."""
        bucket = tokens.shape[1]
        n_blk = block_ids.shape[0]
        bs = bucket // n_blk
        with jax.named_scope("veles.gpt.embed"):
            h = params["embed"][tokens] + params["pos"][:bucket]

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.attn"):
                att = self._attend_prefill(q, k, v)
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):       # the prompt's pages of layer i
                    return c.at[i, block_ids].set(
                        new[0].astype(c.dtype).reshape(
                            n_blk, bs, self.heads, self.head_dim))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_at(params, h, length - 1)

    def paged_decode(self, params, cache, tables, tokens, positions,
                     active):
        """ONE decode step over every slot against the PAGED pool:
        tables (slots, max_blocks) int32 block tables, the rest as
        :meth:`decode`.  The block APPEND is fused into this program
        — position ``p`` scatters into page ``tables[slot, p // BS]``
        at offset ``p % BS`` (inactive slots route to the trash
        block), and the attention read gathers through the table, so
        one fixed-shape dispatch per step survives any allocation
        state."""
        slots = tokens.shape[0]
        bs = cache["k"].shape[2]               # [L, NB, BS, h, dh]
        with jax.named_scope("veles.gpt.embed"):
            h = (params["embed"][tokens]
                 + params["pos"][positions])[:, None, :]   # (slots, 1, d)
        idx = jnp.arange(slots)
        blk_idx = jnp.where(active, tables[idx, positions // bs], 0)
        blk_off = jnp.where(active, positions % bs, 0)

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):
                    return c.at[i, blk_idx, blk_off].set(
                        new[:, 0].astype(c.dtype))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            with jax.named_scope("veles.gpt.attn"):
                att = paged_decode_attention(q, cache["k"][i],
                                             cache["v"][i], tables,
                                             positions + 1,
                                             use_pallas=self.use_pallas)
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_rows(params, h)

    # -- speculative verify (K drafts scored in ONE dispatch) --------------
    def verify(self, params, cache, tokens, positions, drafts,
               active):
        """Score a slot's pending token plus its K draft
        continuations in ONE dispatch against the CONTIGUOUS cache:
        tokens (slots, K+1) int32 — row 0 each slot's last emitted
        token (exactly what :meth:`decode` would consume), rows 1..K
        the proposer's drafts; positions (slots,) int32 — row 0's
        write position (the slot's length); drafts (slots,) int32 —
        how many draft rows are REAL for the slot (0..K, 0 degrades
        to plain decode); active (slots,) bool.  K/V for rows ``j <=
        drafts`` are written at ``positions + j``; rows beyond (and
        inactive slots) re-write the old value — the contiguous twin
        of the trash-block route.  Returns ``(cache', out)`` with
        ``out`` (slots, K+1): ``out[s, j]`` is the greedy token after
        the prefix plus ``tokens[s, :j+1]``, so accepting while
        ``tokens[s, j+1] == out[s, j]`` reproduces plain greedy
        decode bitwise — acceptance only changes how many of these
        tokens were earned per dispatch."""
        slots, kp1 = tokens.shape
        offs = jnp.arange(kp1)
        gpos = positions[:, None] + offs[None, :]     # (slots, K+1)
        with jax.named_scope("veles.gpt.embed"):
            h = (params["embed"][tokens]
                 + params["pos"][jnp.clip(gpos, 0, self.seq_limit - 1)])
        idx = jnp.arange(slots)
        keep = active[:, None] & (offs[None, :] <= drafts[:, None])
        # masked rows park at position 0 and write the OLD value back
        # (positions >= 1 for live slots, so no live row collides)
        safe = jnp.where(keep, gpos, 0)
        rows = jnp.broadcast_to(idx[:, None], (slots, kp1))

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):
                    return c.at[i, rows, safe].set(
                        jnp.where(keep[..., None, None],
                                  new.astype(c.dtype), c[i, rows, safe]))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            with jax.named_scope("veles.gpt.attn"):
                att = verify_attention(q, cache["k"][i], cache["v"][i],
                                       positions + 1,
                                       use_pallas=self.use_pallas)
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_grid(params, h)

    def paged_verify(self, params, cache, tables, tokens, positions,
                     drafts, active):
        """The PAGED twin of :meth:`verify`: K/V rows scatter through
        the block tables exactly like :meth:`paged_decode`'s fused
        append (the engine pre-allocates every page the draft span
        touches), with rows past ``drafts`` — and inactive slots —
        routed to the trash block, and the attention read gathered
        through the tables with the staggered verify mask."""
        slots, kp1 = tokens.shape
        bs = cache["k"].shape[2]               # [L, NB, BS, h, dh]
        offs = jnp.arange(kp1)
        gpos = positions[:, None] + offs[None, :]     # (slots, K+1)
        with jax.named_scope("veles.gpt.embed"):
            h = (params["embed"][tokens]
                 + params["pos"][jnp.clip(gpos, 0, self.seq_limit - 1)])
        idx = jnp.arange(slots)
        keep = active[:, None] & (offs[None, :] <= drafts[:, None])
        safe = jnp.where(keep, gpos, 0)
        blk_idx = jnp.where(keep, tables[idx[:, None], safe // bs], 0)
        blk_off = jnp.where(keep, safe % bs, 0)

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):
                    return c.at[i, blk_idx, blk_off].set(
                        new.astype(c.dtype))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            with jax.named_scope("veles.gpt.attn"):
                att = paged_verify_attention(q, cache["k"][i],
                                             cache["v"][i], tables,
                                             positions + 1,
                                             use_pallas=self.use_pallas)
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_grid(params, h)

    # -- chunked prefill (one chunk per decode-step cadence) ---------------
    def prefill_chunk(self, params, cache, tokens, slot, start,
                      chunk_len):
        """ONE chunk of a prompt through the CONTIGUOUS cache: tokens
        (1, C) int32 (zero-padded past ``chunk_len`` on the final
        chunk), writes K/V at [slot, start:start+C), attends the
        chunk's queries causally against the slot's full cache row
        (keys ≥ start+C are masked by the causal offset), returns
        (cache', token) — the token is the greedy continuation and is
        meaningful on the final chunk only."""
        chunk = tokens.shape[1]
        with jax.named_scope("veles.gpt.embed"):
            pos = jax.lax.dynamic_slice_in_dim(params["pos"], start, chunk)
            h = params["embed"][tokens] + pos

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):       # [i, slot, start:start+C] <- chunk
                    return jax.lax.dynamic_update_slice(
                        c, new.astype(c.dtype)[None],
                        (i, slot, start, 0, 0))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            with jax.named_scope("veles.gpt.attn"):
                def row(c):            # the slot's whole row of layer i
                    return jax.lax.dynamic_slice(
                        c, (i, slot, 0, 0, 0), (1, 1) + c.shape[2:])[0]
                att = chunk_attention(q, row(cache["k"]), row(cache["v"]),
                                      start, use_pallas=self.use_pallas)
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_at(params, h, chunk_len - 1)

    def paged_prefill_chunk(self, params, cache, tokens, chunk_ids,
                            table, start, chunk_len):
        """ONE chunk of a prompt through the PAGED pool: chunk_ids
        (C // block_size,) int32 — the pages covering [start,
        start+C) (trash 0 past the allocation); table (max_blocks,)
        int32 — the sequence's full block table for the attention
        gather.  Semantics otherwise identical to
        :meth:`prefill_chunk`."""
        n_blk = chunk_ids.shape[0]
        bs = cache["k"].shape[2]               # [L, NB, BS, h, dh]
        chunk = tokens.shape[1]
        with jax.named_scope("veles.gpt.embed"):
            pos = jax.lax.dynamic_slice_in_dim(params["pos"], start, chunk)
            h = params["embed"][tokens] + pos

        def kv_hook(i, cache, q, k, v):
            with jax.named_scope("veles.gpt.kv_write"):
                def put(c, new):       # the chunk's pages of layer i
                    return c.at[i, chunk_ids].set(
                        new[0].astype(c.dtype).reshape(
                            n_blk, bs, self.heads, self.head_dim))
                cache = {"k": put(cache["k"], k), "v": put(cache["v"], v)}
            with jax.named_scope("veles.gpt.attn"):
                def gather(c):
                    g = c[i, table]            # (max_blocks, bs, h, dh)
                    return g.reshape(1, g.shape[0] * bs,
                                     self.heads, self.head_dim)
                att = chunk_attention(q, gather(cache["k"]),
                                      gather(cache["v"]), start,
                                      use_pallas=self.use_pallas)
            return cache, att

        h, cache = self._run_layers(params, cache, h, kv_hook)
        return cache, self._greedy_at(params, h, chunk_len - 1)

    # -- analytic FLOPs (cost_analysis counts the layer scan once) ---------
    def _per_token_layer_flops(self, attended):
        d, f = self.dim, self.cfg["mlp_ratio"] * self.dim
        return (2.0 * d * 3 * d          # qkv projection
                + 4.0 * attended * d     # QK^T + AV over the read KV
                + 2.0 * d * d            # output projection
                + 4.0 * d * f)           # mlp up + down

    def prefill_flops(self, bucket):
        """Forward FLOPs of one bucket prefill (causal-discounted
        attention, the ``train_step_flops`` convention) + one
        readout."""
        per_token = self.layers * self._per_token_layer_flops(
            bucket / 2.0)
        return bucket * per_token + 2.0 * self.dim * self.vocab

    def prefill_chunk_flops(self, chunk, max_seq):
        """Forward FLOPs of one prefill chunk: each chunk token
        attends to its whole prefix — counted at the ``max_seq / 2``
        mean extent (start is traced, so the analytic form can't see
        it) + one readout."""
        per_token = self.layers * self._per_token_layer_flops(
            max_seq / 2.0)
        return chunk * per_token + 2.0 * self.dim * self.vocab

    def verify_flops(self, slots, k, max_seq):
        """FLOPs of one K-draft verify step: K+1 query rows per slot,
        each reading the masked KV extent like a decode row."""
        per_token = (self.layers
                     * self._per_token_layer_flops(float(max_seq))
                     + 2.0 * self.dim * self.vocab)
        return slots * (k + 1.0) * per_token

    def decode_flops(self, slots, max_seq):
        """FLOPs of one decode step: every slot reads its masked KV
        buffer — counted at the full ``max_seq`` extent the dense
        masked path actually computes (the Pallas kernel's block skip
        makes this an upper bound on TPU)."""
        per_token = (self.layers
                     * self._per_token_layer_flops(float(max_seq))
                     + 2.0 * self.dim * self.vocab)
        return slots * per_token
