"""HybridGenModel: Mamba-2 mixers, latent mixture-of-experts layers and
grouped-query attention in ONE stack, behind the protocol
:class:`~veles_tpu.gen.engine.GenerativeEngine` already calls
(:mod:`veles_tpu.gen.model`).

Parameters are :mod:`veles_tpu.samples.hybrid_lm`'s: a list of
per-layer dicts, the kind of each given by a letter of
``cfg["pattern"]``.  Every layer is ``x <- x + f(RMSNorm(x))``; no
positional encoding of any kind; a final RMSNorm and an untied head.

The cache holds TWO kinds of state, one entry a layer:

- ``M``: the recurrent state ``h [slots, heads, head_dim, state]``
  (float32) and the convolution's tail ``conv [slots, kernel - 1,
  conv_dim]`` (the last inputs, before the convolution);
- ``*``: keys and values ``[slots, max_seq, kv_heads * head_dim]``
  (a position's heads side by side in one row, so that a decode step
  writes one row a slot and a KV head's keys are a slice of lanes);
- ``E``: nothing.

It follows the carry-and-write-in-place rule of the transformer's
cache: each layer's arrays enter and leave the program as they are
(the engine donates them), a prefill writes ONE slot's rows, a decode
step rewrites each array elementwise where the slot is active.  A
prefill starts from the zero state and overwrites the slot's, so
admission IS the reset; a padded bucket's tail gets ``dt = 0``, which
leaves the state as of the prompt's real last token; an inactive slot's
state does not move in a decode step.

Recurrent state cannot be paged, shared by prefix, chunked, verified
k tokens at once or shipped as pages: the model declares
``recurrent_state`` and the engine refuses those modes by name.

The expert layer is one chip's share of expert parallelism
(:mod:`veles_tpu.gen.experts`, shared with
:mod:`veles_tpu.gen.window_moe`): it routes over ALL ``router_width``
experts and computes the part of the result that the ``experts_held``
experts it holds give; an expert here is ungated, ``relu(l W1)^2 W2``
in the latent.  Both programs return, behind the tokens, that module's
``COUNTERS`` over the expert layers.
"""

import math

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.gen import experts
from veles_tpu.gen.experts import COUNTERS
from veles_tpu.samples import hybrid_lm

F32 = jnp.float32


def _rmsnorm(x, g, eps, out):
    x = x.astype(F32)
    x = x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps)
    return (x * g.astype(F32)).astype(out)


def ssd_chunked(xs, dt, A, B, C, chunk):
    """The state-space recurrence of one sequence from the zero state,
    in its chunked form: ``xs [T, H, P]``, ``dt [T, H]`` (float32, 0 on
    padding), ``A [H]``, ``B``/``C`` ``[T, G, N]``.  Returns ``(y [T,
    H, P] float32, h [H, P, N] float32 after the last token)``."""
    T, H, P = xs.shape
    G, N = B.shape[1], B.shape[2]
    r = H // G
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:     # rows with dt = 0 move nothing
        xs, dt, B, C = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                        for a in (xs, dt, B, C))
    nc = (T + pad) // Q
    a = (dt * A).reshape(nc, Q, G, r)
    cum = jnp.cumsum(a, axis=1)                         # [nc, Q, G, r]
    xdt = (xs.astype(F32) * dt[..., None]).reshape(nc, Q, G, r, P)
    Bc = B.reshape(nc, Q, G, N).astype(F32)
    Cc = C.reshape(nc, Q, G, N).astype(F32)
    # within a chunk: every earlier token's input, decayed to this one
    seg = cum[:, :, None] - cum[:, None]                # [nc, t, s, G, r]
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]
    decay = jnp.exp(jnp.where(lower, seg, -jnp.inf))
    cb = jnp.einsum("ctgn,csgn->ctsg", Cc, Bc)
    y = jnp.einsum("ctsgr,csgrp->ctgrp", decay * cb[..., None], xdt)
    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(cum[:, -1:] - cum)                 # [nc, Q, G, r]
    states = jnp.einsum("csgn,csgrp->cgrpn", Bc, xdt * to_end[..., None])
    whole = jnp.exp(cum[:, -1])                         # [nc, G, r]

    def carry(h, c):
        states_c, whole_c = c
        return whole_c[..., None, None] * h + states_c, h

    h, entering = jax.lax.scan(carry, jnp.zeros((G, r, P, N), F32),
                               (states, whole))
    # what the state entering the chunk still gives each of its tokens
    y = y + jnp.einsum("ctgn,cgrpn->ctgrp", Cc, entering) \
        * jnp.exp(cum)[..., None]
    return y.reshape(nc * Q, H, P)[:T], h.reshape(H, P, N)


class HybridGenModel(object):
    """The ``nemotron_h`` family behind the generative protocol.
    ``compute_dtype`` defaults to float32 (the CPU parity tests);
    serving deployments on the TPU pass ``jnp.bfloat16``.  The
    recurrent state, ``dt``, ``A`` and the router are float32
    whatever it is."""

    causal = True
    #: the engine refuses every mode that assumes K/V pages
    recurrent_state = True
    counters = COUNTERS

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
        self.ssm_heads = int(cfg["ssm_heads"])
        self.ssm_head_dim = int(cfg["ssm_head_dim"])
        self.ssm_groups = int(cfg["ssm_groups"])
        self.ssm_state = int(cfg["ssm_state"])
        if self.ssm_heads % self.ssm_groups:
            raise ValueError("%d state-space heads do not divide over %d "
                             "groups" % (self.ssm_heads, self.ssm_groups))
        self.d_inner, self.conv_dim = hybrid_lm.ssm_dims(cfg)
        self.conv_kernel = int(cfg["conv_kernel"])
        self.chunk = int(cfg["chunk"])
        self.router_width = int(cfg["router_width"])
        self.held = int(cfg["experts_held"])
        self.held_from = int(cfg.get("held_from", 0))
        if self.held_from + self.held > self.router_width:
            raise ValueError(
                "experts %d..%d are not among the router's %d"
                % (self.held_from, self.held_from + self.held,
                   self.router_width))
        self.top_k = int(cfg["top_k"])
        self.eps = float(cfg["norm_eps"])
        self.routed_scale = float(cfg["routed_scale"])
        self.compute_dtype = compute_dtype or jnp.float32
        #: up to this many rows ALL go through the held experts that a
        #: valid row chose; above, the pairs are sorted by expert
        self.dense_tokens = int(dense_tokens)
        #: the grouped product's kernel: None lets the platform decide
        self.use_pallas = use_pallas

    # -- params / cache ----------------------------------------------------
    def init_params(self, seed=0):
        return hybrid_lm.init_params(self.cfg, seed=seed)

    def cache_shape(self, slots, max_seq):
        """One entry a layer: name -> (shape, dtype)."""
        cd = jnp.dtype(self.compute_dtype)
        kv = (int(slots), int(max_seq), self.kv_heads * self.head_dim)
        table = {
            "M": {"h": ((int(slots), self.ssm_heads, self.ssm_head_dim,
                         self.ssm_state), jnp.dtype(F32)),
                  "conv": ((int(slots), self.conv_kernel - 1,
                            self.conv_dim), cd)},
            "*": {"k": (kv, cd), "v": (kv, cd)},
            "E": {}}
        return [table[kind] for kind in self.pattern]

    def init_cache(self, slots, max_seq):
        return {"layers": [
            {name: jnp.zeros(shape, dtype)
             for name, (shape, dtype) in layer.items()}
            for layer in self.cache_shape(slots, max_seq)]}

    def _nbytes(self, slots, max_seq, kinds):
        return sum(int(numpy.prod(shape)) * dtype.itemsize
                   for kind, layer in zip(self.pattern,
                                          self.cache_shape(slots, max_seq))
                   if kind in kinds
                   for shape, dtype in layer.values())

    def cache_nbytes(self, slots, max_seq):
        """The whole tree: recurrent state, convolution tails, K and V."""
        return self._nbytes(slots, max_seq, "M*")

    def recurrent_nbytes(self, slots):
        """The part of :meth:`cache_nbytes` that is not keys and values
        (it does not grow with ``max_seq``)."""
        return self._nbytes(slots, 1, "M")

    def param_specs(self):
        """Replicated: this model is divided over chips by its experts
        and its layers, not over a ``model`` axis."""
        from jax.sharding import PartitionSpec as P
        return jax.tree.map(lambda _leaf: P(),
                            hybrid_lm.param_shapes(self.cfg))

    def cache_spec(self):
        from jax.sharding import PartitionSpec as P
        return {"layers": [{name: P() for name in layer}
                           for layer in self.cache_shape(1, 1)]}

    # -- the three kinds of layer ------------------------------------------
    def _dot(self, x, w):
        cd = self.compute_dtype
        return jnp.dot(x.astype(cd), w.astype(cd),
                       preferred_element_type=F32)

    def _ssm_project(self, p, x):
        cd = self.compute_dtype
        with jax.named_scope("veles.hybrid.ssm.in_proj"):
            u = _rmsnorm(x, p["norm"], self.eps, cd)
            zxbcdt = self._dot(u, p["w_in"])
            z = zxbcdt[:, :self.d_inner].astype(cd)
            xbc = zxbcdt[:, self.d_inner:self.d_inner
                         + self.conv_dim].astype(cd)
            dt = jax.nn.softplus(zxbcdt[:, self.d_inner + self.conv_dim:]
                                 + p["dt_bias"])
        return z, xbc, dt

    def _ssm_split(self, xbc):
        """The convolution's output -> ``xs [T, H, P]``, ``B``/``C``
        ``[T, G, N]``."""
        T = xbc.shape[0]
        G, N = self.ssm_groups, self.ssm_state
        xs = xbc[:, :self.d_inner].reshape(T, self.ssm_heads,
                                           self.ssm_head_dim)
        B = xbc[:, self.d_inner:self.d_inner + G * N].reshape(T, G, N)
        C = xbc[:, self.d_inner + G * N:].reshape(T, G, N)
        return xs, B, C

    def _ssm_finish(self, p, x, y, xs, z):
        """``y [T, H, P]`` float32 -> the layer's new residual."""
        cd = self.compute_dtype
        T, G = y.shape[0], self.ssm_groups
        with jax.named_scope("veles.hybrid.ssm.scan"):
            y = y + p["D"][:, None] * xs.astype(F32)
            y = y.reshape(T, self.d_inner) * jax.nn.silu(z.astype(F32))
            y = _rmsnorm(y.reshape(T, G, -1),
                         p["norm_g"].reshape(G, -1), self.eps,
                         cd).reshape(T, self.d_inner)
        with jax.named_scope("veles.hybrid.ssm.out_proj"):
            return x + self._dot(y, p["w_out"]).astype(x.dtype)

    def _ssm_prefill(self, p, state, x, slot, length):
        """One sequence ``x [T, d]`` from the zero state; the slot's
        state becomes that after token ``length - 1``."""
        cd = self.compute_dtype
        T, k = x.shape[0], self.conv_kernel
        z, xbc, dt = self._ssm_project(p, x)
        with jax.named_scope("veles.hybrid.ssm.conv"):
            padded = jnp.concatenate(
                [jnp.zeros((k - 1, self.conv_dim), cd), xbc])
            conv = sum(padded[j:j + T].astype(F32)
                       * p["conv_w"][j].astype(F32) for j in range(k))
            # the inputs at length-(k-1) .. length-1, zeros before 0
            tail = jax.lax.dynamic_slice_in_dim(padded, length, k - 1)
            xbc = jax.nn.silu(conv + p["conv_b"].astype(F32)).astype(cd)
        with jax.named_scope("veles.hybrid.ssm.scan"):
            xs, B, C = self._ssm_split(xbc)
            # a padded row moves nothing: decay 1, input 0
            dt = jnp.where(jnp.arange(T)[:, None] < length, dt, 0.0)
            y, h = ssd_chunked(xs, dt, -jnp.exp(p["A_log"]), B, C,
                               self.chunk)
        x = self._ssm_finish(p, x, y, xs, z)
        with jax.named_scope("veles.hybrid.ssm.state_write"):
            state = {
                "h": jax.lax.dynamic_update_slice(
                    state["h"], h[None], (slot, 0, 0, 0)),
                "conv": jax.lax.dynamic_update_slice(
                    state["conv"], tail[None].astype(state["conv"].dtype),
                    (slot, 0, 0))}
        return state, x

    def _ssm_decode(self, p, state, x, active):
        """One token a slot, ``x [slots, d]``: one step of the
        recurrence, the convolution over the kept columns and the new
        one.  An inactive slot's state stays as it was."""
        cd = self.compute_dtype
        r = self.ssm_heads // self.ssm_groups
        z, xbc, dt = self._ssm_project(p, x)
        with jax.named_scope("veles.hybrid.ssm.conv"):
            window = jnp.concatenate(
                [state["conv"], xbc[:, None].astype(state["conv"].dtype)],
                axis=1)                                 # [slots, k, c]
            conv = (window.astype(F32)
                    * p["conv_w"].astype(F32)[None]).sum(1)
            xbc = jax.nn.silu(conv + p["conv_b"].astype(F32)).astype(cd)
        with jax.named_scope("veles.hybrid.ssm.scan"):
            xs, B, C = self._ssm_split(xbc)
            B = jnp.repeat(B.astype(F32), r, axis=1)    # [slots, H, N]
            C = jnp.repeat(C.astype(F32), r, axis=1)
            decay = jnp.exp(dt * -jnp.exp(p["A_log"]))  # [slots, H]
            h = (decay[..., None, None] * state["h"]
                 + (dt[..., None] * xs.astype(F32))[..., None]
                 * B[:, :, None, :])
            y = (h * C[:, :, None, :]).sum(-1)          # [slots, H, P]
        with jax.named_scope("veles.hybrid.ssm.state_write"):
            state = {
                "h": jnp.where(active[:, None, None, None], h,
                               state["h"]),
                "conv": jnp.where(active[:, None, None], window[:, 1:],
                                  state["conv"])}
        return state, self._ssm_finish(p, x, y, xs, z)

    def _attn_project(self, p, x):
        cd = self.compute_dtype
        u = _rmsnorm(x, p["norm"], self.eps, cd)
        g = self.kv_heads
        q, k, v = (jnp.einsum("td,dhx->thx", u, p[name].astype(cd),
                              preferred_element_type=F32).astype(cd)
                   for name in ("wq", "wk", "wv"))
        # query head j reads KV head j // (heads / kv_heads)
        return q.reshape(q.shape[0], g, self.heads // g,
                         self.head_dim), k, v

    def _attn_out(self, p, x, att):
        cd = self.compute_dtype
        att = att.reshape(att.shape[0], self.heads, self.head_dim)
        return x + jnp.einsum("thx,hxd->td", att.astype(cd),
                              p["wo"].astype(cd),
                              preferred_element_type=F32).astype(x.dtype)

    def _attn_prefill(self, p, state, x, slot):
        T = x.shape[0]
        with jax.named_scope("veles.hybrid.attn"):
            q, k, v = self._attn_project(p, x)
            scores = jnp.einsum("tgqx,sgx->gqts", q, k,
                                preferred_element_type=F32) \
                / math.sqrt(self.head_dim)
            causal = jnp.tril(jnp.ones((T, T), bool))
            scores = jnp.where(causal[None, None], scores, -jnp.inf)
            att = jnp.einsum("gqts,sgx->tgqx",
                             jax.nn.softmax(scores, -1).astype(v.dtype), v,
                             preferred_element_type=F32)
            x = self._attn_out(p, x, att)
        with jax.named_scope("veles.hybrid.attn.kv_write"):
            def put(c, new):    # [slot, :T] <- the prompt
                return jax.lax.dynamic_update_slice(
                    c, new.astype(c.dtype).reshape(1, T, -1),
                    (slot, 0, 0))
            state = {"k": put(state["k"], k), "v": put(state["v"], v)}
        return state, x

    def _attn_decode(self, p, state, x, positions, active):
        slots, dh = x.shape[0], self.head_dim
        idx = jnp.arange(slots)
        with jax.named_scope("veles.hybrid.attn"):
            q, k, v = self._attn_project(p, x)
        with jax.named_scope("veles.hybrid.attn.kv_write"):
            def put(c, new):    # one row a slot; inactive: as it was
                return c.at[idx, positions].set(
                    jnp.where(active[:, None],
                              new.astype(c.dtype).reshape(slots, -1),
                              c[idx, positions]))
            state = {"k": put(state["k"], k), "v": put(state["v"], v)}
        with jax.named_scope("veles.hybrid.attn"):
            seen = jnp.arange(state["k"].shape[1])[None] \
                <= positions[:, None]
            att = []
            for head in range(self.kv_heads):   # a slice of lanes each
                lanes = slice(head * dh, (head + 1) * dh)
                scores = jnp.einsum(
                    "bqx,bsx->bqs", q[:, head], state["k"][:, :, lanes],
                    preferred_element_type=F32) / math.sqrt(dh)
                scores = jnp.where(seen[:, None], scores, -jnp.inf)
                att.append(jnp.einsum(
                    "bqs,bsx->bqx",
                    jax.nn.softmax(scores, -1).astype(q.dtype),
                    state["v"][:, :, lanes], preferred_element_type=F32))
            att = jnp.stack(att, axis=1)
            x = self._attn_out(p, x, att)
        return state, x

    def _moe(self, p, x, valid):
        """``x [T, d]`` -> ``(x', counters)``; ``valid [T]`` says which
        rows are real tokens (the counters leave the others out)."""
        cd = self.compute_dtype
        with jax.named_scope("veles.hybrid.moe.router"):
            u = _rmsnorm(x, p["norm"], self.eps, cd)
            local, g = experts.route(
                u, p["router"], self.top_k, self.held_from, self.held,
                e_bias=p["e_bias"], scale=self.routed_scale)
            loads = experts.load_counts(local, valid, self.held,
                                        self.top_k)
        with jax.named_scope("veles.hybrid.moe.latent"):
            latent = self._dot(u, p["w_down"]).astype(cd)
        with jax.named_scope("veles.hybrid.moe.experts"):
            mixed, counts = experts.mix(
                "relu2", p, latent, local, g, valid, loads, self.held,
                self.top_k, self.dense_tokens, cd, self.use_pallas)
        with jax.named_scope("veles.hybrid.moe.latent"):
            x = x + self._dot(mixed, p["w_up"]).astype(x.dtype)
        with jax.named_scope("veles.hybrid.moe.shared"):
            hidden = experts.relu2(self._dot(u, p["s1"])).astype(cd)
            x = x + self._dot(hidden, p["s2"]).astype(x.dtype)
        return x, counts

    def head_logits(self, params, x):
        """``x [rows, d]`` -> float32 logits over the head's rows of
        the vocabulary."""
        cd = self.compute_dtype
        with jax.named_scope("veles.hybrid.readout"):
            x = _rmsnorm(x, params["norm_f"], self.eps, cd)
            return jnp.einsum("bd,vd->bv", x, params["head"].astype(cd),
                              preferred_element_type=F32)

    def _greedy(self, params, x, total):
        """One greedy token a row of ``x``, then the counters."""
        logits = self.head_logits(params, x)
        with jax.named_scope("veles.hybrid.readout"):
            return jnp.concatenate(
                [jnp.argmax(logits, axis=-1).astype(jnp.int32), total])

    # -- the protocol's two programs ---------------------------------------
    def _stack(self, params, cache, x, valid, ssm, attn):
        """The layers in their published order over ``x [T, d]``:
        ``ssm(p, state, x)`` and ``attn(p, state, x)`` give ``(state',
        x')`` for the two kinds that keep state; ``valid [T]`` marks the
        rows the expert layers count.  Returns ``(cache', x, counters)``.
        The loop is unrolled (the layers are not alike) and has no scope
        of its own."""
        total = jnp.zeros(len(COUNTERS), jnp.int32)
        states = []
        for kind, p, state in zip(self.pattern, params["layers"],
                                  cache["layers"]):
            if kind == "M":
                state, x = ssm(p, state, x)
            elif kind == "*":
                state, x = attn(p, state, x)
            else:
                x, counts = self._moe(p, x, valid)
                total = experts.merge(total, counts)
            states.append(state)
        return {"layers": states}, x, total

    def _prefill_rows(self, params, cache, tokens, slot, length):
        """Every row of the bucket through the stack, the slot's state
        written: ``(cache', x [bucket, d], counters)``."""
        with jax.named_scope("veles.hybrid.embed"):
            x = params["embed"][tokens[0]].astype(self.compute_dtype)
        return self._stack(
            params, cache, x, jnp.arange(tokens.shape[1]) < length,
            lambda p, state, x: self._ssm_prefill(p, state, x, slot,
                                                  length),
            lambda p, state, x: self._attn_prefill(p, state, x, slot))

    def prefill(self, params, cache, tokens, slot, length):
        """tokens (1, bucket) int32 (zero-padded past ``length``) ->
        ``(cache', [next token, *COUNTERS])``.  Nothing of the slot's
        old state is read."""
        cache, last, total = self.prefill_hidden(params, cache, tokens,
                                                 slot, length)
        return cache, self._greedy(params, last, total)

    def prefill_hidden(self, params, cache, tokens, slot, length):
        """:meth:`prefill` up to the head: ``(cache', the residual
        stream of token ``length - 1`` [1, d], counters)``."""
        cache, x, total = self._prefill_rows(params, cache, tokens, slot,
                                             length)
        return cache, jax.lax.dynamic_slice_in_dim(x, length - 1, 1,
                                                   axis=0), total

    def decode(self, params, cache, tokens, positions, active):
        """ONE decode step over every slot -> ``(cache', [slots tokens,
        *COUNTERS])``.  Inactive slots ride along computing garbage
        (they choose no expert); none of their state moves."""
        cache, x, total = self.decode_hidden(params, cache, tokens,
                                             positions, active)
        return cache, self._greedy(params, x, total)

    def decode_hidden(self, params, cache, tokens, positions, active):
        """:meth:`decode` up to the head: ``(cache', the residual
        stream [slots, d], counters)``."""
        with jax.named_scope("veles.hybrid.embed"):
            x = params["embed"][tokens].astype(self.compute_dtype)
        return self._stack(
            params, cache, x, active,
            lambda p, state, x: self._ssm_decode(p, state, x, active),
            lambda p, state, x: self._attn_decode(p, state, x, positions,
                                                  active))

    def logits(self, params, tokens):
        """float32 logits ``[T, vocab]`` of ONE sequence through the
        prefill path's layers, keeping no cache: what the parity tests
        compare with the reference."""
        tokens = jnp.asarray(tokens, jnp.int32).reshape(1, -1)
        T = tokens.shape[1]
        _cache, x, _total = self._prefill_rows(
            params, self.init_cache(1, T), tokens, 0, T)
        return self.head_logits(params, x)

    # -- analytic flops (the ledger's; matrix work only) -------------------
    def _per_token_flops(self, attended):
        """One token through the stack: every dense product, its
        ``top_k`` experts' share held here, attention over ``attended``
        positions."""
        d = self.dim
        cfg = self.cfg
        ssm = 2 * d * (self.d_inner + self.conv_dim + self.ssm_heads) \
            + 2 * self.d_inner * d \
            + 6 * self.d_inner * self.ssm_state
        attn = 4 * d * (self.heads + self.kv_heads) * self.head_dim \
            + 4 * self.heads * self.head_dim * attended
        expert = 4 * cfg["latent"] * cfg["expert_width"]
        moe = 2 * d * self.router_width + 4 * d * cfg["latent"] \
            + 4 * d * cfg["shared_width"] \
            + expert * self.top_k * self.held / float(self.router_width)
        return (self.pattern.count("M") * ssm
                + self.pattern.count("*") * attn
                + self.pattern.count("E") * moe)

    def prefill_flops(self, bucket):
        return bucket * self._per_token_flops(bucket / 2.0) \
            + 2.0 * self.dim * self.vocab

    def decode_flops(self, slots, max_seq):
        return slots * (self._per_token_flops(float(max_seq))
                        + 2.0 * self.dim * self.vocab)
