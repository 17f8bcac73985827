"""Plain reference of the ``cohere2_moe`` family (Command A+: sliding
window layers with rotary positions among full layers without, a
parallel block, sigmoid-routed gated experts beside averaged shared
experts) as ``veles_tpu/samples/window_moe_lm.py`` lays its parameters
out.  One whole forward pass over ONE sequence in straightforward
``jax.numpy`` at float32 / ``highest``: no cache, no chunks, no ring, no
kernels, every held expert in a loop over all tokens with a masked
weight, the shared experts one at a time, nothing imported from the
program.  The weights are made HERE from the seed (in the type they are
served in) and GIVEN to the program.

Every layer is ``x <- x + Attn(u) + FFN(u)`` with ``u = LayerNorm(x)``
(mean and variance over the width, eps ``layer_norm_eps``, times a
weight, no bias): ONE norm a layer (``use_parallel_block``).

``Attn``  ``q, k, v = u Wq, u Wk, u Wv`` (no bias, no QK-norm); query
       head ``j`` reads KV head ``j // (heads / kv_heads)``.  A
       ``sliding_attention`` layer rotates ``q`` and ``k``
       (``rope_gptj``: the pairs ``(2i, 2i + 1)`` of the whole head by
       ``pos * rope_theta^(-2i / head_dim)``) and position ``t`` sees
       keys ``t - sliding_window + 1 .. t``; a ``full_attention`` layer
       has no positions of any kind and sees ``0 .. t``.  ``softmax(q
       k^T / sqrt(head_dim)) v``, then ``Wo``.
``FFN``   ``s = sigmoid(u Wr)`` in float32 over ALL ``router_width``
       experts; the ``num_experts_per_tok`` largest; ``w = s[chosen] /
       (sum of the chosen s + 1e-20)`` (``norm_topk_prob``; no bias
       term, no scaling factor); for the experts HELD here
       (``held_from .. held_from + num_experts``) ``E(u) = (silu(u Wg)
       * (u Wu)) Wd``; the ``num_shared_experts`` of the same form run
       on every token; ``FFN(u) = sum_e w_e E_e(u) + (1 / shared) sum_j
       S_j(u)``.  What the absent experts would add is left out, as in
       the program.

After the last layer a LayerNorm and ``logits = h E^T * logit_scale``
over the vocabulary's slice (the embedding is the head).

Departures from the published description, each an ``assumed`` line of
the configuration's file: rotary positions on the sliding layers only
(the catalog's "global NoPE"); the window counts the token itself; the
shared experts are averaged among themselves and the average ADDED to
the routed sum; an expert's width is ``intermediate_size``; the router
and its scores are float32.  Left out: the vision tower (text requests
never run it).  Attention is computed a block of queries at a time, in a
sliding layer of a long sequence against the keys its window can reach
alone, which changes no number.

``quant="fp8"`` (e4m3) rounds both operands of every linear product
(projections, experts, shared experts, head) to 8 bits, per-row scale
for activations and per-output-channel for weights: the CONTROL, the
nearest precision below the configuration's bfloat16.  The router stays
float32 there too: the configuration states it so on both sides.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def dims(config):
    """The sizes every function here needs, from the configuration's
    (published) keys."""
    return {
        "d": config["hidden_size"], "vocab": config["vocab_size"],
        "kinds": list(config["layer_types"][:config["num_hidden_layers"]]),
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window"],
        "theta": float(config["rope_theta"]),
        "router_width": config["router_width"],
        "held": config["num_experts"],
        "held_from": config.get("held_from", 0),
        "top_k": config["num_experts_per_tok"],
        "width": config["intermediate_size"],
        "shared": config["num_shared_experts"],
        "eps": float(config["layer_norm_eps"]),
        "logit_scale": float(config["logit_scale"]),
    }


def shapes(config):
    """The parameter layout: a list of per-layer tables under
    ``layers``, each leaf ``(shape, init, dtype or None = the served
    type)``.  The family's initialisation: normal, 0.02, on every linear
    weight and on the embedding."""
    m = dims(config)
    d, f = m["d"], m["width"]
    wide = 0.02 * float(config.get("init_gain", 1.0))
    layer = {
        "norm": ((d,), "ones", None),
        "wq": ((d, m["q_heads"], m["head_dim"]), wide, None),
        "wk": ((d, m["kv_heads"], m["head_dim"]), wide, None),
        "wv": ((d, m["kv_heads"], m["head_dim"]), wide, None),
        "wo": ((m["q_heads"], m["head_dim"], d), wide, None),
        "router": ((d, m["router_width"]), wide, F32),
        "wg": ((m["held"], d, f), wide, None),
        "wu": ((m["held"], d, f), wide, None),
        "wd": ((m["held"], f, d), wide, None),
        "sg": ((m["shared"], d, f), wide, None),
        "su": ((m["shared"], d, f), wide, None),
        "sd": ((m["shared"], f, d), wide, None)}
    return {"embed": ((m["vocab"], d), wide, None),
            "layers": [dict(layer) for _kind in m["kinds"]],
            "norm_f": ((d,), "ones", None)}


def init_params(config, seed, dtype=jnp.bfloat16):
    """Every weight drawn on the device from the seed, in the type it is
    served in; one jitted call a layer, so that the draw's float32
    temporaries are one layer's and not the model's."""
    table = shapes(config)

    def make(key, entry):
        shape, init, own = entry
        kind = own or dtype
        if init == "ones":
            return jnp.ones(shape, kind)
        return (jax.random.normal(key, shape, F32) * init).astype(kind)

    @functools.partial(jax.jit, static_argnums=(1,))
    def build(key, entries):
        return {name: make(jax.random.fold_in(key, i), entry)
                for i, (name, entry) in enumerate(entries)}

    def one(key, entries):      # the layers share one program
        return build(key, tuple(entries.items()))

    root = jax.random.key(int(seed))
    top = one(jax.random.fold_in(root, 0),
              {k: v for k, v in table.items() if k != "layers"})
    top["layers"] = [one(jax.random.fold_in(root, i + 1), layer)
                     for i, layer in enumerate(table["layers"])]
    return top


# -- the layers' equations --------------------------------------------------

def layernorm(x, g, eps):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _fp8(x, axes):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _linear(x, w, quant):
    """x [T, K] @ w [K, N]."""
    if quant is not None:
        if quant != "fp8":
            raise ValueError("unknown control precision %r" % quant)
        x, w = _fp8(x, (1,)), _fp8(w, (0,))
    return jnp.dot(x, w, precision=HIGHEST)


def rotate(x, positions, theta):
    """``x [T, heads, head_dim]``: each pair ``(x[2i], x[2i + 1])``
    turned by the angle ``pos * theta^(-2i / head_dim)``."""
    T, H, dh = x.shape
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    angle = positions.astype(F32)[:, None, None] * inv[None, None, :]
    pairs = x.reshape(T, H, dh // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(T, H, dh)


def _query_block(T, keys):
    """Queries a block: the scores of a block against ``keys`` keys stay
    near half a gigabyte at 128 heads."""
    block = 1
    while block * 2 <= T and block * 2 * keys <= 2 ** 20:
        block *= 2
    while T % block:
        block //= 2
    return block


def attention(p, u, m, kind, quant=None):
    """u [T, d] -> [T, d]: causal grouped-query attention, a block of
    queries at a time; against all keys, or in a sliding layer of a long
    sequence against the keys its window can reach (those before are
    masked either way, so the slice changes no number)."""
    T, d = u.shape
    Hq, Hk, dh = m["q_heads"], m["kv_heads"], m["head_dim"]
    sliding = kind == "sliding_attention"
    if not sliding and kind != "full_attention":
        raise ValueError("unknown layer type %r" % kind)
    k = _linear(u, p["wk"].reshape(d, -1), quant).reshape(T, Hk, dh)
    v = _linear(u, p["wv"].reshape(d, -1), quant).reshape(T, Hk, dh)
    if sliding:
        k = rotate(k, jnp.arange(T), m["theta"])
    wq, wo = p["wq"].reshape(d, -1), p["wo"].reshape(-1, d)
    band = sliding and T > 2 * m["window"]
    block = _query_block(T, 2 * m["window"] if band else T)
    reach = m["window"] + block if band else T
    if band:    # rows before position 0, so that every slice is whole
        k, v = (jnp.pad(a, ((m["window"], 0), (0, 0), (0, 0)))
                for a in (k, v))

    def one(first):
        at = first + jnp.arange(block)
        q = _linear(jax.lax.dynamic_slice_in_dim(u, first, block), wq,
                    quant).reshape(block, Hq, dh)
        if sliding:
            q = rotate(q, at, m["theta"])
        # the query head's group: head j reads KV head j // (Hq / Hk)
        q = q.reshape(block, Hk, Hq // Hk, dh)
        if band:    # the keys at positions first - window .. at[-1]
            pos = first - m["window"] + jnp.arange(reach)
            keys, values = (jax.lax.dynamic_slice_in_dim(a, first, reach)
                            for a in (k, v))
        else:
            pos, keys, values = jnp.arange(T), k, v
        scores = jnp.einsum("tgrx,sgx->grts", q, keys,
                            precision=HIGHEST) / math.sqrt(dh)
        seen = (pos[None, :] >= 0) & (pos[None, :] <= at[:, None])
        if sliding:
            seen &= at[:, None] - pos[None, :] < m["window"]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        att = jnp.einsum("grts,sgx->tgrx", jax.nn.softmax(scores, -1),
                         values, precision=HIGHEST)
        return _linear(att.reshape(block, -1), wo, quant)

    return jax.lax.map(one, jnp.arange(0, T, block)).reshape(T, d)


def route(p, u, m):
    """The routing weight of every (token, expert of the router's whole
    width): ``w`` where the expert is among the token's ``top_k``, else
    0.  float32 whatever ``quant``."""
    s = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=HIGHEST))
    _best, chosen = jax.lax.top_k(s, m["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(w)


def expert(u, wg, wu, wd, quant=None):
    """One gated expert over every token: ``(silu(u Wg) * (u Wu))
    Wd``."""
    hidden = jax.nn.silu(_linear(u, wg.astype(F32), quant)) \
        * _linear(u, wu.astype(F32), quant)
    return _linear(hidden, wd.astype(F32), quant)


def moe_routed(p, u, m, quant=None):
    """What the experts held here add."""
    weights = jax.lax.dynamic_slice_in_dim(
        route(p, u, m), m["held_from"], m["held"], axis=1)

    def one(acc, e):
        wg, wu, wd, w = e
        return acc + w[:, None] * expert(u, wg, wu, wd, quant), None

    mixed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            (p["wg"], p["wu"], p["wd"], weights.T))
    return mixed


def moe_shared(p, u, m, quant=None):
    """The shared experts on every token, averaged."""
    def one(acc, e):
        return acc + expert(u, *e, quant=quant), None

    total, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            (p["sg"], p["su"], p["sd"]))
    return total / m["shared"]


def ffn(p, u, m, quant=None):
    return moe_routed(p, u, m, quant) + moe_shared(p, u, m, quant)


#: a layer's weights that stay in the type they are stored in: the
#: expert loops raise one expert at a time
_STORED = ("wg", "wu", "wd", "sg", "su", "sd")


def _as_f32(tree):
    return {k: v if k in _STORED else v.astype(F32)
            for k, v in tree.items()}


def layer(p, x, m, kind, quant=None):
    """One parallel block: both halves from the SAME ``u``."""
    u = layernorm(x, p["norm"], m["eps"])
    return x + attention(p, u, m, kind, quant) + ffn(p, u, m, quant)


def logits_at(params, config, tokens, rows, quant=None):
    """float32 logits ``[len(rows), vocab]`` of the positions ``rows``
    of ONE sequence ``tokens`` (int32 ``[T]``; what lies past the last
    row of interest is padding, which causality keeps out of sight)."""
    m = dims(config)
    x = params["embed"][tokens].astype(F32)
    for kind, stored in zip(m["kinds"], params["layers"]):
        x = layer(_as_f32(stored), x, m, kind, quant)
    x = layernorm(x[rows], params["norm_f"].astype(F32), m["eps"])
    return _linear(x, params["embed"].astype(F32).T, quant) \
        * m["logit_scale"]


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _gaps_jit(params, sequence, rows, served, spec, quant):
    config = json.loads(spec)
    reference = logits_at(params, config, sequence, rows, None)
    chosen = served if quant is None else jnp.argmax(
        logits_at(params, config, sequence, rows, quant), axis=-1)
    picked = jnp.take_along_axis(reference, chosen[:, None], axis=1)[:, 0]
    return reference.max(axis=-1) - picked


def served_gaps(params, config, prompt, served, pad_to, max_rows,
                quant=None):
    """For each served token: how far its reference logit lies below
    the reference's best at that position (0 where it IS the best).
    With ``quant``, the same for the token the lower precision puts
    first instead of the served one (the control).  The sequence is
    padded to the power of two at or above ``pad_to`` (at least prompt
    + served), so a cell's requests run a handful of compiled programs,
    one a length class; ``max_rows`` fixes the rows compared.  Returns
    a float32 array ``[len(served)]``."""
    import numpy
    n, count = len(prompt), len(served)
    size = 1
    while size < max(pad_to, n + count):
        size *= 2
    sequence = numpy.zeros(size, numpy.int32)
    sequence[:n] = prompt
    sequence[n:n + count - 1] = served[:-1]
    rows = numpy.zeros(max_rows, numpy.int32)
    rows[:count] = numpy.arange(n - 1, n + count - 1)
    chosen = numpy.zeros(max_rows, numpy.int32)
    chosen[:count] = served
    keys = {k: config[k] for k in _SHAPE_KEYS if k in config}
    gaps = _gaps_jit(params, jnp.asarray(sequence), jnp.asarray(rows),
                     jnp.asarray(chosen), json.dumps(keys, sort_keys=True),
                     quant)
    return numpy.asarray(gaps)[:count]


_SHAPE_KEYS = (
    "hidden_size", "vocab_size", "layer_types", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "sliding_window", "rope_theta", "router_width", "num_experts",
    "held_from", "num_experts_per_tok", "intermediate_size",
    "num_shared_experts", "layer_norm_eps", "logit_scale")
