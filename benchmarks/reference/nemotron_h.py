"""Plain reference of the ``nemotron_h`` family (Nemotron-H / Nemotron 3:
Mamba-2 mixers, LatentMoE feed-forward layers and grouped-query
attention in one stack) as ``veles_tpu/samples/hybrid_lm.py`` lays its
parameters out.  One whole forward pass over ONE sequence in
straightforward ``jax.numpy`` at float32 / ``highest``: the recurrence
as a plain sequential scan (not the chunked form), every held expert in
a loop over all tokens with a masked weight, no cache, no batching, no
kernels, nothing imported from the program.  The weights are made HERE
from the seed (in the type they are served in) and GIVEN to the program.

Every layer is ``x <- x + f(RMSNorm(x))`` (eps ``norm_eps``); ``f`` by
the layer's letter in ``hybrid_override_pattern``:

``M``  ``[z | xBC | dt] = u W_in``; ``xBC <- silu(causal depthwise
       conv(xBC, k) + b)``, split into ``xs`` [heads, head_dim], ``B``
       and ``C`` [groups, state]; ``dt <- softplus(dt + dt_bias)``,
       ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (x)
       B_t``; ``y_t = h_t C_t + D xs_t``; ``y <- RMSNorm over each
       group's channels (y * silu(z)) * g``; ``out = y W_out``.
``*``  ``q, k, v = u W_q, u W_k, u W_v``; causal ``softmax(q k^T /
       sqrt(head_dim)) v`` with ``heads / kv_heads`` query heads a KV
       head; ``out = a W_o``.  No positional encoding of any kind.
``E``  ``s = sigmoid(u W_r)`` in float32 over ALL ``router_width``
       experts; the ``top_k`` largest ``s + e_bias``; ``g = s[chosen] /
       (sum + 1e-20) * routed_scaling_factor`` (the sum over all chosen
       experts, held here or not); ``l = u W_down``; for the experts
       HELD here (``held_from .. held_from + n_routed_experts``)
       ``y_e = relu(l W1_e)^2 W2_e``; ``out = (sum_e g_e y_e) W_up +
       relu(u S1)^2 S2``.  What the absent experts would add is left
       out, as in the program.

After the last layer ``RMSNorm`` and an untied head over the
vocabulary's slice.  Left out: the multi-token-prediction module (a
drafter; it adds nothing to the next token's logits).

``quant="fp8"`` (e4m3) rounds both operands of every linear product
(projections, experts, latent, shared expert, head) to 8 bits, per-row
scale for activations and per-output-channel for weights: the CONTROL,
the nearest precision below the configuration's bfloat16.  The router
stays float32 there too: the configuration states it so on both sides.
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
    heads, head_dim = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    d_inner = heads * head_dim
    return {
        "d": config["hidden_size"], "vocab": config["vocab_size"],
        "pattern": config["hybrid_override_pattern"][
            :config["num_hidden_layers"]],
        "ssm_heads": heads, "ssm_head_dim": head_dim, "groups": groups,
        "state": state, "d_inner": d_inner,
        "conv_dim": d_inner + 2 * groups * state,
        "conv_k": config["conv_kernel"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "router_width": config["router_width"],
        "held": config["n_routed_experts"],
        "held_from": config.get("held_from", 0),
        "top_k": config["num_experts_per_tok"],
        "latent": config["moe_latent_size"],
        "expert_width": config["moe_intermediate_size"],
        "shared_width": config["moe_shared_expert_intermediate_size"],
        "scale": float(config["routed_scaling_factor"]),
        "eps": float(config["norm_eps"]),
    }


def shapes(config):
    """The parameter layout: a list of per-layer tables under
    ``layers`` (the layers are not alike, so nothing is stacked), each
    leaf ``(shape, init, dtype or None = the served type)``."""
    m = dims(config)
    d = m["d"]
    # the family's initialisation: 0.02 on every linear weight, the
    # projections back into the residual stream divided by the root of
    # the PUBLISHED depth (rescale_prenorm_residual)
    gain = float(config.get("init_gain", 1.0))
    wide = 0.02 * gain
    deep = wide / math.sqrt(config.get("published", {}).get(
        "num_hidden_layers", len(m["pattern"])))
    layers = []
    for kind in m["pattern"]:
        if kind == "M":
            layers.append({
                "norm": ((d,), "ones", None),
                "w_in": ((d, m["d_inner"] + m["conv_dim"]
                          + m["ssm_heads"]), wide, None),
                "conv_w": ((m["conv_k"], m["conv_dim"]), "conv", None),
                "conv_b": ((m["conv_dim"],), "conv", None),
                "dt_bias": ((m["ssm_heads"],), "dt_bias", F32),
                "A_log": ((m["ssm_heads"],), "A_log", F32),
                "D": ((m["ssm_heads"],), "ones", F32),
                "norm_g": ((m["d_inner"],), "ones", None),
                "w_out": ((m["d_inner"], d), deep, None)})
        elif kind == "*":
            layers.append({
                "norm": ((d,), "ones", None),
                "wq": ((d, m["q_heads"], m["head_dim"]), wide, None),
                "wk": ((d, m["kv_heads"], m["head_dim"]), wide, None),
                "wv": ((d, m["kv_heads"], m["head_dim"]), wide, None),
                "wo": ((m["q_heads"], m["head_dim"], d), deep, None)})
        elif kind == "E":
            layers.append({
                "norm": ((d,), "ones", None),
                "router": ((d, m["router_width"]), wide, F32),
                "e_bias": ((m["router_width"],), "zeros", F32),
                "w_down": ((d, m["latent"]), wide, None),
                "w1": ((m["held"], m["latent"], m["expert_width"]),
                       wide, None),
                "w2": ((m["held"], m["expert_width"], m["latent"]),
                       wide, None),
                "w_up": ((m["latent"], d), deep, None),
                "s1": ((d, m["shared_width"]), wide, None),
                "s2": ((m["shared_width"], d), deep, None)})
        else:
            raise ValueError("unknown layer kind %r in the pattern" % kind)
    return {"embed": ((m["vocab"], d), 0.02, None), "layers": layers,
            "norm_f": ((d,), "ones", None),
            "head": ((m["vocab"], d), wide, None)}


def init_params(config, seed, dtype=jnp.bfloat16):
    """Every weight drawn on the device from the seed, in the type it is
    served in; one jitted call a layer, so that the draw's float32
    temporaries are one layer's and not the model's."""
    table = shapes(config)
    lo, hi = config["time_step_min"], config["time_step_max"]
    floor = config["time_step_floor"]

    def make(key, entry):
        shape, init, own = entry
        kind = own or dtype
        if init == "ones":
            return jnp.ones(shape, kind)
        if init == "zeros":
            return jnp.zeros(shape, kind)
        if init == "conv":      # uniform within 1 / sqrt(kernel)
            bound = 1.0 / math.sqrt(config["conv_kernel"])
            return jax.random.uniform(key, shape, F32, -bound,
                                      bound).astype(kind)
        if init == "A_log":     # A in (1, 16), as Mamba-2 draws it
            return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
        if init == "dt_bias":   # inverse softplus of dt in [min, max]
            dt = jnp.exp(jax.random.uniform(key, shape, F32)
                         * (math.log(hi) - math.log(lo)) + math.log(lo))
            dt = jnp.maximum(dt, floor)
            return dt + jnp.log(-jnp.expm1(-dt))
        return (jax.random.normal(key, shape, F32) * init).astype(kind)

    @functools.partial(jax.jit, static_argnums=(1,))
    def build(key, entries):
        return {name: make(jax.random.fold_in(key, i), entry)
                for i, (name, entry) in enumerate(entries)}

    def one(key, entries):      # layers of one kind share one program
        return build(key, tuple(entries.items()))

    root = jax.random.key(int(seed))
    top = one(jax.random.fold_in(root, 0),
              {k: v for k, v in table.items() if k != "layers"})
    top["layers"] = [one(jax.random.fold_in(root, i + 1), layer)
                     for i, layer in enumerate(table["layers"])]
    return top




# -- the layers' equations --------------------------------------------------

def _rmsnorm(x, g, eps):
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


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba_mixer(p, u, m, quant=None):
    """u [T, d] -> [T, d]: the recurrence one token at a time."""
    T = u.shape[0]
    H, P, G, N = m["ssm_heads"], m["ssm_head_dim"], m["groups"], m["state"]
    zxbcdt = _linear(u, p["w_in"], quant)
    z = zxbcdt[:, :m["d_inner"]]
    xbc = zxbcdt[:, m["d_inner"]:m["d_inner"] + m["conv_dim"]]
    dt = zxbcdt[:, m["d_inner"] + m["conv_dim"]:]
    k = m["conv_k"]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[j:j + T] * p["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[:, :m["d_inner"]].reshape(T, H, P)
    B = xbc[:, m["d_inner"]:m["d_inner"] + G * N].reshape(T, G, N)
    C = xbc[:, m["d_inner"] + G * N:].reshape(T, G, N)
    B = jnp.repeat(B, H // G, axis=1)           # the head's group
    C = jnp.repeat(C, H // G, axis=1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    def step(h, t):
        xs_t, B_t, C_t, dt_t = t
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * xs_t)[:, :, None] * B_t[:, None, :])
        return h, (h * C_t[:, None, :]).sum(-1)

    _h, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (xs, B, C, dt))
    y = y + p["D"][:, None] * xs
    y = y.reshape(T, m["d_inner"]) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(T, G, -1), p["norm_g"].reshape(G, -1),
                 m["eps"]).reshape(T, m["d_inner"])
    return _linear(y, p["w_out"], quant)


def attention(p, u, m, quant=None):
    """u [T, d] -> [T, d]: causal grouped-query attention, no
    positions."""
    T, d = u.shape
    Hq, Hk, dh = m["q_heads"], m["kv_heads"], m["head_dim"]
    q = _linear(u, p["wq"].reshape(d, -1), quant).reshape(T, Hq, dh)
    k = _linear(u, p["wk"].reshape(d, -1), quant).reshape(T, Hk, dh)
    v = _linear(u, p["wv"].reshape(d, -1), quant).reshape(T, Hk, dh)
    k = jnp.repeat(k, Hq // Hk, axis=1)         # the query head's group
    v = jnp.repeat(v, Hq // Hk, axis=1)
    scores = jnp.einsum("shx,thx->hst", q, k,
                        precision=HIGHEST) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hst,thx->shx", jax.nn.softmax(scores, -1), v,
                     precision=HIGHEST)
    return _linear(att.reshape(T, -1), p["wo"].reshape(-1, d), quant)


def route(p, u, m):
    """The routing weight of every (token, expert of the router's whole
    width): ``g`` where the expert is among the token's ``top_k``, else
    0.  float32 whatever ``quant``."""
    s = jax.nn.sigmoid(jnp.dot(u, p["router"], precision=HIGHEST))
    _best, chosen = jax.lax.top_k(s + p["e_bias"], m["top_k"])
    picked = jnp.take_along_axis(s, chosen, axis=1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-20) * m["scale"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(g)


def moe_routed(p, u, m, quant=None):
    """What the experts held here add, through the up-projection."""
    weights = route(p, u, m)
    weights = jax.lax.dynamic_slice_in_dim(weights, m["held_from"],
                                           m["held"], axis=1)
    latent = _linear(u, p["w_down"], quant)

    def expert(acc, e):
        w1, w2, g = e
        y = _linear(_relu2(_linear(latent, w1.astype(F32), quant)),
                    w2.astype(F32), quant)
        return acc + g[:, None] * y, None

    mixed, _ = jax.lax.scan(expert, jnp.zeros_like(latent),
                            (p["w1"], p["w2"], weights.T))
    return _linear(mixed, p["w_up"], quant)


def moe_shared(p, u, m, quant=None):
    return _linear(_relu2(_linear(u, p["s1"], quant)), p["s2"], quant)


def moe_layer(p, u, m, quant=None):
    return moe_routed(p, u, m, quant) + moe_shared(p, u, m, quant)


MIXERS = {"M": mamba_mixer, "*": attention, "E": moe_layer}


def _as_f32(tree, keep=("w1", "w2")):
    """A layer's weights in float32, the experts' left in the type they
    are stored in: the expert loop raises one expert at a time."""
    return {k: v if k in keep else v.astype(F32) for k, v in tree.items()}


def logits_at(params, config, tokens, rows, quant=None):
    """float32 logits ``[len(rows), vocab]`` of the positions ``rows``
    of ONE sequence ``tokens`` (int32 ``[T]``; what lies past the last
    row of interest is padding, which causality keeps out of sight: the
    recurrence and the convolution only look back)."""
    m = dims(config)
    x = params["embed"][tokens].astype(F32)
    for kind, layer in zip(m["pattern"], params["layers"]):
        p = _as_f32(layer)
        u = _rmsnorm(x, p["norm"], m["eps"])
        x = x + MIXERS[kind](p, u, m, quant)
    x = _rmsnorm(x[rows], params["norm_f"].astype(F32), m["eps"])
    return _linear(x, params["head"].astype(F32).T, quant)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _gaps_jit(params, sequence, rows, served, spec, quant):
    """Every shape here is the cell's (``pad_to``, ``max_rows``), so ONE
    program serves every request of every seed."""
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
    first instead of the served one (the control).  ``pad_to`` and
    ``max_rows`` fix the shapes (the cell's longest sequence and longest
    output), so every request runs the one compiled program.  Returns a
    float32 array ``[len(served)]``."""
    import numpy
    n, count = len(prompt), len(served)
    sequence = numpy.zeros(pad_to, numpy.int32)
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
    "hidden_size", "vocab_size", "hybrid_override_pattern",
    "num_hidden_layers",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "num_attention_heads", "num_key_value_heads",
    "head_dim", "router_width", "n_routed_experts", "held_from",
    "num_experts_per_tok", "moe_latent_size", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "routed_scaling_factor",
    "norm_eps")
