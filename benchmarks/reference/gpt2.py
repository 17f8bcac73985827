"""Plain reference of the GPT-2 family as ``veles_tpu/samples/
transformer.py`` lays it out: learned positions, pre-LN blocks
(LayerNorm eps 1e-5), fused qkv, tanh-GELU MLP, final LayerNorm, readout
tied to the embedding.  One whole forward pass over a sequence in
straightforward ``jax.numpy`` at float32 / ``highest``: no cache, no
batching, no kernels, nothing imported from the program.  The weights
are made HERE from the seed (in the type they are served in) and GIVEN
to the program.

Departures from GPT-2 / Cerebras-GPT, all the repo's own equations: no
bias on ``wqkv`` and ``wo`` (under 0.01% of the parameters); GELU in its
tanh form.

``quant="fp8"`` (e4m3) rounds both operands of every linear layer and
of the readout to 8 bits (per-row scale for
activations, per-output-channel for weights): the CONTROL, the nearest precision
below the configuration's bfloat16.  The benchmark's own runs never run
it.
"""

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def shapes(config):
    """name -> (shape, init scale or "ones"/"zeros"): the layout of
    ``samples/transformer.py`` with GPT-2's initial deviations."""
    d, h, L = config["n_embd"], config["n_head"], config["n_layer"]
    dh, f = d // h, config["n_inner"]
    # ``block_init_gain`` (the rehearsal's only): at a tiny width the
    # tied readout just repeats the last token with a wide margin, and
    # no precision ever changes the best token; louder blocks make the
    # logits close enough for the control to be told apart on the CPU
    gain = float(config.get("block_init_gain", 1.0))
    deep = gain * 0.02 / math.sqrt(2 * L)
    wide = gain * 0.02
    return {
        "embed": ((config["vocab_size"], d), 0.02),
        "pos": ((config["n_positions"], d), 0.01),
        "blocks": {
            "ln1_g": ((L, d), "ones"), "ln1_b": ((L, d), "zeros"),
            "wqkv": ((L, d, 3, h, dh), wide),
            "wo": ((L, h, dh, d), deep),
            "ln2_g": ((L, d), "ones"), "ln2_b": ((L, d), "zeros"),
            "w1": ((L, d, f), wide), "b1": ((L, f), "zeros"),
            "w2": ((L, f, d), deep), "b2": ((L, d), "zeros"),
        },
        "lnf_g": ((d,), "ones"), "lnf_b": ((d,), "zeros"),
    }


def init_params(config, seed, dtype=jnp.bfloat16):
    """Every weight drawn on the device in ONE jitted call from the
    seed, in the type it is served in."""
    table = shapes(config)

    def make(key, entry, salt):
        shape, init = entry
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        return (jax.random.normal(jax.random.fold_in(key, salt), shape,
                                  jnp.float32) * init).astype(dtype)

    @jax.jit
    def build(key):
        out, salt = {}, 0
        for name, entry in table.items():
            if isinstance(entry, dict):
                out[name] = {}
                for leaf, sub in entry.items():
                    salt += 1
                    out[name][leaf] = make(key, sub, salt)
            else:
                salt += 1
                out[name] = make(key, entry, salt)
        return out

    return build(jax.random.key(int(seed)))


def _layernorm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _fp8(x, axes):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale


def _linear(x, w, quant):
    """x [S, K] @ w [K, N]."""
    if quant is not None:
        if quant != "fp8":
            raise ValueError("unknown control precision %r" % quant)
        x, w = _fp8(x, (1,)), _fp8(w, (0,))
    return jnp.dot(x, w, precision=HIGHEST)


def logits_at(params, tokens, rows, quant=None):
    """float32 logits ``[len(rows), vocab]`` of the positions ``rows``
    of ONE sequence ``tokens`` (int32 ``[T]``; what lies past the last
    row of interest is padding, which causality keeps out of sight)."""
    f32 = jnp.float32
    embed = params["embed"].astype(f32)
    T = tokens.shape[0]
    h = embed[tokens] + params["pos"][:T].astype(f32)
    causal = jnp.tril(jnp.ones((T, T), bool))

    def block(h, blk):
        blk = jax.tree.map(lambda a: a.astype(f32), blk)
        d = h.shape[-1]
        _d, _three, heads, dh = blk["wqkv"].shape
        x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
        qkv = _linear(x, blk["wqkv"].reshape(d, -1), quant).reshape(
            T, 3, heads, dh)
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        scores = jnp.einsum("shx,thx->hst", q, k,
                            precision=HIGHEST) / math.sqrt(dh)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jnp.einsum("hst,thx->shx", jax.nn.softmax(scores, -1), v,
                         precision=HIGHEST)
        h = h + _linear(att.reshape(T, -1), blk["wo"].reshape(-1, d),
                        quant)
        x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        up = _gelu(_linear(x, blk["w1"], quant) + blk["b1"])
        return h + _linear(up, blk["w2"], quant) + blk["b2"], None

    h, _ = jax.lax.scan(block, h, params["blocks"])
    h = _layernorm(h, params["lnf_g"].astype(f32),
                   params["lnf_b"].astype(f32))
    return _linear(h[rows], embed.T, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _gaps_jit(params, sequence, rows, served, quant):
    """Every shape here is the cell's (``pad_to``, ``max_rows``), so ONE
    program serves every request of every seed; cut to a request's own
    length outside it, each new length would compile its own slices."""
    reference = logits_at(params, sequence, rows, None)
    chosen = served if quant is None else jnp.argmax(
        logits_at(params, sequence, rows, quant), axis=-1)
    picked = jnp.take_along_axis(reference, chosen[:, None], axis=1)[:, 0]
    return reference.max(axis=-1) - picked


def served_gaps(params, prompt, served, pad_to, max_rows, quant=None):
    """For each served token: how far its reference logit lies below
    the reference's best at that position (0 where it IS the best).
    With ``quant``, the same for the token the lower precision puts
    first instead of the served one (the control).  ``pad_to`` and
    ``max_rows`` fix the shapes (the cell's longest sequence and longest
    output), so every request runs the one compiled program.  Returns a
    float32 array ``[len(served)]``."""
    import numpy
    n, m = len(prompt), len(served)
    sequence = numpy.zeros(pad_to, numpy.int32)
    sequence[:n] = prompt
    sequence[n:n + m - 1] = served[:-1]
    rows = numpy.zeros(max_rows, numpy.int32)
    rows[:m] = numpy.arange(n - 1, n + m - 1)
    chosen = numpy.zeros(max_rows, numpy.int32)
    chosen[:m] = served
    gaps = _gaps_jit(params, jnp.asarray(sequence), jnp.asarray(rows),
                     jnp.asarray(chosen), quant)
    return numpy.asarray(gaps)[:m]
