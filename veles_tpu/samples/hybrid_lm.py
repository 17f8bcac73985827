"""Hybrid state-space / sparse-expert / attention LM: the parameter
layout :class:`veles_tpu.gen.hybrid.HybridGenModel` serves.

One stack of three kinds of layer, each a mixer or a feed-forward part
alone, chosen by a letter of ``pattern``: ``M`` a Mamba-2 mixer, ``E``
a latent mixture of experts (router over ``router_width`` experts of
which this chip HOLDS ``experts_held`` starting at ``held_from``, a
shared expert on the full width), ``*`` grouped-query attention
without positions.  RMSNorm before every layer, an untied head.  The
layers are not alike, so ``layers`` is a list of per-layer dicts and
nothing is stacked.
"""

import math

import jax
import numpy

CONFIG = {
    "vocab": 32768, "dim": 4096, "pattern": "MEMEMEM*EME",
    "seq_len": 2048,
    "ssm_heads": 128, "ssm_head_dim": 64, "ssm_groups": 8,
    "ssm_state": 128, "conv_kernel": 4, "chunk": 128,
    "heads": 32, "kv_heads": 2, "head_dim": 128,
    "router_width": 512, "experts_held": 128, "held_from": 0,
    "top_k": 22, "latent": 1024, "expert_width": 2688,
    "shared_width": 5376, "routed_scale": 5.0, "norm_eps": 1e-5,
}
TINY = {
    "vocab": 64, "dim": 32, "pattern": "ME*",
    "seq_len": 64,
    "ssm_heads": 4, "ssm_head_dim": 8, "ssm_groups": 2,
    "ssm_state": 16, "conv_kernel": 4, "chunk": 8,
    "heads": 4, "kv_heads": 2, "head_dim": 8,
    "router_width": 8, "experts_held": 4, "held_from": 0,
    "top_k": 2, "latent": 16, "expert_width": 24,
    "shared_width": 48, "routed_scale": 2.5, "norm_eps": 1e-5,
}

F32 = numpy.float32


def ssm_dims(cfg):
    """``(d_inner, conv_dim)``: the mixer's inner width, and the width
    the convolution runs over (inner + the groups' B and C)."""
    d_inner = cfg["ssm_heads"] * cfg["ssm_head_dim"]
    return d_inner, d_inner + 2 * cfg["ssm_groups"] * cfg["ssm_state"]


def _layer_table(cfg, kind):
    """``name -> (shape, init, dtype or None = the served type)`` of one
    layer.  ``dt_bias``, ``A_log``, ``D``, the router and its bias are
    float32 whatever the served type."""
    d, depth = cfg["dim"], cfg.get("published_layers", len(cfg["pattern"]))
    wide, deep = 0.02, 0.02 / math.sqrt(depth)
    if kind == "M":
        d_inner, conv_dim = ssm_dims(cfg)
        return {
            "norm": ((d,), "ones", None),
            "w_in": ((d, d_inner + conv_dim + cfg["ssm_heads"]), wide,
                     None),
            "conv_w": ((cfg["conv_kernel"], conv_dim), "conv", None),
            "conv_b": ((conv_dim,), "conv", None),
            "dt_bias": ((cfg["ssm_heads"],), "dt_bias", F32),
            "A_log": ((cfg["ssm_heads"],), "A_log", F32),
            "D": ((cfg["ssm_heads"],), "ones", F32),
            "norm_g": ((d_inner,), "ones", None),
            "w_out": ((d_inner, d), deep, None)}
    if kind == "*":
        h, kv, dh = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
        return {
            "norm": ((d,), "ones", None),
            "wq": ((d, h, dh), wide, None),
            "wk": ((d, kv, dh), wide, None),
            "wv": ((d, kv, dh), wide, None),
            "wo": ((h, dh, d), deep, None)}
    if kind == "E":
        held, lat = cfg["experts_held"], cfg["latent"]
        return {
            "norm": ((d,), "ones", None),
            "router": ((d, cfg["router_width"]), wide, F32),
            "e_bias": ((cfg["router_width"],), "zeros", F32),
            "w_down": ((d, lat), wide, None),
            "w1": ((held, lat, cfg["expert_width"]), wide, None),
            "w2": ((held, cfg["expert_width"], lat), wide, None),
            "w_up": ((lat, d), deep, None),
            "s1": ((d, cfg["shared_width"]), wide, None),
            "s2": ((cfg["shared_width"], d), deep, None)}
    raise ValueError("unknown layer kind %r in pattern %r (want M, E "
                     "or *)" % (kind, cfg["pattern"]))


def _shape_table(cfg):
    return {"embed": ((cfg["vocab"], cfg["dim"]), 0.02, None),
            "layers": [_layer_table(cfg, kind) for kind in cfg["pattern"]],
            "norm_f": ((cfg["dim"],), "ones", None),
            "head": ((cfg["vocab"], cfg["dim"]), 0.02, None)}


def _build(table, make):
    out = {}
    for name, entry in table.items():
        if isinstance(entry, list):
            out[name] = [_build(layer, make) for layer in entry]
        else:
            out[name] = make(entry)
    return out


def init_params(cfg, seed=0, dtype=numpy.float32):
    """Host parameters by the family's initialisation: 0.02 on linear
    weights (the projections back into the stream over the root of the
    depth), ``A`` in (1, 16), ``dt`` log-uniform in [0.001, 0.1]
    through the inverse softplus, ``D`` ones, the router's bias zero."""
    rng = numpy.random.default_rng(seed)
    lo, hi, floor = (cfg.get("time_step_min", 0.001),
                     cfg.get("time_step_max", 0.1),
                     cfg.get("time_step_floor", 1e-4))

    def make(entry):
        shape, init, own = entry
        kind = own or dtype
        if init == "ones":
            return numpy.ones(shape, kind)
        if init == "zeros":
            return numpy.zeros(shape, kind)
        if init == "conv":
            bound = 1.0 / math.sqrt(cfg["conv_kernel"])
            return rng.uniform(-bound, bound, shape).astype(kind)
        if init == "A_log":
            return numpy.log(rng.uniform(1.0, 16.0, shape)).astype(kind)
        if init == "dt_bias":
            dt = numpy.exp(rng.uniform(math.log(lo), math.log(hi), shape))
            dt = numpy.maximum(dt, floor)
            return (dt + numpy.log(-numpy.expm1(-dt))).astype(kind)
        return (rng.standard_normal(shape) * init).astype(kind)

    return _build(_shape_table(cfg), make)


def param_shapes(cfg, dtype=numpy.float32):
    """Zero-alloc :class:`jax.ShapeDtypeStruct` twin of
    :func:`init_params`."""
    return _build(
        _shape_table(cfg),
        lambda entry: jax.ShapeDtypeStruct(
            entry[0], numpy.dtype(entry[2] or dtype)))


def param_count(cfg):
    return sum(int(numpy.prod(leaf.shape))
               for leaf in jax.tree.leaves(param_shapes(cfg)))
