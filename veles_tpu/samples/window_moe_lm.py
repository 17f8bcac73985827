"""Window / full attention LM with a parallel block and a gated
mixture of experts: the parameter layout
:class:`veles_tpu.gen.window_moe.WindowMoEGenModel` serves.

Every layer is ONE block, ``x <- x + Attn(u) + FFN(u)`` with ``u =
LayerNorm(x)`` (no bias), its kind a letter of ``pattern``: ``W``
attends a sliding window of ``window`` positions with rotary positions
(interleaved pairs), ``F`` attends everything with no positions.
``FFN`` routes over ``router_width`` experts of which this chip HOLDS
``experts_held`` starting at ``held_from``; an expert is gated,
``(silu(u Wg) * (u Wu)) Wd``; ``shared_experts`` of the same form run
on every token and are averaged.  A final LayerNorm; the head is the
embedding (tied).  The layers keep caches of different lengths, so
``layers`` is a list of per-layer dicts and nothing is stacked.
"""

import jax
import numpy

CONFIG = {
    "vocab": 32768, "dim": 4096, "pattern": "WWWF", "seq_len": 32768,
    "heads": 128, "kv_heads": 8, "head_dim": 128,
    "window": 4096, "rope_theta": 50000.0,
    "router_width": 128, "experts_held": 16, "held_from": 0,
    "top_k": 8, "expert_width": 4096, "shared_experts": 4,
    "norm_eps": 1e-5, "logit_scale": 1.0,
}
TINY = {
    "vocab": 64, "dim": 32, "pattern": "WWWF", "seq_len": 64,
    "heads": 4, "kv_heads": 2, "head_dim": 8,
    "window": 8, "rope_theta": 50000.0,
    "router_width": 8, "experts_held": 4, "held_from": 0,
    "top_k": 2, "expert_width": 24, "shared_experts": 2,
    "norm_eps": 1e-5, "logit_scale": 1.0,
}

F32 = numpy.float32


def _layer_table(cfg):
    """``name -> (shape, init, dtype or None = the served type)`` of one
    layer; the router is float32 whatever the served type."""
    d, f = cfg["dim"], cfg["expert_width"]
    h, kv, dh = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    held, shared = cfg["experts_held"], cfg["shared_experts"]
    wide = 0.02 * float(cfg.get("init_gain", 1.0))
    return {
        "norm": ((d,), "ones", None),
        "wq": ((d, h, dh), wide, None),
        "wk": ((d, kv, dh), wide, None),
        "wv": ((d, kv, dh), wide, None),
        "wo": ((h, dh, d), wide, None),
        "router": ((d, cfg["router_width"]), wide, F32),
        "wg": ((held, d, f), wide, None),
        "wu": ((held, d, f), wide, None),
        "wd": ((held, f, d), wide, None),
        "sg": ((shared, d, f), wide, None),
        "su": ((shared, d, f), wide, None),
        "sd": ((shared, f, d), wide, None)}


def _shape_table(cfg):
    for kind in cfg["pattern"]:
        if kind not in "WF":
            raise ValueError("unknown layer kind %r in pattern %r (want "
                             "W or F)" % (kind, cfg["pattern"]))
    wide = 0.02 * float(cfg.get("init_gain", 1.0))
    return {"embed": ((cfg["vocab"], cfg["dim"]), wide, None),
            "layers": [_layer_table(cfg) for _kind in cfg["pattern"]],
            "norm_f": ((cfg["dim"],), "ones", None)}


def _build(table, make):
    out = {}
    for name, entry in table.items():
        if isinstance(entry, list):
            out[name] = [_build(layer, make) for layer in entry]
        else:
            out[name] = make(entry)
    return out


def init_params(cfg, seed=0, dtype=numpy.float32):
    """Host parameters by the family's initialisation: normal, 0.02, on
    every linear weight and on the embedding; the norms' weights one."""
    rng = numpy.random.default_rng(seed)

    def make(entry):
        shape, init, own = entry
        kind = own or dtype
        if init == "ones":
            return numpy.ones(shape, kind)
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
