"""One chip's share of a mixture of experts, for every model class that
has one (:mod:`veles_tpu.gen.hybrid`, :mod:`veles_tpu.gen.window_moe`).

The layer routes over ALL ``router_width`` experts (sigmoid scores,
``top_k``, weights normalised over all chosen) and computes the part of
the result that the ``held`` experts it holds give, for the tokens
routed to them, dropping none and with no capacity buffers.  On one
chip it runs without its exchange.  Up to ``dense_tokens`` rows (a
decode step, a short prompt) ALL rows go through each held expert that
a valid row chose, with a zero weight where a row did not choose it,
and through no other (:func:`veles_tpu.ops.grouped.expert_mix`: on a
TPU the kernel ``veles_expert_mix`` over the list of touched experts,
which never fetches an untouched expert's matrices; elsewhere every
held expert over every row).  At that size the pass is bound by reading
the experts' weights, so it costs what the routing touched.  A row that
is not valid (a slot with no request, a bucket's padding) chooses no
expert there.  More rows sort their token-expert pairs by expert and
take the grouped product (:func:`veles_tpu.ops.grouped.grouped_matmul`:
on a TPU the kernel ``veles_grouped_matmul`` over row blocks of one
expert each, which reads each touched expert's matrices once and no row
past the last pair held here; elsewhere :func:`jax.lax.ragged_dot`).

An expert has one of two FORMS, named by a string:

- ``"relu2"``: ungated, ``relu(x W1)^2 W2`` (parameters ``w1``, ``w2``);
- ``"gated_silu"``: ``(silu(x Wg) * (x Wu)) Wd`` (``wg``, ``wu``,
  ``wd``), the activation and the product on the float32 values.

A model's programs return, behind their tokens, the counters
``COUNTERS`` over their expert layers (a few int32 in the array the
engine fetches anyway): sums, and the largest of a name that ends in
``_max``.  ``moe_grouped_rows`` / (``moe_grouped_blocks`` x
``grouped.BLOCK_ROWS``) is the fill of the grouped product's blocks.
"""

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.ops import grouped

F32 = jnp.float32

#: what a program counts over its expert layers, behind the tokens
COUNTERS = ("moe_local_pairs", "moe_experts_touched", "moe_pairs_total",
            "moe_expert_load_max", "moe_grouped_rows", "moe_grouped_blocks")

#: form -> (the projections into the expert's width, the one back)
FORMS = {"relu2": (("w1",), "w2"), "gated_silu": (("wg", "wu"), "wd")}


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def route(u, router, top_k, held_from, held, e_bias=None, scale=1.0):
    """``(local [T, top_k] index among the held experts, or ``held``
    where the chosen expert lives elsewhere; g [T, top_k] float32)``.
    float32 and ``highest``: a near-tie must fall the way the
    reference's falls.  ``e_bias`` moves the choice and not the
    weights."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(F32), router, precision=jax.lax.Precision.HIGHEST))
    ranked = scores if e_bias is None else scores + e_bias
    _best, chosen = jax.lax.top_k(ranked, top_k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    g = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    local = chosen - held_from
    here = (local >= 0) & (local < held)
    return jnp.where(here, local, held), g


def load_counts(local, valid, held, top_k):
    """``(the first four of COUNTERS, load [held])`` over the rows where
    ``valid [T]``: pairs held here, held experts some token chose, all
    pairs, the most pairs one held expert got; and the pairs each held
    expert got."""
    pairs = (local < held) & valid[:, None]
    load = jnp.bincount(jnp.where(pairs, local, held).reshape(-1),
                        length=held + 1)[:held]
    return [pairs.sum(), (load > 0).sum(), valid.sum() * top_k,
            load.max()], load


def _few(form, p, x, local, g, valid, load, held, cd, use_pallas):
    """ALL rows through each held expert that a valid row chose, weight
    0 where a row did not choose it: the touched experts are those of
    ``load``, which is what ``moe_experts_touched`` counts."""
    into, back = FORMS[form]
    # a row that is not valid chooses no expert; a row chooses an
    # expert once, so the sum has one term
    local = jnp.where(valid[:, None], local, held)
    weights = jnp.where(local[:, :, None] == jnp.arange(held), g[:, :, None],
                        0.0).sum(1)
    return grouped.expert_mix(
        x, weights, [p[name].astype(cd) for name in into],
        p[back].astype(cd), load, use_pallas=use_pallas)


def _grouped(form, p, x, local, g, valid, held, top_k, cd, use_pallas):
    """The token-expert pairs sorted by expert, one grouped product a
    projection; a pair whose expert lives elsewhere sorts last and
    belongs to no group.  Returns the mixture and how many of the
    product's blocks held a pair of a ``valid`` token."""
    into, back = FORMS[form]
    T = x.shape[0]
    keys = local.reshape(-1)
    # ONE sort carries everything that has to follow the pairs: a
    # gather of 22,528 scalars costs the chip more than the sort
    ranked, order, marked = jax.lax.sort(
        (keys, jnp.arange(keys.shape[0], dtype=jnp.int32),
         jnp.repeat(valid, top_k)), num_keys=1)
    # how many pairs sort before each held expert's, and before the
    # pairs held elsewhere
    starts = (ranked[None, :] < jnp.arange(held + 1)[:, None]) \
        .sum(1).astype(jnp.int32)
    sizes = starts[1:] - starts[:-1]
    blocks = grouped.block_map(sizes, keys.shape[0])
    rows = x[order // top_k]
    if form == "relu2":
        hidden = grouped.grouped_matmul(
            rows, p[into[0]].astype(cd), sizes, relu2=True, out_dtype=cd,
            blocks=blocks, use_pallas=use_pallas)
    else:
        gate, up = (grouped.grouped_matmul(
            rows, p[name].astype(cd), sizes, blocks=blocks,
            use_pallas=use_pallas) for name in into)
        hidden = (jax.nn.silu(gate) * up).astype(cd)
    out = grouped.grouped_matmul(
        hidden, p[back].astype(cd), sizes, blocks=blocks,
        use_pallas=use_pallas)
    # back in the tokens' order (the inverse of a permutation is its
    # argsort), where the weights are
    back_order = jnp.argsort(order)
    out = out[back_order].reshape(T, top_k, -1)
    weight = jnp.where(local < held, g, 0.0)[..., None]
    # rows past the last group are whatever the product left there
    return jnp.where(weight != 0, out * weight, 0.0).sum(1), \
        grouped.blocks_holding(blocks, marked)


def mix(form, p, x, local, g, valid, loads, held, top_k, dense_tokens,
        cd, use_pallas=None):
    """``x [T, k]`` through the held experts of ``form``, mixed by the
    routing of :func:`route`: ``(mixture [T, k] float32, COUNTERS
    int32)``, ``loads`` being :func:`load_counts` of the same routing.
    The touched experts alone up to ``dense_tokens`` rows (the mixture
    of a row that is not valid is then 0), the grouped product above."""
    counts, load = loads
    if x.shape[0] <= dense_tokens:
        mixed = _few(form, p, x, local, g, valid, load, held, cd,
                     use_pallas)
        counts = counts + [0, 0]
    else:
        mixed, blocks = _grouped(form, p, x, local, g, valid, held, top_k,
                                 cd, use_pallas)
        counts = counts + [counts[0], blocks]
    return mixed, jnp.stack(counts).astype(jnp.int32)


def merge(total, counts):
    """By name, as ``GenerativeEngine._count`` does: the largest of a
    name that ends in ``_max``, else the sum."""
    largest = numpy.array([name.endswith("_max") for name in COUNTERS])
    return jnp.where(largest, jnp.maximum(total, counts), total + counts)
