"""Grouped matrix product: rows sorted by group, one matrix a group.

``grouped_matmul(rows [M, K], weights [E, K, N], sizes [E])`` multiplies
the first ``sizes[0]`` rows by ``weights[0]``, the next ``sizes[1]`` by
``weights[1]`` and so on; rows past ``sizes.sum()`` belong to no group
and their result is whatever was there.  It is the product of a
mixture-of-experts layer whose token-expert pairs were sorted by expert
(:mod:`veles_tpu.gen.hybrid`).

On the TPU the Pallas kernel ``veles_grouped_matmul`` walks a list of
BLOCKS made from ``sizes`` (:func:`block_map`): a block is ``tm``
consecutive rows (a row tile) against ONE group's matrix, and a row tile
that holds rows of several groups is visited once for each, every visit
storing its own group's rows alone.  The list and its length are scalar
arguments: the length is the grid's bound, so a row tile past the last
real row is neither fetched nor computed, a group with no rows has no
block and its matrix is not read, and consecutive blocks of one group
find the matrix already in fast memory: each touched group's matrix is
read once a column tile.  bf16 (or float32) operands, float32
accumulation; ``relu2`` squares the positive part of the float32 value
before the cast to ``out_dtype``, so a wide float32 hidden array is
never written.  Elsewhere :func:`jax.lax.ragged_dot` does the same
product (its TPU lowering spends a 512-row tile on every group however
few rows it has, which is why the TPU does not take it).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.util import pad_axis

F32 = jnp.float32

#: rows of a block
BLOCK_ROWS = 128
#: fast memory the kernel may take: two buffers of one group's matrix at
#: the widest column tile (2 x 5.5 MB at 1024 x 2688 bf16) and the rows
_VMEM_LIMIT = 64 * 1024 * 1024


def block_map(sizes, m, tm=BLOCK_ROWS):
    """The blocks of ``m`` rows sorted into groups of ``sizes``:
    ``(offsets [E + 1], groups [B], tiles [B], count)``, all int32.
    Block ``i < count`` is row tile ``tiles[i]`` against group
    ``groups[i]``, whose rows are ``offsets[g]:offsets[g + 1]``; blocks
    are in the order of the rows, so a row tile's visits are consecutive
    and so are a group's.  ``B = ceil(m / tm) + E - 1`` is the most
    there can be (neighbouring groups share at most one tile)."""
    sizes = sizes.astype(jnp.int32)
    row_tiles = -(-m // tm)
    most = row_tiles + sizes.shape[0] - 1
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    touched = jnp.where(sizes == 0, 0, (ends + tm - 1) // tm - first)
    upto = jnp.cumsum(touched)
    block = jnp.arange(most, dtype=jnp.int32)
    # the group whose blocks end past this one (one comparison a pair:
    # both lists are short)
    groups = jnp.minimum((upto[None, :] <= block[:, None]).sum(1),
                         sizes.shape[0] - 1).astype(jnp.int32)
    tiles = first[groups] + block - (upto - touched)[groups]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets, groups,
            jnp.clip(tiles, 0, row_tiles - 1).astype(jnp.int32),
            upto[-1].astype(jnp.int32))


def blocks_holding(blocks, marked, tm=BLOCK_ROWS):
    """How many of the blocks hold at least one row where ``marked
    [M]`` (bool, in the sorted order) is true: the denominator of a
    block's fill."""
    offsets, groups, tiles, count = blocks
    upto = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(marked.astype(jnp.int32))])
    lo = jnp.maximum(tiles * tm, offsets[groups])
    hi = jnp.minimum(tiles * tm + tm, offsets[groups + 1])
    live = jnp.arange(groups.shape[0]) < count
    return (live & (upto[jnp.maximum(hi, lo)] > upto[lo])).sum() \
        .astype(jnp.int32)


def _kernel(offsets, groups, tiles, x_ref, w_ref, o_ref, *, relu2):
    block = pl.program_id(1)
    tm = x_ref.shape[0]
    acc = jnp.dot(x_ref[...], w_ref[...], preferred_element_type=F32)
    if relu2:
        acc = jnp.square(jnp.maximum(acc, 0.0))
    group = groups[block]
    row = tiles[block] * tm + jax.lax.broadcasted_iota(
        jnp.int32, acc.shape, 0)
    mine = (row >= offsets[group]) & (row < offsets[group + 1])
    # the tile's other rows are another block's (or nobody's)
    o_ref[...] = jnp.where(mine, acc.astype(o_ref.dtype), o_ref[...])


def _column_tile(n, k, itemsize):
    """The widest column tile, a multiple of 128 that divides ``n``,
    two buffers of which fit a third of the kernel's fast memory (the
    whole of ``n`` where it is no multiple of 128)."""
    if n % 128:
        return n
    fits = [tn for tn in range(128, n + 1, 128)
            if n % tn == 0 and 2 * k * tn * itemsize <= _VMEM_LIMIT // 3]
    return max(fits) if fits else 128


def _grouped_pallas(rows, weights, blocks, relu2, out_dtype, tm, tn,
                    interpret):
    m, k = rows.shape
    n = weights.shape[2]
    tn = tn or _column_tile(n, k, weights.dtype.itemsize)
    offsets, groups, tiles, count = blocks
    return pl.pallas_call(
        functools.partial(_kernel, relu2=relu2),
        name="veles_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # columns outermost: within one column tile consecutive
            # blocks of a group keep its matrix
            grid=(n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, _o, _g, t: (t[i], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, i, _o, g, _t: (g[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, _o, _g, t: (t[i], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(offsets, groups, tiles, rows, weights)


def grouped_matmul(rows, weights, sizes, relu2=False, out_dtype=F32,
                   blocks=None, tm=BLOCK_ROWS, tn=None, use_pallas=None,
                   interpret=None):
    """``rows [M, K]`` sorted by group x ``weights [E, K, N]`` by
    ``sizes [E]`` (int32) -> ``[M, N]`` of ``out_dtype``, accumulated in
    float32, ``relu2`` applied to the float32 value where asked.  Rows
    past ``sizes.sum()`` come back undefined (not zero).  ``blocks``:
    :func:`block_map` of ``sizes`` where the caller has it already;
    ``use_pallas``: ``None`` lets the platform decide (the kernel on a
    TPU, :func:`jax.lax.ragged_dot` elsewhere)."""
    from veles_tpu.ops import on_tpu
    pallas = use_pallas if use_pallas is not None else on_tpu()
    if not pallas:
        out = jax.lax.ragged_dot(rows, weights, sizes.astype(jnp.int32),
                                 preferred_element_type=F32)
        if relu2:
            out = jnp.square(jax.nn.relu(out))
        return out.astype(out_dtype)
    if interpret is None:
        from veles_tpu.config import root
        interpret = bool(root.common.engine.get("interpret", False))
    m = rows.shape[0]
    if blocks is None:
        blocks = block_map(sizes, m, tm)
    # whole row tiles; no group reaches the padding
    out = _grouped_pallas(pad_axis(rows, tm, 0), weights, blocks, relu2,
                          out_dtype, tm, tn, interpret)
    return out[:m]
