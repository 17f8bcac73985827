"""The products of an expert layer.  Grouped: rows sorted by group, one
matrix a group.  Mixed (:func:`expert_mix`, at the end): few rows
through the experts they chose and no other.

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


# -- few rows: every row through every TOUCHED expert, mixed in place -------

def touched_list(load):
    """The experts with ``load [E] > 0`` first and in their order, then
    zeros, and how many they are: ``(ids [E], count)``, both int32."""
    touched = load > 0
    place = jnp.cumsum(touched) - 1
    slot = jnp.arange(load.shape[0], dtype=jnp.int32)
    # one comparison a pair (the list is short) where a scatter would
    # cost the chip more
    ids = jnp.where(touched[None, :] & (place[None, :] == slot[:, None]),
                    slot[None, :], 0).sum(1).astype(jnp.int32)
    return ids, touched.sum().astype(jnp.int32)


def _activate(wide):
    """``relu(a)^2`` of one projection, ``silu(gate) * up`` of two, on
    the float32 values."""
    if len(wide) == 1:
        return jnp.square(jnp.maximum(wide[0], 0.0))
    return jax.nn.silu(wide[0]) * wide[1]


def _mix_kernel(ids, x_ref, weights_ref, *refs):
    into, back_ref, o_ref = refs[:-2], refs[-2], refs[-1]
    step, tile = pl.program_id(0), pl.program_id(1)

    @pl.when((step == 0) & (tile == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]
    hidden = _activate([jnp.dot(x, ref[...], preferred_element_type=F32)
                        for ref in into])
    # this expert's column of the rows' weights: 0 where a row did not
    # choose it
    weights = weights_ref[...]
    mine = jax.lax.broadcasted_iota(jnp.int32, weights.shape, 1) \
        == ids[step]
    weight = jnp.sum(jnp.where(mine, weights, 0.0), axis=1, keepdims=True)
    o_ref[...] += jnp.dot((hidden * weight).astype(x.dtype), back_ref[...],
                          preferred_element_type=F32)


def _width_tile(f, k, rows, matrices, itemsize):
    """The widest tile of an expert's width, a multiple of 128 that
    divides ``f``, whose two buffers of every matrix's tile and the
    rows' float32 hidden values fit half the kernel's fast memory (the
    whole of ``f`` where it is no multiple of 128)."""
    if f % 128:
        return f
    fits = [tf for tf in range(128, f + 1, 128)
            if f % tf == 0 and tf * (2 * matrices * k * itemsize
                                     + matrices * rows * 4)
            <= _VMEM_LIMIT // 2]
    return max(fits) if fits else 128


def _mix_pallas(x, weights, into, back, ids, count, interpret):
    rows, k = x.shape
    f = back.shape[1]
    tf = _width_tile(f, k, rows, len(into) + 1, back.dtype.itemsize)
    whole = lambda e, j, _ids: (0, 0)  # noqa: E731
    return pl.pallas_call(
        _mix_kernel,
        name="veles_expert_mix",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # the touched experts alone, each by tiles of its width:
            # the result stays in fast memory across the whole grid
            grid=(count, f // tf),
            in_specs=[pl.BlockSpec((rows, k), whole),
                      pl.BlockSpec(weights.shape, whole)]
            + [pl.BlockSpec((None, k, tf),
                            lambda e, j, ids: (ids[e], 0, j))] * len(into)
            + [pl.BlockSpec((None, tf, k),
                            lambda e, j, ids: (ids[e], j, 0))],
            out_specs=pl.BlockSpec((rows, k), whole)),
        out_shape=jax.ShapeDtypeStruct((rows, k), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(ids, x, weights, *into, back)


def expert_mix(x, weights, into, back, load, use_pallas=None,
               interpret=None):
    """``x [T, k]`` through the experts ``into`` (one ``[E, k, f]``
    matrix: ``relu(x W1)^2``; two: ``silu(x Wg) * (x Wu)``) and ``back
    [E, f, k]``, each row's results mixed by ``weights [T, E]``
    (float32, 0 where the row did not choose the expert) -> ``[T, k]``
    float32.  ``load [E]``: how many rows chose each expert; an expert
    with none is not read.  Operands as they come (bf16 or float32), the
    activation, the weight and the sums in float32.

    On the TPU the kernel ``veles_expert_mix`` walks the list of
    touched experts (:func:`touched_list`; list and length are scalar
    arguments, the length the grid's bound), every step ALL ``T`` rows
    through one tile of one expert's width and back, added into the one
    result that stays in fast memory: no sort, no gather, no ``[E, T,
    f]`` array.  Elsewhere (``use_pallas``: ``None`` lets the platform
    decide) every expert runs over every row in two contractions."""
    from veles_tpu.ops import on_tpu
    pallas = use_pallas if use_pallas is not None else on_tpu()
    if not pallas:
        wide = [jnp.einsum("tl,elf->etf", x, w,
                           preferred_element_type=F32) for w in into]
        hidden = (_activate(wide) * weights.T[:, :, None]).astype(x.dtype)
        return jnp.einsum("etf,efl->tl", hidden, back,
                          preferred_element_type=F32)
    if interpret is None:
        from veles_tpu.config import root
        interpret = bool(root.common.engine.get("interpret", False))
    rows = x.shape[0]
    ids, count = touched_list(load)
    # whole sublane tiles of rows; a padded row has no weight
    out = _mix_pallas(pad_axis(x, 16, 0), pad_axis(weights, 16, 0),
                      tuple(into), back, ids, count, interpret)
    # no expert touched: no grid step ran, nothing was written
    return jnp.where(count > 0, out[:rows], 0.0)
