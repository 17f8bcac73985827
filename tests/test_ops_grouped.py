"""``ops.grouped.grouped_matmul``: the Pallas kernel in interpret mode on
the CPU against a float32 loop over the groups, and the block list it
walks against the definition of a block."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import grouped


def _uneven(seed, groups, rows):
    """``groups`` sizes that add up to ``rows``, some of them zero."""
    rng = numpy.random.default_rng(seed)
    p = rng.lognormal(0.0, 1.0, groups) * (rng.random(groups) > 0.2)
    return rng.multinomial(rows, p / p.sum())


#: name -> (sizes, rows M, K, N, tm, tn, relu2)
CASES = {
    "empty_groups_first_last_and_in_the_middle":
        ([0, 0, 5, 0, 3, 40, 0], 48, 16, 24, 16, None, False),
    "all_rows_in_one_group": ([0, 70, 0], 70, 16, 24, 16, None, False),
    "sizes_that_are_no_multiple_of_the_block":
        ([3, 4, 5, 1, 17], 30, 16, 24, 8, None, False),
    "a_group_that_spans_several_blocks_of_its_own":
        ([40, 2, 38], 80, 8, 128, 16, None, False),
    "rows_past_the_last_group_are_left_alone":
        ([3, 0, 9], 40, 16, 24, 8, None, False),
    "no_rows_at_all_but_one": ([0, 0, 0, 1], 30, 16, 24, 8, None, False),
    "128_groups_of_uneven_loads_and_column_tiles":
        (_uneven(5, 128, 700), 1024, 256, 384, 64, 128, False),
    "128_groups_the_second_projections_way_round":
        (_uneven(6, 128, 700), 1024, 384, 256, 64, 128, False),
    "relu2_on_the_float32_value_before_the_cast":
        ([3, 4, 5, 0, 20], 32, 16, 24, 8, None, True),
    "relu2_at_128_groups": (_uneven(7, 128, 300), 512, 128, 256, 32, 128,
                            True),
}


def _loop(x, w, sizes, relu2):
    """Group after group in float32; rows of no group stay NaN."""
    want = numpy.full((x.shape[0], w.shape[2]), numpy.nan, numpy.float32)
    row = 0
    for group, size in enumerate(sizes):
        y = x[row:row + size] @ w[group]
        want[row:row + size] = numpy.square(numpy.maximum(y, 0)) \
            if relu2 else y
        row += size
    return want


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_a_loop_over_the_groups(case):
    sizes, m, k, n, tm, tn, relu2 = CASES[case]
    sizes = numpy.asarray(sizes, numpy.int32)
    live = int(sizes.sum())
    rng = numpy.random.default_rng(len(case))
    x = rng.standard_normal((m, k)).astype(numpy.float32)
    # whatever the rows of no group hold, the live rows do not see it
    x[live:] = numpy.inf
    w = rng.standard_normal((len(sizes), k, n)).astype(numpy.float32)

    def both(x, w, sizes):
        return [grouped.grouped_matmul(
            x, w, sizes, relu2=relu2, tm=tm, tn=tn, use_pallas=pallas,
            interpret=True) for pallas in (True, False)]

    kernel, ragged = (numpy.asarray(out) for out in jax.jit(both)(
        x, w, sizes))
    want = _loop(x, w, sizes, relu2)[:live]
    assert kernel.shape == ragged.shape == (m, n)
    numpy.testing.assert_allclose(kernel[:live], want, rtol=1e-5,
                                  atol=1e-4)
    numpy.testing.assert_allclose(ragged[:live], want, rtol=1e-5,
                                  atol=1e-4)
    # the block list: every live row in exactly one block of its group,
    # a row tile's visits consecutive, no block for an empty group
    offsets, groups, tiles, count = (numpy.asarray(a) for a in
                                     grouped.block_map(sizes, m, tm))
    count = int(count)
    assert count <= len(groups) == -(-m // tm) + len(sizes) - 1
    seen = numpy.zeros(m, int)
    for group, tile in zip(groups[:count], tiles[:count]):
        assert sizes[group] > 0
        lo = max(tile * tm, offsets[group])
        hi = min(tile * tm + tm, offsets[group + 1])
        assert lo < hi
        seen[lo:hi] += 1
    assert (seen[:live] == 1).all() and not seen[live:].any()
    assert (numpy.diff(tiles[:count]) >= 0).all()
    assert (numpy.diff(groups[:count]) >= 0).all()
    marked = numpy.zeros(m, bool)
    assert int(grouped.blocks_holding((offsets, groups, tiles, count),
                                      jnp.asarray(marked), tm)) == 0
    marked[:live] = True
    assert int(grouped.blocks_holding((offsets, groups, tiles, count),
                                      jnp.asarray(marked), tm)) == count
    if live:
        marked[:] = False
        marked[live - 1] = True
        assert int(grouped.blocks_holding(
            (offsets, groups, tiles, count), jnp.asarray(marked), tm)) == 1


def test_bf16_operands_accumulate_in_float32_and_cast_after_relu2():
    sizes = numpy.asarray([5, 0, 11], numpy.int32)
    rng = numpy.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((16, 256)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, 256, 128)), jnp.bfloat16)
    got = grouped.grouped_matmul(x, w, sizes, relu2=True,
                                 out_dtype=jnp.bfloat16, tm=16,
                                 use_pallas=True, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _loop(numpy.asarray(x.astype(jnp.float32)),
                 numpy.asarray(w.astype(jnp.float32)), sizes, True)
    numpy.testing.assert_array_equal(
        numpy.asarray(got.astype(jnp.float32)),
        numpy.asarray(jnp.asarray(want).astype(jnp.bfloat16)
                      .astype(jnp.float32)))
