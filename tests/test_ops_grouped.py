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


# -- few rows through the experts they chose: ``expert_mix`` behind
# ``gen.experts.mix``, the kernel in interpret mode ------------------------

HELD, WIDTH, TOP_K = 8, 16, 4
#: which held experts the valid rows may choose (an expert of 8..15 is
#: held elsewhere); the rows that are not valid choose 7 and 2
SHARES = {"none": [], "one": [5], "a_third": [1, 4, 6],
          "all": [0, 1, 2, 3, 4, 5, 6, 7]}
#: (rows, of them valid): a decode step of 1, 24 and 64 slots, a short
#: bucket two thirds full
ROWS = {"1_row": (1, 1), "24_rows_of_which_6_live": (24, 6),
        "64_rows": (64, 64), "a_32_bucket_holding_21": (32, 21)}


def _routing(seed, rows, live, may):
    """``(local [rows, TOP_K], g, valid)``: every valid row chooses
    ``TOP_K`` experts among ``may`` and those held elsewhere, each of
    ``may`` by some row where the rows allow it."""
    rng = numpy.random.default_rng(seed)
    elsewhere = numpy.arange(HELD, WIDTH)
    chosen = numpy.empty((rows, TOP_K), numpy.int64)
    for row in range(rows):
        if row >= live:
            chosen[row] = [7, 2] + list(elsewhere[:TOP_K - 2])
            continue
        here = rng.permutation(may)[:rng.integers(1, TOP_K + 1)] \
            if len(may) else numpy.zeros(0, numpy.int64)
        if len(may):
            here[0] = may[row % len(may)]
            here = numpy.unique(here)
        chosen[row] = numpy.concatenate(
            [here, rng.permutation(elsewhere)[:TOP_K - len(here)]])
    local = numpy.where(chosen < HELD, chosen, HELD).astype(numpy.int32)
    g = rng.random((rows, TOP_K)).astype(numpy.float32)
    return local, g / g.sum(1, keepdims=True), numpy.arange(rows) < live


def _dense_arithmetic(form, p, x, local, g):
    """What the dense pass computed: every held expert over every row,
    weight 0 where the row did not choose it, in float32."""
    rows = x.shape[0]
    weights = numpy.zeros((rows, HELD + 1), numpy.float32)
    weights[numpy.arange(rows)[:, None], local] = g
    out = numpy.zeros_like(x)
    for expert in range(HELD):
        if form == "relu2":
            hidden = numpy.square(numpy.maximum(x @ p["w1"][expert], 0))
        else:
            gate = x @ p["wg"][expert]
            hidden = gate / (1 + numpy.exp(-gate)) * (x @ p["wu"][expert])
        out += (hidden * weights[:, expert:expert + 1]) @ p[
            "w2" if form == "relu2" else "wd"][expert]
    return out


@pytest.mark.parametrize("rows", sorted(ROWS))
@pytest.mark.parametrize("share", sorted(SHARES))
@pytest.mark.parametrize("form", ["relu2", "gated_silu"])
def test_few_rows_go_through_the_experts_a_valid_row_chose_and_no_other(
        form, share, rows, monkeypatch, mix_lists):
    from veles_tpu.gen import experts
    k, f = 128, 256
    rows, live = ROWS[rows]
    may = SHARES[share]
    rng = numpy.random.default_rng(len(form) + 7 * rows + len(may))
    local, g, valid = _routing(rows + len(may), rows, live, may)
    into, back = experts.FORMS[form]
    p = {name: (rng.standard_normal((HELD, k, f)) * 0.1)
         .astype(numpy.float32) for name in into}
    p[back] = (rng.standard_normal((HELD, f, k)) * 0.1) \
        .astype(numpy.float32)
    x = rng.standard_normal((rows, k)).astype(numpy.float32)
    # the list the kernel walks is the experts the VALID rows chose: a
    # row that is not valid reads none
    want_list = sorted(set(local[:live].reshape(-1)) - {HELD})
    if live < rows and share != "all":
        assert 7 not in want_list and 2 not in want_list
    loads = experts.load_counts(jnp.asarray(local), jnp.asarray(valid),
                                HELD, TOP_K)
    ids, count = (numpy.asarray(a) for a in grouped.touched_list(loads[1]))
    assert list(ids[:count]) == want_list and not ids[count:].any()
    assert int(loads[0][1]) == count == len(want_list)
    # what the list leaves out is never multiplied: poison it
    poisoned = {name: numpy.where(
        numpy.isin(numpy.arange(HELD), want_list)[:, None, None], w,
        numpy.nan) for name, w in p.items()}

    def both(x, local, g, valid, p, poisoned):
        return [experts.mix(form, weights, x, local, g, valid, loads, HELD,
                            TOP_K, 256, jnp.float32, use_pallas=pallas)
                for pallas, weights in ((True, poisoned), (False, p))]

    # two tiles of the width, so that the sum runs over both grid axes
    monkeypatch.setattr(grouped, "_width_tile", lambda *args: 128)
    (kernel, counts), (dense, counts2) = jax.jit(both)(
        x, local, g, valid, p, poisoned)
    jax.effects_barrier()
    assert mix_lists == [len(want_list)]    # the kernel's form ran, once
    want = _dense_arithmetic(form, p, x, local, g)
    # a row that is not valid chose no expert
    want[live:] = 0
    numpy.testing.assert_allclose(kernel, want, rtol=1e-5, atol=1e-5)
    numpy.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-5)
    assert list(numpy.asarray(counts)) == list(numpy.asarray(counts2)) \
        == [int(((local < HELD) & valid[:, None]).sum()), len(want_list),
            live * TOP_K, int(numpy.asarray(loads[1]).max()), 0, 0]


def test_the_mix_takes_bf16_operands_and_sums_in_float32(monkeypatch):
    monkeypatch.setattr(grouped, "_width_tile", lambda *args: 128)
    rng = numpy.random.default_rng(11)
    rows, k, f, held = 24, 128, 256, 4
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.bfloat16)
    into = [jnp.asarray(rng.standard_normal((held, k, f)) * 0.1,
                        jnp.bfloat16) for _ in range(2)]
    back = jnp.asarray(rng.standard_normal((held, f, k)) * 0.1,
                       jnp.bfloat16)
    chosen = rng.random((rows, held)) < 0.5
    chosen[:, 2] = False
    weights = jnp.asarray(numpy.where(chosen, rng.random((rows, held)), 0),
                          jnp.float32)
    load = jnp.asarray(chosen.sum(0), jnp.int32)
    kernel, dense = (grouped.expert_mix(
        x, weights, into, back, load, use_pallas=pallas, interpret=True)
        for pallas in (True, False))
    assert kernel.dtype == dense.dtype == jnp.float32
    # the same bf16 hidden values either way; the sums' order differs
    numpy.testing.assert_allclose(kernel, dense, rtol=2e-3, atol=2e-3)
