"""HybridGenModel (Mamba-2 + latent mixture of experts + grouped-query
attention) against its plain float32 reference, on the CPU at the
rehearsal size with seeded random weights: prefill then decode through
the engine's cache equals the reference's whole forward pass; admission
resets a slot's state; an inactive slot's state does not move; the
shares of an expert layer add up to the uncut layer; no token is
dropped under the most uneven routing; every engine mode that cannot
hold recurrent state refuses the model by name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmarks import harness  # noqa: E402
from benchmarks.drivers import serve_hybrid  # noqa: E402
from benchmarks.reference import nemotron_h as reference  # noqa: E402
from veles_tpu.gen import GenerativeEngine, HybridGenModel  # noqa: E402
from veles_tpu.ops import grouped  # noqa: E402
from veles_tpu.samples import hybrid_lm  # noqa: E402

CONFIG_FILE = os.path.join(REPO_ROOT, "benchmarks", "configs",
                           "nemotron3_super_120b_a12b.json")
SLOTS, MAX_SEQ = 4, 64


@pytest.fixture(scope="module")
def config():
    whole = harness.load_json(CONFIG_FILE)
    return harness.merge(whole, whole["rehearsal"])


@pytest.fixture(scope="module")
def params(config):
    return reference.init_params(config, 2 ** 31 + 7, jnp.float32)


@pytest.fixture(scope="module")
def model(config):
    return HybridGenModel(serve_hybrid.program_config(config))


@pytest.fixture
def interpret():
    """``use_pallas=True`` on this CPU: the kernels in interpret mode."""
    from veles_tpu.config import root
    prior = root.common.engine.get("interpret", False)
    root.common.engine.interpret = True
    yield
    root.common.engine.interpret = prior


def _tokens(seed, n, vocab=64):
    return numpy.random.default_rng(seed).integers(
        0, vocab, n).astype(numpy.int32)


def _reference_logits(config, params, sequence):
    return numpy.asarray(reference.logits_at(
        params, config, jnp.asarray(sequence),
        jnp.arange(len(sequence))))


_JITS = {}


def _jit(model, name):
    """One jitted function a model and a method: a fresh ``jax.jit``
    every call would compile every call."""
    key = (id(model), name)
    if key not in _JITS:
        _JITS[key] = jax.jit(getattr(model, name))
    return _JITS[key]


def _prefill(model, params, cache, prompt, slot, bucket):
    padded = numpy.zeros((1, bucket), numpy.int32)
    padded[0, :len(prompt)] = prompt
    cache, last, _counts = _jit(model, "prefill_hidden")(
        params, cache, jnp.asarray(padded), jnp.int32(slot),
        jnp.int32(len(prompt)))
    return cache, numpy.asarray(model.head_logits(params, last))[0]


def _decode(model, params, cache, slot, token, position):
    tokens = numpy.zeros(SLOTS, numpy.int32)
    positions = numpy.zeros(SLOTS, numpy.int32)
    active = numpy.zeros(SLOTS, bool)
    tokens[slot], positions[slot], active[slot] = token, position, True
    cache, x, _counts = _jit(model, "decode_hidden")(
        params, cache, jnp.asarray(tokens), jnp.asarray(positions),
        jnp.asarray(active))
    return cache, numpy.asarray(model.head_logits(params, x))[slot]


def test_the_rehearsal_covers_all_three_kinds_and_the_sample_agrees(
        config, params, model):
    assert set(model.pattern) == {"M", "E", "*"}
    assert model.held < model.router_width and model.top_k > 1
    want = jax.tree.map(lambda s: (s.shape, numpy.dtype(s.dtype)),
                        hybrid_lm.param_shapes(model.cfg))
    have = jax.tree.map(lambda a: (a.shape, numpy.dtype(a.dtype)), params)
    assert want == have
    own = hybrid_lm.init_params(model.cfg, seed=3)
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)
    # dt_bias inverts the softplus of a step in [0.001, 0.1]
    dt = numpy.log1p(numpy.exp(own["layers"][0]["dt_bias"]))
    assert 0.00099 < dt.min() and dt.max() < 0.1001


# (a) ----------------------------------------------------------------------
# the chunk is 8 tokens: a prompt shorter than, equal to and longer than
# a chunk, each shorter than its bucket except the one that fills it
@pytest.mark.parametrize("n,bucket", [(5, 8), (8, 8), (8, 32), (19, 32),
                                      (32, 32)])
def test_prefill_then_decode_through_the_cache_is_the_whole_forward_pass(
        config, params, model, n, bucket):
    prompt, more = _tokens(n, n), _tokens(100 + n, 6)
    sequence = numpy.concatenate([prompt, more])
    want = _reference_logits(config, params, sequence)
    slot = 2
    # float32 on both sides; the rehearsal's logits reach tens (its
    # init gain), so agreement is to a few 1e-5 absolute
    cache = model.init_cache(SLOTS, MAX_SEQ)
    cache, got = _prefill(model, params, cache, prompt, slot, bucket)
    numpy.testing.assert_allclose(got, want[n - 1], atol=2e-4)
    for i, token in enumerate(more):
        cache, got = _decode(model, params, cache, slot, token, n + i)
        numpy.testing.assert_allclose(got, want[n + i], atol=2e-4)


@pytest.mark.parametrize("dense_tokens,use_pallas", [
    (0, False), (256, None), (0, True)])
def test_both_forms_of_the_expert_product_are_the_references_stack(
        config, params, dense_tokens, use_pallas, interpret):
    """The whole stack's logits at every position of one sequence: with
    every prompt taking the grouped product (``dense_tokens`` 0) as
    ``ragged_dot`` and as the TPU's kernel, and with the dense pass the
    rehearsal's buckets take by default."""
    model = HybridGenModel(serve_hybrid.program_config(config),
                           dense_tokens=dense_tokens,
                           use_pallas=use_pallas)
    sequence = _tokens(21, 27)
    numpy.testing.assert_allclose(
        model.logits(params, sequence),
        _reference_logits(config, params, sequence), atol=2e-4)


def test_a_padded_bucket_leaves_the_state_as_of_the_real_last_token(
        params, model):
    prompt = _tokens(4, 11)
    exact = _prefill(model, params, model.init_cache(SLOTS, MAX_SEQ),
                     prompt, 1, 11)[0]
    padded = _prefill(model, params, model.init_cache(SLOTS, MAX_SEQ),
                      prompt, 1, 32)[0]
    for kind, a, b in zip(model.pattern, exact["layers"],
                          padded["layers"]):
        if kind == "M":
            numpy.testing.assert_allclose(a["h"][1], b["h"][1], atol=1e-6)
            numpy.testing.assert_array_equal(a["conv"][1], b["conv"][1])
            assert float(jnp.abs(a["h"][1]).max()) > 0


def test_the_chunked_form_is_the_sequential_recurrence(model):
    from veles_tpu.gen.hybrid import ssd_chunked
    rng = numpy.random.default_rng(5)
    T, H, P, G, N = 21, 4, 8, 2, 16
    xs = rng.standard_normal((T, H, P)).astype(numpy.float32)
    dt = rng.uniform(0.001, 1.5, (T, H)).astype(numpy.float32)
    A = -rng.uniform(1, 16, H).astype(numpy.float32)
    B = rng.standard_normal((T, G, N)).astype(numpy.float32)
    C = rng.standard_normal((T, G, N)).astype(numpy.float32)
    h = numpy.zeros((H, P, N))
    want = numpy.zeros((T, H, P))
    for t in range(T):
        Bt, Ct = numpy.repeat(B[t], H // G, 0), numpy.repeat(C[t], H // G, 0)
        h = numpy.exp(dt[t] * A)[:, None, None] * h \
            + (dt[t][:, None] * xs[t])[:, :, None] * Bt[:, None, :]
        want[t] = (h * Ct[:, None, :]).sum(-1)
    for chunk in (4, 8, 21, 128):
        y, last = ssd_chunked(jnp.asarray(xs), jnp.asarray(dt),
                              jnp.asarray(A), jnp.asarray(B),
                              jnp.asarray(C), chunk)
        numpy.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)
        numpy.testing.assert_allclose(last, h, rtol=1e-4, atol=1e-4)


# (b) ----------------------------------------------------------------------
def test_a_slot_admitted_again_gives_the_logits_of_a_fresh_engine(
        params, model):
    first, second, more = _tokens(1, 27), _tokens(2, 9), _tokens(3, 4)
    used = model.init_cache(SLOTS, MAX_SEQ)
    used, _ = _prefill(model, params, used, first, 0, 32)
    for i, token in enumerate(_tokens(9, 5)):
        used, _ = _decode(model, params, used, 0, token, 27 + i)
    fresh = model.init_cache(SLOTS, MAX_SEQ)
    used, again = _prefill(model, params, used, second, 0, 32)
    fresh, clean = _prefill(model, params, fresh, second, 0, 32)
    numpy.testing.assert_array_equal(again, clean)
    for i, token in enumerate(more):
        used, again = _decode(model, params, used, 0, token, 9 + i)
        fresh, clean = _decode(model, params, fresh, 0, token, 9 + i)
        numpy.testing.assert_array_equal(again, clean)


@pytest.mark.parametrize("dense_tokens", [256, 8])
def test_the_engine_serves_the_references_greedy_tokens_and_counts(
        config, params, dense_tokens, interpret):
    """``dense_tokens`` 8: the 32 bucket is a prompt above it and takes
    the grouped product (the kernel), a decode step of 4 slots does
    not."""
    model = HybridGenModel(serve_hybrid.program_config(config),
                           dense_tokens=dense_tokens, use_pallas=True)
    engine = GenerativeEngine(model, params=params, max_slots=SLOTS,
                              max_seq=MAX_SEQ, prefill_buckets=(8, 32))
    try:
        first = _tokens(11, 13)
        slot, token = engine.prefill(first)
        engine.decode_step()
        engine.release_slot(slot)
        prompt = _tokens(12, 11)
        slot, token = engine.prefill(prompt)      # the same slot again
        served = [token]
        for _ in range(6):
            out, active = engine.decode_step()
            assert active[slot] and active.sum() == 1
            served.append(int(out[slot]))
        want = _reference_logits(
            config, params,
            numpy.concatenate([prompt, served[:-1]]).astype(numpy.int32))
        assert served == [int(t) for t in want[10:].argmax(-1)]
        layers = model.pattern.count("E")
        counted = engine.counters
        assert counted["prefill"]["moe_pairs_total"] == \
            (13 + 11) * model.top_k * layers
        assert counted["decode"]["moe_pairs_total"] == \
            7 * model.top_k * layers
        for kind in counted.values():
            assert 0 < kind["moe_local_pairs"] <= kind["moe_pairs_total"]
            assert 0 < kind["moe_experts_touched"]
            assert 0 < kind["moe_expert_load_max"] <= 13
        assert counted["decode"]["moe_grouped_rows"] == \
            counted["decode"]["moe_grouped_blocks"] == 0
        through = counted["prefill"]["moe_grouped_rows"]
        blocks = counted["prefill"]["moe_grouped_blocks"]
        if dense_tokens == 8:       # both prompts took the 32 bucket
            assert 0 < through == counted["prefill"]["moe_local_pairs"]
            # a block holds a pair, and at most a block's rows
            assert through <= blocks * grouped.BLOCK_ROWS
            assert 0 < blocks <= min(through, 2 * layers * (
                -(-32 * model.top_k // grouped.BLOCK_ROWS)
                + model.held - 1))
        else:
            assert through == blocks == 0
        info = engine.describe()
        assert info["state_bytes_per_slot"] == \
            model.recurrent_nbytes(SLOTS) // SLOTS > 0
        assert info["kv_bytes_per_slot"] == \
            2 * MAX_SEQ * model.kv_heads * model.head_dim * 4
        assert info["kv_cache_bytes"] == SLOTS * (
            info["state_bytes_per_slot"] + info["kv_bytes_per_slot"]) \
            == sum(leaf.nbytes for leaf in jax.tree.leaves(engine._cache))
        assert engine.hbm_per_request_bytes() == \
            info["kv_cache_bytes"] // SLOTS + engine.params_nbytes
    finally:
        engine.close()


def test_the_hbm_ledgers_kv_category_holds_the_whole_tree(params, model):
    from veles_tpu.memory import Watcher
    before = Watcher.bytes_by_category.get("kv", 0)
    engine = GenerativeEngine(model, params=params, max_slots=SLOTS,
                              max_seq=MAX_SEQ, prefill_buckets=(8,))
    held = Watcher.bytes_by_category.get("kv", 0) - before
    engine.close()
    assert held == model.cache_nbytes(SLOTS, MAX_SEQ) \
        > model.recurrent_nbytes(SLOTS) > 0


# (c) ----------------------------------------------------------------------
def test_an_inactive_slots_state_is_bit_identical_after_a_decode_step(
        params, model):
    cache = model.init_cache(SLOTS, MAX_SEQ)
    cache, _ = _prefill(model, params, cache, _tokens(6, 14), 1, 32)
    cache, _ = _prefill(model, params, cache, _tokens(7, 9), 3, 32)
    before = jax.tree.map(numpy.asarray, cache)
    after, _ = _decode(model, params, cache, 3, 5, 9)
    moved = False
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        b = numpy.asarray(b)
        for slot in (0, 1, 2):
            numpy.testing.assert_array_equal(a[slot], b[slot])
        moved = moved or not numpy.array_equal(a[3], b[3])
    assert moved


# (d) ----------------------------------------------------------------------
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(config):
    """Four chips, two experts each, of a layer of eight: what the
    shares give, with the shared expert (computed alike on every chip)
    counted once, is the uncut reference's result."""
    uncut = dict(config, n_routed_experts=config["router_width"],
                 held_from=0, hybrid_override_pattern="E",
                 num_hidden_layers=1, init_gain=8.0)
    dims = reference.dims(uncut)
    layer = reference.init_params(uncut, 13, jnp.float32)["layers"][0]
    x = numpy.random.default_rng(0).standard_normal(
        (24, dims["d"])).astype(numpy.float32)
    u = reference._rmsnorm(jnp.asarray(x), layer["norm"], dims["eps"])
    whole = reference.moe_layer(layer, u, dims)
    shared = reference.moe_shared(layer, u, dims)
    shares, per = 4, dims["router_width"] // 4
    total = 0
    for share in range(shares):
        held = slice(share * per, (share + 1) * per)
        cut = dict(uncut, n_routed_experts=per, held_from=share * per)
        mine = dict(layer, w1=layer["w1"][held], w2=layer["w2"][held])
        # the reference, given the share
        numpy.testing.assert_allclose(
            reference.moe_layer(mine, u, reference.dims(cut)),
            reference.moe_routed(mine, u, reference.dims(cut)) + shared,
            atol=1e-6)
        for dense_tokens in (64, 0):        # both forms of the program
            model = HybridGenModel(serve_hybrid.program_config(cut),
                                   dense_tokens=dense_tokens)
            out, counts = model._moe(mine, jnp.asarray(x),
                                     jnp.ones(24, bool))
            numpy.testing.assert_allclose(
                out - x, reference.moe_layer(mine, u, reference.dims(cut)),
                atol=2e-5)
        total = total + (out - x)
    numpy.testing.assert_allclose(total - (shares - 1) * shared, whole,
                                  atol=5e-5)
    assert float(jnp.abs(whole - shared).max()) > 1e-3


# (e) ----------------------------------------------------------------------
@pytest.mark.parametrize("dense_tokens,use_pallas", [
    (0, False), (64, None), (0, True), (64, True)])
def test_no_token_is_dropped_when_every_token_goes_to_one_expert(
        config, dense_tokens, use_pallas, interpret):
    """Up to ``dense_tokens`` rows a row that is not valid chooses no
    expert: it gets the shared expert's part alone."""
    one = dict(config, hybrid_override_pattern="E", num_hidden_layers=1,
               init_gain=8.0)
    dims = reference.dims(one)
    layer = reference.init_params(one, 17, jnp.float32)["layers"][0]
    # the bias enters the choice and not the weight: expert 1 is every
    # token's first choice
    layer = dict(layer, e_bias=layer["e_bias"].at[1].set(10.0))
    T = 40
    x = numpy.random.default_rng(1).standard_normal(
        (T, dims["d"])).astype(numpy.float32)
    u = reference._rmsnorm(jnp.asarray(x), layer["norm"], dims["eps"])
    model = HybridGenModel(serve_hybrid.program_config(one),
                           dense_tokens=dense_tokens,
                           use_pallas=use_pallas)
    valid = jnp.arange(T) < 37
    out, counts = model._moe(layer, jnp.asarray(x), valid)
    want = reference.moe_layer(layer, u, dims)
    if dense_tokens:
        want = want.at[37:].set(reference.moe_shared(layer, u, dims)[37:])
    numpy.testing.assert_allclose(out - x, want, atol=2e-5)
    counts = dict(zip(model.counters, (int(c) for c in counts)))
    assert counts["moe_expert_load_max"] == 37
    assert counts["moe_pairs_total"] == 37 * model.top_k
    assert 37 <= counts["moe_local_pairs"] <= counts["moe_pairs_total"]
    assert 1 <= counts["moe_experts_touched"] <= model.held
    if dense_tokens:
        assert counts["moe_grouped_rows"] == \
            counts["moe_grouped_blocks"] == 0
    else:
        assert counts["moe_grouped_rows"] == counts["moe_local_pairs"]
        assert counts["moe_experts_touched"] <= \
            counts["moe_grouped_blocks"] <= counts["moe_local_pairs"]


def test_a_served_batch_reads_the_touched_experts_and_says_the_dense_passes_tokens(
        config, params, mix_lists):
    """Three prompts in three slots, five decode steps of a pool three
    quarters full: the kernel's form (every bucket and every decode step
    here has few rows) serves the tokens of every held expert over every
    row, and ``moe_experts_touched`` is the experts its lists held."""
    held, served = mix_lists, {}
    for pallas in (True, False):
        model = HybridGenModel(serve_hybrid.program_config(config),
                               use_pallas=pallas)
        engine = GenerativeEngine(model, params=params, max_slots=SLOTS,
                                  max_seq=MAX_SEQ, prefill_buckets=(8, 32))
        try:
            tokens = [engine.prefill(_tokens(40 + i, n))[1]
                      for i, n in enumerate((5, 13, 8))]
            jax.effects_barrier()
            in_prefills = sum(held)
            for _ in range(5):
                out, active = engine.decode_step()
                assert active.sum() == 3
                tokens.extend(int(token) for token in out[active])
            jax.effects_barrier()
            counted = engine.counters
        finally:
            engine.close()
        served[pallas] = tokens
        if pallas:
            layers = model.pattern.count("E")
            assert len(held) == (3 + 5) * layers
            assert 0 < counted["prefill"]["moe_experts_touched"] \
                == in_prefills
            assert 0 < counted["decode"]["moe_experts_touched"] \
                == sum(held) - in_prefills
            # a slot with no request chooses no expert
            assert max(held) <= min(3 * model.top_k, model.held)
            del held[:]
    assert not held         # the dense form runs no kernel
    assert served[True] == served[False]


# (f) ----------------------------------------------------------------------
@pytest.mark.parametrize("mode,kwargs", [
    ("kv='paged'", {"kv": "paged", "block_size": 8}),
    ("prefix_cache", {"prefix_cache": "on"}),
    ("prefill_chunk", {"prefill_chunk": 8}),
    ("speculative", {"speculative": "ngram"}),
])
def test_a_mode_that_assumes_kv_pages_refuses_the_model_by_name(
        params, model, mode, kwargs):
    with pytest.raises(ValueError) as caught:
        GenerativeEngine(model, params=params, max_slots=SLOTS,
                         max_seq=MAX_SEQ, prefill_buckets=(8,), **kwargs)
    assert mode in str(caught.value)
    assert "recurrent state" in str(caught.value)
    assert "HybridGenModel" in str(caught.value)


@pytest.mark.parametrize("call", ["export_slot", "adopt_sequence",
                                  "preempt"])
def test_export_adopt_and_preempt_refuse_the_model_by_name(
        params, model, call):
    engine = GenerativeEngine(model, params=params, max_slots=SLOTS,
                              max_seq=MAX_SEQ, prefill_buckets=(8,))
    try:
        slot, _token = engine.prefill(_tokens(8, 5))
        argument = {"n": 5} if call == "adopt_sequence" else slot
        with pytest.raises(ValueError) as caught:
            getattr(engine, call)(argument)
        assert "recurrent state" in str(caught.value)
        assert call.split("_")[0] in str(caught.value)
        assert engine.slot_active[slot]         # nothing was corrupted
    finally:
        engine.close()


def test_the_transformer_is_not_refused_and_counts_nothing():
    from veles_tpu.gen import TransformerGenModel
    from veles_tpu.samples.transformer import TINY
    engine = GenerativeEngine(TransformerGenModel(dict(TINY, seq_len=32)),
                              max_slots=2, max_seq=32, kv="paged",
                              block_size=8, prefill_buckets=(8,))
    try:
        assert not engine.recurrent and engine.state_cache_bytes == 0
        assert engine.counters == {"prefill": {}, "decode": {}}
        assert engine.describe()["state_bytes_per_slot"] == 0
    finally:
        engine.close()
