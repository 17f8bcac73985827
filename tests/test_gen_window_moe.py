"""WindowMoEGenModel against the plain reference of its family
(``benchmarks/reference/cohere2_moe.py``), float32 on the CPU at the
configuration's rehearsal sizes (window 8, chunk 4): chunked prefill
then decode through ``GenerativeEngine`` is the reference's whole
forward pass across ring wraps, with three slots live at different
lengths; a slot admitted again sees nothing of its last occupant;
rotary positions on the window layers only; the shares of the expert
layer add up to the uncut layer; the engine refuses by name every mode
that assumes each layer keeps each position."""

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
from benchmarks.drivers import serve_window_moe  # noqa: E402
from benchmarks.reference import cohere2_moe as reference  # noqa: E402
from veles_tpu.gen import GenerativeEngine, WindowMoEGenModel  # noqa: E402
from veles_tpu.gen import window_moe  # noqa: E402
from veles_tpu.samples import window_moe_lm  # noqa: E402

CONFIG_FILE = os.path.join(REPO_ROOT, "benchmarks", "configs",
                           "command_a_plus_05_2026.json")
SLOTS, MAX_SEQ, CHUNK = 3, 64, 4


@pytest.fixture(scope="module")
def config():
    whole = harness.load_json(CONFIG_FILE)
    return harness.merge(whole, whole["rehearsal"])


@pytest.fixture(scope="module")
def params(config):
    return reference.init_params(config, 2 ** 31 + 7, jnp.float32)


@pytest.fixture(scope="module")
def model(config):
    return WindowMoEGenModel(serve_window_moe.program_config(config))


def _tokens(seed, n, vocab=64):
    return numpy.random.default_rng(seed).integers(
        0, vocab, n).astype(numpy.int32)


def _reference_logits(config, params, sequence):
    return numpy.asarray(reference.logits_at(
        params, config, jnp.asarray(sequence, jnp.int32),
        jnp.arange(len(sequence))))


_JITS = {}


def _decode_logits(engine):
    """The logits the engine's NEXT decode step will take its tokens
    from, over its own cache as it stands (nothing donated)."""
    model = engine.model
    if id(model) not in _JITS:
        _JITS[id(model)] = jax.jit(model.decode_hidden)
    active = engine.slot_active.copy()
    _cache, x, _counts = _JITS[id(model)](
        engine._params, engine._cache,
        jnp.asarray(numpy.where(active, engine.slot_token, 0)),
        jnp.asarray(numpy.where(active, engine.slot_len, 0)),
        jnp.asarray(active))
    return numpy.asarray(model.head_logits(engine._params, x))


def _admit(engine, prompt):
    slot, token = engine.admit(prompt)
    assert token is None
    while token is None:
        token = engine.prefill_step(slot)
    return slot, token


def _engine(model, params, **kwargs):
    return GenerativeEngine(model, params=params, max_slots=SLOTS,
                            max_seq=MAX_SEQ, prefill_chunk=CHUNK, **kwargs)


@pytest.fixture(scope="module")
def shared_engine(model, params):
    """ONE engine for the tests that take the default model: an engine
    compiles its two programs when it is built."""
    engine = _engine(model, params)
    yield engine
    engine.close()


@pytest.fixture
def engine(shared_engine):
    yield shared_engine
    for slot in range(SLOTS):
        if shared_engine.slot_active[slot] \
                or slot in shared_engine._chunking:
            shared_engine.release_slot(slot)


def _counted(engine):
    return {kind: dict(values)
            for kind, values in engine.counters.items()}


def test_the_rehearsal_is_one_period_and_the_sample_agrees(config, params,
                                                           model):
    assert model.pattern == "WWWF" and model.window_rows == 8
    assert model.held < model.router_width and model.top_k > 1
    assert model.heads // model.kv_heads > 1 and model.shared > 1
    want = jax.tree.map(lambda s: (s.shape, numpy.dtype(s.dtype)),
                        window_moe_lm.param_shapes(model.cfg))
    have = jax.tree.map(lambda a: (a.shape, numpy.dtype(a.dtype)), params)
    assert want == have
    own = window_moe_lm.init_params(model.cfg, seed=3)
    assert jax.tree.map(lambda a: a.shape, own) == \
        jax.tree.map(lambda a: a.shape, params)
    # the tree: a ring of the window's rows, a full layer of max_seq
    assert model.layer_rows(MAX_SEQ) == [8, 8, 8, MAX_SEQ]
    assert model.cache_nbytes(SLOTS, MAX_SEQ) == \
        SLOTS * (3 * 8 + MAX_SEQ) * 2 * 2 * 8 * 4
    published = serve_window_moe.program_config(
        harness.load_json(CONFIG_FILE))
    assert published == window_moe_lm.CONFIG
    assert 4.73e9 < window_moe_lm.param_count(published) < 4.74e9


# (a) ----------------------------------------------------------------------
# window 8, chunk 4: no wrap, an exact fit of the ring, a wrap in the
# third chunk, several wraps
@pytest.mark.parametrize("n", [3, 8, 13, 29])
def test_chunks_then_decode_through_the_engine_are_the_whole_forward_pass(
        config, params, engine, n):
    before_all = _counted(engine)["host"]
    prompts = [_tokens(1, 5), _tokens(2, 17), _tokens(10 + n, n)]
    streams, logits = [], [[] for _ in prompts]
    for prompt in prompts:      # three slots live, at different lengths
        slot, token = _admit(engine, prompt)
        assert slot == len(streams)
        streams.append(list(prompt) + [token])
    for _ in range(7):          # slot 2 wraps its ring again at n = 3
        before = _decode_logits(engine)
        out, active = engine.decode_step()
        assert active.all()
        for slot, stream in enumerate(streams):
            logits[slot].append(before[slot])
            assert int(out[slot]) == int(before[slot].argmax())
            stream.append(int(out[slot]))
    for prompt, stream, got in zip(prompts, streams, logits):
        want = _reference_logits(config, params, stream[:-1])
        # the chunks' own token, then every decode step's logits
        assert stream[len(prompt)] == int(want[len(prompt) - 1].argmax())
        numpy.testing.assert_allclose(
            numpy.stack(got), want[len(prompt):], atol=2e-4)
    host = engine.counters["host"]
    assert host["kv_rows_full"] - before_all["kv_rows_full"] == sum(
        len(p) + 1 + i for p in prompts for i in range(7))
    assert host["kv_rows_window"] - before_all["kv_rows_window"] == 3 * sum(
        min(len(p) + 1 + i, 8) for p in prompts for i in range(7))


def test_the_model_feeds_a_sequence_by_chunks_as_the_reference_reads_it(
        config, params, model):
    sequence = _tokens(5, 29)
    numpy.testing.assert_allclose(
        model.logits(params, sequence, CHUNK),
        _reference_logits(config, params, sequence), atol=2e-4)
    # a chunk of the ring's whole length, and the grouped product
    grouped = WindowMoEGenModel(model.cfg, dense_tokens=0)
    numpy.testing.assert_allclose(
        grouped.logits(params, sequence, 8),
        _reference_logits(config, params, sequence), atol=2e-4)


def test_decode_visits_the_full_layer_by_blocks(config, params, engine):
    """Blocks of the window's 8 rows of the full layer's 64: every
    slot's first block in one visit, then the long slot's three further
    blocks one by one while the short slot has none; the long slot
    crosses into a fifth block on the way."""
    prompts = [_tokens(3, 29), _tokens(4, 6)]
    streams, logits = [], [[], []]
    for prompt in prompts:
        _slot, token = _admit(engine, prompt)
        streams.append(list(prompt) + [token])
    for _ in range(5):
        before = _decode_logits(engine)
        out, _active = engine.decode_step()
        for slot, stream in enumerate(streams):
            logits[slot].append(before[slot])
            stream.append(int(out[slot]))
    for prompt, stream, got in zip(prompts, streams, logits):
        want = _reference_logits(config, params, stream[:-1])
        numpy.testing.assert_allclose(
            numpy.stack(got), want[len(prompt):], atol=2e-4)


@pytest.mark.parametrize("rows,window", [(32, 32), (128, None)])
@pytest.mark.parametrize("start", [0, 16, 48, 112])
def test_the_chunk_kernel_is_the_block_loop(model, rows, window, start):
    """``veles_attn_ring_chunk`` (interpreted) against the jnp visits of
    the live blocks: a ring before its first lap, at its end and laps
    on; every position kept; garbage in the rows never written."""
    from veles_tpu.ops import attention
    C, G, R, dh = 16, 2, 2, 8
    rng = numpy.random.default_rng(start + rows)
    state = {name: jnp.asarray(rng.standard_normal((3, rows, G * dh)),
                               jnp.float32) for name in "kv"}
    q = jnp.asarray(rng.standard_normal((C, G, R, dh)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((C, G * dh)), jnp.float32)
            for _ in range(2))
    want = model._attend_chunk(q, k, v, state, 1, start, window)
    for block_q, block_k in ((8, 8), (32, 16), (4, 4)):
        got = attention.ring_chunk_attention(
            q, k, v, state["k"], state["v"], jnp.int32(1),
            jnp.int32(start), window, floor_rows=32, block_q=block_q,
            block_k=block_k, interpret=True)
        numpy.testing.assert_allclose(got, want, atol=1e-5)


def test_the_model_with_the_kernels_is_the_reference(config, params,
                                                     model):
    from veles_tpu.config import root
    sequence = _tokens(6, 29)
    was = root.common.engine.get("interpret", False)
    root.common.engine.interpret = True
    try:
        got = WindowMoEGenModel(model.cfg, use_pallas=True).logits(
            params, sequence, 8)
    finally:
        root.common.engine.interpret = was
    numpy.testing.assert_allclose(
        got, _reference_logits(config, params, sequence), atol=2e-4)


# (b) ----------------------------------------------------------------------
def test_a_slot_admitted_again_does_not_see_its_last_occupant(
        config, params, engine):
    slot, _token = _admit(engine, _tokens(7, 29))
    for _ in range(4):
        engine.decode_step()
    engine.release_slot(slot)
    prompt = _tokens(8, 5)      # shorter than the ring: stale rows beside
    again, token = _admit(engine, prompt)
    assert again == slot
    stream, got = list(prompt) + [token], []
    for _ in range(6):
        got.append(_decode_logits(engine)[slot])
        out, _active = engine.decode_step()
        stream.append(int(out[slot]))
    want = _reference_logits(config, params, stream[:-1])
    assert token == int(want[len(prompt) - 1].argmax())
    numpy.testing.assert_allclose(numpy.stack(got), want[len(prompt):],
                                  atol=2e-4)


# (c) ----------------------------------------------------------------------
def test_the_rotation_is_the_complex_one_of_the_interleaved_pairs():
    rng = numpy.random.default_rng(0)
    x = rng.standard_normal((6, 3, 8)).astype(numpy.float32)
    positions = numpy.array([0, 1, 5, 8, 13, 4000])
    theta = 50000.0
    pairs = x[..., 0::2] + 1j * x[..., 1::2]
    angle = positions[:, None, None] \
        * theta ** (-numpy.arange(0, 8, 2) / 8.0)[None, None, :]
    turned = pairs * numpy.exp(1j * angle)
    want = numpy.stack([turned.real, turned.imag], -1).reshape(x.shape)
    for rotate in (reference.rotate, window_moe.rope):
        numpy.testing.assert_allclose(
            rotate(jnp.asarray(x), jnp.asarray(positions), theta), want,
            atol=2e-4)      # float32 angles at position 4000
    # a window layer's attention logits see distances alone: every
    # position shifted by one constant changes none of them
    q, k = (jnp.asarray(rng.standard_normal((6, 3, 8)), jnp.float32)
            for _ in range(2))
    near = jnp.arange(6)

    def scores(shift):
        return jnp.einsum(
            "thx,shx->hts", window_moe.rope(q, near + shift, theta),
            window_moe.rope(k, near + shift, theta))

    numpy.testing.assert_allclose(scores(0), scores(37), atol=1e-4)


def test_positions_are_on_the_window_layers_only(config, params, model):
    """What each layer writes to its cache: a full layer's rows are ``u
    Wk`` as they are, a window layer's the rotated ones."""
    dims = reference.dims(config)
    tokens = _tokens(21, 8)
    cache, _x, _counts = model.chunk_hidden(
        params, model.init_cache(1, 8), jnp.asarray(tokens[None]), 0, 0, 8)
    x = params["embed"][tokens]
    for kind, p, state in zip(dims["kinds"], params["layers"],
                              cache["layers"]):
        u = reference.layernorm(x, p["norm"], dims["eps"])
        plain = jnp.dot(u, p["wk"].reshape(dims["d"], -1))
        turned = reference.rotate(
            plain.reshape(8, dims["kv_heads"], dims["head_dim"]),
            jnp.arange(8), dims["theta"]).reshape(8, -1)
        if kind == "full_attention":
            numpy.testing.assert_allclose(state["k"][0], plain, atol=1e-4)
        else:
            numpy.testing.assert_allclose(state["k"][0], turned, atol=1e-4)
            assert float(jnp.abs(turned - plain).max()) > 1e-2
        numpy.testing.assert_allclose(
            state["v"][0], jnp.dot(u, p["wv"].reshape(dims["d"], -1)),
            atol=1e-4)
        x = reference.layer(p, x, dims, kind)


def test_the_reference_reads_a_long_sliding_layer_by_its_band(config,
                                                              params):
    """Past twice the window the reference slices the keys a block of
    queries can reach: the whole masked matrix gives the same."""
    dims = reference.dims(config)
    p = params["layers"][0]
    u = jnp.asarray(numpy.random.default_rng(3).standard_normal(
        (64, dims["d"])), jnp.float32)
    T, d = u.shape
    at = jnp.arange(T)
    q, k, v = (jnp.dot(u, p[name].reshape(d, -1)).reshape(
        T, -1, dims["head_dim"]) for name in ("wq", "wk", "wv"))
    q, k = (reference.rotate(a, at, dims["theta"]) for a in (q, k))
    k, v = (jnp.repeat(a, dims["q_heads"] // dims["kv_heads"], axis=1)
            for a in (k, v))
    scores = jnp.einsum("thx,shx->hts", q, k) / dims["head_dim"] ** 0.5
    seen = (at[None] <= at[:, None]) \
        & (at[:, None] - at[None] < dims["window"])
    att = jnp.einsum("hts,shx->thx", jax.nn.softmax(
        jnp.where(seen[None], scores, -jnp.inf), -1), v)
    whole = jnp.dot(att.reshape(T, -1), p["wo"].reshape(-1, d))
    assert T > 2 * dims["window"]
    for rows in (T, 2 * dims["window"]):    # by its band, and whole
        numpy.testing.assert_allclose(
            reference.attention(p, u[:rows], dims, "sliding_attention"),
            whole[:rows], rtol=1e-5, atol=1e-4)


# (d) ----------------------------------------------------------------------
def test_the_shares_of_a_layer_add_up_to_the_uncut_layer(config):
    """Eight chips, one expert each, of a layer of eight: what the
    shares give, with attention and the shared experts (computed alike
    on every chip) counted once, is the uncut reference's layer."""
    uncut = dict(config, num_experts=config["router_width"], held_from=0,
                 num_hidden_layers=1)
    dims = reference.dims(uncut)
    layer = reference.init_params(uncut, 13, jnp.float32)["layers"][0]
    x = jnp.asarray(numpy.random.default_rng(0).standard_normal(
        (24, dims["d"])).astype(numpy.float32))
    u = reference.layernorm(x, layer["norm"], dims["eps"])
    whole = reference.layer(layer, x, dims, "full_attention")
    alike = reference.attention(layer, u, dims, "full_attention") \
        + reference.moe_shared(layer, u, dims)
    shares = dims["router_width"]
    total = 0
    for share in range(shares):
        held = slice(share, share + 1)
        cut = dict(uncut, num_experts=1, held_from=share)
        mine = dict(layer, **{name: layer[name][held]
                              for name in ("wg", "wu", "wd")})
        routed = reference.moe_routed(mine, u, reference.dims(cut))
        for dense_tokens in (64, 0):        # both forms of the program
            model = WindowMoEGenModel(
                serve_window_moe.program_config(cut),
                dense_tokens=dense_tokens)
            out, _counts = model._ffn(mine, u, jnp.ones(24, bool))
            numpy.testing.assert_allclose(
                out, routed + reference.moe_shared(layer, u, dims),
                atol=2e-5)
        total = total + routed
    numpy.testing.assert_allclose(x + alike + total, whole, atol=5e-5)
    assert float(jnp.abs(total).max()) > 1e-3


def test_the_engine_counts_the_experts_behind_every_chunk(model, engine):
    before, chunks = _counted(engine), engine.prefill_calls
    _admit(engine, _tokens(31, 13))
    engine.decode_step()
    layers = len(model.pattern)
    counted = {kind: {name: value - before[kind][name]
                      for name, value in values.items()}
               for kind, values in engine.counters.items()}
    assert engine.prefill_calls - chunks == 4       # chunks of 4, 4, 4, 1
    assert counted["prefill"]["moe_pairs_total"] == \
        13 * model.top_k * layers
    assert counted["decode"]["moe_pairs_total"] == model.top_k * layers
    for kind in ("prefill", "decode"):
        assert 0 < counted[kind]["moe_local_pairs"] \
            <= counted[kind]["moe_pairs_total"]
        assert 0 < counted[kind]["moe_experts_touched"]


def test_a_served_batch_reads_the_touched_experts_and_says_the_dense_passes_tokens(
        config, params, model, mix_lists):
    """Two prompts by chunks, five decode steps of a pool half full: the
    kernel's form (a chunk and a decode step here have few rows) serves
    the tokens of every held expert over every row, and
    ``moe_experts_touched`` is the experts its lists held."""
    held, served = mix_lists, {}
    for pallas in (True, False):
        engine = _engine(WindowMoEGenModel(model.cfg, use_pallas=pallas),
                         params)
        try:
            tokens = [_admit(engine, _tokens(50 + i, n))[1]
                      for i, n in enumerate((13, 6))]
            jax.effects_barrier()
            in_chunks = sum(held)
            for _ in range(5):
                out, active = engine.decode_step()
                assert active.sum() == 2
                tokens.extend(int(token) for token in out[active])
            jax.effects_barrier()
            counted = _counted(engine)
        finally:
            engine.close()
        served[pallas] = tokens
        if pallas:
            layers = len(model.pattern)
            assert len(held) == (4 + 2 + 5) * layers    # 13 = 4, 4, 4, 1
            assert 0 < counted["prefill"]["moe_experts_touched"] \
                == in_chunks
            assert 0 < counted["decode"]["moe_experts_touched"] \
                == sum(held) - in_chunks
            del held[:]
    assert not held         # the dense form runs no kernel
    assert served[True] == served[False]


# (e) ----------------------------------------------------------------------
@pytest.mark.parametrize("mode,kwargs", [
    ("kv='paged'", {"kv": "paged", "block_size": 4}),
    ("prefix_cache", {"prefix_cache": "on"}),
    ("speculative", {"speculative": "ngram"}),
])
def test_the_engine_refuses_what_a_window_cannot_hold(model, params, mode,
                                                      kwargs):
    with pytest.raises(ValueError) as refused:
        _engine(model, params, **kwargs)
    assert mode in str(refused.value)
    assert "window layers of WindowMoEGenModel" in str(refused.value)


def test_a_live_engine_refuses_replay_and_shipping_by_name(model, params,
                                                           engine):
    slot, _token = _admit(engine, _tokens(1, 5))
    for name, call in (
            ("preempt's replay", lambda: engine.preempt(slot)),
            ("export_slot", lambda: engine.export_slot(slot)),
            ("adopt_sequence", lambda: engine.adopt_sequence({}))):
        with pytest.raises(ValueError) as refused:
            call()
        assert name in str(refused.value)
        assert "window layers" in str(refused.value)
    assert engine.slot_active[slot]
    # prompts enter by chunks alone, and a chunk is one run of the ring
    for chunk in (None, 3, 16):
        with pytest.raises(ValueError, match="by chunks only"):
            GenerativeEngine(model, params=params, max_slots=SLOTS,
                             max_seq=48, prefill_chunk=chunk)
