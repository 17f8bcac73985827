"""The KV cache rides the layer loop as its CARRY and is written in place.

Two gates over the eight entry points of the model protocol (``prefill``,
``decode``, ``verify``, ``prefill_chunk`` and their ``paged_*`` twins), at
a tiny geometry on the CPU:

- **structure**: in the entry point's jaxpr the layer loop's ``scan``
  takes no cache-shaped ``xs`` and stacks no cache-shaped ``ys``, and
  both cache arrays are among its carries; compiled with the cache
  donated, the executable aliases cache-in to cache-out.  A cache that
  goes in as ``xs`` and comes back as ``ys`` is sliced, copied and
  written back whole on every call (4.8 GB a decode step in the chat
  cell: PERF.md, PR 27), and no parity test can see that.
- **parity**: against the semantics the ``xs``/``ys`` scan computed,
  written plainly here over per-layer slabs: tokens equal and the cache equal bitwise, and every row outside the
  window an entry point owns (inactive slots, other slots, other pages)
  untouched.
"""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.gen import TransformerGenModel
from veles_tpu.ops.attention import (chunk_attention, decode_attention,
                                     flash_attention,
                                     paged_decode_attention,
                                     paged_verify_attention,
                                     verify_attention)

CFG = {"vocab": 50, "dim": 16, "heads": 2, "layers": 3, "mlp_ratio": 2,
       "seq_len": 32}
SLOTS, MAX_SEQ, BS = 4, 32, 8
MAX_BLOCKS = MAX_SEQ // BS
NUM_BLOCKS = SLOTS * MAX_BLOCKS + 1        # block 0 is the trash block
K = 2                                      # draft rows of a verify step
ENTRY_POINTS = ("prefill", "decode", "verify", "prefill_chunk",
                "paged_prefill", "paged_decode", "paged_verify",
                "paged_prefill_chunk")


def _i32(x):
    return jnp.asarray(x, jnp.int32)


def _case(name):
    """``(model, params, cache, args, written)``: the entry point's
    arguments after ``(params, cache)``, a cache filled with noise (so an
    untouched row is told from a zeroed one), and the boolean mask over
    ``cache["k"][0]``'s first two axes of the rows the call may write."""
    model = TransformerGenModel(CFG)
    params = jax.tree.map(jnp.asarray, model.init_params(seed=3))
    rng = numpy.random.default_rng(7)
    paged = name.startswith("paged_")
    shape = (model.paged_cache_shape(NUM_BLOCKS, BS) if paged
             else model.cache_shape(SLOTS, MAX_SEQ))
    cache = {key: jnp.asarray(rng.standard_normal(shape), jnp.float32)
             for key in ("k", "v")}
    # slot s owns pages 1 + s*MAX_BLOCKS ...; slot 2 is inactive
    tables = 1 + numpy.arange(SLOTS * MAX_BLOCKS).reshape(SLOTS, MAX_BLOCKS)
    positions = numpy.array([5, 9, 3, 17])
    active = numpy.array([True, True, False, True])
    drafts = numpy.array([2, 0, 1, 1])
    written = numpy.zeros(shape[1:3], bool)
    kind = name[len("paged_"):] if paged else name

    def mark(slot, lo, hi):
        for pos in range(lo, hi):
            if paged:
                written[tables[slot, pos // BS], pos % BS] = True
            else:
                written[slot, pos] = True

    if paged:
        written[0] = True               # the trash block takes anything
    if kind == "prefill":
        bucket, length, slot = 16, 11, 1
        tokens = _i32(rng.integers(0, CFG["vocab"], (1, bucket)))
        mark(slot, 0, bucket)
        args = ((tokens, _i32(tables[slot, :bucket // BS]), _i32(length))
                if paged else (tokens, _i32(slot), _i32(length)))
    elif kind == "decode":
        tokens = _i32(rng.integers(0, CFG["vocab"], SLOTS))
        for slot in numpy.flatnonzero(active):
            mark(slot, positions[slot], positions[slot] + 1)
        args = (tokens, _i32(positions), jnp.asarray(active))
        if paged:
            args = (_i32(tables),) + args
    elif kind == "verify":
        tokens = _i32(rng.integers(0, CFG["vocab"], (SLOTS, K + 1)))
        for slot in numpy.flatnonzero(active):
            mark(slot, positions[slot],
                 positions[slot] + drafts[slot] + 1)
        args = (tokens, _i32(positions), _i32(drafts),
                jnp.asarray(active))
        if paged:
            args = (_i32(tables),) + args
    else:                               # prefill_chunk
        chunk, start, chunk_len, slot = 8, 8, 6, 3
        tokens = _i32(rng.integers(0, CFG["vocab"], (1, chunk)))
        mark(slot, start, start + chunk)
        if paged:
            ids = tables[slot, start // BS:(start + chunk) // BS]
            args = (tokens, _i32(ids), _i32(tables[slot]), _i32(start),
                    _i32(chunk_len))
        else:
            args = (tokens, _i32(slot), _i32(start), _i32(chunk_len))
    return model, params, cache, args, written


# -- structure --------------------------------------------------------------

def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


@pytest.mark.parametrize("name", ENTRY_POINTS)
def test_cache_is_carried_not_scanned_and_aliased(name):
    model, params, cache, args, _written = _case(name)
    fn = getattr(model, name)
    whole = cache["k"].shape
    loops = [eqn for eqn in _scans(jax.make_jaxpr(fn)(
        params, cache, *args).jaxpr)
        if eqn.params["length"] == model.layers]
    assert len(loops) == 1, "one layer loop, found %d" % len(loops)
    loop = loops[0]
    n_consts = loop.params["num_consts"]
    n_carry = loop.params["num_carry"]
    carries = [v.aval.shape for v in
               loop.invars[n_consts:n_consts + n_carry]]
    xs = [v.aval.shape for v in loop.invars[n_consts + n_carry:]]
    ys = [v.aval.shape for v in loop.outvars[n_carry:]]
    assert carries.count(whole) == 2, \
        "both cache arrays ride the loop as carries: %r" % (carries,)
    for shape in xs:
        assert shape != whole and shape[1:] != whole[1:], \
            "the loop scans a cache-shaped array: xs %r" % (xs,)
    assert not ys, "the loop stacks per-layer results: %r" % (ys,)

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    nbytes = sum(c.size * c.dtype.itemsize for c in cache.values())
    memory = compiled.memory_analysis()
    aliased = memory.alias_size_in_bytes
    assert aliased >= nbytes or \
        "input_output_alias" in compiled.as_text(), \
        "cache-in is not aliased to cache-out (%d of %d bytes)" \
        % (aliased, nbytes)
    # the xs/ys scan held a second cache among its temporaries (and
    # 1.3-1.8 caches in all here); in place, the activations alone
    assert memory.temp_size_in_bytes < nbytes, \
        "%d bytes of temporaries beside a cache of %d" \
        % (memory.temp_size_in_bytes, nbytes)


# -- parity with the per-layer-slab semantics -------------------------------

def _layernorm(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b


def _slab_forward(params, cache, h, slab_hook):
    """What the ``xs``/``ys`` scan computed, as it computed it: layer
    ``i`` is handed ITS slab of K and of V as the scan's ``xs``, returns
    the slab it leaves, and the slabs are stacked back into a new cache
    as the scan's ``ys``.  A scan and not a Python loop over the layers,
    because bitwise is the claim: the unrolled program fuses the float32
    arithmetic of a prompt's rows otherwise and lands one unit in the
    last place away in 0.5-2% of a prefill's K and V (decode and verify
    agree either way)."""
    def layer(h, xs):
        blk, kc, vc = xs
        x = _layernorm(h, blk["ln1_g"], blk["ln1_b"])
        qkv = jnp.einsum("bsd,dchx->bschx", x, blk["wqkv"])
        kc, vc, att = slab_hook(kc, vc, qkv[:, :, 0], qkv[:, :, 1],
                                qkv[:, :, 2])
        h = h + jnp.einsum("bshx,hxd->bsd", att, blk["wo"])
        x = _layernorm(h, blk["ln2_g"], blk["ln2_b"])
        up = jax.nn.gelu(x @ blk["w1"] + blk["b1"])
        return h + (up @ blk["w2"] + blk["b2"]), (kc, vc)

    h, (ks, vs) = jax.lax.scan(
        layer, h, (params["blocks"], cache["k"], cache["v"]))
    h = _layernorm(h, params["lnf_g"], params["lnf_b"])
    return h, {"k": ks, "v": vs}


def _greedy(params, rows):
    return jnp.argmax(jnp.einsum("...d,vd->...v", rows, params["embed"]),
                      axis=-1).astype(jnp.int32)


def _attend(q, k, v):
    return flash_attention(q, k, v, True, None, None, None)


def _pages(x, n_blk):
    return x[0].reshape(n_blk, BS, CFG["heads"], -1)


def _ref_prefill(params, cache, tokens, slot, length):
    h = params["embed"][tokens] + params["pos"][:tokens.shape[1]]

    def hook(kc, vc, q, k, v):
        return (jax.lax.dynamic_update_slice(kc, k, (slot, 0, 0, 0)),
                jax.lax.dynamic_update_slice(vc, v, (slot, 0, 0, 0)),
                _attend(q, k, v))

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h[0, length - 1])


def _ref_paged_prefill(params, cache, tokens, block_ids, length):
    h = params["embed"][tokens] + params["pos"][:tokens.shape[1]]
    n_blk = block_ids.shape[0]

    def hook(kc, vc, q, k, v):
        return (kc.at[block_ids].set(_pages(k, n_blk)),
                vc.at[block_ids].set(_pages(v, n_blk)), _attend(q, k, v))

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h[0, length - 1])


def _ref_decode(params, cache, tokens, positions, active):
    h = (params["embed"][tokens] + params["pos"][positions])[:, None]
    idx = jnp.arange(tokens.shape[0])
    keep = active[:, None, None]

    def hook(kc, vc, q, k, v):
        kc = kc.at[idx, positions].set(
            jnp.where(keep, k[:, 0], kc[idx, positions]))
        vc = vc.at[idx, positions].set(
            jnp.where(keep, v[:, 0], vc[idx, positions]))
        return kc, vc, decode_attention(q, kc, vc, positions + 1)

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h[:, 0])


def _ref_paged_decode(params, cache, tables, tokens, positions, active):
    h = (params["embed"][tokens] + params["pos"][positions])[:, None]
    idx = jnp.arange(tokens.shape[0])
    blk_idx = jnp.where(active, tables[idx, positions // BS], 0)
    blk_off = jnp.where(active, positions % BS, 0)

    def hook(kc, vc, q, k, v):
        kc = kc.at[blk_idx, blk_off].set(k[:, 0])
        vc = vc.at[blk_idx, blk_off].set(v[:, 0])
        return kc, vc, paged_decode_attention(q, kc, vc, tables,
                                              positions + 1)

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h[:, 0])


def _verify_rows(params, tokens, positions, drafts, active):
    offs = jnp.arange(tokens.shape[1])
    gpos = positions[:, None] + offs[None, :]
    h = (params["embed"][tokens]
         + params["pos"][jnp.clip(gpos, 0, CFG["seq_len"] - 1)])
    keep = active[:, None] & (offs[None, :] <= drafts[:, None])
    return h, keep, jnp.where(keep, gpos, 0)


def _ref_verify(params, cache, tokens, positions, drafts, active):
    h, keep, safe = _verify_rows(params, tokens, positions, drafts, active)
    rows = jnp.broadcast_to(jnp.arange(tokens.shape[0])[:, None],
                            tokens.shape)
    keep = keep[..., None, None]

    def hook(kc, vc, q, k, v):
        kc = kc.at[rows, safe].set(jnp.where(keep, k, kc[rows, safe]))
        vc = vc.at[rows, safe].set(jnp.where(keep, v, vc[rows, safe]))
        return kc, vc, verify_attention(q, kc, vc, positions + 1)

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h)


def _ref_paged_verify(params, cache, tables, tokens, positions, drafts,
                      active):
    h, keep, safe = _verify_rows(params, tokens, positions, drafts, active)
    idx = jnp.arange(tokens.shape[0])
    blk_idx = jnp.where(keep, tables[idx[:, None], safe // BS], 0)
    blk_off = jnp.where(keep, safe % BS, 0)

    def hook(kc, vc, q, k, v):
        kc = kc.at[blk_idx, blk_off].set(k)
        vc = vc.at[blk_idx, blk_off].set(v)
        return kc, vc, paged_verify_attention(q, kc, vc, tables,
                                              positions + 1)

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h)


def _ref_prefill_chunk(params, cache, tokens, slot, start, chunk_len):
    chunk = tokens.shape[1]
    h = params["embed"][tokens] + jax.lax.dynamic_slice_in_dim(
        params["pos"], start, chunk)

    def hook(kc, vc, q, k, v):
        kc = jax.lax.dynamic_update_slice(kc, k, (slot, start, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v, (slot, start, 0, 0))
        return kc, vc, chunk_attention(
            q, jax.lax.dynamic_slice_in_dim(kc, slot, 1),
            jax.lax.dynamic_slice_in_dim(vc, slot, 1), start)

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h[0, chunk_len - 1])


def _ref_paged_prefill_chunk(params, cache, tokens, chunk_ids, table,
                             start, chunk_len):
    chunk = tokens.shape[1]
    n_blk = chunk_ids.shape[0]
    h = params["embed"][tokens] + jax.lax.dynamic_slice_in_dim(
        params["pos"], start, chunk)

    def hook(kc, vc, q, k, v):
        kc = kc.at[chunk_ids].set(_pages(k, n_blk))
        vc = vc.at[chunk_ids].set(_pages(v, n_blk))

        def gather(c):
            return c[table].reshape(1, -1, CFG["heads"], c.shape[-1])

        return kc, vc, chunk_attention(q, gather(kc), gather(vc), start)

    h, cache = _slab_forward(params, cache, h, hook)
    return cache, _greedy(params, h[0, chunk_len - 1])


REFERENCE = {
    "prefill": _ref_prefill, "decode": _ref_decode,
    "verify": _ref_verify, "prefill_chunk": _ref_prefill_chunk,
    "paged_prefill": _ref_paged_prefill,
    "paged_decode": _ref_paged_decode,
    "paged_verify": _ref_paged_verify,
    "paged_prefill_chunk": _ref_paged_prefill_chunk,
}


@pytest.mark.parametrize("name", ENTRY_POINTS)
def test_carried_cache_matches_per_layer_slabs_bitwise(name):
    model, params, cache, args, written = _case(name)
    new_cache, tokens = jax.jit(getattr(model, name))(params, cache, *args)
    ref_cache, ref_tokens = jax.jit(REFERENCE[name])(params, cache, *args)
    numpy.testing.assert_array_equal(numpy.asarray(tokens),
                                     numpy.asarray(ref_tokens))
    assert written.any() and not written.all()
    for key in ("k", "v"):
        before = numpy.asarray(cache[key])
        after = numpy.asarray(new_cache[key])
        numpy.testing.assert_array_equal(after,
                                         numpy.asarray(ref_cache[key]))
        # what the call does not own it leaves as it found it ...
        numpy.testing.assert_array_equal(after[:, ~written],
                                         before[:, ~written])
        # ... and what it owns, it wrote, in every layer (the trash
        # block aside, which only masked rows reach)
        owned = written.copy()
        if name.startswith("paged_"):
            owned[0] = False
        changed = (after != before).any(axis=(-1, -2))
        assert changed[:, owned].all(), \
            "%s left a row it owns unwritten" % name
