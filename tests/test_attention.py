"""Flash-attention kernel tests: Pallas interpret-mode vs the jnp
reference (golden pattern from test_ops.py), gradients via the
blockwise VJP vs autodiff of the reference, and the ring-attention
composition."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops.attention import (
    _bwd_blockwise, _flash_fwd, _mha_jnp, flash_attention)
from veles_tpu.parallel.ring import mha_reference


def _qkv(b=2, sq=24, sk=24, h=3, d=16, seed=0):
    rng = numpy.random.default_rng(seed)
    mk = lambda s: jnp.asarray(
        rng.standard_normal((b, s, h, d)).astype(numpy.float32))
    return mk(sq), mk(sk), mk(sk)


@pytest.mark.parametrize("causal", [False, True])
def test_interpret_matches_reference(causal):
    q, k, v = _qkv()
    ref = mha_reference(q, k, v, causal=causal)
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=8, block_k=8,
                          interpret=True)
    assert out.shape == ref.shape
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=2e-5)
    assert lse.shape == (2, 3, 24)


def test_interpret_ragged_and_rect():
    """Non-multiple-of-block seq lengths and Sq != Sk."""
    q, k, v = _qkv(sq=13, sk=29, d=20, seed=1)
    ref = mha_reference(q, k, v)
    out, _ = _flash_fwd(q, k, v, block_q=8, block_k=8, interpret=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=2e-5)


def test_interpret_mismatched_blocks():
    """bq != bk with lengths that are multiples of neither: every
    tensor must pad to its OWN block size (regression: shared padding
    left trailing q rows unwritten / k blocks unvisited)."""
    q, k, v = _qkv(sq=12, sk=12, d=8, seed=5)
    ref = mha_reference(q, k, v)
    for bq, bk in ((8, 12), (12, 8)):
        out, _ = _flash_fwd(q, k, v, block_q=bq, block_k=bk,
                            interpret=True)
        assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                              atol=2e-5), (bq, bk)


def test_jnp_fallback_matches_reference():
    q, k, v = _qkv(seed=2)
    out, lse = _mha_jnp(q, k, v, causal=True)
    ref = mha_reference(q, k, v, causal=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_vjp_matches_autodiff(causal):
    q, k, v = _qkv(b=1, sq=16, sk=16, h=2, d=8, seed=3)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal, 8, 8, False) ** 2).sum()

    dq, dk, dv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(ref),
                              atol=5e-4), \
            float(numpy.abs(numpy.asarray(got) -
                            numpy.asarray(ref)).max())


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bwd_matches_autodiff(causal):
    """The Pallas two-kernel backward (interpret mode) against
    autodiff of the dense reference."""
    from veles_tpu.ops.attention import _flash_bwd, _flash_fwd
    q, k, v = _qkv(b=1, sq=16, sk=16, h=2, d=8, seed=3)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)
    o, lse = _flash_fwd(q, k, v, causal=causal, block_q=8, block_k=8,
                        interpret=True)
    do = 2.0 * o
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, causal=causal,
                            block_q=8, block_k=8, interpret=True)
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert got.shape == ref.shape
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(ref),
                              atol=5e-4), \
            float(numpy.abs(numpy.asarray(got) -
                            numpy.asarray(ref)).max())


@pytest.mark.parametrize("bq,bk", [(8, 8), (8, 16), (16, 8)])
def test_pallas_bwd_ragged_and_mismatched_blocks(bq, bk):
    """Ragged seq lengths (padding rows/blocks) and bq != bk: padded
    q rows must contribute zero to dk/dv, padded k rows zero to dq."""
    from veles_tpu.ops.attention import _flash_bwd, _flash_fwd
    q, k, v = _qkv(b=2, sq=13, sk=21, h=2, d=12, seed=7)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)
    o, lse = _flash_fwd(q, k, v, block_q=bq, block_k=bk,
                        interpret=True)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, 2.0 * o, block_q=bq,
                            block_k=bk, interpret=True)
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(ref),
                              atol=5e-4), (bq, bk)


def test_pallas_bwd_causal_ragged():
    """Causal + non-block-multiple lengths: the block-skip condition
    must not skip partially-unmasked diagonal blocks."""
    from veles_tpu.ops.attention import _flash_bwd, _flash_fwd
    q, k, v = _qkv(b=1, sq=21, sk=21, h=2, d=8, seed=9)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)
    o, lse = _flash_fwd(q, k, v, causal=True, block_q=8, block_k=8,
                        interpret=True)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, 2.0 * o, causal=True,
                            block_q=8, block_k=8, interpret=True)
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(ref),
                              atol=5e-4)


def test_pallas_bwd_bf16_operands():
    """bf16 inputs: MXU-dtype operands with f32 accumulation must stay
    within bf16 tolerance of the f32 reference grads."""
    from veles_tpu.ops.attention import _flash_bwd, _flash_fwd
    q32, k32, v32 = _qkv(b=1, sq=16, sk=16, h=2, d=8, seed=11)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q32, k32, v32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    o, lse = _flash_fwd(q, k, v, causal=True, block_q=8, block_k=8,
                        interpret=True)
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, 2.0 * o, causal=True,
                            block_q=8, block_k=8, interpret=True)
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert got.dtype == jnp.bfloat16
        assert numpy.allclose(
            numpy.asarray(got, numpy.float32), numpy.asarray(ref),
            atol=0.12, rtol=0.1)


@pytest.mark.parametrize("causal", [False, True])
def test_grad_through_public_entry_pallas_path(causal):
    """jax.grad through flash_attention with use_pallas=True and the
    interpret config flag on: exercises the PRODUCTION dispatch —
    _flash_vjp_fwd residual pack, _resolve_bwd, _flash_bwd unpack —
    not just the kernels in isolation."""
    from veles_tpu.config import root
    q, k, v = _qkv(b=1, sq=16, sk=16, h=2, d=8, seed=13)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal, 8, 8, True) ** 2).sum()

    root.common.engine.interpret = True
    try:
        dq, dk, dv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        root.common.engine.interpret = False
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(ref),
                              atol=5e-4), \
            float(numpy.abs(numpy.asarray(got) -
                            numpy.asarray(ref)).max())


def test_bwd_autotune_sweep_writes_db(tmp_path, monkeypatch):
    """autotune_flash_attention_bwd persists flash_attention_bwd_v2
    winners that _resolve_bwd then consumes (CPU: XLA must win)."""
    from veles_tpu.ops import benchmark
    from veles_tpu.ops.attention import _resolve_bwd
    db_path = str(tmp_path / "db.json")
    info = benchmark.autotune_flash_attention_bwd(
        shape=(1, 32, 2, 8), dtypes=("float32",),
        candidates=((8, 8),), runs=1, db_path=db_path)
    entry = info.ratings["flash_attention_bwd_v2"]["float32"]
    assert len(entry) == 1
    cls = next(iter(entry))
    assert entry[cls]["backend"] in ("xla", "pallas")
    assert entry[cls]["shape"] == [1, 32, 2, 8]
    # gemm_choice routes the new kernel key with an attention shape
    choice = benchmark.gemm_choice(
        jnp.float32, db_path=db_path, kernel="flash_attention_bwd",
        shape=(1, 32, 2, 8))
    assert choice is not None


def test_flash_attention_jit_and_fallback():
    """Public entry jits and auto-selects the fallback off-TPU."""
    q, k, v = _qkv(seed=4)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v))(q, k, v)
    ref = mha_reference(q, k, v)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=1e-5)


def test_pallas_bwd_under_shard_map():
    """The custom VJP with the Pallas backward must trace through
    shard_map (the transformer's head-sharded _attend wrapper): grads
    via the interpret-mode Pallas path on a 1-axis CPU mesh match
    autodiff of the dense reference."""
    from jax.sharding import PartitionSpec as P
    from veles_tpu.config import root
    from veles_tpu.parallel.mesh import make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    mesh = make_mesh({"model": 2})
    q, k, v = _qkv(b=1, sq=16, sk=16, h=4, d=8, seed=17)

    def ref_loss(q, k, v):
        return (mha_reference(q, k, v, causal=True) ** 2).sum()

    dq_ref, dk_ref, dv_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(
        q, k, v)

    spec = P(None, None, "model", None)
    att = jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, True, 8, 8, True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)

    def loss(q, k, v):
        return (att(q, k, v) ** 2).sum()

    prior = root.common.engine.get("interpret", False)
    root.common.engine.interpret = True
    try:
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        root.common.engine.interpret = prior
    for got, ref in ((dq, dq_ref), (dk, dk_ref), (dv, dv_ref)):
        assert numpy.allclose(numpy.asarray(got), numpy.asarray(ref),
                              atol=5e-4), \
            float(numpy.abs(numpy.asarray(got) -
                            numpy.asarray(ref)).max())


# -- decode fast path (q_len=1 against a masked KV buffer) -----------------

def _decode_case(b=3, S=24, h=2, d=16, seed=11):
    rng = numpy.random.default_rng(seed)
    mk = lambda shape: jnp.asarray(
        rng.standard_normal(shape).astype(numpy.float32))
    return (mk((b, 1, h, d)), mk((b, S, h, d)), mk((b, S, h, d)))


def test_decode_dense_matches_prefix_reference():
    """The dense masked decode reference equals full attention over
    each row's valid KV prefix — the oracle everything else chains
    to."""
    from veles_tpu.ops.attention import _decode_jnp, _mha_jnp
    q, k, v = _decode_case()
    lengths = [1, 13, 24]
    out = _decode_jnp(q, k, v, jnp.asarray(lengths, jnp.int32))
    for i, n in enumerate(lengths):
        ref, _ = _mha_jnp(q[i:i + 1], k[i:i + 1, :n], v[i:i + 1, :n],
                          causal=False)
        assert numpy.allclose(numpy.asarray(out[i]),
                              numpy.asarray(ref[0]), atol=1e-5), i


def test_decode_pallas_interpret_matches_dense():
    """Pallas decode kernel (interpret mode) vs the dense masked
    reference: mixed lengths including a fully-masked tail block and
    a full-cache row."""
    from veles_tpu.ops.attention import _decode_jnp, _decode_pallas
    q, k, v = _decode_case()
    lengths = jnp.asarray([1, 13, 24], jnp.int32)
    ref = _decode_jnp(q, k, v, lengths)
    out = _decode_pallas(q, k, v, lengths, block_k=8, interpret=True)
    assert out.shape == ref.shape
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=2e-5)


def test_decode_pallas_ragged_shapes():
    """Cache length not a block multiple, head dim off the 128 lane
    boundary — per-tensor padding must stay masked."""
    from veles_tpu.ops.attention import _decode_jnp, _decode_pallas
    rng = numpy.random.default_rng(7)
    mk = lambda shape: jnp.asarray(
        rng.standard_normal(shape).astype(numpy.float32))
    q, k, v = mk((2, 1, 3, 20)), mk((2, 29, 3, 20)), mk((2, 29, 3, 20))
    lengths = jnp.asarray([7, 29], jnp.int32)
    ref = _decode_jnp(q, k, v, lengths)
    out = _decode_pallas(q, k, v, lengths, block_k=8, interpret=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=2e-5)


def test_decode_public_entry_squeezes_and_jits():
    """decode_attention accepts (b, h, d) queries, returns the same
    leading shape, and traces under jit with traced lengths (the
    engine's fixed-shape decode program)."""
    from veles_tpu.ops.attention import _decode_jnp, decode_attention
    q, k, v = _decode_case(seed=3)
    lengths = jnp.asarray([5, 9, 2], jnp.int32)
    ref = _decode_jnp(q, k, v, lengths)
    out3 = decode_attention(q[:, 0], k, v, lengths, use_pallas=False)
    assert out3.shape == (3, 2, 16)
    assert numpy.allclose(numpy.asarray(out3), numpy.asarray(ref[:, 0]),
                          atol=1e-6)
    jitted = jax.jit(lambda q, k, v, n: decode_attention(
        q, k, v, n, use_pallas=False))
    outj = jitted(q, k, v, lengths)
    assert numpy.allclose(numpy.asarray(outj), numpy.asarray(ref),
                          atol=1e-6)


def test_decode_row_independence():
    """A slot's output is bitwise independent of what other slots
    hold — the property continuous batching's parity gate rests on."""
    from veles_tpu.ops.attention import _decode_jnp
    q, k, v = _decode_case(seed=19)
    lengths = jnp.asarray([9, 4, 17], jnp.int32)
    base = numpy.asarray(_decode_jnp(q, k, v, lengths))
    # scramble every OTHER row's query and cache (valid and garbage)
    rng = numpy.random.default_rng(23)
    for i in range(3):
        q2 = numpy.array(q)
        k2 = numpy.array(k)
        v2 = numpy.array(v)
        others = [j for j in range(3) if j != i]
        q2[others] = rng.standard_normal(q2[others].shape)
        k2[others] = rng.standard_normal(k2[others].shape)
        v2[others] = rng.standard_normal(v2[others].shape)
        out = numpy.asarray(_decode_jnp(
            jnp.asarray(q2), jnp.asarray(k2), jnp.asarray(v2), lengths))
        assert (out[i] == base[i]).all(), i


# -- paged decode (block-pool KV + block tables) ---------------------------

def _paged_case(b=3, max_blocks=3, bs=8, h=2, d=16, seed=31):
    """A contiguous decode case + its EXACT paged mirror: the same K/V
    values scattered into a shuffled block pool with tables mapping
    them back, a trash block 0 full of garbage, and unallocated table
    entries pointing at it."""
    rng = numpy.random.default_rng(seed)
    S = max_blocks * bs
    mk = lambda shape: rng.standard_normal(shape).astype(numpy.float32)
    q, k, v = mk((b, 1, h, d)), mk((b, S, h, d)), mk((b, S, h, d))
    num_blocks = b * max_blocks + 1
    k_pool = mk((num_blocks, bs, h, d))      # garbage incl. trash
    v_pool = mk((num_blocks, bs, h, d))
    # deterministic shuffle of the allocatable ids over rows
    ids = rng.permutation(numpy.arange(1, num_blocks))
    tables = numpy.zeros((b, max_blocks), numpy.int32)
    lengths = numpy.asarray([1, bs + 3, S], numpy.int32)
    next_id = 0
    for i in range(b):
        n_blk = -(-int(lengths[i]) // bs)    # ceil
        for j in range(n_blk):
            bid = int(ids[next_id])
            next_id += 1
            tables[i, j] = bid
            k_pool[bid] = k[i, j * bs:(j + 1) * bs]
            v_pool[bid] = v[i, j * bs:(j + 1) * bs]
    ja = jnp.asarray
    return (ja(q), ja(k), ja(v), ja(k_pool), ja(v_pool),
            jnp.asarray(tables), jnp.asarray(lengths))


def test_paged_decode_dense_matches_contiguous_bitwise():
    """The paged dense path (gather through the block tables) is
    BITWISE identical to the contiguous dense decode at every valid
    position — the substrate of the paged==contiguous engine parity
    gate.  Garbage beyond lengths differs between the layouts on
    purpose; the masked softmax must zero it out exactly."""
    from veles_tpu.ops.attention import _decode_jnp, _paged_decode_jnp
    q, k, v, k_pool, v_pool, tables, lengths = _paged_case()
    ref = numpy.asarray(_decode_jnp(q, k, v, lengths))
    out = numpy.asarray(_paged_decode_jnp(q, k_pool, v_pool, tables,
                                          lengths))
    assert (out == ref).all()


def test_paged_decode_pallas_interpret_matches_dense():
    """Paged Pallas kernel (interpret mode — the block table routes
    each K/V page's DMA via scalar prefetch) vs the gather+dense
    reference."""
    from veles_tpu.ops.attention import (_paged_decode_jnp,
                                         _paged_decode_pallas)
    q, k, v, k_pool, v_pool, tables, lengths = _paged_case()
    ref = _paged_decode_jnp(q, k_pool, v_pool, tables, lengths)
    out = _paged_decode_pallas(q, k_pool, v_pool, tables, lengths,
                               interpret=True)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=1e-5), \
        float(numpy.abs(numpy.asarray(out) -
                        numpy.asarray(ref)).max())


def test_paged_decode_pallas_rejects_misaligned_block_size():
    from veles_tpu.ops.attention import _paged_decode_pallas
    q, k, v, k_pool, v_pool, tables, lengths = _paged_case()
    with pytest.raises(ValueError):
        _paged_decode_pallas(q, k_pool[:, :5], v_pool[:, :5],
                             tables, lengths, interpret=True)


def test_paged_decode_public_entry_squeezes_and_jits():
    """paged_decode_attention accepts (b, h, d) queries and jits with
    traced tables/lengths — the fixed-shape paged decode program's
    contract."""
    from veles_tpu.ops.attention import (_paged_decode_jnp,
                                         paged_decode_attention)
    q, k, v, k_pool, v_pool, tables, lengths = _paged_case(seed=7)
    ref = _paged_decode_jnp(q, k_pool, v_pool, tables, lengths)
    out3 = paged_decode_attention(q[:, 0], k_pool, v_pool, tables,
                                  lengths, use_pallas=False)
    assert out3.shape == (q.shape[0], q.shape[2], q.shape[3])
    assert (numpy.asarray(out3) == numpy.asarray(ref[:, 0])).all()
    jitted = jax.jit(lambda q, kp, vp, t, n: paged_decode_attention(
        q, kp, vp, t, n, use_pallas=False))
    out = jitted(q, k_pool, v_pool, tables, lengths)
    assert numpy.allclose(numpy.asarray(out), numpy.asarray(ref),
                          atol=1e-6)


# -- chunked prefill attention ---------------------------------------------

def test_chunk_attention_matches_full_prefix():
    """One chunk's offset-causal attention over the full cache buffer
    equals the matching query rows of whole-prompt causal attention
    over the written prefix — stale cache tail (beyond start+C)
    hidden by the causal offset."""
    from veles_tpu.ops.attention import _mha_jnp, chunk_attention
    rng = numpy.random.default_rng(5)
    S, C, start, h, d = 32, 8, 16, 2, 16
    q_full = jnp.asarray(
        rng.standard_normal((1, start + C, h, d)).astype(numpy.float32))
    kv = rng.standard_normal((2, 1, S, h, d)).astype(numpy.float32)
    kv[:, :, start + C:] = 1e3               # stale tail: must not leak
    k, v = jnp.asarray(kv[0]), jnp.asarray(kv[1])
    ref, _ = _mha_jnp(q_full[:, :start + C], k[:, :start + C],
                      v[:, :start + C], causal=True)
    out = chunk_attention(q_full[:, start:], k, v, start,
                          use_pallas=False)
    assert numpy.allclose(numpy.asarray(out),
                          numpy.asarray(ref[:, start:]), atol=1e-5)
    # traced start (the chunk program's fixed-shape contract)
    jitted = jax.jit(lambda q, k, v, s: chunk_attention(
        q, k, v, s, use_pallas=False))
    out2 = jitted(q_full[:, start:], k, v, jnp.int32(start))
    assert numpy.allclose(numpy.asarray(out2), numpy.asarray(out),
                          atol=1e-6)
