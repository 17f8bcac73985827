"""Flash attention: Pallas TPU kernels (forward AND backward) + VJP.

The hot op of the transformer family (SURVEY §5.7 notes attention is
beyond reference parity — this is the TPU build's flagship Pallas
kernel).  Forward is a tiled online-softmax kernel: Q blocks stream
through VMEM while K/V blocks arrive per grid step, so the (Sq, Sk)
score matrix never materializes in HBM.  Backward recomputes
probabilities blockwise from the saved log-sum-exp (the standard
flash-attention trade: extra FLOPs for O(S) memory) via TWO Pallas
kernels — dq streams K blocks per Q block; dk/dv streams Q blocks per
K block — with causal block skipping and swept block sizes
(``flash_attention_bwd_v2`` in the autotune DB); the pre-Pallas
``lax.scan`` fallback (:func:`_bwd_blockwise`) remains the non-TPU
path.  Both directions accept global causal offsets (static for the
offset-0 flagship path, scalar-prefetched when traced) so the kernels
serve as ring-attention hop blocks.

Layouts follow :mod:`veles_tpu.parallel.ring` — tensors are
``(batch, seq, heads, head_dim)`` — so :func:`flash_attention` is a
drop-in for its per-hop block math, composing with ring/Ulysses
sequence parallelism.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _round_up(x, mult):
    return (x + mult - 1) // mult * mult


def _bhsd(x, b, h, d, block):
    """(b, s, h, d) → (b·h, s_pad, d_pad) for the kernels' per-(b·h)
    grids.  Each tensor pads to ITS OWN block multiple: padding q and
    k to a common multiple would leave trailing blocks unvisited when
    the smaller block size doesn't divide the padded length.  Shared
    by forward and backward so a padding fix can never apply to one
    side only."""
    x = jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)
    s_pad = _round_up(x.shape[1], block)
    d_pad = _round_up(d, 128)
    return jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]),
                       (0, d_pad - d)))


def _row_stat_spec(bq, index_map):
    """Block of a per-query-row statistic (``lse``, ``delta``): the
    arrays ride as ``(b·h, s_pad, 1)`` COLUMNS so a ``(1, bq, 1)`` block
    obeys Mosaic's (8, 128) rule — bq is a sublane multiple and the
    lane dim equals the array's — and lands in VMEM in the same
    ``(bq, 1)`` layout as the running ``m``/``l`` scratch, broadcasting
    against ``(bq, bk)`` scores with no relayout.  (A ``(1, bq)`` block
    over ``(b·h, s_pad)`` is refused by the chip's compiler.)"""
    return pl.BlockSpec((1, bq, 1), index_map)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                 acc_ref, m_ref, l_ref, *, n_k, scale, causal,
                 block_q, block_k, seq_k, q_off, k_off):
    """Grid: (batch*heads, q_blocks, k_blocks); K is the arbitrary
    (sequential) dimension; running (acc, m, l) live in VMEM scratch.
    ``q_off``/``k_off``: global positions of element 0 — causal masks
    stay correct when q/k are shards of a longer (ring-distributed)
    sequence; padding masks stay LOCAL."""
    qi = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip blocks strictly above the (global) diagonal
    run = True
    if causal:
        run = q_off + qi * block_q + block_q - 1 >= k_off + kk * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale       # (bq, d)
        k = k_ref[0].astype(jnp.float32)               # (bk, d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = k_pos < seq_k                           # key padding
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            mask = mask & (k_off + k_pos <= q_off + q_pos)
        scores = jnp.where(mask, scores, NEG_INF)

        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)                    # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, d)
        m_ref[...] = m_new

    @pl.when(kk == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)           # (bq, 1)


def _attn_kernel_dyn(offs_ref, *args, kernel, **kw):
    """Scalar-prefetch wrapper: ring shards pass TRACED global offsets
    (device-index-dependent), which cannot be closure constants — they
    ride in as a prefetched (2,) int32 and the causal block-skip
    becomes a runtime predicate."""
    kernel(*args, q_off=offs_ref[0], k_off=offs_ref[1], **kw)


def _static_offsets(q_offset, k_offset):
    return isinstance(q_offset, int) and isinstance(k_offset, int)


def _dyn_spec(spec):
    """Same block routing, one extra (ignored) scalar-prefetch arg —
    keeps the static and dynamic paths structurally identical."""
    return pl.BlockSpec(
        spec.block_shape,
        lambda a, b_, c, offs, _m=spec.index_map: _m(a, b_, c))


def _flash_fwd(q, k, v, causal=False, block_q=128, block_k=128,
               interpret=False, q_offset=0, k_offset=0):
    """(o, lse); inputs (b, s, h, d) — kernel works per (b·h) slice.
    ``q_offset``/``k_offset``: global causal positions of element 0
    (ring/sequence shards); python ints compile to the static
    block-skip, traced scalars take the scalar-prefetch path."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k, _round_up(sk, 8))

    q3 = _bhsd(q, b, h, d, bq)
    k3, v3 = _bhsd(k, b, h, d, bk), _bhsd(v, b, h, d, bk)
    sq_p, d_p = q3.shape[1], q3.shape[2]
    sk_p = k3.shape[1]
    n_q, n_k = sq_p // bq, sk_p // bk
    grid = (b * h, n_q, n_k)

    in_specs = [
        pl.BlockSpec((1, bq, d_p), lambda bh, qi, kk: (bh, qi, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, qi, kk: (bh, kk, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, qi, kk: (bh, kk, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, d_p), lambda bh, qi, kk: (bh, qi, 0)),
        _row_stat_spec(bq, lambda bh, qi, kk: (bh, qi, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b * h, sq_p, d_p), q.dtype),
        jax.ShapeDtypeStruct((b * h, sq_p, 1), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bq, d_p), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
        pltpu.VMEM((bq, 1), jnp.float32),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    kw = dict(n_k=n_k, scale=scale, causal=causal, block_q=bq,
              block_k=bk, seq_k=sk)
    if _static_offsets(q_offset, k_offset):
        out, lse = pl.pallas_call(
            functools.partial(_attn_kernel, q_off=q_offset,
                              k_off=k_offset, **kw),
            name="veles_flash_fwd",
            grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch,
            compiler_params=params, interpret=interpret,
        )(q3, k3, v3)
    else:
        offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                          jnp.asarray(k_offset, jnp.int32)])
        out, lse = pl.pallas_call(
            functools.partial(_attn_kernel_dyn, kernel=_attn_kernel,
                              **kw),
            name="veles_flash_fwd",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=grid,
                in_specs=[_dyn_spec(s) for s in in_specs],
                out_specs=[_dyn_spec(s) for s in out_specs],
                scratch_shapes=scratch),
            out_shape=out_shape, compiler_params=params,
            interpret=interpret,
        )(offs, q3, k3, v3)
    out = out[:, :sq, :d].reshape(b, h, sq, d)
    return jnp.moveaxis(out, 1, 2), lse[:, :sq, 0].reshape(b, h, sq)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, n_k, scale, causal, block_q,
                   block_k, seq_k, q_off, k_off):
    """dq: grid (b·h, q_blocks, k_blocks); K sequential; the running
    dq accumulator lives in VMEM scratch (the forward's layout)."""
    qi = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = True
    if causal:
        # skip K blocks strictly above the (global) diagonal — the 2x
        # FLOP saving the XLA scan fallback cannot express
        run = q_off + qi * block_q + block_q - 1 >= k_off + kk * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0]                                   # (bq, d) mm dtype
        k = k_ref[0]                                   # (bk, d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = k_pos < seq_k
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            mask = mask & (k_off + k_pos <= q_off + q_pos)
        p = jnp.where(mask, jnp.exp(scores - lse_ref[0]), 0.0)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        ds = p * (dp - delta_ref[0]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, d)

    @pl.when(kk == n_k - 1)
    def _finish():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, n_q, scale,
                    causal, block_q, block_k, seq_k, q_off, k_off):
    """dk/dv: grid (b·h, k_blocks, q_blocks); Q sequential; running
    (dk, dv) accumulators in VMEM scratch."""
    kk = pl.program_id(1)
    qj = pl.program_id(2)

    @pl.when(qj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = q_off + qj * block_q + block_q - 1 >= k_off + kk * block_k

    @pl.when(run)
    def _step():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bk, d)
        do = do_ref[0]                                 # (bq, d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (bq, bk)
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        mask = k_pos < seq_k
        if causal:
            q_pos = qj * block_q + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            mask = mask & (k_off + k_pos <= q_off + q_pos)
        p = jnp.where(mask, jnp.exp(scores - lse_ref[0]), 0.0)
        p_mm = p.astype(q.dtype)
        dv_acc[...] += jax.lax.dot_general(
            p_mm, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, d)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        ds = p * (dp - delta_ref[0]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bk, d)

    @pl.when(qj == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal=False, block_q=128,
               block_k=128, interpret=False, q_offset=0, k_offset=0,
               delta=None):
    """Pallas flash backward: (dq, dk, dv) from saved (q, k, v, o,
    lse).  Two kernels — dq streams K blocks per Q block; dk/dv
    streams Q blocks per K block — each shaped exactly like the
    forward (VMEM accumulators, per-tensor padding, causal block
    skipping), so the backward's matmuls tile the MXU at the swept
    block sizes instead of the XLA scan fallback's fixed-128 serial
    chain (the backward's share of an LM step: not measured on the
    chip).  ``lse`` is (b, h, sq)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    bq = min(block_q, _round_up(sq, 8))
    bk = min(block_k, _round_up(sk, 8))

    def bhs(x, block):    # (b, h, s) → (b·h, s_pad, 1) row-stat columns
        x = x.reshape(b * h, x.shape[2], 1).astype(jnp.float32)
        s_pad = _round_up(x.shape[1], block)
        return jnp.pad(x, ((0, 0), (0, s_pad - x.shape[1]), (0, 0)))

    # delta = rowsum(do ⊙ o): one cheap bandwidth-bound pass outside
    # the kernels (the standard flash-backward preprocessing); ring
    # callers precompute it ONCE for all n hops
    if delta is None:
        delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                           o.astype(jnp.float32))

    q3 = _bhsd(q, b, h, d, bq)
    k3, v3 = _bhsd(k, b, h, d, bk), _bhsd(v, b, h, d, bk)
    do3 = _bhsd(do.astype(q.dtype), b, h, d, bq)
    lse2, delta2 = bhs(lse, bq), bhs(delta, bq)
    sq_p, d_p = q3.shape[1], q3.shape[2]
    sk_p = k3.shape[1]
    n_q, n_k = sq_p // bq, sk_p // bk

    dq_specs = [
        pl.BlockSpec((1, bq, d_p), lambda bh, qi, kk: (bh, qi, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, qi, kk: (bh, kk, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, qi, kk: (bh, kk, 0)),
        pl.BlockSpec((1, bq, d_p), lambda bh, qi, kk: (bh, qi, 0)),
        _row_stat_spec(bq, lambda bh, qi, kk: (bh, qi, 0)),
        _row_stat_spec(bq, lambda bh, qi, kk: (bh, qi, 0)),
    ]
    dq_out_spec = pl.BlockSpec((1, bq, d_p),
                               lambda bh, qi, kk: (bh, qi, 0))
    dq_out_shape = jax.ShapeDtypeStruct((b * h, sq_p, d_p), q.dtype)
    dq_scratch = [pltpu.VMEM((bq, d_p), jnp.float32)]
    dkv_specs = [
        pl.BlockSpec((1, bq, d_p), lambda bh, kk, qj: (bh, qj, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, kk, qj: (bh, kk, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, kk, qj: (bh, kk, 0)),
        pl.BlockSpec((1, bq, d_p), lambda bh, kk, qj: (bh, qj, 0)),
        _row_stat_spec(bq, lambda bh, kk, qj: (bh, qj, 0)),
        _row_stat_spec(bq, lambda bh, kk, qj: (bh, qj, 0)),
    ]
    dkv_out_specs = [
        pl.BlockSpec((1, bk, d_p), lambda bh, kk, qj: (bh, kk, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, kk, qj: (bh, kk, 0)),
    ]
    dkv_out_shape = [
        jax.ShapeDtypeStruct((b * h, sk_p, d_p), k.dtype),
        jax.ShapeDtypeStruct((b * h, sk_p, d_p), v.dtype),
    ]
    dkv_scratch = [pltpu.VMEM((bk, d_p), jnp.float32),
                   pltpu.VMEM((bk, d_p), jnp.float32)]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    dq_kw = dict(n_k=n_k, scale=scale, causal=causal, block_q=bq,
                 block_k=bk, seq_k=sk)
    dkv_kw = dict(n_q=n_q, scale=scale, causal=causal, block_q=bq,
                  block_k=bk, seq_k=sk)
    if _static_offsets(q_offset, k_offset):
        dq3 = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, q_off=q_offset,
                              k_off=k_offset, **dq_kw),
            name="veles_flash_bwd_dq",
            grid=(b * h, n_q, n_k), in_specs=dq_specs,
            out_specs=dq_out_spec, out_shape=dq_out_shape,
            scratch_shapes=dq_scratch, compiler_params=params,
            interpret=interpret,
        )(q3, k3, v3, do3, lse2, delta2)
        dk3, dv3 = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, q_off=q_offset,
                              k_off=k_offset, **dkv_kw),
            name="veles_flash_bwd_dkv",
            grid=(b * h, n_k, n_q), in_specs=dkv_specs,
            out_specs=dkv_out_specs, out_shape=dkv_out_shape,
            scratch_shapes=dkv_scratch, compiler_params=params,
            interpret=interpret,
        )(q3, k3, v3, do3, lse2, delta2)
    else:
        offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                          jnp.asarray(k_offset, jnp.int32)])
        _dyn = _dyn_spec
        dq3 = pl.pallas_call(
            functools.partial(_attn_kernel_dyn,
                              kernel=_bwd_dq_kernel, **dq_kw),
            name="veles_flash_bwd_dq",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b * h, n_q, n_k),
                in_specs=[_dyn(s) for s in dq_specs],
                out_specs=_dyn(dq_out_spec),
                scratch_shapes=dq_scratch),
            out_shape=dq_out_shape, compiler_params=params,
            interpret=interpret,
        )(offs, q3, k3, v3, do3, lse2, delta2)
        dk3, dv3 = pl.pallas_call(
            functools.partial(_attn_kernel_dyn,
                              kernel=_bwd_dkv_kernel, **dkv_kw),
            name="veles_flash_bwd_dkv",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(b * h, n_k, n_q),
                in_specs=[_dyn(s) for s in dkv_specs],
                out_specs=[_dyn(s) for s in dkv_out_specs],
                scratch_shapes=dkv_scratch),
            out_shape=dkv_out_shape, compiler_params=params,
            interpret=interpret,
        )(offs, q3, k3, v3, do3, lse2, delta2)

    def unsd(x3, s):      # (b·h, s_pad, d_pad) → (b, s, h, d)
        x = x3[:, :s, :d].reshape(b, h, s, d)
        return jnp.moveaxis(x, 1, 2)

    return unsd(dq3, sq), unsd(dk3, sk), unsd(dv3, sk)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, n_k, scale, block_k,
                   heads, row_step=0):
    """Single-query decode step: grid (batch*heads, k_blocks); K is
    the sequential dimension; the per-row KV length arrives scalar-
    prefetched (``len_ref``, one int32 per *batch* row — heads share
    it).  The q block is the forward kernel's layout padded to the
    8-sublane minimum (row 0 is the real query; rows 1–7 compute
    garbage that is sliced away), so the online-softmax scratch
    discipline is identical to :func:`_attn_kernel`.  K blocks fully
    beyond the row's length are skipped — the decode analogue of the
    causal block skip, and where the win over a dense masked pass
    comes from when the cache is long but the sequence is young.

    ``row_step=1`` is the VERIFY variant (speculative decode): the 8
    q sublanes are CONSECUTIVE positions of one sequence — row ``j``
    writes at ``length - 1 + j`` and may read keys ``< length + j`` —
    so the per-row mask staggers by the sublane index and one dispatch
    prices K+1 draft tokens at one decode step's DMA traffic."""
    bh = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[bh // heads]
    run = kk * block_k < length + 7 * row_step

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale       # (8, d)
        k = k_ref[0].astype(jnp.float32)               # (bk, d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (8, bk)
        k_pos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        limit = length + row_step * jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)
        scores = jnp.where(k_pos < limit, scores, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kk == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_jnp(q, k, v, lengths):
    """Dense masked reference for the decode step: q (b, 1, h, d)
    against a (b, S, h, d) KV buffer where only the first
    ``lengths[i]`` keys of row ``i`` are valid.  The oracle the Pallas
    kernel is parity-tested against (``tests/test_attention.py``)."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    mask = (jnp.arange(k.shape[1])[None, None, None, :]
            < lengths[:, None, None, None])
    scores = jnp.where(mask, scores, NEG_INF)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def _decode_pallas(q, k, v, lengths, block_k=128, interpret=False,
                   row_step=0):
    b, sq, h, d = q.shape
    if sq > 8:
        raise ValueError(
            "decode/verify q carries %d rows but the kernel's q tile "
            "is one 8-sublane block — draft_k must stay <= 7" % sq)
    scale = 1.0 / (d ** 0.5)
    sk = k.shape[1]
    bk = min(block_k, _round_up(sk, 8))
    q3 = _bhsd(q, b, h, d, 8)                   # (b·h, 8, d_p)
    k3, v3 = _bhsd(k, b, h, d, bk), _bhsd(v, b, h, d, bk)
    d_p = q3.shape[2]
    n_k = k3.shape[1] // bk
    grid = (b * h, n_k)
    in_specs = [
        pl.BlockSpec((1, 8, d_p), lambda bh, kk, lens: (bh, 0, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, kk, lens: (bh, kk, 0)),
        pl.BlockSpec((1, bk, d_p), lambda bh, kk, lens: (bh, kk, 0)),
    ]
    out_spec = pl.BlockSpec((1, 8, d_p), lambda bh, kk, lens: (bh, 0, 0))
    scratch = [
        pltpu.VMEM((8, d_p), jnp.float32),
        pltpu.VMEM((8, 1), jnp.float32),
        pltpu.VMEM((8, 1), jnp.float32),
    ]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, n_k=n_k, scale=scale,
                          block_k=bk, heads=h, row_step=row_step),
        name="veles_attn_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b * h, 8, d_p), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), q3, k3, v3)
    return jnp.moveaxis(out[:, :sq, :d].reshape(b, h, sq, d), 1, 2)


def decode_attention(q, k, v, lengths, block_k=None, use_pallas=None,
                     interpret=None):
    """Single-query (q_len = 1) attention against a masked KV buffer —
    the generative decode step's hot op (:mod:`veles_tpu.gen`).

    ``q``: (b, 1, h, d) or (b, h, d); ``k``/``v``: (b, S, h, d) cache
    buffers whose tail beyond ``lengths[i]`` (int32, (b,), each ≥ 1)
    is garbage and masked out; returns attention over the valid prefix
    with q's leading shape.  Row ``i``'s output depends only on row
    ``i``'s query and valid keys, so slots of a continuous batch can
    never bleed into each other (the batching parity gate's
    substrate).  TPU takes the Pallas kernel (lengths scalar-
    prefetched, fully-masked K blocks skipped); elsewhere the dense
    masked reference runs — both share the start-aligned mask
    convention of the prefill flash path (``q_offset``/``k_offset``
    there, ``lengths`` here)."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    lengths = jnp.asarray(lengths, jnp.int32)
    pallas = use_pallas if use_pallas is not None else _on_tpu()
    if pallas:
        if interpret is None:
            from veles_tpu.config import root
            interpret = bool(root.common.engine.get("interpret", False))
        out = _decode_pallas(q, k, v, lengths,
                             block_k=block_k or 128,
                             interpret=interpret)
    else:
        out = _decode_jnp(q, k, v, lengths)
    return out[:, 0] if squeeze else out


def _gather_pool(pool, tables):
    """(num_blocks, BS, h, d) pool + (b, max_blocks) int32 tables →
    the (b, max_blocks·BS, h, d) contiguous VIEW of each sequence.
    Table entries past a sequence's allocation point at the trash
    block (id 0), whose garbage lands beyond ``lengths`` and is
    masked — when ``max_blocks·BS`` equals the contiguous engine's
    ``max_seq`` the gathered buffer is value-identical to the
    slot-major cache at every valid position, which is what keeps the
    paged==contiguous parity gate bitwise on the dense path."""
    g = pool[tables]                       # (b, mb, BS, h, d)
    b, mb, bs = g.shape[:3]
    return g.reshape(b, mb * bs, g.shape[3], g.shape[4])


def _paged_decode_jnp(q, k_pool, v_pool, tables, lengths):
    """Dense masked reference for the PAGED decode step: gather the
    block pool through the block tables into the contiguous layout,
    then run the exact :func:`_decode_jnp` math.  The oracle the
    paged Pallas kernel is parity-tested against."""
    return _decode_jnp(q, _gather_pool(k_pool, tables),
                       _gather_pool(v_pool, tables), lengths)


def _paged_decode_kernel(len_ref, tab_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, n_b, scale,
                         block_size, heads, row_step=0):
    """Paged decode step: grid (batch*heads, max_blocks); the KV
    blocks arrive ALREADY ROUTED by the block table — the BlockSpec
    index map reads the scalar-prefetched ``tab_ref`` to aim each
    grid step's DMA at ``tables[row, kk]`` in the shared pool (the
    PagedAttention gather, done by the memory system instead of an
    HBM materialization).  Everything else is :func:`_decode_kernel`:
    per-row lengths scalar-prefetched, online-softmax scratch, and
    blocks fully past the row's length skipped (their table entries
    point at the trash block; the DMA still lands but the compute
    does not run)."""
    bh = pl.program_id(0)
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[bh // heads]
    run = kk * block_size < length + 7 * row_step

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale       # (8, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (BS, d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (8, BS)
        k_pos = kk * block_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        limit = length + row_step * jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 0)
        scores = jnp.where(k_pos < limit, scores, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kk == n_b - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pool, v_pool, tables, lengths,
                         interpret=False, row_step=0):
    b, sq, h, d = q.shape
    if sq > 8:
        raise ValueError(
            "decode/verify q carries %d rows but the kernel's q tile "
            "is one 8-sublane block — draft_k must stay <= 7" % sq)
    block_size = k_pool.shape[1]
    if block_size % 8:
        raise ValueError(
            "paged block_size %d breaks the kernel's 8-sublane "
            "padding — use a multiple of 8" % block_size)
    n_b = tables.shape[1]
    scale = 1.0 / (d ** 0.5)
    q3 = _bhsd(q, b, h, d, 8)                   # (b·h, 8, d_p)
    d_p = q3.shape[2]
    # pool → (num_blocks, h, BS, d_p): per-(b·h, block) DMA units
    def pool4(x):
        x = jnp.moveaxis(x, 2, 1)               # (NB, h, BS, d)
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, d_p - d)))
    k4, v4 = pool4(k_pool), pool4(v_pool)
    grid = (b * h, n_b)
    in_specs = [
        pl.BlockSpec((1, 8, d_p),
                     lambda bh, kk, lens, tabs: (bh, 0, 0)),
        pl.BlockSpec((1, 1, block_size, d_p),
                     lambda bh, kk, lens, tabs:
                     (tabs[bh // h, kk], bh % h, 0, 0)),
        pl.BlockSpec((1, 1, block_size, d_p),
                     lambda bh, kk, lens, tabs:
                     (tabs[bh // h, kk], bh % h, 0, 0)),
    ]
    out_spec = pl.BlockSpec((1, 8, d_p),
                            lambda bh, kk, lens, tabs: (bh, 0, 0))
    scratch = [
        pltpu.VMEM((8, d_p), jnp.float32),
        pltpu.VMEM((8, 1), jnp.float32),
        pltpu.VMEM((8, 1), jnp.float32),
    ]
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, n_b=n_b, scale=scale,
                          block_size=block_size, heads=h,
                          row_step=row_step),
        name="veles_attn_paged_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b * h, 8, d_p), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32),
      jnp.asarray(tables, jnp.int32), q3, k4, v4)
    return jnp.moveaxis(out[:, :sq, :d].reshape(b, h, sq, d), 1, 2)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths,
                           use_pallas=None, interpret=None):
    """Single-query attention against a PAGED KV pool — the decode hot
    op of ``veles_tpu.gen``'s block-pool cache (ROADMAP item 3a).

    ``q``: (b, 1, h, d) or (b, h, d); ``k_pool``/``v_pool``:
    (num_blocks, block_size, h, d) shared pools; ``tables``: (b,
    max_blocks) int32 — row ``i``'s sequence lives in blocks
    ``tables[i]`` in order, entries past its allocation pointing at
    the trash block 0; ``lengths``: (b,) int32 valid token counts.
    Same row-independence contract as :func:`decode_attention` (the
    continuous-batching parity substrate).  TPU takes the paged
    Pallas kernel — the block table rides in scalar-prefetched and
    routes each K/V block's DMA, so the gather never materializes in
    HBM; elsewhere an XLA gather + the dense masked reference runs,
    value-identical to the contiguous cache path at every valid
    position."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    pallas = use_pallas if use_pallas is not None else _on_tpu()
    if pallas:
        if interpret is None:
            from veles_tpu.config import root
            interpret = bool(root.common.engine.get("interpret", False))
        out = _paged_decode_pallas(q, k_pool, v_pool, tables, lengths,
                                   interpret=interpret)
    else:
        out = _paged_decode_jnp(q, k_pool, v_pool, tables, lengths)
    return out[:, 0] if squeeze else out


def chunk_attention(q, k, v, start, use_pallas=None, interpret=None):
    """Causal attention of ONE prefill chunk against the sequence's
    full KV buffer — the chunked-prefill hot op.  ``q``: (1, C, h, d)
    chunk queries whose global positions are ``start + i`` (``start``
    may be a traced int32 — the chunk program stays fixed-shape);
    ``k``/``v``: (1, S, h, d) the sequence's cache buffer (chunk K/V
    already written at [start, start+C)).  Keys at or beyond
    ``start + C`` are hidden by the causal offset mask, so the stale
    tail of the cache can never leak into a chunk.  TPU rides the
    flash kernel's scalar-prefetched q_offset path; elsewhere the
    XLA-fused reference."""
    pallas = _resolve_backend(use_pallas, q.dtype, q.shape)
    if pallas:
        if interpret is None:
            from veles_tpu.config import root
            interpret = bool(root.common.engine.get("interpret", False))
        o, _lse = _flash_fwd(q, k, v, causal=True,
                             q_offset=jnp.asarray(start, jnp.int32),
                             k_offset=jnp.asarray(0, jnp.int32),
                             interpret=interpret)
        return o
    o, _lse = _mha_jnp(q, k, v, True, q_offset=start)
    return o


def _ring_chunk_kernel(meta_ref, q_ref, t_ref, ko_ref, vo_ref, kc_ref,
                       vc_ref, o_ref, acc_ref, m_ref, l_ref, *, n_own,
                       n_k, block_q, block_k, group, rows, window, scale):
    """Grid (kv_heads, q_blocks, own blocks then cache blocks); the key
    blocks are the sequential dimension.  A q block is ``block_q //
    group`` tokens, ``group`` query heads each, against ONE KV head.
    ``meta_ref`` (scalar-prefetched): slot, start, live cache blocks.
    The chunk's own keys come FIRST, so that every row has seen a key
    (block 0 holds one at or before every query) before a block in
    which it sees none: a masked score is then ``exp(-1e30 - m) = 0``
    with no second select."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    start, live = meta_ref[1], meta_ref[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def visit(k_ref, v_ref, first):
        """A block of keys at positions ``first ..`` (negative: rows
        never written since the slot was admitted)."""
        t = start + t_ref[...]                         # (bq, 1)
        lo = jnp.maximum(t - (window - 1), 0) if window else 0
        scores = jax.lax.dot_general(
            q_ref[0], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (bq, bk)
        p = first + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        scores = jnp.where((p <= t) & (p >= lo), scores, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1,
                                            keepdims=True))
        w = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + w.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            w.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    # own blocks wholly after the q block's last token are skipped: a
    # function of the grid alone, the same in every chunk
    @pl.when((j < n_own)
             & (j * block_k <= ((qi + 1) * block_q - 1) // group))
    def _own():
        visit(ko_ref, vo_ref, start + j * block_k)

    @pl.when((j >= n_own) & (j - n_own < live))
    def _cache():
        # rows before the write pointer hold the newest lap, rows from
        # it on the lap before; a block lies on one side (the pointer
        # is a multiple of the chunk, the chunk of the block)
        row = (j - n_own) * block_k
        pointer = jax.lax.rem(start, rows)
        visit(kc_ref, vc_ref, start - pointer + row
              - jnp.where(row >= pointer, rows, 0))

    @pl.when(j == n_k - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def ring_chunk_attention(q, k, v, k_cache, v_cache, slot, start,
                         window=None, floor_rows=0, block_q=2048,
                         block_k=1024, interpret=False):
    """Grouped-query attention of ONE prefill chunk against its slot's
    cache rows AS THEY ARE and then its own keys, before they are
    written: the kernel ``veles_attn_ring_chunk``.

    ``q``: (C, g, r, d), the queries at positions ``start + i``
    (``start`` a traced multiple of C), ``r`` query heads a KV head;
    ``k``/``v``: (C, g * d), the chunk's own; ``k_cache``/``v_cache``:
    (slots, rows, g * d) in which position ``p`` lives in row ``p mod
    rows`` (a ring where ``rows`` is the window, every position where
    it is the sequence limit), read IN PLACE a block at a time.  A
    query sees ``p <= t``, and ``t - p < window`` where one is given.
    Which position a row holds follows from ``start`` alone, so rows
    that an earlier, longer request left are never seen.

    The first ``max(start, floor_rows)`` rows are visited (all of a
    ring with ``floor_rows = rows``), dead or live: up to that depth a
    chunk costs the same wherever it lies in its prompt, which is what
    lets a token that waits behind ONE chunk wait one length of time.
    Returns (C, g, r, d) in ``q``'s type."""
    C, G, R, d = q.shape
    rows = k_cache.shape[1]
    bk = min(block_k, C)
    bq = min(block_q, C * R)
    if C % bk or rows % C or (C * R) % bq or bq % R:
        raise ValueError(
            "a chunk of %d x %d query rows, %d cache rows: blocks of %d "
            "queries and %d keys do not tile them" % (C, R, rows, bq, bk))
    n_own, n_cache = C // bk, rows // bk
    n_k = n_own + n_cache
    start = jnp.asarray(start, jnp.int32)
    live = jnp.minimum(jnp.maximum(start, floor_rows), rows) // bk
    meta = jnp.stack([jnp.asarray(slot, jnp.int32), start, live])
    q3 = jnp.moveaxis(q, 1, 0).reshape(G, C * R, d)
    t_rel = (jnp.arange(C * R, dtype=jnp.int32) // R)[:, None]

    def own(g, qi, j, meta):
        needed = ((qi + 1) * bq - 1) // R // bk
        return jnp.minimum(j, jnp.minimum(needed, n_own - 1)), g

    def cached(g, qi, j, meta):
        return (meta[0],
                jnp.clip(j - n_own, 0, jnp.maximum(meta[2] - 1, 0)), g)

    in_specs = [
        pl.BlockSpec((1, bq, d), lambda g, qi, j, meta: (g, qi, 0)),
        pl.BlockSpec((bq, 1), lambda g, qi, j, meta: (qi, 0)),
        pl.BlockSpec((bk, d), own), pl.BlockSpec((bk, d), own),
        pl.BlockSpec((None, bk, d), cached),
        pl.BlockSpec((None, bk, d), cached),
    ]
    out = pl.pallas_call(
        functools.partial(
            _ring_chunk_kernel, n_own=n_own, n_k=n_k, block_q=bq,
            block_k=bk, group=R, rows=rows, window=window,
            scale=1.0 / (d ** 0.5)),
        name="veles_attn_ring_chunk",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(G, C * R // bq, n_k),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda g, qi, j, meta: (g, qi, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((G, C * R, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(meta, q3, t_rel, k, v, k_cache, v_cache)
    return jnp.moveaxis(out.reshape(G, C, R, d), 0, 1)


def _verify_jnp(q, k, v, lengths):
    """Dense masked reference for the K-token VERIFY step
    (speculative decode): q (b, Kp1, h, d) — row ``j`` of sequence
    ``i`` is the query at global position ``lengths[i] - 1 + j`` and
    may read keys ``< lengths[i] + j`` (its own K/V is already
    written, like the decode step's).  Row 0 is EXACTLY the plain
    decode query — same einsum forms and mask arithmetic as
    :func:`_decode_jnp`, the greedy-acceptance equivalence gate's
    substrate."""
    d = q.shape[-1]
    kp1 = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    limits = (lengths[:, None] + jnp.arange(kp1)[None, :])
    mask = (jnp.arange(k.shape[1])[None, None, None, :]
            < limits[:, None, :, None])
    scores = jnp.where(mask, scores, NEG_INF)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def verify_attention(q, k, v, lengths, block_k=None, use_pallas=None,
                     interpret=None):
    """K-token causal verify against a masked KV buffer — the
    speculative-decode hot op (ROADMAP item 3b): ONE dispatch scores
    a slot's current token plus its K draft continuations.

    ``q``: (b, K+1, h, d) — row ``j`` of sequence ``i`` queries from
    global position ``lengths[i] - 1 + j`` (K/V for all K+1 tokens
    already written at [lengths-1, lengths+K)); ``k``/``v``: (b, S,
    h, d) cache buffers; ``lengths``: (b,) int32 — the valid extent
    INCLUDING row 0's token.  Row ``j`` reads keys ``< lengths[i] +
    j``: the same mask plain decode would apply after accepting
    ``j`` drafts, so greedy acceptance over the outputs is an exact
    equivalence with plain decode.  TPU rides the decode kernel with
    the per-sublane staggered mask (``row_step=1``); elsewhere the
    dense masked reference.  K+1 must stay <= 8 (one q sublane
    tile)."""
    lengths = jnp.asarray(lengths, jnp.int32)
    pallas = use_pallas if use_pallas is not None else _on_tpu()
    if pallas:
        if interpret is None:
            from veles_tpu.config import root
            interpret = bool(root.common.engine.get("interpret", False))
        return _decode_pallas(q, k, v, lengths,
                              block_k=block_k or 128,
                              interpret=interpret, row_step=1)
    return _verify_jnp(q, k, v, lengths)


def paged_verify_attention(q, k_pool, v_pool, tables, lengths,
                           use_pallas=None, interpret=None):
    """The PAGED twin of :func:`verify_attention`: same staggered
    per-row mask, KV gathered through the block tables (Pallas: the
    table-routed BlockSpec DMA of the paged decode kernel; elsewhere
    the XLA gather + dense reference).  Draft positions past a
    sequence's allocation route their writes to the trash block
    upstream, so the gathered garbage sits beyond every row's mask."""
    lengths = jnp.asarray(lengths, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    pallas = use_pallas if use_pallas is not None else _on_tpu()
    if pallas:
        if interpret is None:
            from veles_tpu.config import root
            interpret = bool(root.common.engine.get("interpret", False))
        return _paged_decode_pallas(q, k_pool, v_pool, tables, lengths,
                                    interpret=interpret, row_step=1)
    return _verify_jnp(q, _gather_pool(k_pool, tables),
                       _gather_pool(v_pool, tables), lengths)


def _mha_jnp(q, k, v, causal, q_offset=0, k_offset=0):
    """XLA-fused fallback (CPU / tiny shapes); returns (o, lse).
    ``q_offset``/``k_offset``: global causal positions of element 0
    (ring/sequence shards)."""
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    if causal:
        # start-aligned (k_pos <= q_pos) like the Pallas kernel, the
        # blockwise VJP and mha_reference — NOT end-aligned tril
        sq, sk = scores.shape[-2], scores.shape[-1]
        mask = (k_offset + jnp.arange(sk))[None, :] <= \
            (q_offset + jnp.arange(sq))[:, None]
        scores = jnp.where(mask, scores, NEG_INF)
    lse = jax.scipy.special.logsumexp(scores, axis=-1)
    probs = jnp.exp(scores - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype), lse


def _bwd_dense_block(q, k_blk, v_blk, lse, do, delta, causal, q_off,
                     k_off):
    """Dense (un-tiled) flash backward of ONE K/V block against the
    GLOBAL (lse, delta): the ring fallback's hop math, kept here next
    to its siblings so the mm-dtype / f32-accumulation conventions
    live in one module.  Returns (dq_blk, dk_blk, dv_blk)."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])[:, None]
        kpos = k_off + jnp.arange(k_blk.shape[1])[None, :]
        p = jnp.where(qpos >= kpos,
                      jnp.exp(scores - lse[..., None]), 0.0)
    else:
        p = jnp.exp(scores - lse[..., None])
    mm = q.dtype
    do_mm = do.astype(mm)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p.astype(mm), do_mm,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do_mm, v_blk,
                    preferred_element_type=jnp.float32)
    ds = (p * (dp - delta[..., None]) * scale).astype(mm)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk,
                    preferred_element_type=jnp.float32)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q,
                    preferred_element_type=jnp.float32)
    return (dq.astype(q.dtype), dk.astype(k_blk.dtype),
            dv.astype(v_blk.dtype))


def _bwd_blockwise(res, do, causal, block_k):
    """Flash backward from saved (q, k, v, o, lse): scan over K blocks,
    probabilities recomputed — O(S·block) memory."""
    q, k, v, o, lse = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    do32 = do.astype(jnp.float32)
    delta = jnp.einsum("bqhd,bqhd->bhq", do32,
                       o.astype(jnp.float32))        # rowsum(do ⊙ o)

    n_blocks = (sk + block_k - 1) // block_k
    sk_pad = n_blocks * block_k
    kp = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0), (0, 0)))

    q_pos = jnp.arange(sq)

    # matmul OPERANDS in the inputs' own dtype (bf16 stays bf16 on the
    # MXU — all-f32 operands force the 3-pass f32 matmul mode, ~3x
    # slower), accumulation in f32 via preferred_element_type; the
    # softmax/rescale arithmetic (exp, lse, delta, ds) stays f32
    mm = q.dtype
    do_mm = do.astype(mm)

    def one_block(carry, idx):
        dq_acc, = carry
        k_blk = jax.lax.dynamic_slice_in_dim(kp, idx * block_k,
                                             block_k, axis=1)
        v_blk = jax.lax.dynamic_slice_in_dim(vp, idx * block_k,
                                             block_k, axis=1)
        k_pos = idx * block_k + jnp.arange(block_k)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                            preferred_element_type=jnp.float32) * scale
        mask = (k_pos < sk)[None, None, None, :]
        if causal:
            mask = mask & (k_pos[None, None, None, :]
                           <= q_pos[None, None, :, None])
        p = jnp.where(mask, jnp.exp(scores - lse[..., None]), 0.0)
        dv_blk = jnp.einsum("bhqk,bqhd->bkhd", p.astype(mm), do_mm,
                            preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqhd,bkhd->bhqk", do_mm, v_blk,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        ds_mm = ds.astype(mm)
        dq_blk = jnp.einsum("bhqk,bkhd->bqhd", ds_mm, k_blk,
                            preferred_element_type=jnp.float32)
        dk_blk = jnp.einsum("bhqk,bqhd->bkhd", ds_mm, q,
                            preferred_element_type=jnp.float32)
        return (dq_acc + dq_blk,), (dk_blk, dv_blk)

    (dq,), (dk_blocks, dv_blocks) = jax.lax.scan(
        one_block, (jnp.zeros(q.shape, jnp.float32),),
        jnp.arange(n_blocks))
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(b, sk_pad, h, d)[:, :sk]
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(b, sk_pad, h, d)[:, :sk]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal=False, block_q=None, block_k=None,
                    use_pallas=None):
    """Tiled attention ``softmax(q·kᵀ/√d)·v`` over (b, s, h, d) tensors.

    ``block_q``/``block_k`` default to the autotune DB's measured blocks
    for this device generation (``ops.benchmark.gemm_choice`` with
    kernel="flash_attention"), falling back to 128.  ``use_pallas``:
    force the kernel choice; default auto — the Pallas kernel on TPU,
    the XLA-fused fallback elsewhere.
    """
    o, _lse = _fwd_impl(q, k, v, causal, block_q, block_k, use_pallas)
    return o


def _on_tpu():
    from veles_tpu.ops import on_tpu
    return on_tpu()


def _db_choice(dtype, shape=None, kernel="flash_attention"):
    from veles_tpu.ops.benchmark import gemm_choice   # deferred: cycle
    return gemm_choice(dtype, kernel=kernel, shape=shape)


def _resolve_blocks(block_q, block_k, dtype, shape=None):
    """Caller-supplied blocks win; else the autotune DB's measured
    blocks for this device generation — routed by the actual
    (b, s, h, d) onto the measured sequence-regime classes
    (``flash_attention_v2``); else 128s.  Trace-time only."""
    if block_q is None or block_k is None:
        choice = _db_choice(dtype, shape)
        db = choice[1] if choice else None
        if db:
            block_q = block_q or int(db[0])
            block_k = block_k or int(db[1])
    return block_q or 128, block_k or 128


def _resolve_backend(use_pallas, dtype, shape=None):
    """Explicit arg > the autotune DB's measured winner for this
    device generation (per sequence regime) > Pallas-on-TPU default."""
    if use_pallas is not None:
        return use_pallas
    if not _on_tpu():
        return False
    choice = _db_choice(dtype, shape)
    if choice is not None:
        return choice[0] == "pallas"
    return True


def _fwd_impl(q, k, v, causal, block_q, block_k, use_pallas):
    block_q, block_k = _resolve_blocks(block_q, block_k, q.dtype,
                                       q.shape)
    pallas = _resolve_backend(use_pallas, q.dtype, q.shape)
    if pallas:
        from veles_tpu.config import root
        o, lse = _flash_fwd(
            q, k, v, causal=causal, block_q=block_q, block_k=block_k,
            interpret=bool(root.common.engine.get("interpret", False)))
        return o, lse
    return _mha_jnp(q, k, v, causal)


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, use_pallas):
    o, lse = _fwd_impl(q, k, v, causal, block_q, block_k, use_pallas)
    # backward expects lse as (b, h, s)
    return o, (q, k, v, o, lse)


def _resolve_bwd(block_q, block_k, use_pallas, dtype, shape):
    """Backward backend + blocks: explicit arg > the DB's measured
    ``flash_attention_bwd`` winner > the forward's choice (the
    backward kernels share the forward's tiling structure, so its
    measured blocks are the best available prior) > Pallas-on-TPU."""
    choice = _db_choice(dtype, shape, kernel="flash_attention_bwd")
    if choice is None:
        choice = _db_choice(dtype, shape)
    if use_pallas is None:
        pallas = _on_tpu() if choice is None \
            else (choice[0] == "pallas" and _on_tpu())
    else:
        pallas = use_pallas
    if block_q is None or block_k is None:
        db = choice[1] if choice else None
        if db:
            block_q = block_q or int(db[0])
            block_k = block_k or int(db[1])
    return pallas, block_q or 128, block_k or 128


def _flash_vjp_bwd(causal, block_q, block_k, use_pallas, res, do):
    pallas, block_q, block_k = _resolve_bwd(
        block_q, block_k, use_pallas, res[0].dtype, res[0].shape)
    if pallas:
        from veles_tpu.config import root
        q, k, v, o, lse = res
        return _flash_bwd(
            q, k, v, o, lse, do, causal=causal, block_q=block_q,
            block_k=block_k,
            interpret=bool(root.common.engine.get("interpret", False)))
    return _bwd_blockwise(res, do, causal, block_k)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
