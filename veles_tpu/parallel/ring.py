"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no attention at all (SURVEY §5.7) — this is the
beyond-parity capability the TPU build treats as first-class: long
sequences are sharded over a ``seq`` mesh axis and attention runs
either as

* **ring attention** (:func:`ring_attention`): each device keeps its
  query shard resident and streams every key/value shard past it around
  the ICI ring with ``ppermute``.  Default = ring-FLASH: every hop's
  block math runs through the Pallas flash kernels (forward AND the
  swept backward) with global causal offsets; hops merge by the stable
  two-softmax rule, and the hand-rolled backward is a second ring in
  which dk/dv accumulators travel with their k/v blocks (the
  global-lse flash identity makes each hop's contribution exact).
  Memory per chip is O(S/n); comms overlap with the block matmuls
  under XLA's latency-hiding scheduler.  ``use_flash=False`` keeps the
  dense-einsum online-softmax body as the equivalence oracle.
* **Ulysses** (:func:`ulysses_attention`): two ``all_to_all``s re-shard
  activations seq-sharded → head-sharded, run dense local attention on
  full sequences for the local head group, and shard back.  Cheaper at
  moderate S (2 collectives instead of n-1 hops) but caps the seq-axis
  size at the head count.

Both are exact (== dense attention) — tested against
:func:`mha_reference` on the virtual CPU mesh.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def mha_reference(q, k, v, causal=False, q_offset=0, k_offset=0):
    """Dense multi-head attention, the golden reference.

    Shapes: q [B, Sq, H, D], k/v [B, Sk, H, D] → [B, Sq, H, D].
    ``q_offset``/``k_offset`` are the global positions of element 0 —
    how causal masks stay correct when q/k are shards of a longer
    sequence.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(q.shape[1])[:, None]
        kpos = k_offset + jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(qpos >= kpos, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def _block_update(carry, q, k_blk, v_blk, mask):
    """Online-softmax accumulation of one K/V block (the flash-attention
    inner update)."""
    o, m, l = carry
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    # fully-masked rows: keep p exactly zero (exp(NEG_INF-NEG_INF)=1)
    p = jnp.where(mask, p, 0.0)
    l = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_blk.dtype), v_blk)
    o = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o, m_new, l


def _ring_attention_local(q, k, v, axis_name, causal):
    """Body under shard_map: q/k/v are the local sequence shards."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_offset = idx * s_local

    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:2] + (q.shape[2],), NEG_INF,
                 jnp.float32).transpose(0, 2, 1)      # [B, H, Sq]
    l = jnp.zeros_like(m)
    qpos = q_offset + jnp.arange(s_local)

    def step(t, carry):
        o, m, l, k_cur, v_cur = carry
        # after t forward shifts, device idx holds block (idx - t) mod n
        blk = (idx - t) % n
        kpos = blk * s_local + jnp.arange(s_local)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]        # [Sq, Sk]
        else:
            mask = jnp.ones((s_local, s_local), bool)
        mask = jnp.broadcast_to(
            mask[None, None], (q.shape[0], q.shape[2]) + mask.shape)
        o, m, l = _block_update((o, m, l), q, k_cur, v_cur, mask)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _k, _v = jax.lax.fori_loop(
        0, n, step, (o, m, l, k, v), unroll=True)
    l = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (o / l).astype(q.dtype)


# --------------------------------------------------------------------------
# ring FLASH attention: the Pallas-block variant with a hand-rolled
# backward ring (the standard ring-flash-attention algorithm)
# --------------------------------------------------------------------------

def _use_flash_blocks():
    """Pallas kernels for the per-hop block math?  TPU, or interpret
    mode forced (how the CPU-mesh tests pin the kernel path)."""
    from veles_tpu.config import root
    from veles_tpu.ops import on_tpu
    return on_tpu() or bool(root.common.engine.get("interpret", False))


def _block_fwd(q, k_blk, v_blk, causal, q_off, k_off):
    """One ring hop's (o_i, lse_i) with GLOBAL causal offsets; block
    sizes come from the autotune DB (``_resolve_blocks``), exactly as
    the single-shard flash_attention path."""
    from veles_tpu.config import root
    from veles_tpu.ops.attention import (_flash_fwd, _mha_jnp,
                                         _resolve_blocks)
    if _use_flash_blocks():
        bq, bk = _resolve_blocks(None, None, q.dtype, q.shape)
        return _flash_fwd(
            q, k_blk, v_blk, causal=causal, block_q=bq, block_k=bk,
            q_offset=q_off, k_offset=k_off,
            interpret=bool(root.common.engine.get("interpret", False)))
    return _mha_jnp(q, k_blk, v_blk, causal, q_offset=q_off,
                    k_offset=k_off)


def _block_bwd(q, k_blk, v_blk, o, lse, do, delta, causal, q_off,
               k_off):
    """One ring hop's (dq_i, dk_blk, dv_blk) from the GLOBAL (o, lse)
    — the flash backward identity p = exp(s − lse_global) makes each
    hop's contribution exact without per-hop renormalization.
    ``delta`` is hop-invariant and precomputed once by the caller."""
    from veles_tpu.config import root
    from veles_tpu.ops.attention import (_bwd_dense_block, _flash_bwd,
                                         _resolve_bwd)
    if _use_flash_blocks():
        _pl, bq, bk = _resolve_bwd(None, None, True, q.dtype, q.shape)
        return _flash_bwd(
            q, k_blk, v_blk, o, lse, do, causal=causal, block_q=bq,
            block_k=bk, q_offset=q_off, k_offset=k_off, delta=delta,
            interpret=bool(root.common.engine.get("interpret", False)))
    return _bwd_dense_block(q, k_blk, v_blk, lse, do, delta, causal,
                            q_off, k_off)


def _ring_flash_fwd_pass(q, k, v, axis_name, causal):
    """Forward ring: per hop, one flash block (o_i, lse_i); hops merge
    by the stable two-softmax rule.  Returns (o, lse); after n hops
    k/v are HOME again, so the residuals need no extra collective."""
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_off = idx * s_local
    b, _s, h, _d = q.shape

    o = jnp.zeros(q.shape, jnp.float32)
    lse = jnp.full((b, h, s_local), NEG_INF, jnp.float32)

    def step(t, carry):
        o, lse, k_cur, v_cur = carry
        blk = (idx - t) % n
        o_i, lse_i = _block_fwd(q, k_cur, v_cur, causal, q_off,
                                blk * s_local)
        m = jnp.maximum(lse, lse_i)
        # fully-masked hops have lse_i ≈ -inf → weight exactly 0;
        # m can only be -inf while NOTHING has been accumulated yet
        e_prev = jnp.exp(lse - m)
        e_new = jnp.exp(lse_i - m)
        denom = jnp.maximum(e_prev + e_new, 1e-30)
        w_prev = (e_prev / denom).transpose(0, 2, 1)[..., None]
        w_new = (e_new / denom).transpose(0, 2, 1)[..., None]
        o = o * w_prev + o_i.astype(jnp.float32) * w_new
        lse = m + jnp.log(denom)
        p = [(i, (i + 1) % n) for i in range(n)]
        return (o, lse, jax.lax.ppermute(k_cur, axis_name, p),
                jax.lax.ppermute(v_cur, axis_name, p))

    o, lse, _k, _v = jax.lax.fori_loop(0, n, step, (o, lse, k, v),
                                       unroll=True)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring_flash_local(q, k, v, axis_name, causal):
    o, _lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal)
    return o


def _ring_flash_vjp_fwd(q, k, v, axis_name, causal):
    o, lse = _ring_flash_fwd_pass(q, k, v, axis_name, causal)
    return o, (q, k, v, o, lse)


def _ring_flash_vjp_bwd(axis_name, causal, res, do):
    """Backward ring: dk/dv accumulators TRAVEL with their k/v block —
    each hop adds the local q shard's contribution (computed against
    the GLOBAL lse), and after n hops every block (and its gradient)
    is home with contributions from every shard."""
    q, k, v, o, lse = res
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_off = idx * s_local

    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    # rowsum(do ⊙ o) is hop-invariant: one bandwidth pass for all n
    delta = jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                       o.astype(jnp.float32))

    def step(t, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        blk = (idx - t) % n
        dq_i, dk_i, dv_i = _block_bwd(q, k_cur, v_cur, o, lse, do,
                                      delta, causal, q_off,
                                      blk * s_local)
        dq = dq + dq_i.astype(jnp.float32)
        dk_cur = dk_cur + dk_i.astype(jnp.float32)
        dv_cur = dv_cur + dv_i.astype(jnp.float32)
        p = [(i, (i + 1) % n) for i in range(n)]
        return (dq,
                jax.lax.ppermute(k_cur, axis_name, p),
                jax.lax.ppermute(v_cur, axis_name, p),
                jax.lax.ppermute(dk_cur, axis_name, p),
                jax.lax.ppermute(dv_cur, axis_name, p))

    dq, _k, _v, dk, dv = jax.lax.fori_loop(
        0, n, step, (dq, k, v, dk, dv), unroll=True)
    return (dq.astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_ring_flash_local.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_attention(q, k, v, mesh, causal=False, seq_axis="seq",
                   batch_axis="data", head_axis=None, use_flash=True):
    """Exact attention over a ``seq``-sharded sequence.

    q/k/v: GLOBAL [B, S, H, D] arrays (or tracers inside an enclosing
    jit over the same mesh).  B is sharded over ``batch_axis``, S over
    ``seq_axis``, and optionally H over ``head_axis`` (compose with TP).

    ``use_flash=True`` (default): ring-FLASH — every hop's block math
    runs through the Pallas flash kernels (forward + the swept
    backward) with global causal offsets, merged by the stable
    two-softmax rule, and the backward is its own ring in which dk/dv
    accumulators travel with their blocks.  ``use_flash=False`` keeps
    the dense-einsum online-softmax body (the equivalence oracle, and
    the only path whose backward is pure autodiff)."""
    spec = P(batch_axis, seq_axis, head_axis, None)
    body = _ring_flash_local if use_flash else _ring_attention_local
    fn = jax.shard_map(
        functools.partial(body, axis_name=seq_axis, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name, causal):
    """Body under shard_map: all-to-all seq-sharded → head-sharded,
    dense local attention, all-to-all back."""
    n = jax.lax.psum(1, axis_name)

    def scatter_heads(x):
        # [B, S/n, H, D] → [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    def gather_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    del n
    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    # the local attention after the head-scatter is ordinary full
    # attention over H/n heads: route it through the flash kernel
    # (Pallas fwd + the swept Pallas backward on TPU; the XLA-fused
    # fallback elsewhere — value-identical to mha_reference) instead
    # of the O(S²) dense reference
    from veles_tpu.ops.attention import flash_attention
    out = flash_attention(qh, kh, vh, causal=causal)
    return gather_heads(out)


def ulysses_attention(q, k, v, mesh, causal=False, seq_axis="seq",
                      batch_axis="data"):
    """All-to-all sequence parallelism (Ulysses).  Requires
    ``H % mesh.shape[seq_axis] == 0``."""
    if q.shape[2] % mesh.shape[seq_axis]:
        raise ValueError(
            "ulysses needs heads (%d) divisible by seq axis (%d)"
            % (q.shape[2], mesh.shape[seq_axis]))
    spec = P(batch_axis, seq_axis, None, None)
    fn = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=seq_axis,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
