"""Quantized (int8-weight) matmul: the serving-side MXU kernel.

The int8 half of the reference's dtype-specialized GEMM family — the
native engine's int8/register-blocking change banked +35% serving
throughput (PAPER.md §L0/L1), and the same headroom exists on-chip:
weights cross the HBM→VMEM boundary at a quarter of the f32 width, so
a weight-bound serving GEMM speeds up with the bytes.

ONE Pallas kernel, the ``ops/gemm.py`` shape discipline verbatim — a
(M/bm, N/bn, K/bk) grid with float32 VMEM accumulation — but the B
operand stays **int8 end to end**: it is DMA'd from HBM as stored (no
dequantized f32 copy ever materializes), widened to the activation
dtype inside VMEM for the MXU pass, and the per-output-channel dequant
(``acc * scale[N]``) is fused into the epilogue together with bias and
activation.  Weight-only quantization: activations stay bf16/f32, so
the numerics are "W8A16" — ``out = act((x @ q) * scale + bias)``.

No custom VJP on purpose: this is a SERVING kernel (deploy-time
quantized params are not trained through), so ``qmatmul`` is a plain
function — gradients through a quantized deploy are a bug, and the
missing VJP makes them a loud one.

The dense-jnp reference path (``_qmatmul_jnp``) is the interpret/CPU
fallback AND the parity oracle: it performs the dot-then-scale in the
same order as the kernel epilogue, so interpret-mode Pallas output is
bitwise-comparable (``tests/test_quant.py``).

Dispatch consults the autotune DB like :func:`veles_tpu.ops.gemm
.matmul` does — ``ratings["gemm_int8"]`` rows written by
``scripts/autotune.py``'s int8 sweep (``--skip-int8`` to omit).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from veles_tpu.ops.gemm import _ACTIVATIONS as _GEMM_ACTIVATIONS
from veles_tpu.ops.gemm import _precision
from veles_tpu.ops.util import pad_axis as _pad_to, round_up

#: fallback tiles when neither the caller nor the autotune DB supplies
#: measured ones — MXU-aligned; bk is the int8 operand's sublane dim
#: and must stay a multiple of 32 (the int8 (32, 128) register tile)
DEFAULT_TILES = (512, 512, 512)   # (bm, bk, bn)

#: the fused-epilogue activations: the shared gemm table plus gelu —
#: the transformer MLP's up-projection runs ``gelu(x @ w1 + b1)`` in
#: one quantized dispatch
_ACTIVATIONS = dict(_GEMM_ACTIVATIONS)
_ACTIVATIONS["gelu"] = jax.nn.gelu


def _qmatmul_kernel(a_ref, b_ref, scale_ref, bias_ref, o_ref, acc_ref,
                    *, n_k, activation, has_bias):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # the int8 block widens to the ACTIVATION dtype in VMEM — the MXU
    # pass is bf16/f32 like the float kernel; only the HBM traffic and
    # footprint are int8
    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:].astype(a_ref.dtype),
                          preferred_element_type=jnp.float32,
                          precision=_precision())

    @pl.when(k == n_k - 1)
    def _epilogue():
        # dot-then-scale: per-output-channel dequant commutes with the
        # K contraction exactly (scale depends only on the column), so
        # the epilogue pays ONE multiply per output element instead of
        # one per weight — and the dense reference does the same order
        acc = acc_ref[:] * scale_ref[:].astype(jnp.float32)
        if has_bias:
            acc = acc + bias_ref[:].astype(jnp.float32)
        acc = _ACTIVATIONS[activation](acc)
        o_ref[:] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("activation", "tiles",
                                             "out_dtype", "interpret"))
def _qmatmul_pallas(a, q, scale, bias, activation=None, tiles=None,
                    out_dtype=None, interpret=False):
    m, k = a.shape
    k2, n = q.shape
    assert k == k2, (a.shape, q.shape)
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = tiles or DEFAULT_TILES
    # bk is simultaneously a's lane dim (128-aligned) and the int8
    # operand's sublane dim (32-aligned): 128 covers both
    bm, bk, bn = min(bm, round_up(m, 8)), min(bk, round_up(k, 128)), \
        min(bn, round_up(n, 128))
    a_p = _pad_to(_pad_to(a, bm, 0), bk, 1)
    q_p = _pad_to(_pad_to(q, bk, 0), bn, 1)
    scale_p = _pad_to(scale.reshape(1, -1).astype(jnp.float32), bn, 1)
    has_bias = bias is not None
    bias_p = _pad_to(bias.reshape(1, -1), bn, 1) if has_bias \
        else jnp.zeros((1, bn), a.dtype)
    mp, kp = a_p.shape
    np_ = q_p.shape[1]
    n_k = kp // bk
    grid = (mp // bm, np_ // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_qmatmul_kernel, n_k=n_k,
                          activation=activation, has_bias=has_bias),
        name="veles_qmatmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_p, q_p, scale_p, bias_p)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("activation",
                                             "out_dtype"))
def _qmatmul_jnp(a, q, scale, bias, activation=None, out_dtype=None):
    """The dense reference: int8 widened to the activation dtype, dot
    with f32 accumulation, then scale/bias/activation in the SAME
    order as the kernel epilogue — the interpret/CPU fallback and the
    parity oracle in one function.  Jitted so XLA applies the same
    mul+add fusion it applies inside the interpret-mode kernel body
    (the single-block bitwise gate would otherwise differ by one ulp
    of fma)."""
    out = jnp.dot(a, q.astype(a.dtype),
                  preferred_element_type=jnp.float32,
                  precision=_precision())
    out = out * scale.reshape(-1).astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    out = _ACTIVATIONS[activation](out)
    return out.astype(out_dtype or a.dtype)


def _dispatch(use_pallas, tiles, dtype, shape=None):
    """(use_pallas_bool, tiles) for this call — the ``ops.gemm``
    priority order: explicit arg > ``root.common.engine.pallas_gemm``
    config > the autotune DB's measured ``gemm_int8`` winner for this
    device generation > XLA (the dense-jnp path).  Runs at trace time
    only."""
    from veles_tpu.ops.benchmark import gemm_choice
    choice = None if use_pallas is False else gemm_choice(
        dtype, kernel="gemm_int8", shape=shape)
    db_tiles = choice[1] if choice else None
    if use_pallas is not None:
        return use_pallas, tiles or db_tiles
    from veles_tpu.config import root
    from veles_tpu.ops import on_tpu
    configured = root.common.engine.get("pallas_gemm", None)
    if configured is not None:
        return bool(configured) and on_tpu(), tiles or db_tiles
    if not on_tpu() or choice is None:
        # no measurement for this generation: the dense path is the
        # safe default (run scripts/autotune.py on the chip to decide)
        return False, tiles
    return choice[0] == "pallas", tiles or db_tiles


def qmatmul(a, q, scale, bias=None, activation=None, tiles=None,
            use_pallas=None, out_dtype=None):
    """``activation((a @ q) * scale + bias)`` with int8 weights.

    a: (M, K) bf16/f32 activations; q: (K, N) **int8** weights as
    stored in HBM; scale: (N,) float32 per-output-channel dequant
    factors; bias: (N,) or None.  ``tiles``: (bm, bk, bn) from the
    autotune DB's ``gemm_int8`` entry.  ``use_pallas``: force the
    kernel choice (default: the DB's measured winner on TPU, dense
    jnp elsewhere).  Serving-only: no VJP is defined — quantized
    params are not trained through.
    """
    pallas, eff_tiles = _dispatch(use_pallas, tiles, a.dtype,
                                  (a.shape[0], a.shape[1], q.shape[1]))
    if pallas:
        from veles_tpu.config import root
        return _qmatmul_pallas(
            a, q, scale, bias, activation=activation, tiles=eff_tiles,
            out_dtype=out_dtype,
            interpret=bool(root.common.engine.get("interpret", False)))
    return _qmatmul_jnp(a, q, scale, bias, activation=activation,
                        out_dtype=out_dtype)
