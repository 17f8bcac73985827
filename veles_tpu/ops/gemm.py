"""Tiled matmul: the MXU workhorse.

Re-designs the reference's ``#define``-specialized GEMM family
(``ocl/matrix_multiplication_begin.cl:1-64``, ``_precise.cl``,
``_subsum.cl``, ``_end.cl``, ``ocl/gemm.cl``; CUDA twins) as ONE Pallas
kernel: a (M/bm, N/bn, K/bk) grid with float32 VMEM accumulation and a
fused epilogue (bias + activation) — the fusion the reference obtained by
textually pasting activation code between ``_begin``/``_end`` includes.

The reference's precision levels (Kahan / multipartial sums,
``config.py:246-249``) map to the accumulator dtype: the MXU natively
accumulates bf16 products in float32, which is *more* precise than the
reference's float32 products + float32 sums, so PRECISION_LEVEL>0 needs no
special kernel on TPU.

``matmul`` carries a custom VJP so ``jax.grad`` differentiates *through*
the Pallas kernel (backward = two more tiled matmuls) — gradient units and
hand-written GD units share one code path.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: fallback tiles when neither the caller nor the autotune DB
#: (``ops.benchmark.autotune_gemm`` → ``devices/device_infos.json``)
#: supplies measured ones — MXU-aligned, nothing more
DEFAULT_TILES = (512, 512, 512)   # (bm, bk, bn)


def _precision():
    """Map the reference's precision levels (Kahan/multipartial sums,
    ``config.py:246-249``) onto MXU pass counts for float32 operands:
    0 → DEFAULT (bf16 passes), 1 → HIGH (bf16_3x), 2 → HIGHEST (f32)."""
    from veles_tpu.config import root
    level = root.common.engine.get("precision_level", 0)
    return (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH,
            jax.lax.Precision.HIGHEST)[min(int(level), 2)]

_ACTIVATIONS = {
    None: lambda x: x,
    "linear": lambda x: x,
    "tanh": lambda x: jnp.tanh(x * 0.6666) * 1.7159,  # Znicz scaled tanh
    "sigmoid": jax.nn.sigmoid,
    # Znicz smooth ReLU — the clamped log1p form shared with
    # znicz.fused._ACT and the numpy units (the naive log(1+exp(x))
    # overflows to inf past x ≈ 88)
    "relu": lambda x: jnp.log1p(jnp.exp(jnp.minimum(x, 30.0))),
    "strict_relu": lambda x: jnp.maximum(x, 0.0),
}


def _matmul_kernel(a_ref, b_ref, bias_ref, o_ref, acc_ref, *, n_k,
                   activation, has_bias):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.float32,
                          precision=_precision())

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[:]
        if has_bias:
            acc = acc + bias_ref[:].astype(jnp.float32)
        acc = _ACTIVATIONS[activation](acc)
        o_ref[:] = acc.astype(o_ref.dtype)


from veles_tpu.ops.util import pad_axis as _pad_to_impl, round_up


def _pad_to(x, mult, axis):
    return _pad_to_impl(x, mult, axis)


@functools.partial(jax.jit, static_argnames=("activation", "tiles",
                                             "out_dtype", "interpret"))
def _matmul_pallas(a, b, bias, activation=None, tiles=None, out_dtype=None,
                   interpret=False):
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    out_dtype = out_dtype or a.dtype
    bm, bk, bn = tiles or DEFAULT_TILES
    bm, bk, bn = min(bm, round_up(m, 8)), min(bk, round_up(k, 128)), \
        min(bn, round_up(n, 128))
    a_p = _pad_to(_pad_to(a, bm, 0), bk, 1)
    b_p = _pad_to(_pad_to(b, bk, 0), bn, 1)
    has_bias = bias is not None
    bias_p = _pad_to(bias.reshape(1, -1), bn, 1) if has_bias \
        else jnp.zeros((1, bn), a.dtype)
    mp, kp = a_p.shape
    np_ = b_p.shape[1]
    n_k = kp // bk
    grid = (mp // bm, np_ // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k, activation=activation,
                          has_bias=has_bias),
        name="veles_matmul",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_p, b_p, bias_p)
    return out[:m, :n]


def _matmul_jnp(a, b, bias, activation=None, out_dtype=None):
    out = jnp.dot(a, b, preferred_element_type=jnp.float32,
                  precision=_precision())
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    out = _ACTIVATIONS[activation](out)
    return out.astype(out_dtype or a.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def matmul(a, b, bias=None, activation=None, tiles=None, use_pallas=None):
    """``activation(a @ b + bias)`` with MXU tiling.

    a: (M, K); b: (K, N); bias: (N,) or None.  ``tiles``: (bm, bk, bn)
    from the autotune DB.  ``use_pallas``: force kernel choice (default:
    pallas on TPU, jnp elsewhere).
    """
    return _matmul_fwd(a, b, bias, activation, tiles, use_pallas)[0]


def _dispatch(use_pallas, tiles, dtype, shape=None):
    """(use_pallas_bool, tiles) for this call.  Priority: explicit
    ``use_pallas`` arg > explicit ``root.common.engine.pallas_gemm``
    config > the autotune DB's measured winner for this device
    generation, shape class and precision level
    (``ops.benchmark.gemm_choice``) > XLA.  This runs at
    TRACE time only (jit caches the result), so the DB lookup costs
    nothing per step."""
    from veles_tpu.ops.benchmark import gemm_choice
    choice = None if use_pallas is False else gemm_choice(dtype,
                                                          shape=shape)
    db_tiles = choice[1] if choice else None
    if use_pallas is not None:
        # explicit choice still benefits from measured tiles
        return use_pallas, tiles or db_tiles
    from veles_tpu.config import root
    from veles_tpu.ops import on_tpu
    configured = root.common.engine.get("pallas_gemm", None)
    if configured is not None:
        return bool(configured) and on_tpu(), tiles or db_tiles
    if not on_tpu() or choice is None:
        # no measurement for this generation: XLA's GEMM is the safe
        # default (run scripts/autotune.py on the chip to decide)
        return False, tiles
    return choice[0] == "pallas", tiles or db_tiles


def _matmul_fwd(a, b, bias, activation, tiles, use_pallas):
    pallas, eff_tiles = _dispatch(use_pallas, tiles, a.dtype,
                                  (a.shape[0], a.shape[1], b.shape[1]))
    if pallas:
        from veles_tpu.config import root
        out = _matmul_pallas(
            a, b, bias, activation=activation, tiles=eff_tiles,
            interpret=bool(root.common.engine.get("interpret", False)))
    else:
        out = _matmul_jnp(a, b, bias, activation=activation)
    # linear backward never reads the output — don't pin it in residuals
    saved_out = out if activation not in (None, "linear") else None
    return out, (a, b, bias, saved_out)


def _matmul_bwd(activation, tiles, use_pallas, residuals, g):
    a, b, bias, out = residuals
    g = g.astype(jnp.float32)
    # d(activation) evaluated from the *output* where possible — matches
    # the reference's backward units which consume the forward output
    # (e.g. GDTanh uses y: err *= y*y*(-0.388484177) + 1.14381894).
    if activation in (None, "linear"):
        dact = g
    elif activation == "tanh":
        y = out.astype(jnp.float32)
        dact = g * (y * y * (-0.388484177) + 1.14381894)
    elif activation == "sigmoid":
        y = out.astype(jnp.float32)
        dact = g * y * (1.0 - y)
    elif activation == "relu":
        y = out.astype(jnp.float32)
        dact = g * (1.0 - jnp.exp(-y))
    elif activation == "strict_relu":
        y = out.astype(jnp.float32)
        dact = g * (y > 0.0)
    else:  # pragma: no cover
        raise ValueError(activation)
    dact = dact.astype(a.dtype)
    da = matmul(dact, b.T, None, None, tiles, use_pallas)
    db = matmul(a.T, dact, None, None, tiles, use_pallas)
    dbias = None if bias is None else jnp.sum(dact, axis=0).astype(
        bias.dtype)
    return da.astype(a.dtype), db.astype(b.dtype), dbias


matmul.defvjp(_matmul_fwd, _matmul_bwd)


# ---------------------------------------------------------------------------
# Fused backward-pass GD kernels — dW / db / dX with the weight-decay +
# momentum update folded into the dW epilogue, updating the DONATED
# parameter buffers in place.  The dense reference is
# ``znicz.gd._gd_math``; these kernels reproduce it block-tiled:
#
#     δ = err_output ⊙ act'(y)        (recomputed per block — cheaper
#                                      than materializing (B, N) in HBM)
#     dW = xᵀ·δ / B ;  v' = m·v − lr·(dW + λ·W) ;  W' = W + v'
#     db = Σδ / B  (own small kernel) ;  err_input = δ·Wᵀ  (δ·W transposed)
#
# Hyper-parameters ride as a (1, 128) float32 VMEM operand (block ==
# array dims, so no tiling constraint) because they are TRACED scalars
# — an LRAdjuster rescaling them must not retrace, mirroring the
# stitched `_gd_math` contract.
# ---------------------------------------------------------------------------

#: fallback (bf, bn, bk) = (fan-in, neurons, batch) tiles for the GD
#: kernel family when the autotune DB (``ops.benchmark.autotune_gd``)
#: has no measurement for this device generation
GD_DEFAULT_TILES = (256, 256, 256)

#: hp operand layout: [0]=lr [1]=lr_bias [2]=decay [3]=decay_bias
#: [4]=moment [5]=moment_bias [6]=1/batch
(_HP_LR, _HP_LR_B, _HP_DECAY, _HP_DECAY_B, _HP_MOM, _HP_MOM_B,
 _HP_INVB) = range(7)

#: activation derivatives from the *output* (Znicz convention) —
#: duplicated from ``znicz.gd._DERIVS`` because ops must not import
#: znicz (gd.py imports from here, not the reverse)
_GD_DERIVS = {
    None: lambda y: jnp.ones_like(y),
    "tanh": lambda y: y * y * (-0.388484177) + 1.14381894,
    "sigmoid": lambda y: y * (1.0 - y),
    "relu": lambda y: 1.0 - jnp.exp(-y),
    "strict_relu": lambda y: (y > 0).astype(y.dtype),
}


def _gd_delta(eo_ref, y_ref, activation):
    return (eo_ref[:].astype(jnp.float32)
            * _GD_DERIVS[activation](y_ref[:].astype(jnp.float32)))


def _gd_dw_kernel(x_ref, eo_ref, y_ref, w_ref, vw_ref, hp_ref, w_out,
                  vw_out, acc_ref, *, n_k, activation, transposed):
    """Grid (F/bf, N/bn, B/bk); batch is the sequential axis.  The
    weight/momentum blocks live in the STORAGE layout ((N, F) when
    transposed) — the transpose is absorbed by swapping the dot operand
    order, never by relaying out a block."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    delta = _gd_delta(eo_ref, y_ref, activation)
    x = x_ref[:].astype(jnp.float32)
    if transposed:
        # storage (N, F): accumulate δᵀ·x directly in that layout
        acc_ref[:] += jax.lax.dot_general(
            delta, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[:] += jax.lax.dot_general(
            x, delta, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        hp = hp_ref[:]
        grad = acc_ref[:] * hp[0, _HP_INVB]
        w = w_ref[:].astype(jnp.float32)
        v_new = hp[0, _HP_MOM] * vw_ref[:].astype(jnp.float32) \
            - hp[0, _HP_LR] * (grad + hp[0, _HP_DECAY] * w)
        w_out[:] = (w + v_new).astype(w_out.dtype)
        vw_out[:] = v_new.astype(vw_out.dtype)


def _gd_db_kernel(eo_ref, y_ref, b_ref, vb_ref, hp_ref, b_out, vb_out,
                  acc_ref, *, n_k, activation):
    """Grid (N/bn, B/bk): the bias row accumulates Σδ over batch blocks
    into a (1, bn) scratch, then applies the same fused update."""
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    delta = _gd_delta(eo_ref, y_ref, activation)
    acc_ref[:] += jnp.sum(delta, axis=0, keepdims=True)

    @pl.when(kk == n_k - 1)
    def _epilogue():
        hp = hp_ref[:]
        grad = acc_ref[:] * hp[0, _HP_INVB]
        b = b_ref[:].astype(jnp.float32)
        v_new = hp[0, _HP_MOM_B] * vb_ref[:].astype(jnp.float32) \
            - hp[0, _HP_LR_B] * (grad + hp[0, _HP_DECAY_B] * b)
        b_out[:] = (b + v_new).astype(b_out.dtype)
        vb_out[:] = v_new.astype(vb_out.dtype)


def _gd_dx_kernel(eo_ref, y_ref, w_ref, o_ref, acc_ref, *, n_k,
                  activation, transposed):
    """Grid (B/bk, F/bf, N/bn): err_input = δ·Wᵀ (δ·W when the storage
    is transposed) against the PRE-update weights — the caller passes
    the original weight array, so standard backprop semantics hold even
    though the dW kernel updates the same logical buffer."""
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    delta = _gd_delta(eo_ref, y_ref, activation)
    w = w_ref[:].astype(jnp.float32)
    contract = (((1,), (0,)), ((), ())) if transposed \
        else (((1,), (1,)), ((), ()))
    acc_ref[:] += jax.lax.dot_general(
        delta, w, contract, preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _out():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def gd_fused_pallas(x, y, err_output, w, b, vw, vb, lr, lr_bias, decay,
                    decay_bias, moment, moment_bias, activation=None,
                    need_err_input=True, has_bias=True, transposed=False,
                    tiles=None, interpret=False):
    """Pallas twin of ``znicz.gd._gd_math`` — same positional signature,
    same ``(w, b, vw, vb, err_input)`` returns (``b``/``vb`` pass
    through untouched when ``has_bias`` is false, ``err_input`` is
    ``None`` when not needed).  Numerics: float32 accumulation like the
    reference, but block-tiled summation order, so parity vs the XLA
    path is documented-tolerance (~1e-5 relative), not bitwise."""
    batch = x.shape[0]
    x2 = x.reshape(batch, -1)
    eo = err_output.reshape(batch, -1)
    y2 = y.reshape(batch, -1)
    f, n = x2.shape[1], eo.shape[1]
    bf, bn, bk = tiles or GD_DEFAULT_TILES
    bf = min(bf, round_up(f, 128))
    bn = min(bn, round_up(n, 128))
    bk = min(bk, round_up(batch, 8))
    x_p = _pad_to(_pad_to(x2, bk, 0), bf, 1)
    eo_p = _pad_to(_pad_to(eo, bk, 0), bn, 1)
    y_p = _pad_to(_pad_to(y2, bk, 0), bn, 1)
    if transposed:
        w_p = _pad_to(_pad_to(w, bn, 0), bf, 1)
        vw_p = _pad_to(_pad_to(vw, bn, 0), bf, 1)
        w_spec = pl.BlockSpec((bn, bf), lambda i, j, kk: (j, i))
        acc_shape = (bn, bf)
    else:
        w_p = _pad_to(_pad_to(w, bf, 0), bn, 1)
        vw_p = _pad_to(_pad_to(vw, bf, 0), bn, 1)
        w_spec = pl.BlockSpec((bf, bn), lambda i, j, kk: (i, j))
        acc_shape = (bf, bn)
    bp, fp = x_p.shape
    np_ = eo_p.shape[1]
    n_kb = bp // bk
    hp = jnp.zeros((1, 128), jnp.float32).at[0, :7].set(jnp.stack(
        [jnp.asarray(v, jnp.float32) for v in
         (lr, lr_bias, decay, decay_bias, moment, moment_bias)]
        + [jnp.float32(1.0 / batch)]))
    hp_spec = pl.BlockSpec((1, 128), lambda *_: (0, 0))

    # err_input FIRST (traced order is irrelevant to XLA, but keeping
    # the pre-update weight read textually before the aliased update
    # makes the intent obvious)
    if need_err_input:
        err_input = pl.pallas_call(
            functools.partial(_gd_dx_kernel, n_k=np_ // bn,
                              activation=activation,
                              transposed=transposed),
            name="veles_gd_err_input",
            grid=(bp // bk, fp // bf, np_ // bn),
            in_specs=[
                pl.BlockSpec((bk, bn), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bn, bf), lambda i, j, kk: (kk, j))
                if transposed else
                pl.BlockSpec((bf, bn), lambda i, j, kk: (j, kk)),
            ],
            out_specs=pl.BlockSpec((bk, bf), lambda i, j, kk: (i, j)),
            out_shape=jax.ShapeDtypeStruct((bp, fp), jnp.float32),
            scratch_shapes=[pltpu.VMEM((bk, bf), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(eo_p, y_p, w_p)[:batch, :f]
    else:
        err_input = None

    w_new, vw_new = pl.pallas_call(
        functools.partial(_gd_dw_kernel, n_k=n_kb,
                          activation=activation, transposed=transposed),
        name="veles_gd_update_w",
        grid=(fp // bf, np_ // bn, n_kb),
        in_specs=[
            pl.BlockSpec((bk, bf), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            w_spec, w_spec, hp_spec,
        ],
        out_specs=[w_spec, w_spec],
        out_shape=[jax.ShapeDtypeStruct(w_p.shape, w.dtype),
                   jax.ShapeDtypeStruct(vw_p.shape, vw.dtype)],
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_p, eo_p, y_p, w_p, vw_p, hp)
    if transposed:
        w_new, vw_new = w_new[:n, :f], vw_new[:n, :f]
    else:
        w_new, vw_new = w_new[:f, :n], vw_new[:f, :n]

    if has_bias:
        b_p = _pad_to(b.reshape(1, -1), bn, 1)
        vb_p = _pad_to(vb.reshape(1, -1), bn, 1)
        row = pl.BlockSpec((1, bn), lambda i, kk: (0, i))
        b_new, vb_new = pl.pallas_call(
            functools.partial(_gd_db_kernel, n_k=n_kb,
                              activation=activation),
            name="veles_gd_update_b",
            grid=(np_ // bn, n_kb),
            in_specs=[
                pl.BlockSpec((bk, bn), lambda i, kk: (kk, i)),
                pl.BlockSpec((bk, bn), lambda i, kk: (kk, i)),
                row, row,
                pl.BlockSpec((1, 128), lambda i, kk: (0, 0)),
            ],
            out_specs=[row, row],
            out_shape=[jax.ShapeDtypeStruct(b_p.shape, b.dtype),
                       jax.ShapeDtypeStruct(vb_p.shape, vb.dtype)],
            scratch_shapes=[pltpu.VMEM((1, bn), jnp.float32)],
            input_output_aliases={2: 0, 3: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(eo_p, y_p, b_p, vb_p, hp)
        b_new = b_new[0, :n].reshape(b.shape)
        vb_new = vb_new[0, :n].reshape(vb.shape)
    else:
        b_new, vb_new = b, vb
    return w_new, b_new, vw_new, vb_new, err_input


@functools.lru_cache(maxsize=None)
def _log_interpret_once(backend):
    import logging
    logging.getLogger("veles_tpu.ops.gemm").warning(
        "engine.kernels=pallas on the %r backend: the fused GD kernels "
        "run in Pallas INTERPRET mode (parity/debug only; the compiled "
        "kernels need the TPU)", backend)


def gd_kernel_choice(dtype=jnp.float32, shape=None, db_path=None):
    """Resolve the training-kernel backend for the fused GD stage —
    ``(backend, tiles, interpret)``.

    ``root.common.engine.kernels``: ``xla`` forces the dense reference
    (``_gd_math``); ``pallas`` forces the fused kernels — compiled on
    TPU (interpreted there only when the caller set
    ``root.common.engine.interpret``), interpret-mode Pallas elsewhere
    (parity/debug; slow; logged once); ``auto`` (default) takes the
    autotune DB's measured winner on TPU
    (``ops.benchmark.autotune_gd``) and the dense reference elsewhere.
    Runs at stage-build/trace time only, so the DB lookup costs nothing
    per step and the resolved backend never retraces."""
    from veles_tpu.config import root
    from veles_tpu.ops import on_tpu
    mode = str(root.common.engine.get("kernels", "auto") or "auto")
    if mode == "xla":
        return "xla", None, False
    if not on_tpu():
        if mode != "pallas":
            return "xla", None, False
        _log_interpret_once(jax.default_backend())
        return "pallas", None, True
    from veles_tpu.ops.benchmark import gemm_choice
    choice = gemm_choice(dtype, db_path, kernel="gd", shape=shape)
    if mode != "pallas" and (choice is None or choice[0] != "pallas"):
        return "xla", None, False
    tiles = tuple(choice[1]) if choice and choice[1] else None
    return "pallas", tiles, bool(root.common.engine.get("interpret",
                                                        False))
