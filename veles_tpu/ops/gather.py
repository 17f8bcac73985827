"""Minibatch gather: device-side sample selection by shuffled indices.

Parity target: ``ocl/fullbatch_loader.cl:5-30`` /
``cuda/fullbatch_loader.cu`` — gathers minibatch samples (and labels) from
the device-resident full dataset by an index vector, zero-padding the tail
of a short final batch.

TPU re-design: the jnp path is ``jnp.take`` (XLA emits an efficient
dynamic-gather); the Pallas path uses scalar-prefetched indices as the
BlockSpec index map, so each sample row is DMA'd straight from the
dataset in HBM into the output block — no materialized one-hot, no host
round-trip for the epoch shuffle.
"""

import functools

import jax
import jax.numpy as jnp
import numpy
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _use_pallas(data, use_pallas):
    """Resolve the gather backend.  Priority: explicit ``use_pallas``
    arg > ``root.common.engine.pallas_gather`` (True/False force; a
    config force also honors ``engine.interpret`` so CPU tests can pin
    the in-scan composition through the Pallas interpreter) > the
    device DB's measured A/B (``autotune_gather``) on the TPU > XLA.

    Whatever this resolves to RUNS: a kernel the chip's compiler
    refuses raises at the compile of the enclosing program instead of
    being swapped for the XLA gather behind the caller's back."""
    if data.ndim < 2:
        return False
    if use_pallas is not None:
        return bool(use_pallas)
    from veles_tpu.config import root   # deferred: import cycle
    from veles_tpu.ops import on_tpu
    forced = root.common.engine.get("pallas_gather", None)
    if isinstance(forced, bool):
        interp = bool(root.common.engine.get("interpret", False))
        return forced and (on_tpu() or interp)
    from veles_tpu.ops.benchmark import gather_choice
    # the verdict only transfers to the ROW SIZE it was measured at:
    # the kernel's win is not generic
    measured = gather_choice(str(jnp.dtype(data.dtype)),
                             row_elems=int(numpy.prod(data.shape[1:])))
    return bool(measured) and on_tpu()


def _interpret():
    from veles_tpu.config import root   # deferred: import cycle
    return bool(root.common.engine.get("interpret", False))


def take_rows(data, indices, use_pallas=None):
    """``data[indices]`` along axis 0.  Negative indices (the reference's
    "empty slot" marker for short batches) produce zero rows.  Backend
    dispatch: :func:`_use_pallas`."""
    if _use_pallas(data, use_pallas):
        out = _gather_pallas(data.reshape(data.shape[0], -1), indices,
                             interpret=_interpret())
        return out.reshape((indices.shape[0],) + data.shape[1:])
    return _gather_jnp(data, indices)


def take_rows_norm(data, indices, norm, use_pallas=None):
    """Fused gather + affine normalize: float32
    ``data[indices]*scale + shift`` with negative indices producing
    ZERO rows (masking applies AFTER the normalize, so a short batch's
    padding stays 0 rather than ``shift``).

    This is the fullbatch loader's native-dtype head: the dataset stays
    resident in its storage dtype (e.g. uint8 pixels) and the first
    forward program receives normalized float32 — the gather's DMA and
    the normalizer's multiply-add are one kernel, so the raw bytes are
    read exactly once.  ``norm`` is the loader's affine
    ``(scale, shift)`` pair (``NormalizerBase.as_affine``): scalars or
    flat per-feature arrays.  Dispatch mirrors :func:`take_rows` (the
    gather A/B verdict transfers: the epilogue adds two VPU ops to a
    DMA-bound kernel)."""
    scale, shift = norm
    if _use_pallas(data, use_pallas):
        flat = data.reshape(data.shape[0], -1)
        f = flat.shape[1]
        out = _gather_norm_pallas(
            flat, indices, _norm_row(scale, f), _norm_row(shift, f),
            interpret=_interpret())
        return out.reshape((indices.shape[0],) + data.shape[1:])
    return _gather_norm_jnp(data, indices,
                            jnp.asarray(scale, jnp.float32),
                            jnp.asarray(shift, jnp.float32))


def _norm_row(v, f):
    """scale/shift as a (1, f) float32 row the kernel broadcasts."""
    v = jnp.asarray(v, jnp.float32)
    return jnp.broadcast_to(v.reshape(1, -1), (1, f))


@jax.jit
@jax.named_scope("veles.loader.take_rows_norm")
def _gather_norm_jnp(data, indices, scale, shift):
    taken = jnp.take(data, jnp.maximum(indices, 0), axis=0)
    flat = taken.reshape(taken.shape[0], -1).astype(jnp.float32)
    normed = (flat * scale.reshape(1, -1)
              + shift.reshape(1, -1)).reshape(taken.shape)
    mask = (indices >= 0).reshape((-1,) + (1,) * (data.ndim - 1))
    return jnp.where(mask, normed, 0.0)


def _gather_norm_kernel(idx_ref, data_ref, scale_ref, shift_ref, o_ref):
    i = pl.program_id(0)
    valid = idx_ref[i] >= 0

    @pl.when(valid)
    def _copy():
        x = data_ref[:]
        if jnp.issubdtype(x.dtype, jnp.integer):
            # Mosaic has no direct uint8 -> float32 cast; widen first
            x = x.astype(jnp.int32)
        o_ref[:] = (x.astype(jnp.float32)
                    * scale_ref[:].reshape(1, 1, -1)
                    + shift_ref[:].reshape(1, 1, -1))

    @pl.when(jnp.logical_not(valid))
    def _zero():
        o_ref[:] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_norm_pallas(data, indices, scale, shift, interpret=False):
    # same (n, 1, f) / (1, 1, f) block trick as _gather_pallas (block
    # dims equal to array dims sidestep the sublane rule); scale/shift
    # ride as whole-array (1, f) operands every grid point maps to
    n, f = data.shape
    b = indices.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, 1, f), lambda i, idx_ref: (jnp.maximum(
                idx_ref[i], 0), 0, 0)),
            pl.BlockSpec((1, f), lambda i, idx_ref: (0, 0)),
            pl.BlockSpec((1, f), lambda i, idx_ref: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, f), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_norm_kernel,
        name="veles_gather_norm",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, f), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(indices, jnp.int32), data.reshape(n, 1, f),
      scale, shift)
    return out.reshape(b, f)


@jax.jit
@jax.named_scope("veles.loader.take_rows")
def _gather_jnp(data, indices):
    # jitted: the eager form is 3 separate op dispatches per minibatch;
    # one compiled program per (shape, dtype) serves every batch.  The
    # scope names the gather's device operations wherever it is fused
    taken = jnp.take(data, jnp.maximum(indices, 0), axis=0)
    mask = (indices >= 0).reshape((-1,) + (1,) * (data.ndim - 1))
    return jnp.where(mask, taken, 0)


def _gather_kernel(idx_ref, data_ref, o_ref):
    i = pl.program_id(0)
    valid = idx_ref[i] >= 0

    @pl.when(valid)
    def _copy():
        o_ref[:] = data_ref[:]

    @pl.when(jnp.logical_not(valid))
    def _zero():
        o_ref[:] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _gather_pallas(data, indices, interpret=False):
    # The Mosaic lowering requires a block's last two dims to be
    # divisible by (8, 128) OR equal to the array's dims.  A (1, f)
    # block over (n, f) fails the sublane rule for any n > 1, so the
    # data rides as (n, 1, f) with (1, 1, f) blocks — both trailing
    # block dims then EQUAL the array dims, with no padding and no
    # copy (the reshape is a view of the same HBM bytes).
    n, f = data.shape
    b = indices.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            # the index map reads the prefetched indices: block row i of
            # the output comes from dataset row indices[i]
            pl.BlockSpec((1, 1, f), lambda i, idx_ref: (jnp.maximum(
                idx_ref[i], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, f), lambda i, idx_ref: (i, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        name="veles_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, f), data.dtype),
        interpret=interpret,
    )(jnp.asarray(indices, jnp.int32), data.reshape(n, 1, f))
    return out.reshape(b, f)
