"""Device-side random fills.

Parity target: the reference's xorshift1024* kernels
(``ocl/random.cl:1-125``, ``cuda/random.cu:1-128``) which stream uniform
bits from persistent per-thread states, consumed by
``veles/prng/uniform.py:49`` for dropout masks and stochastic pooling.

TPU re-design: *counter-based* generation — each call derives its stream
from (seed, counter) instead of mutating device state, so results are
reproducible under jit/vmap/pjit and across topology changes (the hard
part flagged in SURVEY §7).  Two paths:

* ``uniform``/``normal`` — ``jax.random`` (threefry), the default;
* ``uniform_pallas`` — the TPU core PRNG (``pltpu.prng_seed`` +
  ``prng_random_bits``) for in-kernel mask generation where a separate
  threefry pass would cost an HBM round-trip (dropout fuses this way).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def uniform(key, shape, dtype=jnp.float32, low=0.0, high=1.0):
    return jax.random.uniform(key, shape, dtype=dtype, minval=low,
                              maxval=high)


def normal(key, shape, dtype=jnp.float32, mean=0.0, stddev=1.0):
    return jax.random.normal(key, shape, dtype=dtype) * stddev + mean


def _uniform_kernel(seed_ref, o_ref, *, low, high):
    # Distinct stream per grid cell: golden-ratio hash of the program id
    # keeps (seed, block) pairs from colliding across *consecutive* seeds
    # the way plain ``seed + i`` would.  All in int32: the multiply
    # wraps to the same bits as the uint32 product, and Mosaic has no
    # scalar bitcast (0x9E3779B9 is this constant, signed).
    pltpu.prng_seed((pl.program_id(0) * jnp.int32(-1640531527))
                    ^ seed_ref[0])
    bits = pltpu.prng_random_bits(o_ref.shape)      # int32
    # 24 high bits → [0, 1) float32 (the reference maps its 64-bit output
    # the same way, ocl/random.cl:96-110); the logical shift leaves a
    # non-negative int32 the VPU converts exactly
    u01 = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) \
        * (1.0 / (1 << 24))
    o_ref[:] = (u01 * (high - low) + low).astype(o_ref.dtype)


def uniform_pallas(seed, shape, dtype=jnp.float32, low=0.0, high=1.0):
    """Uniform fill via the TPU hardware PRNG.  ``seed`` is an int32
    scalar; same (seed, shape) → same bits.

    TPU only: the hardware PRNG has no interpret-mode lowering (the
    Pallas interpreter returns zeros), so off the TPU this raises
    instead of handing back bits from another generator — use
    :func:`uniform` there."""
    from veles_tpu.ops import on_tpu
    if not on_tpu():
        raise NotImplementedError(
            "uniform_pallas needs the TPU core PRNG and this process "
            "runs on %r; use ops.random.uniform (threefry) instead"
            % jax.default_backend())
    return _uniform_pallas_tpu(seed, shape, dtype, low, high)


@functools.partial(jax.jit,
                   static_argnames=("shape", "dtype", "low", "high"))
def _uniform_pallas_tpu(seed, shape, dtype=jnp.float32, low=0.0,
                        high=1.0):
    if len(shape) == 1:
        shape2 = (1, shape[0])
    else:
        shape2 = shape
    rows = max(1, shape2[0] // 512)
    bm = shape2[0] // rows if shape2[0] % rows == 0 else shape2[0]
    rows = shape2[0] // bm
    out = pl.pallas_call(
        functools.partial(_uniform_kernel, low=low, high=high),
        name="veles_uniform",
        grid=(rows,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((bm,) + shape2[1:],
                               lambda i: (i,) + (0,) * (len(shape2) - 1)),
        out_shape=jax.ShapeDtypeStruct(shape2, dtype),
    )(jnp.asarray(seed, jnp.int32).reshape(1))
    return out.reshape(shape)


def dropout_mask(key, shape, keep_prob, dtype=jnp.float32):
    """Inverted-dropout multiplier: 0 with prob (1-keep), else 1/keep
    (ref Znicz ``dropout.DropoutForward`` semantics)."""
    keep = jax.random.bernoulli(key, keep_prob, shape)
    return keep.astype(dtype) / jnp.asarray(keep_prob, dtype)
