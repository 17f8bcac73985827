"""L0 kernel substrate: Pallas TPU kernels + jnp fallbacks.

The TPU-native re-design of the reference's shared kernel templates
(``ocl/`` + ``cuda/``, SURVEY §2.2):

=====================  ==========================================
reference template      this package
=====================  ==========================================
matrix_multiplication   :mod:`veles_tpu.ops.gemm` (Pallas tiled)
matrix_reduce           :mod:`veles_tpu.ops.reduce`
random (xorshift1024*)  :mod:`veles_tpu.ops.random` (TPU PRNG)
fullbatch_loader        :mod:`veles_tpu.ops.gather` (XLA's gather
                        over a rows-major device form; no kernel)
mean_disp_normalizer    :mod:`veles_tpu.ops.normalize`
join.jcl (Jinja2)       :mod:`veles_tpu.ops.join`
benchmark               :mod:`veles_tpu.ops.benchmark`
(none: expert layers)   :mod:`veles_tpu.ops.grouped` (rows sorted
                        by group x one matrix a group)
=====================  ==========================================

An op with a Pallas TPU kernel for the hot path also has a pure jnp
twin that XLA fuses — used on CPU, under interpret mode, and as the
golden reference in tests; dispatch between the two is by the current
JAX default platform unless forced via ``use_pallas=``.
"""

from veles_tpu.ops.gemm import matmul  # noqa: F401
from veles_tpu.ops.reduce import matrix_reduce  # noqa: F401
from veles_tpu.ops.random import uniform, normal  # noqa: F401
from veles_tpu.ops.gather import take_rows  # noqa: F401
from veles_tpu.ops.normalize import mean_disp_normalize  # noqa: F401
from veles_tpu.ops.join import join  # noqa: F401


def on_tpu():
    """True when the default JAX backend is a TPU."""
    import jax
    return jax.default_backend() == "tpu"


def resolved_backend(family, dtype, shape):
    """``"pallas"`` or ``"xla"``: what AUTO dispatch resolves a kernel
    family to for one call, on this process's device and ratings DB —
    the question a smoke or a benchmark record asks so that it never
    implies a kernel ran when the DB routed the family to XLA.

    ``family`` / ``shape``: ``gemm`` and ``gemm_int8`` (m, k, n);
    ``gd`` (batch, fan_in, neurons); ``flash_attention``,
    ``flash_attention_bwd`` and ``chunk_attention`` (b, s, h, d);
    ``decode_attention`` (decode, verify and their paged
    twins: Pallas on the TPU, no DB entry consulted)."""
    import jax.numpy as jnp
    from veles_tpu.ops import attention, gemm, qgemm
    dtype = jnp.dtype(dtype)
    if family == "gemm":
        pallas = gemm._dispatch(None, None, dtype, tuple(shape))[0]
    elif family == "gemm_int8":
        pallas = qgemm._dispatch(None, None, dtype, tuple(shape))[0]
    elif family == "gd":
        pallas = gemm.gd_kernel_choice(dtype, tuple(shape))[0] == "pallas"
    elif family in ("flash_attention", "chunk_attention"):
        pallas = attention._resolve_backend(None, dtype, tuple(shape))
    elif family == "flash_attention_bwd":
        pallas = attention._resolve_bwd(None, None, None, dtype,
                                        tuple(shape))[0]
    elif family == "decode_attention":
        pallas = on_tpu()
    else:
        raise ValueError("unknown kernel family %r" % (family,))
    return "pallas" if pallas else "xla"
