"""Matrix reduction (ref ``ocl/matrix_reduce.cl:1-69``,
``cuda/matrix_reduce.cu``: an include-style template reducing a matrix
along rows or columns with an ``A_COL`` switch and a ``REDUCE_SIZE``
workgroup tree).

On TPU the VPU's (8, 128) lanes make XLA's own reduction codegen
excellent, so the template maps to one fused ``jnp`` reduction that
accumulates in float32.  (A Pallas twin existed until PR 21: no path
turned it on, and its whole-row VMEM scratch did not fit the v5e's
16 MiB scoped VMEM at a float32 (8192, 4096) input.)
"""

import jax.numpy as jnp


def matrix_reduce(a, axis=0, op="sum"):
    """Reduce a 2D matrix along ``axis`` (0: over rows → per-column
    result, like the reference's default; 1: over columns → per-row)."""
    a = jnp.asarray(a)
    fn = {"sum": jnp.sum, "max": jnp.max, "min": jnp.min}[op]
    return fn(a.astype(jnp.float32), axis=axis).astype(a.dtype)
