"""Shared tiling/padding helpers for the kernel substrate."""

import jax.numpy as jnp

def round_up(x, mult):
    return ((x + mult - 1) // mult) * mult


def pad_axis(a, mult, axis, value=0.0):
    """Pad ``axis`` up to a multiple of ``mult`` with ``value``."""
    size = a.shape[axis]
    rem = size % mult
    if rem == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(a, pad, constant_values=value)
