"""Pipeline parallelism: GPipe-style microbatched schedule over a
``pipe`` mesh axis.

Each device owns ONE stage's parameters (leading axis of the stacked
params pytree is sharded over ``pipe``).  A ``lax.scan`` over
``n_micro + n_stages - 1`` ticks moves activations forward around the
ring with ``ppermute``; stage 0 ingests a fresh microbatch each tick,
stage n-1 banks its result.  Differentiable end-to-end (``ppermute``
has a transpose rule), so ``jax.grad`` of a loss over
:func:`pipeline_apply` yields the 1F1B-equivalent backward sweep
scheduled by XLA.

Restriction (GPipe-classic): every stage maps activations of one shape
to the same shape — stack equal-width blocks (the transformer case) or
pad.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _pipeline_local(stage_params, x_stack, stage_fn, axis_name):
    """Per-device body under shard_map.

    stage_params: this stage's params (leading stage axis stripped).
    x_stack: [n_micro, mb, ...] — full input, replicated; only stage 0
    reads it.  Returns [n_micro, mb, ...] — valid on the LAST stage
    (others return zeros; caller slices).
    """
    n = jax.lax.psum(1, axis_name)
    s = jax.lax.axis_index(axis_name)
    # shard_map keeps the sharded stage axis as local size 1 — strip it
    stage_params = jax.tree.map(lambda leaf: leaf[0], stage_params)
    n_micro = x_stack.shape[0]
    act0 = jnp.zeros_like(x_stack[0])
    outs0 = jnp.zeros_like(x_stack)

    def tick(carry, t):
        act, outs = carry
        is_first = (s == 0)
        is_last = (s == n - 1)
        feed = x_stack[jnp.clip(t, 0, n_micro - 1)]
        inp = jnp.where(is_first, feed, act)
        y = stage_fn(stage_params, inp)
        out_idx = t - (n - 1)
        valid = is_last & (out_idx >= 0) & (out_idx < n_micro)
        banked = outs.at[jnp.clip(out_idx, 0, n_micro - 1)].set(y)
        outs = jnp.where(valid, banked, outs)
        act_next = jax.lax.ppermute(
            y, axis_name, [(i, (i + 1) % n) for i in range(n)])
        return (act_next, outs), None

    (act, outs), _ = jax.lax.scan(
        tick, (act0, outs0), jnp.arange(n_micro + n - 1))
    del act
    return outs


def pipeline_apply(stage_fn, stacked_params, x, mesh, n_micro,
                   pipe_axis="pipe", batch_axis=None):
    """Run ``x`` through ``n_stages`` copies of ``stage_fn`` pipelined
    over the mesh's ``pipe`` axis.

    stacked_params: pytree whose leaves have leading dim n_stages.
    x: [batch, ...]; split into ``n_micro`` microbatches.
    Returns stage_{n-1}(…stage_0(x)…) with x's shape.
    """
    n_stages = mesh.shape[pipe_axis]
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] != n_stages:
            raise ValueError(
                "stacked params leading dim %d != %d pipeline stages"
                % (leaf.shape[0], n_stages))
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError("batch %d not divisible by n_micro %d"
                         % (batch, n_micro))
    x_stack = x.reshape((n_micro, batch // n_micro) + x.shape[1:])

    p_spec = jax.tree.map(
        lambda leaf: P(pipe_axis, *([None] * (leaf.ndim - 1))),
        stacked_params)
    data = (batch_axis,) if batch_axis else (None,)
    x_spec = P(None, *data, *([None] * (x.ndim - 2)))
    # every stage returns a full outs buffer; concat over pipe then
    # keep the last stage's block
    out_spec = P(pipe_axis, *data, *([None] * (x.ndim - 2)))

    fn = jax.shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=pipe_axis),
        mesh=mesh, in_specs=(p_spec, x_spec), out_specs=out_spec,
        check_vma=False)
    outs = fn(stacked_params, x_stack)          # [n_stages*n_micro, mb, ...]
    last = outs[(n_stages - 1) * n_micro:]
    return last.reshape(x.shape)
