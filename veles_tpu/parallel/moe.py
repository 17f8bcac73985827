"""Expert parallelism: a mixture-of-experts MLP over an ``expert`` mesh
axis.

Top-1 (switch-style) routing: a learned router scores each token, the
token's FFN runs on whichever device holds its expert.  Tokens travel by
``all_to_all`` — the EP analogue of the TP all-reduce — with a static
per-expert capacity (XLA needs static shapes; overflow tokens are
dropped and pass through the residual, the standard switch-transformer
behavior).

Composes with DP (batch axis) the usual way; the expert axis can alias
the ``model`` axis on small meshes.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _one_hot_capacity(expert_idx, n_experts, capacity):
    """Position of each token within its expert's capacity buffer, or
    ``capacity`` (=drop) on overflow.  [T] → (slot [T], keep [T])."""
    onehot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)
    # rank of the token among same-expert tokens, in order
    ranks = (jnp.cumsum(onehot, axis=0) - 1)
    slot = jnp.take_along_axis(
        ranks, expert_idx[:, None], axis=1)[:, 0]
    keep = slot < capacity
    return jnp.where(keep, slot, capacity), keep


def _moe_local(x, router_w, w1, b1, w2, b2, axis_name, capacity_factor):
    """Per-device body: x [T_local, D]; each device holds ONE expert
    shard's FFN params (leading expert axis of size n_local)."""
    n_exp = jax.lax.psum(1, axis_name) * w1.shape[0]
    n_dev = jax.lax.psum(1, axis_name)
    exp_per_dev = w1.shape[0]
    tokens = x.shape[0]
    capacity = max(1, int(capacity_factor * tokens / n_exp))

    scores = x @ router_w                                  # [T, E]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(
        probs, expert_idx[:, None], axis=1)[:, 0]
    slot, keep = _one_hot_capacity(expert_idx, n_exp, capacity)

    # scatter tokens into [n_exp, capacity, D] send buffer
    buf = jnp.zeros((n_exp, capacity + 1, x.shape[1]), x.dtype)
    buf = buf.at[expert_idx, slot].set(
        jnp.where(keep[:, None], x, 0.0))
    buf = buf[:, :capacity]                                # drop overflow
    # ship: all_to_all over devices (split/concat both on the leading
    # device axis: send piece i to device i, receive stacked by source)
    buf = buf.reshape(n_dev, exp_per_dev, capacity, x.shape[1])
    recv = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    # recv [n_dev(source), exp_per_dev, cap, D] → merge sources into
    # the expert batch
    recv = jnp.moveaxis(recv, 0, 1).reshape(
        exp_per_dev, n_dev * capacity, x.shape[1])
    # expert FFN (batched over local experts)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edf->ecf", recv, w1,
                   preferred_element_type=jnp.float32) + b1[:, None])
    out = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), w2,
                     preferred_element_type=jnp.float32) \
        .astype(x.dtype) + b2[:, None]
    # ship results back: un-merge sources, inverse all_to_all
    out = out.reshape(exp_per_dev, n_dev, capacity, x.shape[1])
    out = jnp.moveaxis(out, 1, 0)       # [n_dev(dest), exp_per_dev, …]
    back = jax.lax.all_to_all(out, axis_name, split_axis=0,
                              concat_axis=0, tiled=False)
    # axis0 = device that processed = expert's home → global expert id
    back = back.reshape(n_exp, capacity, x.shape[1])
    # gather each token's result from its (expert, slot)
    safe_slot = jnp.minimum(slot, capacity - 1)
    y = back[expert_idx, safe_slot]
    y = jnp.where(keep[:, None], y * gate[:, None].astype(x.dtype), 0.0)
    return y


def moe_mlp(x, params, mesh, expert_axis="model", batch_axis="data",
            capacity_factor=2.0):
    """Expert-parallel switch-MLP.

    x [B, T, D] (B on ``batch_axis``); params:
      router [D, E], w1 [E, D, F], b1 [E, F], w2 [E, F, D], b2 [E, D]
    with E divisible by the expert axis size.  Returns [B, T, D]
    (residual NOT added — caller adds).
    """
    n_dev = mesh.shape[expert_axis]
    n_exp = params["w1"].shape[0]
    if n_exp % n_dev:
        raise ValueError("experts %d not divisible by axis %d"
                         % (n_exp, n_dev))
    B, T, D = x.shape
    if T % n_dev:
        raise ValueError("sequence %d not divisible by expert axis %d"
                         % (T, n_dev))

    def body(x2d, router_w, w1, b1, w2, b2):
        flat = x2d.reshape(-1, D)
        y = _moe_local(flat, router_w, w1, b1, w2, b2,
                       axis_name=expert_axis,
                       capacity_factor=capacity_factor)
        return y.reshape(x2d.shape)

    espec = P(expert_axis)
    # tokens are sharded over the expert axis too (sequence dim) —
    # replicating them would make every expert device route and ship
    # n_dev identical copies
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axis, expert_axis, None), P(None, None),
                  espec, espec, espec, espec),
        out_specs=P(batch_axis, expert_axis, None),
        check_vma=False)
    return fn(x, params["router"], params["w1"], params["b1"],
              params["w2"], params["b2"])


def moe_reference(x, params):
    """Dense single-device reference: every token through its argmax
    expert with no capacity limit."""
    B, T, D = x.shape
    flat = x.reshape(-1, D)
    probs = jax.nn.softmax(
        (flat @ params["router"]).astype(jnp.float32), axis=-1)
    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
    h = jax.nn.gelu(
        jnp.einsum("td,edf->tef", flat, params["w1"]) + params["b1"])
    y_all = jnp.einsum("tef,efd->ted", h, params["w2"]) + params["b2"]
    y = jnp.take_along_axis(
        y_all, idx[:, None, None].repeat(D, 2), axis=1)[:, 0]
    return (y * gate[:, None]).reshape(B, T, D).astype(x.dtype)
