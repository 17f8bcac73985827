"""Synchronous data parallelism over the device mesh.

The TPU-native replacement for the reference's gradient path (§3.2 of
SURVEY: slaves pull jobs with weights, push updates; master merges).
Here the whole train step is ONE jitted program over the mesh: batch
sharded on ``data``, parameters replicated; XLA turns the gradient
contractions into ``reduce_scatter``/``all_reduce`` over ICI.  The
update happens inside the step, so parameters never leave HBM and no
host master exists on the hot path.

Also provides tensor-parallel param sharding rules (the mesh design
gives TP "for free" — SURVEY §2.4 table) for models whose layers
exceed a chip.

:func:`tp_rules`, :func:`fsdp_rules`, :func:`pp_rules` and
:func:`ep_rules` double as the pod runtime's ``param_rules``
(:class:`veles_tpu.pod.runtime.PodRuntime`): the same per-leaf
PartitionSpec recipes shard the stitched eager trainer's
parameter/solver Vectors when the V-P02 residency estimate says
replication does not fit (or the mesh carries a ``pipe``/``expert``
axis the plan enumerated).
"""

import jax
import numpy
from jax.sharding import NamedSharding, PartitionSpec as P

from veles_tpu.parallel.mesh import replicated


def _params_sharding(params, mesh, rules=None):
    """Sharding pytree for params.  ``rules``: optional callable
    (path-free) mapping a leaf to a PartitionSpec; default replicate."""
    def leaf_sharding(leaf):
        if rules is not None:
            spec = rules(leaf)
            if spec is not None:
                return NamedSharding(mesh, spec)
        return replicated(mesh)
    return jax.tree.map(leaf_sharding, params)


def data_parallel(step_fn, mesh, params_example, donate_params=True,
                  batch_axis="data", param_rules=None):
    """Compile ``step(params, x, labels) -> (params, metrics)`` for the
    mesh: x/labels sharded over ``batch_axis``, params replicated (or
    sharded per ``param_rules`` for TP), metrics replicated.

    The returned callable accepts ordinary (host or single-device)
    arrays; jit moves them according to the shardings.
    """
    p_shard = _params_sharding(params_example, mesh, param_rules)
    x_shard = NamedSharding(mesh, P(batch_axis))
    return jax.jit(
        step_fn,
        in_shardings=(p_shard, x_shard, x_shard),
        out_shardings=(p_shard, replicated(mesh)),
        donate_argnums=(0,) if donate_params else (),
    )


def shard_params(params, mesh, param_rules=None):
    """Place a params pytree onto the mesh eagerly (replicated or per
    rules) — what a restored snapshot does before resuming on a
    different topology (SURVEY §5.4 'resume with different topology')."""
    shardings = _params_sharding(params, mesh, param_rules)
    return jax.tree.map(jax.device_put, params, shardings)


def tp_rules(mesh, axis="model", min_elements=1024):
    """``param_rules`` for Megatron-style tensor parallelism on fused
    znicz stacks: every large-enough weight shards its LAST dimension
    (the neuron/kernel axis — column parallel) over ``axis``, so each
    chip holds and trains 1/axis_size of every layer's neurons; GSPMD
    partitions the matmuls/convs and inserts the all-gathers where an
    activation must be whole (SURVEY §2.4: TP is the mesh design's
    value-add).  Solver slots shard along with their weights because
    :func:`_params_sharding` applies rules per leaf; biases shard the
    same way only when they clear ``min_elements`` — smaller ones
    stay replicated (the collective would cost more than the bytes).
    Combine with ``data_parallel(batch_axis="data")`` for DP×TP."""
    if axis not in mesh.shape:
        raise ValueError(
            "tp_rules: mesh has no %r axis (mesh_axes must include "
            "it, e.g. {'data': d, %r: m})" % (axis, axis))
    size = mesh.shape[axis]

    def rules(leaf):
        shape = numpy.shape(leaf)
        if not shape or \
                int(numpy.prod(shape, initial=1)) < min_elements:
            return None
        if shape[-1] % size == 0 and shape[-1] >= size:
            spec = [None] * len(shape)
            spec[-1] = axis
            return P(*spec)
        return None

    return rules


def fsdp_rules(mesh, axis="data", min_elements=1024):
    """``param_rules`` sharding every large-enough parameter over the
    data axis — ZeRO-3/FSDP storage without new step code: each chip
    holds ``1/axis_size`` of every weight, its momenta, and its solver
    state, and XLA's GSPMD inserts the all-gather before a layer's
    matmul and the reduce-scatter after its gradient.  Use with
    :func:`data_parallel`/:func:`shard_params`; small leaves (biases,
    counters) stay replicated — sharding them would cost more in
    collective latency than the bytes saved.

    Shards the first dimension divisible by the axis size (weights in
    this framework lead with fan-in, which is usually the largest and
    most divisible dim).
    """
    size = mesh.shape[axis]

    def rules(leaf):
        shape = numpy.shape(leaf)
        if int(numpy.prod(shape, initial=1)) < min_elements:
            return None
        for dim, extent in enumerate(shape):
            if extent % size == 0 and extent >= size:
                spec = [None] * len(shape)
                spec[dim] = axis
                return P(*spec)
        return None

    return rules


def pp_rules(mesh, axis="pipe", min_elements=1024):
    """``param_rules`` for pipeline-style STAGE sharding of stacked
    parameters: every large-enough leaf whose LEADING dim divides the
    ``axis`` size shards that dim over it, so each pipeline rank holds
    only its own stages' weights (plus their solver slots, because the
    pod runtime applies rules per leaf).  This is the storage half of
    GPipe-style pipelining — the ``analyze/plan.py`` planners emit the
    matching ``("pipe",)`` spec for scan-stacked blocks; the compute
    half (the microbatch ring) is
    :func:`veles_tpu.parallel.pp.pipeline_apply`, folded inside the
    epoch-scan window by the pod runtime.  Leaves without a
    stage-divisible leading dim (embeddings, output heads, scalars)
    stay replicated.  Combine with a ``data`` axis for DP×PP."""
    if axis not in mesh.shape:
        raise ValueError(
            "pp_rules: mesh has no %r axis (mesh_axes must include "
            "it, e.g. {'data': d, %r: s})" % (axis, axis))
    size = mesh.shape[axis]

    def rules(leaf):
        shape = numpy.shape(leaf)
        if not shape or \
                int(numpy.prod(shape, initial=1)) < min_elements:
            return None
        if shape[0] % size == 0 and shape[0] >= size:
            spec = [None] * len(shape)
            spec[0] = axis
            return P(*spec)
        return None

    return rules


def ep_rules(mesh, axis="expert", min_elements=1024):
    """``param_rules`` for GShard-style expert parallelism: every
    large-enough leaf whose LEADING dim divides the ``axis`` size
    shards that dim over it — MoE parameter stacks lead with the
    expert dim (``w1[E, D, F]``, ``b1[E, F]``, …,
    :func:`veles_tpu.parallel.moe.moe_mlp`), so each expert shard
    holds and trains only its own experts; token routing rides an
    in-program ``all_to_all`` over the same axis.  Shared
    (non-expert) leaves — the router, embeddings — stay replicated.
    Combine with a ``data`` axis for DP×EP."""
    return pp_rules(mesh, axis=axis, min_elements=min_elements)


def data_parallel_epoch(step_fn, mesh, params_example, n_samples,
                        batch, batch_axis="data", param_rules=None):
    """Whole DP epoch in ONE program over the mesh: compose
    :func:`veles_tpu.znicz.fused_graph.epoch_runner` with the
    data-parallel sharding recipe — the resident dataset shards over
    ``batch_axis``, parameters stay replicated (or TP-sharded per
    ``param_rules``), and GSPMD inserts the gather collectives for the
    globally-permuted minibatches plus the gradient all-reduce, all
    inside a single dispatch per epoch.

    This is the distributed counterpart of the reference's
    master-serves-minibatches loop with ZERO host involvement per
    epoch.  The global permutation keeps sampling semantics identical
    to the single-device :func:`epoch_runner` (bit-comparable params),
    at the cost of gather collectives; a per-shard local sampler is
    the bandwidth optimization when the dataset cannot ride ICI.

    Returns ``epoch_fn(params, data, labels, key) -> (params,
    stacked_metrics)`` compiled for the mesh.
    """
    from veles_tpu.znicz.fused_graph import epoch_runner

    epoch_fn = epoch_runner(step_fn, n_samples, batch)
    p_shard = _params_sharding(params_example, mesh, param_rules)
    d_shard = NamedSharding(mesh, P(batch_axis))
    return jax.jit(
        epoch_fn,
        in_shardings=(p_shard, d_shard, d_shard, None),
        out_shardings=(p_shard, replicated(mesh)),
        donate_argnums=(0,))


def data_parallel_epoch_local(step_fn_reduced, mesh, n_local,
                              batch_local, batch_axis="data"):
    """The bandwidth-optimal distributed epoch: each data shard keeps
    its OWN resident dataset slice and samples it locally (the
    distributed-sampler rule) — minibatch data never crosses chips;
    only the gradient ``pmean`` rides ICI.

    ``step_fn_reduced`` must come from
    ``lower_specs(..., grad_reduce_axis=batch_axis)`` so every shard
    applies the identical globally-reduced update — parameters stay in
    lockstep without ever being communicated.  Each shard folds its
    ``axis_index`` into the epoch key, so shards draw disjoint
    permutation streams of their local slices.

    Compare :func:`data_parallel_epoch` (global permutation, identical
    sampling to single-device at the cost of gather collectives).
    Returns ``epoch_fn(params, data, labels, key)`` compiled for the
    mesh; metrics are the globally-reduced per-minibatch values.
    """
    from veles_tpu.znicz.fused_graph import epoch_runner

    epoch_local = epoch_runner(step_fn_reduced, n_local, batch_local)

    def run(params, data_local, labels_local, key):
        shard = jax.lax.axis_index(batch_axis)
        return epoch_local(params, data_local, labels_local,
                           jax.random.fold_in(key, shard))

    sharded = jax.shard_map(
        run, mesh=mesh,
        in_specs=(P(), P(batch_axis), P(batch_axis), P()),
        # params leave replicated BY CONSTRUCTION (pmean'd grads =>
        # identical updates); metrics are globally reduced in-step.
        # check_vma can't see through the collectives, hence False.
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))
