"""General fused lowering: ANY StandardWorkflow layer stack → one jitted
train step.

Extends :mod:`veles_tpu.znicz.fused` (MLP-specific) to the full layer
zoo: the lowering instantiates the real forward units once to reuse
their shape inference and weight-init logic, then discards the graph and
keeps only (pure_fn, static config, params) triples.  The resulting step
is what AlexNet/CIFAR run under data parallelism — forward, loss,
``jax.grad`` backward and momentum updates in one XLA program.
"""

import functools

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.memory import Vector
from veles_tpu.znicz.gd_base import ortho_grad, reg_term, rprop_update


def _remat_stage(pure, config):
    """Wrap a stage's pure fn in ``jax.checkpoint`` with its static
    config pre-bound; keeps the ``(params, x, **config)`` call shape
    the lowering uses (the passed config is already baked in)."""
    inner = jax.checkpoint(functools.partial(pure, **config))

    def wrapped(params, x, **_config):
        return inner(params, x)

    return wrapped


def _scoped_stage(pure, scope):
    """``pure`` under ``jax.named_scope(scope)``: the stage's device
    operations carry ``veles.layer.<nn>.<kind>`` in their ``op_name``
    (and, under ``value_and_grad``, its backward ones
    ``transpose(jvp(veles.layer.<nn>.<kind>))``), whichever of
    ``loss_fn``, ``apply_fn`` and the eval path calls it.  Metadata
    only: the program XLA builds is the same."""
    return jax.named_scope(scope)(pure)


def default_lr(solver):
    """The canonical learning rate when a spec omits it — adadelta's
    update is self-scaling, so its lr is a plain 1.0 gain.  The ONE
    place this rule lives (rollback_to reads it too)."""
    return 1.0 if str(solver) == "adadelta" else 0.01


def probe_units(layer_specs, sample_shape):
    """Instantiate + host-initialize one probe unit per layer spec:
    numpy weight init, spec ``init`` weights injected, each unit's
    ``output`` feeding the next unit's ``input`` — no jit, no device
    buffers.  The construction half of :func:`lower_specs`, shared
    with the static analyzer (:mod:`veles_tpu.analyze.shapes`) so spec
    lowering and spec analysis can never diverge.  Raises on a broken
    spec."""
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.units import UnitRegistry
    from veles_tpu.znicz import (  # noqa: F401 - populate the registry
        activation, all2all, conv, misc_units, normalization_units,
        pooling, rnn)

    wf = DummyWorkflow()
    probe = Vector(numpy.zeros((2,) + tuple(sample_shape),
                               numpy.float32))
    units = []
    for spec in layer_specs:
        klass = UnitRegistry.mapped[spec["type"]]
        unit = klass(wf, **dict(spec.get("->", {})))
        unit.input = probe
        unit.initialize(device=None)
        init = spec.get("init")
        if init:
            unit.weights.reset(init["weights"])
            if "bias" in init and unit.bias:
                unit.bias.reset(init["bias"])
        probe = unit.output
        units.append(unit)
    return units


def lower_specs(layer_specs, sample_shape, loss="softmax",
                compute_dtype=None, remat=False, grad_accum=1,
                lr_adjuster=None, input_norm=None,
                grad_reduce_axis=None):
    """Build (params, step_fn, eval_fn, apply_fn) from layer specs.

    ``sample_shape``: one sample's shape (no batch dim).
    ``input_norm=(scale, shift)``: affine normalization applied INSIDE
    the jitted program (fused by XLA into the first layer's read), so
    the batch may arrive in its native storage dtype — e.g. uint8
    pixels resident in HBM, quartering the bytes of the tensor an
    HBM-bound step reads twice (forward + weight gradient).  The
    TPU-first counterpart of the reference's device-resident fullbatch
    data (``loader/fullbatch.py:79``); scale/shift may be scalars or
    per-feature arrays broadcastable against ``sample_shape``.
    ``compute_dtype``: optional forward/backward compute dtype (e.g.
    ``jnp.bfloat16`` — the MXU-native mixed-precision mode: bf16
    activations/weights in the matmuls/convs, fp32 accumulation via
    ``preferred_element_type``, fp32 master weights + momentum).
    ``remat``: rematerialize layer activations in the backward pass
    (``jax.checkpoint`` around each layer) — trades one extra forward
    per layer for not holding its activations in HBM, the standard
    lever when deep stacks / long sequences outgrow the chip.  ``True``
    applies to every layer; a per-layer ``{"remat": True}`` spec key
    selects individually.

    Per-layer update rule via the ``<-`` key ``solver``: ``momentum``
    (default, the reference's SGD+momentum), ``adam`` (decoupled
    weight decay; ``adam_beta1/beta2/epsilon``), ``adagrad`` /
    ``adadelta`` (the reference's documented solver knobs
    ``adagrad_epsilon`` / ``adadelta_momentum`` / ``adadelta_epsilon``;
    run adadelta with ``learning_rate`` 1.0), or ``rprop`` (iRprop−
    with the same knobs as :class:`veles_tpu.znicz.gd_base.GDRProp`) —
    the whole rule runs inside the one fused XLA program either way.
    Regularization: ``weights_decay`` with the ``l1_vs_l2`` mix and the
    ``factor_ortho`` soft-orthogonality term apply across solvers.

    ``grad_accum``: the reference's ``accumulate_gradient`` — split the
    batch into N microbatches scanned inside the step (activation HBM ∝
    batch/N), average their gradients, apply ONE update.  Combine with
    ``remat`` for the deepest memory cuts.

    ``lr_adjuster``: the reference's LRAdjuster
    (``manualrst_veles_workflow_parameters.rst:655-685``), evaluated
    INSIDE the jitted step: ``{"lr_policy_name": "exp" | "fixed" |
    "step_exp" | "inv" | "arbitrary_step", "lr_parameters": {...},
    "bias_lr_policy_name": ..., "bias_lr_parameters": ...}``.  An int32
    ``tick`` carried in each layer's state drives the schedule, so the
    learning rate changes every step with NO retrace (bias policy
    defaults to the weights policy).
    """
    grad_accum = max(int(grad_accum), 1)
    w_policy = b_policy = None
    if lr_adjuster:
        from veles_tpu.znicz.lr_adjust import make_policy
        w_policy = make_policy(lr_adjuster.get("lr_policy_name",
                                               "fixed"),
                               lr_adjuster.get("lr_parameters"))
        b_policy = make_policy(
            lr_adjuster.get("bias_lr_policy_name",
                            lr_adjuster.get("lr_policy_name",
                                            "fixed")),
            lr_adjuster.get("bias_lr_parameters",
                            lr_adjuster.get("lr_parameters")))
    units = probe_units(layer_specs, sample_shape)
    stages = []      # (pure_fn, config_dict, hyper_dict, skip_at_eval)
    params = []
    for index, (spec, unit) in enumerate(zip(layer_specs, units)):
        layer_params = unit.pure_params(host=True)
        layer_params = {k: numpy.array(v) for k, v in
                        layer_params.items()}
        bw = spec.get("<-", {})
        solver = str(bw.get("solver", "momentum"))
        if solver not in ("momentum", "adam", "rprop", "adagrad",
                          "adadelta"):
            raise ValueError("unknown solver %r (want momentum / adam "
                             "/ rprop / adagrad / adadelta)" % solver)
        if w_policy is not None and solver == "rprop":
            raise ValueError(
                "lr_adjuster has no effect on the rprop solver (its "
                "per-weight deltas are self-adaptive) — remove the "
                "schedule or pick another solver for this layer")
        lr = float(bw.get("learning_rate", default_lr(solver)))
        hyper = {
            "solver": solver,
            "lr": lr, "lr_b": float(bw.get("learning_rate_bias", lr)),
            "decay": float(bw.get("weights_decay", 0.0)),
            "decay_b": float(bw.get("weights_decay_bias", 0.0)),
            "moment": float(bw.get("gradient_moment", 0.0)),
            "moment_b": float(bw.get("gradient_moment_bias",
                                     bw.get("gradient_moment", 0.0))),
            # regularization (ref docs :559-566): L1/L2 mix + soft
            # orthogonality on the flattened weight
            "l1": float(bw.get("l1_vs_l2", 0.0)),
            "l1_b": float(bw.get("l1_vs_l2_bias",
                                 bw.get("l1_vs_l2", 0.0))),
            "factor_ortho": float(bw.get("factor_ortho", 0.0)),
            # adam
            "beta1": float(bw.get("adam_beta1", 0.9)),
            "beta2": float(bw.get("adam_beta2", 0.999)),
            "eps": float(bw.get("adam_epsilon", 1e-8)),
            # adagrad / adadelta (ref docs list their knobs among the
            # backward parameters: adagrad_epsilon, adadelta_momentum,
            # adadelta_epsilon)
            "adagrad_eps": float(bw.get("adagrad_epsilon", 1e-6)),
            "adadelta_rho": float(bw.get("adadelta_momentum", 0.9)),
            "adadelta_eps": float(bw.get("adadelta_epsilon", 1e-6)),
            # rprop (iRprop−, same knobs as znicz.gd_base.GDRProp)
            "delta_init": float(bw.get("rprop_delta_init", 0.1)),
            "eta_plus": float(bw.get("rprop_eta_plus", 1.2)),
            "eta_minus": float(bw.get("rprop_eta_minus", 0.5)),
            "delta_min": float(bw.get("rprop_delta_min", 1e-6)),
            "delta_max": float(bw.get("rprop_delta_max", 50.0)),
        }
        pure = type(unit).pure
        if spec.get("remat", remat):
            # static config is bound BEFORE checkpointing so the
            # rematerialized callable is (params, x) -> out
            pure = _remat_stage(pure, unit.pure_config())
        pure = _scoped_stage(pure, "veles.layer.%02d.%s"
                             % (index, spec["type"]))
        stages.append((pure, unit.pure_config(), hyper,
                       bool(getattr(type(unit), "SKIP_AT_EVAL", False))))
        state = {k: v for k, v in layer_params.items()}

        def _slot(key):
            if key not in state or state[key] is None:
                return None
            if solver == "rprop":
                # stacked [per-weight step sizes, previous signs]
                s = numpy.zeros((2,) + state[key].shape,
                                numpy.float32)
                s[0] = hyper["delta_init"]
                return s
            return numpy.zeros_like(state[key])

        # vw/vb: momentum velocity, adam first moment, adadelta E[Δ²],
        # rprop stacked state — adagrad needs no first slot
        state["vw"], state["vb"] = (
            (None, None) if solver == "adagrad"
            else (_slot("w"), _slot("b")))
        if solver in ("adam", "adagrad", "adadelta"):
            # squared-gradient accumulators
            state["sw"], state["sb"] = _slot("w"), _slot("b")
        if solver == "adam":
            state["t"] = numpy.int32(0)   # bias-correction counter
        if w_policy is not None and (state.get("w") is not None
                                     or state.get("b") is not None):
            # lr-schedule step counter (only when a schedule is
            # configured: keeps existing snapshots' tree structure)
            state["tick"] = numpy.int32(0)
        if "seed" in state:
            # fresh per-stage stream; step_fn then advances it every
            # step so fused dropout/stochastic-pooling masks differ
            # across iterations (the eager path draws per run() instead)
            from veles_tpu import prng
            state["seed"] = numpy.int32(
                prng.get("dropout").randint(0, 2 ** 30))
        params.append(state)

    @jax.named_scope("veles.ingest")
    def _ingest(x):
        """Entry cast + optional fused affine normalization (see
        ``input_norm`` in the docstring)."""
        h = x
        if jnp.issubdtype(h.dtype, jnp.integer):
            h = h.astype(compute_dtype or jnp.float32)
        if input_norm is not None:
            scale, shift = input_norm
            h = h * jnp.asarray(scale, h.dtype) \
                + jnp.asarray(shift, h.dtype)
        return h

    def apply_fn(params_list, x, train=False):
        h = _ingest(x)
        for (pure, config, _hyper, skip_at_eval), state in zip(
                stages, params_list):
            if skip_at_eval and not train:
                # the unit declares itself identity at inference
                # (e.g. inverted dropout) via SKIP_AT_EVAL — an explicit
                # class attribute, not introspection of config keys
                continue
            # float weights follow the activation stream's dtype: an
            # integer (native-dtype resident) input is ingested in the
            # compute dtype, and lax.conv/dot refuse mixed bf16 x f32
            # operands (quantized {"q", "scale"} leaves pass untouched)
            p = {k: (v.astype(h.dtype)
                     if k != "seed" and hasattr(v, "dtype")
                     and jnp.issubdtype(v.dtype, jnp.floating) else v)
                 for k, v in state.items() if k in ("w", "b", "seed")}
            h = pure(p, h, **config)
        return h

    def loss_fn(wb_list, aux_list, x, labels):
        h = _ingest(x)
        if compute_dtype is not None:
            h = jnp.asarray(h, compute_dtype)
        for (pure, config, _hyper, _skip), wb, aux in zip(stages, wb_list,
                                                          aux_list):
            if compute_dtype is not None:
                p = {k: jnp.asarray(v, compute_dtype)
                     for k, v in wb.items()}
            else:
                p = dict(wb)
            p.update(aux)
            h = pure(p, h, **config)
        return loss_of(h, x, labels)

    @jax.named_scope("veles.loss")
    def loss_of(h, x, labels):
        out = jnp.asarray(h, jnp.float32)
        valid = labels >= 0 if loss == "softmax" \
            else jnp.ones(x.shape[0], bool)
        grad_denom = x.shape[0]
        if loss == "softmax":
            logp = jnp.log(jnp.maximum(out, 1e-30))
            picked = jnp.take_along_axis(
                logp, jnp.maximum(labels, 0)[:, None], axis=1)[:, 0]
            total = -(picked * valid).sum()
            n_err = ((jnp.argmax(out, axis=1) != labels) & valid).sum()
        else:
            flat = out.reshape(out.shape[0], -1)
            target = labels.reshape(flat.shape)
            total = ((flat - target) ** 2).mean(axis=1).sum() / 2
            n_err = jnp.sqrt(((flat - target) ** 2).mean())
        return total / grad_denom, (n_err, total /
                                    jnp.maximum(valid.sum(), 1))

    def step_fn(params_list, x, labels):
        wb_list = tuple({k: s[k] for k in ("w", "b") if s.get(k)
                         is not None} for s in params_list)
        aux_list = tuple({k: s[k] for k in ("seed",) if k in s}
                         for s in params_list)
        if grad_accum == 1:
            (_v, (n_err, report)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(wb_list, aux_list, x, labels)
        else:
            # the reference's accumulate_gradient, TPU-first: the batch
            # is split into grad_accum microbatches scanned INSIDE the
            # step — activations exist for one microbatch at a time
            # (HBM ∝ B/grad_accum), gradients average across chunks,
            # ONE solver update applies at the end
            batch = x.shape[0]
            if batch % grad_accum:
                raise ValueError(
                    "batch %d not divisible by grad_accum %d"
                    % (batch, grad_accum))
            xs = x.reshape((grad_accum, batch // grad_accum)
                           + x.shape[1:])
            ls = labels.reshape((grad_accum, batch // grad_accum)
                                + labels.shape[1:])

            def body(carry, chunk):
                acc, err_acc, loss_acc = carry
                idx, cx, cl = chunk
                # each microbatch draws DISTINCT dropout/stochastic-
                # pool masks: fold the chunk index into every stage
                # seed (golden-ratio-style odd stride keeps the
                # streams disjoint from the +1 per-step seed advance)
                aux_i = tuple(
                    {k: (jnp.int32(
                        (v + idx * jnp.int32(0x3504f325))
                        & 0x3fffffff) if k == "seed" else v)
                     for k, v in aux.items()}
                    for aux in aux_list)
                (_v, (n_err_c, report_c)), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(wb_list, aux_i, cx, cl)
                acc = jax.tree.map(jnp.add, acc, g)
                # float carry: softmax n_err is an int count, mse's is
                # an RMSE — float accumulates both
                return (acc, err_acc + n_err_c.astype(jnp.float32),
                        loss_acc + report_c.astype(jnp.float32)), None

            zeros = jax.tree.map(jnp.zeros_like, wb_list)
            (gsum, n_err, loss_sum), _ = jax.lax.scan(
                body, (zeros, jnp.float32(0.0), jnp.float32(0.0)),
                (jnp.arange(grad_accum, dtype=jnp.int32), xs, ls))
            grads = jax.tree.map(lambda g: g / grad_accum, gsum)
            report = loss_sum / grad_accum
            if loss == "mse":
                # each chunk's "n_err" is an RMSE: average, don't sum
                # (softmax error COUNTS do sum)
                n_err = n_err / grad_accum
        if grad_reduce_axis is not None:
            # explicit-collective data parallelism (the shard_map
            # path, e.g. parallel/dp.data_parallel_epoch_local): mean
            # the per-shard mean-gradients — equal shard batches make
            # that the global-batch gradient — and reduce the metrics
            # so every shard applies the identical update and reports
            # global numbers (softmax n_err is a count -> psum; mse's
            # is an RMSE -> pmean; the loss report is a mean -> pmean)
            grads = jax.lax.pmean(grads, grad_reduce_axis)
            report = jax.lax.pmean(report, grad_reduce_axis)
            n_err = (jax.lax.psum(n_err, grad_reduce_axis)
                     if loss == "softmax"
                     else jax.lax.pmean(n_err, grad_reduce_axis))
        return update_fn(params_list, grads), {"loss": report,
                                               "n_err": n_err}

    @jax.named_scope("veles.update")
    def update_fn(params_list, grads):
        """The solver section of ``step_fn``, under a scope of its own
        (XLA fuses most of it into the weight-gradient fusions)."""
        new_list = []
        for state, gwb, (_pure, _config, hyper, _skip) in zip(
                params_list, grads, stages):
            new_state = dict(state)
            if hyper["solver"] == "adam" and (
                    state.get("w") is not None
                    or state.get("b") is not None):
                new_state["t"] = state["t"] + 1
            for key, vkey, skey, lr_k, dec_k, mom_k in (
                    ("w", "vw", "sw", "lr", "decay", "moment"),
                    ("b", "vb", "sb", "lr_b", "decay_b", "moment_b")):
                if key not in gwb or state.get(key) is None:
                    continue
                grad = gwb[key]
                lr_eff = hyper[lr_k]
                if "tick" in state:
                    # the LRAdjuster schedule, traced on the in-state
                    # step counter — lr changes per step, no retrace
                    pol = w_policy if key == "w" else b_policy
                    lr_eff = lr_eff * pol(state["tick"], xp=jnp)
                l1 = hyper["l1"] if key == "w" else hyper["l1_b"]
                if key == "w" and hyper["factor_ortho"]:
                    grad = grad + ortho_grad(state[key],
                                             hyper["factor_ortho"])
                if hyper["solver"] == "momentum":
                    v = hyper[mom_k] * state[vkey] - lr_eff * (
                        grad + reg_term(state[key], hyper[dec_k], l1))
                    new_state[key] = state[key] + v
                    new_state[vkey] = v
                elif hyper["solver"] == "adagrad":
                    g = grad + reg_term(state[key], hyper[dec_k], l1)
                    s2 = state[skey] + g * g
                    new_state[key] = state[key] - lr_eff * g / (
                        jnp.sqrt(s2) + hyper["adagrad_eps"])
                    new_state[skey] = s2
                elif hyper["solver"] == "adadelta":
                    rho = hyper["adadelta_rho"]
                    eps = hyper["adadelta_eps"]
                    g = grad + reg_term(state[key], hyper[dec_k], l1)
                    s2 = rho * state[skey] + (1.0 - rho) * g * g
                    upd = -jnp.sqrt(state[vkey] + eps) \
                        / jnp.sqrt(s2 + eps) * g
                    # vw accumulates E[Δ²]; conventionally run with
                    # learning_rate=1.0 (the lr is a plain scale here)
                    new_state[key] = state[key] + lr_eff * upd
                    new_state[vkey] = rho * state[vkey] \
                        + (1.0 - rho) * upd * upd
                    new_state[skey] = s2
                elif hyper["solver"] == "adam":
                    t = new_state["t"].astype(jnp.float32)
                    m = hyper["beta1"] * state[vkey] \
                        + (1.0 - hyper["beta1"]) * grad
                    s2 = hyper["beta2"] * state[skey] \
                        + (1.0 - hyper["beta2"]) * grad * grad
                    m_hat = m / (1.0 - hyper["beta1"] ** t)
                    s_hat = s2 / (1.0 - hyper["beta2"] ** t)
                    step = m_hat / (jnp.sqrt(s_hat) + hyper["eps"])
                    # decoupled (AdamW-style) weight decay, l1/l2 mix
                    new_state[key] = state[key] - lr_eff * (
                        step + reg_term(state[key], hyper[dec_k], l1))
                    new_state[vkey], new_state[skey] = m, s2
                else:                           # iRprop−
                    g = grad + reg_term(state[key], hyper[dec_k], l1)
                    new_state[key], new_state[vkey] = rprop_update(
                        state[key], state[vkey], g,
                        hyper["eta_plus"], hyper["eta_minus"],
                        hyper["delta_min"], hyper["delta_max"])
            if "seed" in state:
                # advance the stage's mask stream (int32, wrap-safe)
                new_state["seed"] = jnp.int32(
                    (state["seed"] + 1) & 0x3fffffff)
            if "tick" in state:
                new_state["tick"] = state["tick"] + jnp.int32(1)
            new_list.append(new_state)
        return new_list

    def eval_fn(params_list, x, labels):
        out = apply_fn(params_list, x, train=False)
        if loss == "softmax":
            valid = labels >= 0
            n_err = ((jnp.argmax(out, axis=1) != labels) & valid).sum()
            return {"n_err": n_err, "n": valid.sum()}
        flat = out.reshape(out.shape[0], -1)
        return {"rmse": jnp.sqrt(
            ((flat - labels.reshape(flat.shape)) ** 2).mean())}

    return params, step_fn, eval_fn, apply_fn


def epoch_runner(step_fn, n_samples, batch, shuffle=True):
    """Whole epoch in ONE XLA program: ``lax.scan`` over permuted
    minibatches gathered from the DEVICE-RESIDENT dataset inside the
    program.

    The TPU-first answer to the reference's host-driven minibatch loop
    (``veles/loader/base.py`` serves each minibatch from the master
    process): with the dataset already in HBM (FullBatchLoader) the
    epoch needs no host round-trips at all — device-PRNG permutation,
    gather, in-step normalization, train step and metric stacking all
    live in one program, so epoch throughput matches the
    synthetic-batch line even where per-dispatch host overhead would
    dominate a host-driven loop.

    ``step_fn``: the ``(params, x, labels) -> (params, metrics)``
    program from :func:`lower_specs` (in-step ``input_norm`` welcome —
    the gathered minibatch arrives in storage dtype, e.g. u8 pixels).
    Returns ``epoch_fn(params, data, labels, key) -> (params,
    stacked_metrics)``; the short tail (< batch samples) is dropped,
    the fused trainer's short-tail rule.
    """
    steps = n_samples // batch
    if steps == 0:
        raise ValueError("dataset smaller than one minibatch")

    def epoch_fn(params, data, labels, key):
        # shuffle=False: sequential (coalesced) minibatches — not for
        # training (no sampling), but the A/B that isolates the cost
        # of PERMUTED gather locality from the scan/step itself
        perm = jax.random.permutation(key, n_samples) if shuffle \
            else jnp.arange(n_samples)
        idx = perm[: steps * batch].reshape(steps, batch)

        def body(p, batch_idx):
            # take_rows: the same gather as the host-driven loader
            # path (ops/gather.py)
            from veles_tpu.ops.gather import take_rows
            return step_fn(p, take_rows(data, batch_idx),
                           take_rows(labels, batch_idx))

        return jax.lax.scan(body, params, idx)

    return epoch_fn
