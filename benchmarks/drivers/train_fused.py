"""Driver of the fused training path: ``StandardWorkflow(fused=True)``
over a device-resident ``FullBatchLoader``, as ``chip_smoke.py``'s
``phase_alexnet_train`` proved it on the chip.

ONE ``wf.run()`` call holds set-up's first steps and the window: the
driver wraps ``trainer.run`` (an instance attribute), lets the compiled
step and evaluation programs run ``warm_steps`` train minibatches, opens
the window at a minibatch boundary, counts images, and stops the
workflow at the first boundary after ``--seconds``.  Both edges of the
window are taken after ONE wait on the trainer's parameters, so the
images counted are of steps the device has finished, however the
program dispatches them.  The first three train steps are the ones the
plain reference follows afterwards.
"""

import time

import numpy

TRAIN = 2
#: the first train steps that the plain reference follows
FOLLOW_STEPS = 3


def program_layers(config):
    """The configuration's layers in the program's spec format."""
    solver = dict(config["solver"])
    specs = []
    for layer in config["layers"]:
        kind = layer["type"]
        if kind.startswith("conv"):
            spec = {"type": kind, "->": {
                "n_kernels": layer["kernels"], "kx": layer["kx"],
                "ky": layer["ky"], "sliding": (layer["stride"],) * 2,
                "padding": layer["pad"], "weights_filling": "gaussian",
                "weights_stddev": layer["w_std"]}, "<-": dict(solver)}
        elif kind == "lrn":
            spec = {"type": kind, "->": {
                key: layer[key] for key in ("alpha", "beta", "n", "k")}}
        elif kind == "max_pooling":
            spec = {"type": kind, "->": {
                "kx": layer["kx"], "ky": layer["ky"],
                "sliding": (layer["stride"],) * 2}}
        elif kind == "dropout":
            spec = {"type": kind, "->": {"dropout_ratio": layer["ratio"]}}
        else:
            spec = {"type": kind, "->": {
                "output_sample_shape": layer["out"],
                "weights_filling": "gaussian",
                "weights_stddev": layer["w_std"]}, "<-": dict(solver)}
        specs.append(spec)
    return specs


def make_dataset(config, seed):
    """Seeded uint8 images and labels, made in bulk on the host (the
    loader's ``load_data`` takes host arrays and uploads them once)."""
    assumed = config["assumed"]
    batch = config["batch"]
    n_valid = batch * assumed["validation_minibatches"]
    n_train = batch * assumed["train_minibatches"]
    shape = tuple(config["input_shape"])
    count = n_valid + n_train
    nbytes = count * int(numpy.prod(shape))
    rng = numpy.random.default_rng([int(seed), 1])
    words = rng.integers(0, 2 ** 63, size=(nbytes + 7) // 8,
                         dtype=numpy.int64)
    data = words.view(numpy.uint8)[:nbytes].reshape((count,) + shape)
    # every class is present (wherever there are as many samples as
    # classes), so the loader's label mapping is the identity
    labels = (rng.permutation(count) % config["classes"]).astype(
        numpy.int32)
    return data, labels, n_valid, n_train


class Run(object):
    def __init__(self, ctx, reference):
        self.ctx = ctx
        self.reference = reference
        self.obs = None
        self.wf = None

    # -- set-up and window: one wf.run() ---------------------------------
    def run(self):
        import jax
        import jax.numpy as jnp
        from veles_tpu import prng
        from veles_tpu.backends import AutoDevice
        from veles_tpu.dummy import DummyLauncher
        from veles_tpu.loader.fullbatch import FullBatchLoader
        from veles_tpu.znicz.standard_workflow import StandardWorkflow

        ctx, config = self.ctx, self.ctx.config
        tracer = ctx.tracer
        batch = config["batch"]
        data, labels, n_valid, n_train = make_dataset(config, ctx.seed)
        self.data, self.labels = data, labels

        class SeededLoader(FullBatchLoader):
            def load_data(self):
                self.original_data.mem = data
                self.original_labels = labels.tolist()
                self.class_lengths[:] = [0, n_valid, n_train]

        prng.seed_all(ctx.seed % (2 ** 31 - 1))
        loader_config = dict(config.get("loader", {}))
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: SeededLoader(
                w, minibatch_size=batch,
                normalization_type=config["normalization"]["type"],
                **loader_config),
            layers=program_layers(config),
            decision_config={"max_epochs": 10 ** 9},
            fused=True,
            fused_config={"compute_dtype": jnp.dtype(
                config["compute_dtype"]).type})
        wf.launcher = DummyLauncher()
        wf.initialize(device=AutoDevice())
        self.wf = wf
        mapping = wf.loader.labels_mapping
        if any(raw != mapped for raw, mapped in mapping.items()):
            raise RuntimeError("the loader renumbered the labels: the "
                               "data set does not hold every class")

        # the benchmark's weights, made on the device from the seed,
        # handed to the program through its forward units
        self.params0 = self.reference.init_params(config, ctx.seed)
        host = jax.device_get(self.params0)
        for index, fwd in enumerate(wf.forwards):
            if index in host:
                fwd.weights.map_write()
                fwd.weights.mem[...] = host[index]["w"]
                fwd.bias.map_write()
                fwd.bias.mem[...] = host[index]["b"]
        self.masks_seed = self.reference.dropout_seeds(config, ctx.seed)

        trainer = wf.fused_trainer
        loader = wf.loader
        warm_steps = int(config["assumed"]["warm_steps"])
        state = {"train": 0, "eval": 0, "open": None, "close": None,
                 "images": 0, "steps": 0, "evals": 0, "compiles": None,
                 "indices": [], "losses": [], "v1": None, "w3": None,
                 "step_ends": [], "epoch_ends": []}
        self.state = state
        inner = trainer.run

        def cache_sizes():
            return (trainer._step_._cache_size(),
                    trainer._eval_._cache_size())

        def hooked():
            is_train = int(loader.minibatch_class) == TRAIN
            follow = is_train and state["train"] < FOLLOW_STEPS
            if is_train and state["train"] == 0:
                self._plant_mask_seeds(trainer)
            if follow:
                loader.minibatch_indices.map_read()
                state["indices"].append(numpy.array(
                    loader.minibatch_indices.mem[:batch]))
            with tracer.span("trainer"):
                inner()
            now = time.perf_counter()
            if not is_train:
                state["eval"] += 1
                if state["open"] is not None:
                    state["evals"] += 1
                    state["epoch_ends"].append(len(state["step_ends"]))
                return
            if follow:
                state["losses"].append(float(trainer.loss_value))
                if state["train"] == 0:
                    state["v1"] = [
                        {k: jnp.copy(s[k]) for k in ("vw", "vb")
                         if s.get(k) is not None}
                        for s in trainer._params_]
                if state["train"] == FOLLOW_STEPS - 1:
                    state["w3"] = [
                        {k: jnp.copy(s[k]) for k in ("w", "b")
                         if s.get(k) is not None}
                        for s in trainer._params_]
            state["train"] += 1
            if state["open"] is None:
                if state["train"] >= warm_steps and state["eval"] >= 1:
                    state["compiles"] = cache_sizes()
                    jax.block_until_ready(trainer._params_)
                    tracer.start()
                    state["open"] = time.perf_counter()
                return
            state["images"] += int(loader.minibatch_size)
            state["steps"] += 1
            state["step_ends"].append(now)
            if tracer.running and \
                    now - state["open"] >= ctx.trace_seconds:
                state["traced_images"] = state["images"]
                state["traced_steps"] = state["steps"]
                tracer.stop()
            if now - state["open"] >= ctx.seconds:
                # every step counted has finished on the device
                jax.block_until_ready(trainer._params_)
                state["close"] = time.perf_counter()
                wf.stop()

        trainer.run = hooked
        tracer.wrap(loader, "run", "loader")
        tracer.wrap(wf.decision, "run", "decision")
        wf.run()
        tracer.stop()
        if state["close"] is None:
            raise RuntimeError("the workflow ended before the window "
                               "closed")
        compiled = cache_sizes()
        self._log_step_times(state)
        self.obs = {
            "t_open": state["open"], "t_close": state["close"],
            "window_s": state["close"] - state["open"],
            "train_images": state["images"],
            "train_steps": state["steps"],
            "eval_steps": state["evals"],
            "attempted": state["steps"], "failed": 0,
            "compiles_in_window": sum(compiled) - sum(state["compiles"]),
            "traced": {"train_images": state.get("traced_images"),
                       "train_steps": state.get("traced_steps")},
        }
        return self.obs

    def _log_step_times(self, state):
        """Where the window's time went by the host's clock: the spread
        of the step-to-step intervals, and what the epoch boundaries
        (validation minibatch, decision, ``sync_weights``) took beyond a
        step."""
        ends = numpy.array([state["open"]] + state["step_ends"])
        if len(ends) < 8:
            return
        gaps = numpy.diff(ends) * 1e3
        at_epoch = numpy.zeros(len(gaps), bool)
        at_epoch[[i for i in state["epoch_ends"] if i < len(gaps)]] = True
        plain = gaps[~at_epoch]
        q = numpy.percentile(plain, [10, 50, 90, 99])
        self.ctx.log(
            "step intervals ms: p10 %.3f p50 %.3f p90 %.3f p99 %.3f max "
            "%.3f over %d steps; %d epoch boundaries took %.1f ms beyond "
            "a step each (mean), %.2f%% of the window"
            % (q[0], q[1], q[2], q[3], plain.max(), len(plain),
               int(at_epoch.sum()),
               float((gaps[at_epoch] - q[1]).mean()) if at_epoch.any()
               else 0.0,
               100.0 * float((gaps[at_epoch] - q[1]).sum())
               / float(gaps.sum()) if at_epoch.any() else 0.0))

    def _plant_mask_seeds(self, trainer):
        """Give every dropout stage the benchmark's own mask-stream
        seed, on the leaf's own placement (so nothing recompiles)."""
        import jax
        if trainer._step_ is None:
            trainer._build()
        for index, seed in self.masks_seed.items():
            leaf = trainer._params_[index]["seed"]
            trainer._params_[index]["seed"] = jax.device_put(
                numpy.int32(seed), leaf.sharding)

    # -- what the timed path produced, before its state is freed ---------
    def program_readings(self):
        solver = self.ctx.config["solver"]
        lr, decay = solver["learning_rate"], solver["weights_decay"]
        state = self.state
        norm = self.reference.norm
        grad, delta = {}, {}
        for index, leaves in self.params0.items():
            for name, vname in (("w", "vw"), ("b", "vb")):
                w0 = leaves[name]
                v1 = state["v1"][index][vname]
                leaf = "%d.%s" % (index, name)
                # step 1's gradient as the optimizer got it
                grad[leaf] = -v1 / lr - (decay * w0 if name == "w"
                                         else 0.0)
                delta[leaf] = norm(state["w3"][index][name] - w0)
        return {"losses": list(state["losses"]), "grad": grad,
                "grad_norm": {k: norm(v) for k, v in grad.items()},
                "delta_norm": delta}

    def release(self):
        """Free the program's device state (the reference runs after)."""
        self.readings = self.program_readings()
        wf = self.wf
        trainer = wf.fused_trainer
        trainer._params_ = None
        trainer._step_ = trainer._eval_ = None
        for vec in (wf.loader.original_data, wf.loader.minibatch_data):
            vec.reset(None)
        self.state["v1"] = self.state["w3"] = None
        self.wf = None

    # -- the plain reference over the same three steps -------------------
    def batches(self):
        import jax.numpy as jnp
        out = []
        for idx in self.state["indices"]:
            if len(set(idx.tolist())) != len(idx) or idx.min() < 0:
                raise RuntimeError("a followed minibatch repeats or "
                                   "pads rows: %r" % idx[:8])
            out.append((jnp.asarray(self.data[idx]),
                        jnp.asarray(self.labels[idx])))
        return out

    def reference_readings(self, quant=None, half_batch=False):
        return self.reference.train_steps(
            self.ctx.config, self.params0, self.batches(),
            self.masks_seed, quant=quant, half_batch=half_batch)

    def verify(self):
        tic = time.perf_counter()
        reference = self.reference_readings()
        self.ctx.log("reference followed %d steps in %.3f s"
                     % (len(reference["losses"]),
                        time.perf_counter() - tic))
        checks, leaves = compare(self.readings, reference, detail=True)
        for leaf in sorted(leaves,
                           key=lambda k: (int(k.split(".")[0]), k)):
            row = leaves[leaf]
            self.ctx.log(
                "leaf %-5s grad %.6e / %.6e  diff %.4f  change %.6e / "
                "%.6e%s" % (leaf, self.readings["grad_norm"][leaf],
                            reference["grad_norm"][leaf], row["diff"],
                            self.readings["delta_norm"][leaf],
                            reference["delta_norm"][leaf],
                            "" if row["counted"] else "  (near zero)"))
        self.ctx.log("losses %r / %r" % (self.readings["losses"],
                                         reference["losses"]))
        self.leaves = leaves
        checks["compiles_in_window"] = self.obs["compiles_in_window"]
        return checks

    def controls(self):
        """The reference put in the program's place: in the precision
        below the configuration's, and with each fault this cell can
        have planted in it (a state left unchanged reads 1 on every
        leaf by construction and needs no run)."""
        reference = self.reference_readings()
        out, self.control_leaves = {}, {}
        for name, kwargs in (("fp8", {"quant": "fp8"}),
                             ("half_batch", {"half_batch": True})):
            out[name], self.control_leaves[name] = compare(
                self.reference_readings(**kwargs), reference, detail=True)
        return out


#: a leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding: it moves by round-off alone
NEAR_ZERO = 1e-3


def compare(program, reference, detail=False):
    """The numbers that decide ``correct`` (each has a limit in the
    cell's file), every leaf measured against the REFERENCE'S OWN norm
    of that leaf:

    ``loss_gap``    worst relative gap of the followed steps' losses;
    ``grad_gap``    worst leaf's gap between the program's norm of step
                    1's gradient and the reference's;
    ``grad_diff``   norm of the DIFFERENCE of step 1's whole gradient
                    over the norm of the reference's whole gradient (a
                    gap of norms sees bias only; this sees zero-mean
                    rounding too, where the gradient is large: the
                    leaves nearest the loss, which every layer's forward
                    pass reaches);
    ``delta_gap``   worst leaf's gap between the norms of the
                    parameters' change after the last followed step.

    A leaf whose reference gradient is under ``NEAR_ZERO`` of the median
    leaf's is left out of all three (none is, in AlexNet).  With
    ``detail`` also returns the per-leaf numbers (``diff``: the leaf's
    own norm of the difference over its reference norm)."""
    import statistics

    import jax.numpy as jnp
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(
        program["losses"], reference["losses"]))
    if len(program["losses"]) != len(reference["losses"]):
        loss_gap = float("nan")
    ref_grad = reference["grad_norm"]
    floor = NEAR_ZERO * statistics.median(ref_grad.values())

    def norm(x):
        return float(jnp.sqrt(jnp.sum(jnp.square(x))))

    leaves = {}
    diff_sq = ref_sq = 0.0
    for leaf, ref in ref_grad.items():
        ref_delta = reference["delta_norm"][leaf]
        counted = ref >= floor and ref > 0 and ref_delta > 0
        diff = norm(program["grad"][leaf] - reference["grad"][leaf])
        if counted:
            diff_sq += diff ** 2
            ref_sq += ref ** 2
        leaves[leaf] = {
            "counted": counted,
            "grad": abs(program["grad_norm"][leaf] - ref) / ref
            if ref > 0 else float("inf"),
            "diff": diff / ref if ref > 0 else float("inf"),
            "delta": abs(program["delta_norm"][leaf] - ref_delta)
            / ref_delta if ref_delta > 0 else float("inf")}
    counted = [row for row in leaves.values() if row["counted"]]
    checks = {"loss_gap": loss_gap,
              "grad_gap": max(row["grad"] for row in counted),
              "grad_diff": (diff_sq / ref_sq) ** 0.5,
              "delta_gap": max(row["delta"] for row in counted)}
    return (checks, leaves) if detail else checks
