"""FusedTrainer: the whole layer stack as ONE unit in the graph.

``StandardWorkflow(fused=True)`` replaces the eager per-unit train
chain (forwards → evaluator → gds, one device dispatch per unit per
minibatch) with this single unit running the fused lowering
(:func:`veles_tpu.znicz.fused_graph.lower_specs`): forward, loss,
backward, and the solver update execute as one XLA program per
minibatch, while every graph service — loader scheduling, Decision
epoch accounting, snapshotter, plotters, web status — keeps working
unchanged.  The forward units still exist and hold the weights (the
trainer seeds its params from them and syncs back every epoch and
before snapshots), so export/packaging and eager debugging see live
parameters.

This is the TPU answer to the reference's per-unit OpenCL dispatch
(SURVEY §3.1): the graph stays the coordination layer, the math leaves
it.
"""

import numpy

from veles_tpu import trace
from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.loader.base import TRAIN


class FusedTrainer(AcceleratedUnit):
    """Runs lower_specs' step/eval for the workflow's layer stack.

    Exposes ``n_err`` (softmax) / ``mse`` (MSE) after every run, so a
    Decision unit can use it in place of the evaluator.
    """

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        super(FusedTrainer, self).__init__(workflow, **kwargs)
        self.view_group = "TRAINER"
        # copy each spec AND its nested "->"/"<-" dicts: rollback_to
        # rescales learning rates in place, and the usual shallow
        # [{**s}] copies share the nested dicts all the way up to
        # module-level sample LAYERS (init arrays stay shared — they
        # can be large and are never mutated here)
        self.layers = [
            {**s, **{k: dict(s[k]) for k in ("->", "<-") if k in s}}
            for s in kwargs["layers"]]
        self.loss = kwargs.get("loss", "softmax")
        self.compute_dtype = kwargs.get("compute_dtype")
        self.grad_accum = int(kwargs.get("grad_accum", 1))
        self.remat = bool(kwargs.get("remat", False))
        #: the reference's LRAdjuster config (policy names + params),
        #: evaluated inside the jitted step — see lower_specs
        self.lr_adjuster = kwargs.get("lr_adjuster")
        #: {"data": -1} etc. — train over a device mesh: batch sharded
        #: on "data", gradients all-reduced inside the step (the
        #: BASELINE north-star AlexNet-DP path, via the workflow).
        #: Optionally combine with fsdp=True for ZeRO param storage
        #: and/or tp=True for Megatron column-parallel weights over a
        #: "model" axis (mesh_axes={"data": d, "model": m}).
        self.mesh_axes = kwargs.get("mesh_axes")
        self.fsdp = bool(kwargs.get("fsdp", False))
        self.tp = bool(kwargs.get("tp", False))
        #: whole-epoch-in-one-program training
        #: (fused_graph.epoch_runner): the device permutes/gathers the
        #: resident TRAIN slice and scans the step inside ONE XLA
        #: program — one dispatch + one metric fetch per epoch instead
        #: of per minibatch.  Decision still sees a per-minibatch
        #: metric stream (the stacked scan outputs are replayed one
        #: call at a time).  Sampling uses the trainer's own device
        #: PRNG stream, not the loader's host shuffle; the loader's
        #: per-minibatch gather becomes redundant device work.
        self.epoch_mode = bool(kwargs.get("epoch_mode", False))
        #: picklable epoch-key counter: resume draws fresh epoch
        #: permutation streams
        self.epoch_key_counter = 0
        self.loader = None
        self.forwards = None
        self.n_err = 0.0
        self.mse = 0.0
        self.loss_value = 0.0
        #: host copy of the full per-layer solver state (momentum
        #: velocities, Adam moments/t, rprop deltas) captured at
        #: pickle time so a Snapshotter resume continues with the same
        #: optimizer dynamics — parity with the eager path, where the
        #: gradient Vectors live in the snapshot.
        self.solver_state = None
        self.demand("loader", "forwards")

    def init_unpickled(self):
        super(FusedTrainer, self).init_unpickled()
        self._params_ = None          # device state; rebuilt on resume
        self._step_ = None
        self._eval_ = None
        self._train_divisor_ = 1
        self._batch_shard_ = None
        self._rep_shard_ = None
        self._epoch_fn_ = None        # epoch_mode: jitted epoch program
        self._epoch_data_ = None      # resident TRAIN slice (device)
        self._epoch_labels_ = None
        self._epoch_steps_ = 0        # full minibatches per epoch
        self._epoch_queue_ = None     # stacked metrics being replayed
        self._epoch_ptr_ = 0

    def __getstate__(self):
        state = super(FusedTrainer, self).__getstate__()
        if self._params_ is not None:
            import jax
            state["solver_state"] = jax.tree_util.tree_map(
                numpy.asarray, self._params_)
        return state

    def _build(self):
        import jax

        from veles_tpu.znicz.fused_graph import lower_specs

        specs = []
        for spec, fwd in zip(self.layers, self.forwards):
            spec = {k: v for k, v in spec.items()}
            if fwd.weights:
                fwd.weights.map_read()
                init = {"weights": numpy.array(fwd.weights.mem)}
                if fwd.bias:
                    fwd.bias.map_read()
                    init["bias"] = numpy.array(fwd.bias.mem)
                spec["init"] = init
            specs.append(spec)
        sample_shape = tuple(self.loader.minibatch_data.shape[1:])
        params, step_fn, eval_fn, _apply = lower_specs(
            specs, sample_shape, loss=self.loss,
            compute_dtype=self.compute_dtype, remat=self.remat,
            grad_accum=self.grad_accum, lr_adjuster=self.lr_adjuster,
            # native-dtype resident datasets publish their fitted
            # normalizer for in-step application
            # (FullBatchLoader(native_device_dtype=True))
            input_norm=getattr(self.loader, "input_norm", None))
        params = self._restore_solver_state(params)
        self._train_divisor_ = max(self.grad_accum, 1)
        mesh = rules = None
        if self.mesh_axes:
            from veles_tpu.parallel import data_parallel, make_mesh
            from veles_tpu.parallel.dp import (fsdp_rules, shard_params,
                                               tp_rules)
            mesh = make_mesh(dict(self.mesh_axes))
            rules = self._make_rules(mesh, fsdp_rules, tp_rules)
            self._step_ = data_parallel(step_fn, mesh, params,
                                        param_rules=rules)
            self._params_ = shard_params(params, mesh,
                                         param_rules=rules)
            # eval: params keep their mesh shardings, the batch is
            # replicated — correct for any (short) batch size, and
            # evaluation is a sliver of the epoch
            from jax.sharding import NamedSharding, PartitionSpec
            from veles_tpu.parallel.dp import _params_sharding
            from veles_tpu.parallel.mesh import replicated
            self._eval_ = jax.jit(
                eval_fn,
                in_shardings=(_params_sharding(params, mesh, rules),
                              replicated(mesh), replicated(mesh)),
                out_shardings=replicated(mesh))
            # device-committed loader arrays must be placed onto the
            # mesh explicitly (jit with in_shardings refuses to
            # reshard committed args)
            self._batch_shard_ = NamedSharding(
                mesh, PartitionSpec("data"))
            self._rep_shard_ = replicated(mesh)
            # train batches must also split evenly over the data axis
            self._train_divisor_ *= int(mesh.shape["data"])
        else:
            # COMMITTED placement on the UNIT'S device: device_put
            # with no device yields UNCOMMITTED arrays, while the
            # step's OUTPUT params are committed — the second call
            # then keys the jit cache differently and recompiles the
            # whole step (a second full step compile in the first
            # loop).  The
            # unit's own device, not jax.devices()[0]: the loader's
            # batches are committed there too (memory.py Vector).
            if self.device is not None and \
                    not self.device.is_interpret:
                self._params_ = self.device.put(params)
            else:
                self._params_ = jax.device_put(params)
            self._step_ = jax.jit(step_fn, donate_argnums=(0,))
            self._eval_ = jax.jit(eval_fn)
        if self.epoch_mode:
            from veles_tpu.loader.fullbatch import FullBatchLoader
            from veles_tpu.znicz.fused_graph import epoch_runner
            loader = self.loader
            if not isinstance(loader, FullBatchLoader):
                raise NotImplementedError(
                    "epoch_mode needs a resident FullBatchLoader "
                    "dataset (got %s)" % type(loader).__name__)
            if float(getattr(loader, "train_ratio", 1.0)) != 1.0:
                raise NotImplementedError(
                    "epoch_mode trains the full TRAIN slice; "
                    "train_ratio=%s is not honored — use the "
                    "per-minibatch path for bagged/ensemble runs"
                    % loader.train_ratio)
            n_train = int(loader.class_lengths[TRAIN])
            batch = int(loader.max_minibatch_size)
            if n_train < batch:
                raise ValueError(
                    "epoch_mode needs at least one full minibatch of "
                    "train samples (%d < %d)" % (n_train, batch))
            if batch % self._train_divisor_:
                raise ValueError(
                    "epoch_mode minibatch %d must divide by "
                    "grad_accum%s (%d)" % (
                        batch, " x data-axis" if mesh else "",
                        self._train_divisor_))
            start = int(loader.class_end_offsets[TRAIN - 1])
            data = loader.original_data.devmem[start:start + n_train]
            if self.loss == "mse":
                # regression epochs train toward the resident target
                # rows (the AE family): same gather, float targets
                labels = loader.original_targets.devmem[
                    start:start + n_train]
            else:
                labels = jax.device_put(numpy.ascontiguousarray(
                    loader._mapped_labels[start:start + n_train]))
            self._epoch_steps_ = n_train // batch
            if mesh is not None:
                # "one workflow, any mode": the mesh epoch is the
                # global-permutation DP composition — sampling
                # IDENTICAL to the single-device epoch program, GSPMD
                # inserts the gather collectives + gradient
                # all-reduce (parallel.dp.data_parallel_epoch; the
                # r4 dryrun leg proves the composition compiles)
                from jax.sharding import NamedSharding, PartitionSpec
                from veles_tpu.parallel.dp import data_parallel_epoch
                self._epoch_fn_ = data_parallel_epoch(
                    step_fn, mesh, params, n_train, batch,
                    param_rules=rules)
                shard = NamedSharding(mesh, PartitionSpec("data"))
                data = jax.device_put(data, shard)
                labels = jax.device_put(labels, shard)
            else:
                self._epoch_fn_ = jax.jit(
                    epoch_runner(step_fn, n_train, batch),
                    donate_argnums=(0,))
            self._epoch_data_ = data
            self._epoch_labels_ = labels

    def _make_rules(self, mesh, fsdp_rules, tp_rules):
        """Param sharding rules for the configured mesh: TP (column-
        parallel last dim on "model"), FSDP (first divisible dim on
        "data"), or their merge — TP wins a contested dim, FSDP takes
        any remaining one."""
        if not (self.tp or self.fsdp):
            return None
        from jax.sharding import PartitionSpec as P
        r_tp = tp_rules(mesh) if self.tp else None
        r_fsdp = fsdp_rules(mesh) if self.fsdp else None

        def rules(leaf):
            spec_t = r_tp(leaf) if r_tp else None
            spec_f = r_fsdp(leaf) if r_fsdp else None
            if spec_t is None:
                return spec_f
            if spec_f is None:
                return spec_t
            merged = list(spec_t)
            for dim, axis in enumerate(spec_f):
                if axis is not None and merged[dim] is None:
                    merged[dim] = axis
            return P(*merged)

        return rules

    def _restore_solver_state(self, params):
        """On snapshot resume, continue from the pickled solver state
        (momentum/Adam/rprop dynamics) instead of a fresh optimizer."""
        if self.solver_state is None:
            return params
        import jax

        new_leaves, new_tree = jax.tree_util.tree_flatten(params)
        sav_leaves, sav_tree = jax.tree_util.tree_flatten(
            self.solver_state)
        if new_tree != sav_tree or any(
                numpy.shape(a) != numpy.shape(b)
                for a, b in zip(new_leaves, sav_leaves)):
            self.warning(
                "pickled solver state does not match the rebuilt "
                "layer stack — optimizer dynamics restart fresh")
            return params
        return jax.tree_util.tree_unflatten(new_tree, sav_leaves)

    def initialize(self, device=None, **kwargs):
        super(FusedTrainer, self).initialize(device=device, **kwargs)
        wf = self.workflow
        if self.epoch_mode and getattr(wf, "is_slave", False):
            raise NotImplementedError(
                "epoch_mode trains a whole epoch in ONE program; the "
                "elastic job layer distributes per-minibatch jobs — "
                "use epoch_mode=False on slaves")
        # Under the elastic master–slave layer the trainer otherwise
        # works unchanged: each job's payload updates the forwards'
        # Vectors, the workflow calls refresh_from_forwards() to
        # install them into the built device params, and sync_weights()
        # runs before the forwards compute their update deltas
        # (StandardWorkflow.apply_data_from_master /
        # generate_data_for_master).
        # _build happens lazily on the first run(): the unchained
        # forward units initialize AFTER this unit (they have no
        # control links), and seeding must read their real weights

    def _labels(self, n):
        import jax

        if self.loss == "mse":
            self.loader.minibatch_targets.map_read()
            return jax.device_put(numpy.ascontiguousarray(
                self.loader.minibatch_targets.mem[:n], numpy.float32))
        self.loader.minibatch_labels.map_read()
        return jax.device_put(numpy.ascontiguousarray(
            self.loader.minibatch_labels.mem[:n], numpy.int32))

    def run(self):
        if self._step_ is None:       # first run / snapshot resume
            self._build()
        # slice away the zero-padded tail of a short final batch: MSE
        # has no validity mask, so padded rows would otherwise pull
        # outputs toward zero targets (the eager EvaluatorMSE slices
        # to batch_size the same way).  At most 2 distinct shapes ever
        # compile (full + tail).
        n = int(self.loader.minibatch_size)
        train = int(self.loader.minibatch_class) == TRAIN
        if train and self._epoch_fn_ is not None:
            # whole-epoch program path: per-minibatch sizing/divisors
            # do not apply (epoch_runner drops the short tail itself)
            self._run_epoch_minibatch()
            if bool(self.loader.last_minibatch):
                self.sync_weights()
            return
        div = self._train_divisor_
        if train and div > 1 and n % div:
            # a short tail batch must stay divisible into microbatches
            # and over the data axis; round down (drops < div samples
            # once per epoch)
            n -= n % div
            if n == 0:
                # tail smaller than one microbatch × data-shard:
                # nothing divisible to train on — skip the step
                # entirely rather than hand the traced program an
                # indivisible batch (at most once per epoch).  Zero
                # the metrics: Decision adds them per minibatch, so
                # stale values would double-count the previous batch.
                self.n_err = 0.0
                self.mse = 0.0
                self.loss_value = 0.0
                if bool(self.loader.last_minibatch):
                    self.sync_weights()
                return
        # the step's phases as the host sees them (docs/observability
        # .md): dispatch is the call's return, wait the host blocked
        # on the device
        phase = {"train": int(train)}
        with trace.span("fused", "labels"):
            x = self.loader.minibatch_data.devmem[:n]
            labels = self._labels(n)
            if self._batch_shard_ is not None:
                import jax
                shard = self._batch_shard_ if train \
                    else self._rep_shard_
                x = jax.device_put(x, shard)
                labels = jax.device_put(labels, shard)
        if train:
            with trace.span("fused", "dispatch", phase):
                self._params_, metrics = self._step_(self._params_, x,
                                                     labels)
            with trace.span("fused", "wait", phase):
                err = float(metrics["n_err"])
                self.loss_value = float(metrics["loss"])
        else:
            with trace.span("fused", "dispatch", phase):
                ev = self._eval_(self._params_, x, labels)
            with trace.span("fused", "wait", phase):
                err = float(ev["n_err"] if self.loss != "mse"
                            else ev["rmse"])
        if self.loss == "mse":
            self.mse = err
        else:
            self.n_err = err
        if bool(self.loader.last_minibatch):
            # epoch boundary: the unit graph (snapshotter, export,
            # eager eval) sees the trained weights
            self.sync_weights()

    def _run_epoch_minibatch(self):
        """epoch_mode: the FIRST train minibatch of an epoch runs the
        whole epoch as one program; every train call (this one
        included) replays one minibatch's metrics from the stacked
        scan outputs, so Decision's per-minibatch accounting is
        unchanged.  Loader minibatches beyond the full-batch count
        (the short tail epoch_runner drops) report zero metrics — the
        same rule as the indivisible-tail skip above."""
        import jax

        if self._epoch_queue_ is None:
            key = jax.random.key(self.epoch_key_counter)
            self.epoch_key_counter += 1
            self._params_, stacked = self._epoch_fn_(
                self._params_, self._epoch_data_, self._epoch_labels_,
                key)
            # ONE host fetch per epoch for the whole metric stream
            self._epoch_queue_ = jax.tree_util.tree_map(numpy.asarray,
                                                        stacked)
            self._epoch_ptr_ = 0
        if self._epoch_ptr_ < self._epoch_steps_:
            i = self._epoch_ptr_
            self._epoch_ptr_ += 1
            err = float(self._epoch_queue_["n_err"][i])
            self.loss_value = float(self._epoch_queue_["loss"][i])
        else:                          # dropped short tail
            err = 0.0
            self.loss_value = 0.0
        # mse's "n_err" metric is the minibatch RMSE (fused_graph
        # step metrics are uniform across losses)
        if self.loss == "mse":
            self.mse = err
        else:
            self.n_err = err
        if bool(self.loader.last_minibatch):
            # epoch boundary: the next train call starts a new epoch
            self._epoch_queue_ = None

    def capture_state(self):
        """Host copy of the full solver-state tree (weights, momenta,
        Adam moments/t, rprop deltas, schedule ticks) — what
        :class:`veles_tpu.znicz.rollback.Rollback` snapshots on every
        improved epoch.  None before the first build."""
        if self._params_ is None:
            return None
        import jax
        return jax.tree_util.tree_map(numpy.asarray, self._params_)

    def rollback_to(self, snap, lr_factor=1.0):
        """Restore a :meth:`capture_state` tree and scale every
        layer's learning rate; the jitted step rebuilds lazily (one
        recompile per rollback event)."""
        if lr_factor != 1.0:
            from veles_tpu.znicz.fused_graph import default_lr
            for spec in self.layers:
                bw = spec.setdefault("<-", {})
                default = default_lr(bw.get("solver", "momentum"))
                bw["learning_rate"] = float(
                    bw.get("learning_rate", default)) * lr_factor
                if "learning_rate_bias" in bw:
                    bw["learning_rate_bias"] = float(
                        bw["learning_rate_bias"]) * lr_factor
        self.solver_state = snap
        self._step_ = None            # _build() restores the tree

    def refresh_from_forwards(self):
        """Overwrite the built device params' weight/bias leaves with
        the forward units' (host) Vectors, keeping solver state
        (momenta, Adam moments/t, rprop deltas, schedule ticks)
        local — the async-DP consistency model: every job starts from
        the master's weights while optimizer dynamics stay slave-side,
        exactly like the eager chain's per-unit gradient Vectors (ref
        ``veles/client.py:177-196`` job application).  A no-op before
        the first build: ``_build`` seeds from the same Vectors
        lazily."""
        if self._params_ is None:
            return
        import jax

        refreshed = []
        for fwd, state in zip(self.forwards, self._params_):
            state = dict(state)
            for key, vec in (("w", fwd.weights), ("b", fwd.bias)):
                old = state.get(key)
                if old is None or not vec:
                    continue
                vec.map_read()
                # astype copies: the leaf must not alias the Vector's
                # host array, which sync_weights and the next job rewrite
                host = numpy.ascontiguousarray(vec.mem).astype(old.dtype)
                # the leaf's own sharding: committed single-device
                # placement and mesh NamedShardings both round-trip
                state[key] = jax.device_put(host, old.sharding)
            refreshed.append(state)
        self._params_ = refreshed

    def sync_weights(self):
        """Write the fused params back into the forward units (the
        epoch boundary's D2H)."""
        if self._params_ is None:
            return
        with trace.span("fused", "sync_weights"):
            for fwd, state in zip(self.forwards, self._params_):
                w = state.get("w")
                if w is not None and fwd.weights:
                    fwd.weights.map_write()
                    fwd.weights.mem[...] = numpy.asarray(
                        w, dtype=fwd.weights.mem.dtype)
                b = state.get("b")
                if b is not None and fwd.bias:
                    fwd.bias.map_write()
                    fwd.bias.mem[...] = numpy.asarray(
                        b, dtype=fwd.bias.mem.dtype)
