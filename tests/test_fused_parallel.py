"""Fused train step + mesh data parallelism (runs on the virtual
8-device CPU mesh)."""

import jax
import numpy
import pytest

from veles_tpu import prng
from veles_tpu.parallel import data_parallel, make_mesh
from veles_tpu.parallel.dp import shard_params
from veles_tpu.znicz.fused import (
    init_mlp_params, lower_workflow, make_eval_step, make_train_step,
    mlp_apply, update_workflow, _specs_static)

LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 4},
     "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
]


def _data(n=64, dim=12, classes=4, seed=0):
    rng = numpy.random.default_rng(seed)
    labels = (numpy.arange(n) % classes).astype(numpy.int32)
    centers = rng.standard_normal((classes, dim)) * 3
    x = (centers[labels] + rng.standard_normal((n, dim))).astype(
        numpy.float32)
    return x, labels


def test_fused_step_learns():
    prng.seed_all(0)
    params = init_mlp_params(12, LAYERS)
    step = jax.jit(make_train_step(LAYERS))
    x, labels = _data()
    first = None
    for i in range(60):
        params, metrics = step(params, x, labels)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.5
    assert int(metrics["n_err"]) <= 5


def test_fused_matches_eager_units():
    """One fused step == one eager unit-graph step (same math)."""
    from veles_tpu.backends import NumpyDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    x, labels = _data(n=32)

    class L(FullBatchLoader):
        def load_data(self):
            self.original_data.mem = x
            self.original_labels = list(int(v) for v in labels)
            self.class_lengths[:] = [0, 0, 32]

    prng.seed_all(7)
    wf = StandardWorkflow(
        None, loader_factory=lambda w: L(w, minibatch_size=32,
                                         shuffle_limit=0),
        layers=[{**s} for s in LAYERS],
        decision_config={"max_epochs": 1})
    wf.launcher = DummyLauncher()
    wf.initialize(device=NumpyDevice())

    params, step = lower_workflow(wf)
    # eager one minibatch
    wf.loader.run()
    for fwd in wf.forwards:
        fwd.run()
    wf.evaluator.run()
    for gdu in wf.gds:
        gdu.run()
    # fused one step on the same batch
    mb_x = numpy.array(wf.loader.minibatch_data.mem)
    mb_y = numpy.array(wf.loader.minibatch_labels.mem)
    new_params, _ = jax.jit(step)(params, mb_x, mb_y)
    for layer, fwd in zip(new_params, wf.forwards):
        assert numpy.allclose(numpy.asarray(layer["w"]), fwd.weights.mem,
                              atol=1e-4), fwd.name
        assert numpy.allclose(numpy.asarray(layer["b"]), fwd.bias.mem,
                              atol=1e-4), fwd.name
    # write-back path
    update_workflow(wf, new_params)
    assert numpy.allclose(wf.forwards[0].weights.mem,
                          numpy.asarray(new_params[0]["w"]))


def test_fused_short_batch_matches_eager_scaling():
    """Padded short batch: fused gradients scale by padded length like
    the eager units (valid-count scaling would overstep 1.5x)."""
    prng.seed_all(9)
    params = init_mlp_params(12, LAYERS)
    step = jax.jit(make_train_step(LAYERS))
    x, labels = _data(n=15)
    x = numpy.vstack([x, numpy.zeros((5, 12), numpy.float32)])
    labels = numpy.concatenate([labels,
                                numpy.full(5, -1, numpy.int32)])
    new_params, metrics = step(params, x, labels)
    # manual check of output-layer bias grad scaling
    static = _specs_static(LAYERS)
    out = mlp_apply(params, x, static)
    onehot = numpy.zeros((20, 4), numpy.float32)
    for i, l in enumerate(labels[:15]):
        onehot[i, l] = 1
    delta = (numpy.asarray(out) - onehot)
    delta[15:] = 0
    grad_b = delta.sum(axis=0) / 20.0          # padded length, not 15
    lr = LAYERS[-1]["<-"]["learning_rate"]
    expect_b = numpy.asarray(params[-1]["b"]) - lr * grad_b
    assert numpy.allclose(numpy.asarray(new_params[-1]["b"]), expect_b,
                          atol=1e-5)


def test_data_parallel_8_devices_matches_single():
    prng.seed_all(1)
    params_a = init_mlp_params(12, LAYERS)
    params_b = jax.tree.map(numpy.copy, params_a)
    x, labels = _data(n=64)
    step = make_train_step(LAYERS)
    single = jax.jit(step)
    mesh = make_mesh({"data": 8})
    assert mesh.shape["data"] == 8
    dp = data_parallel(step, mesh, params_a, donate_params=False)
    for _ in range(3):
        params_a, m_dp = dp(params_a, x, labels)
        params_b, m_single = single(params_b, x, labels)
    assert numpy.allclose(numpy.asarray(params_a[0]["w"]),
                          numpy.asarray(params_b[0]["w"]), atol=1e-5)
    assert int(m_dp["n_err"]) == int(m_single["n_err"])


def test_fsdp_sharded_params_match_replicated():
    """ZeRO/FSDP storage via fsdp_rules: every large parameter (and its
    solver state) is sharded over the data axis, XLA gathers/scatters
    as needed, and the math matches the replicated run exactly."""
    from veles_tpu.parallel.dp import fsdp_rules, shard_params

    prng.seed_all(1)
    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 32},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    params_a = init_mlp_params(16, layers)
    params_b = jax.tree.map(numpy.copy, params_a)
    x, labels = _data(n=64, dim=16)
    step = make_train_step(layers)
    mesh = make_mesh({"data": 8})
    rules = fsdp_rules(mesh, min_elements=64)
    fsdp = data_parallel(step, mesh, params_a, donate_params=False,
                         param_rules=rules)
    params_a = shard_params(params_a, mesh, param_rules=rules)
    # the first layer's weight (16, 32) really is sharded over 'data'
    w_shard = params_a[0]["w"].sharding
    assert "data" in str(w_shard.spec), w_shard
    assert not params_a[0]["w"].sharding.is_fully_replicated
    rep = data_parallel(step, mesh, params_b, donate_params=False)
    for _ in range(3):
        params_a, m_f = fsdp(params_a, x, labels)
        params_b, m_r = rep(params_b, x, labels)
    assert int(m_f["n_err"]) == int(m_r["n_err"])
    numpy.testing.assert_allclose(
        numpy.asarray(params_a[0]["w"]),
        numpy.asarray(params_b[0]["w"]), atol=1e-5)
    # state stayed sharded across steps (ZeRO: optimizer state too)
    assert not params_a[0]["vw"].sharding.is_fully_replicated


def test_dp_2x4_mesh_with_model_axis():
    """data×model mesh: params sharded on the model axis (TP) still
    produce the same training step results."""
    from jax.sharding import PartitionSpec as P
    prng.seed_all(2)
    params = init_mlp_params(12, LAYERS)
    reference = jax.tree.map(numpy.copy, params)
    x, labels = _data(n=32)
    mesh = make_mesh({"data": 2, "model": 4})

    def rules(leaf):
        # shard the hidden dimension of 2-D weights over 'model'
        if getattr(leaf, "ndim", 0) == 2 and leaf.shape[1] % 4 == 0:
            return P(None, "model")
        return None

    step = make_train_step(LAYERS)
    dp = data_parallel(step, mesh, params, donate_params=False,
                       param_rules=rules)
    out_tp, m_tp = dp(params, x, labels)
    out_ref, m_ref = jax.jit(step)(reference, x, labels)
    assert numpy.allclose(numpy.asarray(out_tp[0]["w"]),
                          numpy.asarray(out_ref[0]["w"]), atol=1e-5)
    assert int(m_tp["n_err"]) == int(m_ref["n_err"])


def test_shard_params_topology_change():
    """Snapshot on one topology, reshard on another (§5.4 resume)."""
    prng.seed_all(3)
    params = init_mlp_params(12, LAYERS)
    mesh8 = make_mesh({"data": 8})
    placed = shard_params(params, mesh8)
    mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
    replaced = shard_params(jax.tree.map(numpy.asarray, placed), mesh2)
    assert numpy.allclose(numpy.asarray(replaced[0]["w"]),
                          numpy.asarray(params[0]["w"]))


def test_fused_regularizers_l1_and_ortho():
    """l1_vs_l2 mixes sign(w) into the decay term; factor_ortho pushes
    WᵀW toward I — both verified against hand-computed updates."""
    import jax.numpy as jnp

    from veles_tpu.znicz.fused_graph import lower_specs
    from veles_tpu.znicz.gd_base import ortho_grad

    # single linear layer, MSE loss, lr small: one step's weight change
    # must equal -lr * (grad + decay*((1-l)w + l*sign(w)) + ortho)
    w0 = numpy.array([[1.5, -0.5], [0.5, 2.0]], numpy.float32)
    spec = [{"type": "all2all",
             "->": {"output_sample_shape": 2, "include_bias": False},
             "init": {"weights": w0},
             "<-": {"learning_rate": 0.1, "weights_decay": 0.2,
                    "l1_vs_l2": 0.7, "factor_ortho": 0.05}}]
    prng.seed_all(5)
    params, step_fn, _e, _a = lower_specs(spec, (2,), loss="mse")
    x = numpy.array([[1.0, 0.0], [0.0, 1.0]], numpy.float32)
    target = numpy.zeros((2, 2), numpy.float32)
    new, _m = step_fn(params, x, target)

    out = x @ w0
    grad = x.T @ (out - target) / 2 / 2   # d(mean-over-dim MSE/2)/dW
    reg = 0.2 * (0.3 * w0 + 0.7 * numpy.sign(w0))
    ortho = numpy.asarray(ortho_grad(jnp.asarray(w0), 0.05))
    expect = w0 - 0.1 * (grad + reg + ortho)
    numpy.testing.assert_allclose(numpy.asarray(new[0]["w"]), expect,
                                  atol=1e-5)


@pytest.mark.parametrize("solver", ["adam", "rprop", "adagrad",
                                    "adadelta"])
def test_fused_solver_selection_learns(solver):
    """Per-layer 'solver' in the <- spec swaps the fused update rule;
    both alternatives actually train."""
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(42)
    knobs = {"solver": solver}
    if solver == "rprop":
        knobs["rprop_delta_init"] = 0.001
    elif solver == "adadelta":
        knobs["learning_rate"] = 1.0        # canonical adadelta scale
    elif solver == "adagrad":
        knobs["learning_rate"] = 0.05
    else:
        knobs["learning_rate"] = 0.003
    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
         "<-": dict(knobs)},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": dict(knobs)},
    ]
    params, step_fn, _e, _a = lower_specs(layers, (12,))
    x, labels = _data(n=128)
    first = None
    for _ in range(40):
        params, metrics = step_fn(params, x, labels)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.6
    # solver state invariants
    for state in params:
        if state.get("w") is None:
            continue
        if solver == "adam":
            assert int(state["t"]) == 40
            assert state["sw"].shape == state["w"].shape
            assert float(jax.numpy.min(state["sw"])) >= 0.0
        elif solver in ("adagrad", "adadelta"):
            # squared-gradient accumulator is nonnegative and grew
            assert float(jax.numpy.min(state["sw"])) >= 0.0
            assert float(jax.numpy.max(state["sw"])) > 0.0
        else:
            delta, prev = state["vw"][0], state["vw"][1]
            assert float(jax.numpy.min(delta)) >= 1e-6
            assert float(jax.numpy.max(delta)) <= 50.0
            signs = numpy.unique(numpy.asarray(prev))
            assert set(signs).issubset({-1.0, 0.0, 1.0})


def test_fused_step_compiles_exactly_once_across_calls():
    """The trainer's params are COMMITTED device arrays: an
    uncommitted input (plain device_put) plus the step's committed
    output params would re-key the jit cache on the SECOND call and
    recompile the entire step — observed as a 9.6-20 s first-loop
    stall per chip session (r4 session 4 compile log)."""
    import jax

    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(1)
    wf = mnist.create_workflow(device=CPUDevice(), max_epochs=1,
                               minibatch_size=500, fused=True)
    wf.fused_trainer._build()
    tr = wf.fused_trainer
    x = jax.device_put(numpy.zeros((500, 784), numpy.float32))
    labels = jax.device_put(numpy.zeros((500,), numpy.int32))
    params, _m = tr._step_(tr._params_, x, labels)
    assert tr._step_._cache_size() == 1
    params, _m = tr._step_(params, x, labels)
    params, _m = tr._step_(params, x, labels)
    assert tr._step_._cache_size() == 1, \
        "step retraced: params committed-ness must match its outputs"


def test_standard_workflow_fused_mode_trains():
    """StandardWorkflow(fused=True): the graph keeps the loader /
    Decision / services, the math runs as ONE program per minibatch
    (FusedTrainer), and weights sync back into the forward units."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(1)
    wf = mnist.create_workflow(device=CPUDevice(), max_epochs=2,
                               minibatch_size=500, fused=True)
    assert wf.fused_trainer is not None
    assert wf.gds == []                  # no eager backward chain
    # the trainer seeds from the units' REAL initialized weights (the
    # forwards initialize after the trainer, hence the lazy build)
    wf.forwards[0].weights.map_read()
    w_init = numpy.array(wf.forwards[0].weights.mem)
    assert not numpy.allclose(w_init, 0.0)
    wf.fused_trainer._build()
    numpy.testing.assert_allclose(
        numpy.asarray(wf.fused_trainer._params_[0]["w"]), w_init,
        atol=0)
    wf.run()
    results = wf.gather_results()
    # same bar as the eager-mode sample test (measured 25 % there)
    assert results["best_validation_error_pt"] < 35.0
    # the trained parameters are visible in the unit graph
    wf.forwards[0].weights.map_read()
    w_unit = numpy.array(wf.forwards[0].weights.mem)
    w_fused = numpy.asarray(wf.fused_trainer._params_[0]["w"])
    numpy.testing.assert_allclose(w_unit, w_fused, atol=1e-6)


def test_standard_workflow_fused_mse_trains():
    """fused=True with an MSE stack (autoencoder shape): DecisionMSE
    reads the trainer's mse metric."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist_ae

    prng.seed_all(2)
    # minibatch 300 does NOT divide the synthetic class sizes: the
    # short-tail slicing path (MSE has no validity mask) is exercised
    wf = mnist_ae.create_workflow(device=CPUDevice(), max_epochs=2,
                                  minibatch_size=300, fused=True)
    wf.run()
    results = wf.gather_results()
    assert numpy.isfinite(results["best_rmse"])
    assert float(wf.decision.best_mse) < numpy.inf


def test_fused_workflow_deterministic():
    """Two identically-seeded fused runs (incl. dropout's per-stage
    seed streams) produce bit-identical weights — the reproducible-
    randomness contract under jit."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    def train_once():
        prng.seed_all(77)
        # 2 epochs: max_epochs=1 would stop after the initial eval
        # pass with zero train steps, making the comparison vacuous
        wf = mnist.create_workflow(
            device=CPUDevice(), max_epochs=2, minibatch_size=500,
            fused=True,
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 32},
                 "<-": {"learning_rate": 0.03, "gradient_moment": 0.9}},
                {"type": "dropout", "->": {"dropout_ratio": 0.3}},
                {"type": "softmax", "->": {"output_sample_shape": 10},
                 "<-": {"learning_rate": 0.03}},
            ])
        wf.run()
        wf.forwards[0].weights.map_read()
        return numpy.array(wf.forwards[0].weights.mem)

    numpy.testing.assert_array_equal(train_once(), train_once())


def test_standard_workflow_fused_snapshot_resume(tmp_path):
    """A fused workflow pickles and resumes: the trainer's device
    state is rebuilt from the unit weights it synced at epoch end, so
    training continues from the trained parameters."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.samples import mnist
    from veles_tpu.snapshotter import load_snapshot

    prng.seed_all(1)
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=500,
        fused=True, snapshot_dir=str(tmp_path))
    wf.run()
    first_best = float(wf.decision.best_n_err_pt)
    # Decision triggered at least the first-improvement export
    assert wf.snapshotter.destination is not None
    wf.forwards[0].weights.map_read()
    w_trained = numpy.array(wf.forwards[0].weights.mem)

    # the Decision-triggered snapshot is the BEST epoch's cut, which
    # equals the final weights only when the last epoch improved — a
    # numerics coin-flip XLA CPU thread availability can tip.  The
    # equality leg uses an explicit operator export of the final state
    # (the same public API), which is deterministic.
    from veles_tpu.mutable import LinkableAttribute
    LinkableAttribute.unlink(wf.snapshotter, "suffix")
    wf.snapshotter.suffix = "final"
    wf.snapshotter.export()

    restored = load_snapshot(wf.snapshotter.destination)
    restored.launcher = DummyLauncher()
    # the trainer's jitted state is deliberately not pickled
    assert restored.fused_trainer._step_ is None
    restored.forwards[0].weights.map_read()
    numpy.testing.assert_allclose(
        numpy.array(restored.forwards[0].weights.mem), w_trained)
    restored.decision.complete <<= False
    restored.decision.max_epochs = 3
    restored.initialize(device=CPUDevice())
    restored.run()
    assert restored.loader.epoch_number >= 2
    # resumed training did not regress below the snapshot's best
    assert float(restored.decision.best_n_err_pt) <= first_best + 1e-6


def test_fused_snapshot_preserves_solver_state(tmp_path):
    """Snapshotter resume continues with the SAME optimizer dynamics:
    the momentum velocities pickled with the workflow are restored
    into the rebuilt device state (parity with the eager path, where
    the gradient Vectors live in the snapshot)."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.samples import mnist
    from veles_tpu.snapshotter import load_snapshot

    prng.seed_all(5)
    # NB max_epochs=1 completes after the initial validation pass with
    # zero train steps; 2 epochs = one real training epoch
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=500,
        fused=True, snapshot_dir=str(tmp_path))
    wf.run()
    v_orig = [numpy.asarray(st["vw"])
              for st in wf.fused_trainer._params_ if "vw" in st]
    assert v_orig and any(numpy.abs(v).max() > 0 for v in v_orig)

    # explicit final export: the velocities compared below must be the
    # FINAL ones, not the best-epoch ones (see the resume test above)
    from veles_tpu.mutable import LinkableAttribute
    LinkableAttribute.unlink(wf.snapshotter, "suffix")
    wf.snapshotter.suffix = "final"
    wf.snapshotter.export()

    restored = load_snapshot(wf.snapshotter.destination)
    restored.launcher = DummyLauncher()
    assert restored.fused_trainer.solver_state is not None
    restored.decision.complete <<= False
    restored.decision.max_epochs = 2
    restored.initialize(device=CPUDevice())
    restored.fused_trainer._build()
    v_rest = [numpy.asarray(st["vw"])
              for st in restored.fused_trainer._params_ if "vw" in st]
    assert len(v_rest) == len(v_orig)
    for a, b in zip(v_orig, v_rest):
        numpy.testing.assert_array_equal(b, a)


def test_standard_workflow_fused_mesh_dp():
    """fused_config={'mesh_axes': ...}: the workflow's FusedTrainer
    trains data-parallel over the 8-device mesh (the BASELINE
    north-star AlexNet-DP shape, via the graph), optionally with FSDP
    param storage."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(1)
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=500,
        fused=True,
        fused_config={"mesh_axes": {"data": -1}, "fsdp": True})
    wf.run()
    results = wf.gather_results()
    assert results["best_validation_error_pt"] < 35.0
    # params are mesh-sharded (FSDP): not fully replicated
    w = wf.fused_trainer._params_[0]["w"]
    assert not w.sharding.is_fully_replicated


def test_grad_accum_matches_full_batch():
    """grad_accum=N (the reference's accumulate_gradient, as an
    in-step scan over microbatches) produces the same update as the
    full-batch step, with microbatch-sized activation memory."""
    from veles_tpu.znicz.fused_graph import lower_specs

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05}},
    ]
    prng.seed_all(9)
    params_a, step_a, _e, _a = lower_specs(layers, (12,))
    prng.seed_all(9)
    params_b, step_b, _e2, _a2 = lower_specs(layers, (12,),
                                             grad_accum=4)
    x, labels = _data(n=64)
    for _ in range(3):
        params_a, m_a = step_a(params_a, x, labels)
        params_b, m_b = step_b(params_b, x, labels)
    assert int(m_a["n_err"]) == int(m_b["n_err"])
    assert float(m_a["loss"]) == pytest.approx(float(m_b["loss"]),
                                               rel=1e-5)
    for sa, sb in zip(params_a, params_b):
        numpy.testing.assert_allclose(numpy.asarray(sa["w"]),
                                      numpy.asarray(sb["w"]),
                                      atol=1e-5)

    with pytest.raises(ValueError, match="not divisible"):
        step_b(params_b, x[:30], labels[:30])


def test_grad_accum_microbatches_draw_distinct_dropout_masks():
    """Each microbatch in the grad-accum scan must draw its own
    dropout mask.  Probe: duplicate a half-batch — if both microbatches
    used the SAME mask, the grad_accum=2 update on the duplicated batch
    would exactly equal the grad_accum=1 update on the half batch
    (average of two identical gradients); distinct masks break that."""
    from veles_tpu.znicz.fused_graph import lower_specs

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
         "<-": {"learning_rate": 0.05}},
        {"type": "dropout", "->": {"dropout_ratio": 0.5}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05}},
    ]
    prng.seed_all(21)
    params_half, step_half, _e, _a = lower_specs(layers, (12,))
    prng.seed_all(21)            # identical init weights AND seeds
    params_dup, step_dup, _e2, _a2 = lower_specs(layers, (12,),
                                                 grad_accum=2)
    x_half, l_half = _data(n=16)
    x_dup = numpy.concatenate([x_half, x_half])
    l_dup = numpy.concatenate([l_half, l_half])
    params_half, _m = step_half(params_half, x_half, l_half)
    params_dup, _m2 = step_dup(params_dup, x_dup, l_dup)
    w_half = numpy.asarray(params_half[0]["w"])
    w_dup = numpy.asarray(params_dup[0]["w"])
    assert not numpy.allclose(w_half, w_dup, atol=1e-7)


def test_fused_tail_smaller_than_divisor_skips_step():
    """A train tail batch SMALLER than grad_accum × data-axis (here:
    6000 % 857 = 1 < grad_accum=4) must be skipped, not handed to the
    traced step as an indivisible size (which raised mid-epoch)."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(3)
    # 2 epochs: max_epochs=1 stops at the initial eval close with zero
    # train steps, so the tail path would never execute
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=857,
        fused=True, fused_config={"grad_accum": 4})
    wf.run()                      # raised ValueError before the fix
    results = wf.gather_results()
    assert numpy.isfinite(results["best_validation_error_pt"])
    # the epoch-boundary weight sync still happened
    wf.forwards[0].weights.map_read()
    numpy.testing.assert_allclose(
        numpy.array(wf.forwards[0].weights.mem),
        numpy.asarray(wf.fused_trainer._params_[0]["w"]), atol=1e-6)


def test_fused_unknown_solver_rejected():
    from veles_tpu.znicz.fused_graph import lower_specs

    with pytest.raises(ValueError, match="unknown solver"):
        lower_specs([{"type": "softmax",
                      "->": {"output_sample_shape": 2},
                      "<-": {"solver": "sgdfast"}}], (4,))


def test_remat_matches_and_rematerializes():
    """lower_specs(remat=...): numerically identical step, with the
    checkpoint primitive actually present in the jaxpr (activations
    recomputed in backward instead of held in HBM)."""
    from veles_tpu.znicz.fused_graph import lower_specs

    specs = [
        {"type": "conv_tanh", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
         "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
        {"type": "max_pooling", "->": {"kx": 2, "ky": 2}},
        {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
         "<-": {"learning_rate": 0.01}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.01}},
    ]
    rng = numpy.random.default_rng(5)
    x = rng.standard_normal((8, 10, 10, 2)).astype(numpy.float32)
    labels = (numpy.arange(8) % 4).astype(numpy.int32)

    prng.seed_all(7)
    params0, step0, _e, _a = lower_specs(specs, (10, 10, 2))
    prng.seed_all(7)
    params1, step1, _e, _a = lower_specs(specs, (10, 10, 2),
                                         remat=True)
    new0, m0 = step0(params0, x, labels)
    new1, m1 = step1(params1, x, labels)
    assert float(m0["loss"]) == pytest.approx(float(m1["loss"]),
                                              rel=1e-6)
    for s0, s1 in zip(new0, new1):
        for key in s0:
            if s0[key] is None:
                continue
            numpy.testing.assert_allclose(
                numpy.asarray(s0[key]), numpy.asarray(s1[key]),
                atol=1e-5)
    # the checkpoint (remat) primitive is really in the program
    jaxpr1 = jax.make_jaxpr(step1)(params1, x, labels)
    jaxpr0 = jax.make_jaxpr(step0)(params0, x, labels)
    assert "remat" in str(jaxpr1)
    assert "remat" not in str(jaxpr0)

    # per-layer opt-in: only the flagged layer is checkpointed
    specs_one = [dict(s) for s in specs]
    specs_one[0]["remat"] = True
    prng.seed_all(7)
    _p, step_one, _e2, _a2 = lower_specs(specs_one, (10, 10, 2))
    assert "remat" in str(jax.make_jaxpr(step_one)(params1, x, labels))


def test_eval_step():
    prng.seed_all(4)
    params = init_mlp_params(12, LAYERS)
    x, labels = _data(n=16)
    ev = jax.jit(make_eval_step(LAYERS))
    out = ev(params, x, labels)
    assert 0 <= int(out["n_err"]) <= 16
    assert int(out["n"]) == 16


def test_graft_entry_contract():
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (128, 10)
    assert numpy.allclose(numpy.asarray(out).sum(axis=1), 1.0, atol=1e-3)


@pytest.mark.slow
def test_dryrun_multichip_8():
    # compiles the whole real-dims multichip ladder (~85 s on the
    # virtual CPU mesh) — outside the tier-1 budget
    import __graft_entry__ as ge
    ge.dryrun_multichip(8)


def test_standard_workflow_fused_mesh_tp():
    """fused_config={'mesh_axes': {'data': 2, 'model': 4}, 'tp': True}:
    Megatron column-parallel weights through the workflow — each chip
    holds 1/4 of every wide layer's neurons, batch splits on 'data',
    and training still converges; tp+fsdp merge onto distinct dims."""
    from jax.sharding import PartitionSpec as P

    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(22)
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=500,
        fused=True,
        fused_config={"mesh_axes": {"data": 2, "model": 4},
                      "tp": True})
    wf.run()
    results = wf.gather_results()
    assert results["best_validation_error_pt"] < 35.0
    w = wf.fused_trainer._params_[0]["w"]          # (784, 100)
    assert not w.sharding.is_fully_replicated
    assert w.sharding.spec == P(None, "model")
    # momentum velocity shards with its weight
    vw = wf.fused_trainer._params_[0]["vw"]
    assert vw.sharding.spec == P(None, "model")

    # tp+fsdp: contested dims resolve TP-first, FSDP takes the rest
    prng.seed_all(22)
    wf2 = mnist.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=500,
        fused=True,
        fused_config={"mesh_axes": {"data": 2, "model": 4},
                      "tp": True, "fsdp": True})
    wf2.run()
    w2 = wf2.fused_trainer._params_[0]["w"]
    assert w2.sharding.spec == P("data", "model")
    assert numpy.isfinite(
        wf2.gather_results()["best_validation_error_pt"])


def test_tp_requires_model_axis():
    from veles_tpu.parallel.dp import tp_rules

    with pytest.raises(ValueError, match="model"):
        tp_rules(make_mesh({"data": 8}))


def test_fused_u8_input_norm_matches_f32_path():
    """uint8-resident x + in-step normalization (mlp_apply input_norm)
    trains identically to pre-normalized float32 x — the storage-dtype
    change may not alter the trajectory."""
    import numpy
    import jax.numpy as jnp

    from veles_tpu import prng
    from veles_tpu.znicz.fused import init_mlp_params, make_train_step

    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 32},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.05}},
    ]
    rng = numpy.random.default_rng(7)
    xu8 = rng.integers(0, 256, (64, 49)).astype(numpy.uint8)
    labels = rng.integers(0, 10, 64).astype(numpy.int32)
    xf32 = (xu8.astype(numpy.float32) / 255.0) - 0.5

    prng.seed_all(99)
    p_f32 = init_mlp_params(49, layers)
    prng.seed_all(99)
    p_u8 = init_mlp_params(49, layers)

    step_f32 = make_train_step(layers)
    step_u8 = make_train_step(layers, input_norm=(1.0 / 255.0, -0.5))
    for _ in range(5):
        p_f32, m_f32 = step_f32(p_f32, jnp.asarray(xf32),
                                jnp.asarray(labels))
        p_u8, m_u8 = step_u8(p_u8, jnp.asarray(xu8),
                             jnp.asarray(labels))
    numpy.testing.assert_allclose(
        numpy.asarray(p_f32[0]["w"]), numpy.asarray(p_u8[0]["w"]),
        rtol=1e-5, atol=1e-6)
    assert int(m_f32["n_err"]) == int(m_u8["n_err"])


def test_epoch_runner_matches_host_loop():
    """epoch_runner (one-program epoch: in-program permutation +
    gather + step scan) must produce BIT-identical params to the
    host-driven loop applying the same step over the same permuted
    minibatches."""
    import jax
    import numpy
    from veles_tpu.znicz.fused_graph import epoch_runner, lower_specs

    rng = numpy.random.default_rng(0)
    n, batch = 43, 8       # 43 % 8 == 3: the dropped-tail leg is real
    data = rng.integers(0, 256, (n, 12)).astype(numpy.uint8)
    labels = rng.integers(0, 4, n).astype(numpy.int32)
    specs = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 6},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    params, step_fn, _e, _a = lower_specs(
        specs, (12,),
        input_norm=(numpy.float32(1 / 255.0), numpy.float32(0.0)))

    key = jax.random.key(7)
    epoch_fn = jax.jit(epoch_runner(step_fn, n, batch))
    p_epoch, metrics = epoch_fn(params, data, labels, key)

    # the host-driven oracle: same permutation, same minibatches
    perm = numpy.asarray(jax.random.permutation(key, n))
    steps = n // batch
    p_host = params
    host_step = jax.jit(step_fn)
    for i in range(steps):
        idx = perm[i * batch:(i + 1) * batch]
        p_host, _m = host_step(p_host, data[idx], labels[idx])

    # scan-body and standalone compilations may round differently;
    # same tolerance as test_fused_u8_input_norm_matches_f32_path
    for a, b in zip(jax.tree.leaves(p_epoch), jax.tree.leaves(p_host)):
        numpy.testing.assert_allclose(numpy.asarray(a),
                                      numpy.asarray(b),
                                      rtol=1e-5, atol=1e-6)
    # stacked per-minibatch metrics, short tail dropped
    assert all(numpy.asarray(v).shape[0] == steps
               for v in metrics.values())


def test_epoch_runner_rejects_tiny_dataset():
    import pytest as _pytest
    from veles_tpu.znicz.fused_graph import epoch_runner

    with _pytest.raises(ValueError):
        epoch_runner(lambda p, x, y: (p, {}), n_samples=4, batch=8)


def test_data_parallel_epoch_matches_single_device():
    """One-program DP epoch over the 8-device mesh: the globally-
    permuted sampling makes its result comparable to the single-device
    epoch_runner with the same key — params agree to float tolerance,
    while the dataset lives sharded over the data axis."""
    import jax
    import numpy
    from veles_tpu.parallel.dp import data_parallel_epoch
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.znicz.fused_graph import epoch_runner, lower_specs

    rng = numpy.random.default_rng(2)
    n, batch = 64, 16
    data = rng.integers(0, 256, (n, 12)).astype(numpy.uint8)
    labels = rng.integers(0, 4, n).astype(numpy.int32)
    specs = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 6},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    params, step_fn, _e, _a = lower_specs(
        specs, (12,),
        input_norm=(numpy.float32(1 / 255.0), numpy.float32(0.0)))

    key = jax.random.key(3)
    single = jax.jit(epoch_runner(step_fn, n, batch))
    p_single, m_single = single(params, data, labels, key)

    mesh = make_mesh({"data": 8})
    dp_epoch = data_parallel_epoch(step_fn, mesh, params, n, batch)
    p_dp, m_dp = dp_epoch(params, data, labels, key)
    for a, b in zip(jax.tree.leaves(p_single), jax.tree.leaves(p_dp)):
        numpy.testing.assert_allclose(numpy.asarray(a),
                                      numpy.asarray(b),
                                      rtol=1e-5, atol=1e-6)
    numpy.testing.assert_allclose(
        numpy.asarray(m_single["loss"]), numpy.asarray(m_dp["loss"]),
        rtol=1e-5, atol=1e-6)
    # the dataset really was sharded over the mesh's data axis
    placed = jax.device_put(
        data, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("data")))
    assert not placed.sharding.is_fully_replicated


def test_data_parallel_epoch_local_matches_simulation():
    """Local-sampler DP epoch (shard_map + in-step pmean): each shard
    permutes its own slice; the update equals a single-device step on
    the CONCATENATION of all shards' m-th local minibatches (equal
    shard batches make the pmean of per-shard mean-grads the global-
    batch gradient).  Verified against that exact simulation."""
    import jax
    import numpy
    from veles_tpu.parallel.dp import data_parallel_epoch_local
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.znicz.fused_graph import lower_specs

    shards, n_local, batch_local = 4, 16, 4
    n = shards * n_local
    rng = numpy.random.default_rng(4)
    data = rng.integers(0, 256, (n, 12)).astype(numpy.uint8)
    labels = rng.integers(0, 4, n).astype(numpy.int32)
    specs = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 6},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    norm = (numpy.float32(1 / 255.0), numpy.float32(0.0))
    params, step_red, _e, _a = lower_specs(
        specs, (12,), input_norm=norm, grad_reduce_axis="data")
    mesh = make_mesh({"data": shards})
    key = jax.random.key(11)
    epoch_fn = data_parallel_epoch_local(step_red, mesh, n_local,
                                         batch_local)
    p_mesh, m_mesh = epoch_fn(params, data, labels, key)

    # single-device simulation of the same semantics (REUSING the
    # same initial params — lower_specs draws from the stateful init
    # PRNG, so a second call would start from different weights)
    _params2, step_plain, _e2, _a2 = lower_specs(
        specs, (12,), input_norm=norm)
    step_plain = jax.jit(step_plain)
    perms = [numpy.asarray(jax.random.permutation(
        jax.random.fold_in(key, i), n_local)) for i in range(shards)]
    steps = n_local // batch_local
    p_sim = params
    for m in range(steps):
        idx = numpy.concatenate([
            i * n_local + perms[i][m * batch_local:(m + 1) * batch_local]
            for i in range(shards)])
        p_sim, m_sim = step_plain(p_sim, data[idx], labels[idx])

    for a, b in zip(jax.tree.leaves(p_mesh), jax.tree.leaves(p_sim)):
        numpy.testing.assert_allclose(numpy.asarray(a),
                                      numpy.asarray(b),
                                      rtol=1e-5, atol=1e-6)
    # final minibatch's globally-reduced error count matches too
    assert float(numpy.asarray(m_mesh["n_err"])[-1]) == \
        float(numpy.asarray(m_sim["n_err"]))


def test_fused_epoch_mode_trains_and_keeps_decision_stream():
    """fused_config={'epoch_mode': True}: the whole TRAIN epoch runs
    as one program; Decision still receives a per-minibatch metric
    stream and the workflow trains to the usual synthetic accuracy.
    minibatch 512 does NOT divide the train set, so the dropped-tail
    replay leg is exercised too."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(1)
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=3, minibatch_size=512,
        fused=True, fused_config={"epoch_mode": True})
    assert wf.fused_trainer.epoch_mode
    wf.run()
    results = wf.gather_results()
    assert results["best_validation_error_pt"] < 35.0
    # the epoch program really was built and consumed
    assert wf.fused_trainer._epoch_fn_ is not None
    assert wf.fused_trainer.epoch_key_counter >= 2
    # weights synced back into the unit graph at epoch boundaries
    wf.forwards[0].weights.map_read()
    import numpy as _np
    assert float(_np.abs(wf.forwards[0].weights.mem).max()) > 0


def test_fused_epoch_mode_on_mesh():
    """'One workflow, any mode' (ref manualrst_veles_distributed_
    training.rst:14-16): StandardWorkflow(fused, epoch_mode,
    mesh_axes) routes the whole-epoch program through
    parallel.dp.data_parallel_epoch — batch sharded over the 8-device
    CPU mesh, gradient all-reduce inside the one-dispatch epoch —
    and still trains to the usual synthetic accuracy (VERDICT r4
    next-round item 5)."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(1)
    wf = mnist.create_workflow(
        device=CPUDevice(), max_epochs=3, minibatch_size=512,
        fused=True,
        fused_config={"epoch_mode": True, "mesh_axes": {"data": -1}})
    wf.run()
    results = wf.gather_results()
    assert results["best_validation_error_pt"] < 35.0
    assert wf.fused_trainer._epoch_fn_ is not None
    # the resident TRAIN slice really is sharded over the data axis
    assert not wf.fused_trainer._epoch_data_.sharding \
        .is_fully_replicated


def test_fused_epoch_mode_mse_autoencoder():
    """epoch_mode with the MSE loss (the AE family): the epoch
    program gathers resident float targets and the per-minibatch
    replay feeds Decision's mse stream (VERDICT r4 item 5)."""
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist_ae

    prng.seed_all(2)
    wf = mnist_ae.create_workflow(
        device=CPUDevice(), max_epochs=2, minibatch_size=500,
        fused=True, fused_config={"epoch_mode": True})
    wf.run()
    assert wf.fused_trainer._epoch_fn_ is not None
    assert wf.fused_trainer.epoch_key_counter >= 1
    # the replay populated the mse metric (an RMSE, finite, nonzero)
    results = wf.gather_results()
    assert 0.0 < results["best_rmse"] < 10.0


def test_fused_epoch_mode_rejects_train_ratio():
    # bagged runs (train_ratio) are per-minibatch-path only
    from veles_tpu.backends import CPUDevice
    from veles_tpu.samples import mnist

    prng.seed_all(2)
    wf3 = mnist.create_workflow(
        device=CPUDevice(), max_epochs=1, minibatch_size=500,
        fused=True, fused_config={"epoch_mode": True})
    wf3.loader.train_ratio = 0.5
    with pytest.raises(NotImplementedError):
        wf3.run()


def test_data_parallel_epoch_with_tp_rules():
    """DP×TP one-program epoch: epoch_runner's jit composition accepts
    param_rules, so wide layers shard column-parallel over 'model'
    while the epoch result still matches the single-device run."""
    import jax
    import numpy
    from veles_tpu.parallel.dp import data_parallel_epoch, tp_rules
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.znicz.fused_graph import epoch_runner, lower_specs

    rng = numpy.random.default_rng(9)
    n, batch = 32, 8
    data = rng.integers(0, 256, (n, 12)).astype(numpy.uint8)
    labels = rng.integers(0, 4, n).astype(numpy.int32)
    specs = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 64},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]
    params, step_fn, _e, _a = lower_specs(
        specs, (12,),
        input_norm=(numpy.float32(1 / 255.0), numpy.float32(0.0)))
    key = jax.random.key(5)
    p_single, _m = jax.jit(epoch_runner(step_fn, n, batch))(
        params, data, labels, key)

    mesh = make_mesh({"data": 2, "model": 4})
    rules = tp_rules(mesh, min_elements=64)
    epoch_fn = data_parallel_epoch(step_fn, mesh, params, n, batch,
                                   param_rules=rules)
    p_mesh, _m2 = epoch_fn(params, data, labels, key)
    # the wide layer's weight really is model-sharded
    w0 = p_mesh[0]["w"]
    assert not w0.sharding.is_fully_replicated
    for a, b in zip(jax.tree.leaves(p_single), jax.tree.leaves(p_mesh)):
        numpy.testing.assert_allclose(numpy.asarray(a),
                                      numpy.asarray(b),
                                      rtol=1e-4, atol=1e-5)
