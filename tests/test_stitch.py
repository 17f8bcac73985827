"""Eager unit-chain fast path (veles_tpu.stitch): segment construction
over the standard training graph, O(segments) dispatch counts per
minibatch, stitched↔unstitched numerical parity (weights AND metrics,
short epoch tails included), gate-semantics regressions (Repeater
re-fire, Decision barrier, shared TRAIN skip gate, ``stitch=off``
restoring the per-unit path), deferred device-scalar metrics, and the
``-m slow`` throughput floor: stitched ≥ 1.5× unstitched on CPU JAX."""

import time

import numpy
import pytest

from veles_tpu import prng
from veles_tpu.backends import CPUDevice, NumpyDevice
from veles_tpu.config import root
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.znicz.standard_workflow import StandardWorkflow


class BlobLoader(FullBatchLoader):
    """Separable 10-class gaussian blobs (the test_znicz_mlp stand-in),
    sized so minibatch 48 leaves short epoch tails in BOTH classes."""

    def __init__(self, workflow, n_train=400, n_valid=100, dim=64,
                 **kwargs):
        self._cfg = (n_train, n_valid, dim)
        super(BlobLoader, self).__init__(workflow, **kwargs)

    def load_data(self):
        n_train, n_valid, dim = self._cfg
        rng = numpy.random.default_rng(42)
        total = n_train + n_valid
        labels = numpy.tile(numpy.arange(10), total // 10 + 1)[:total]
        centers = rng.standard_normal((10, dim)) * 3.0
        data = centers[labels] + rng.standard_normal((total, dim)) * 0.7
        self.original_data.mem = data.astype(numpy.float32)
        self.original_labels = list(int(x) for x in labels)
        self.class_lengths[:] = [0, n_valid, n_train]


def _layers(hidden=32, lr=0.05):
    return [
        {"type": "all2all_tanh", "->": {"output_sample_shape": hidden},
         "<-": {"learning_rate": lr, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": lr, "gradient_moment": 0.9}},
    ]


def build(device, max_epochs=3, minibatch_size=48, seed=5, **loader_kw):
    prng.seed_all(seed)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: BlobLoader(
            w, minibatch_size=minibatch_size, **loader_kw),
        layers=_layers(),
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 10 ** 6})
    wf.launcher = DummyLauncher()
    wf.initialize(device=device)
    return wf


@pytest.fixture
def stitch_config():
    """Snapshot/restore the engine knobs every test touches."""
    saved = (root.common.engine.get("stitch", "on"),
             root.common.engine.get("metrics_every", 0))
    yield root.common.engine
    root.common.engine.stitch = saved[0]
    root.common.engine.metrics_every = saved[1]


def _params(wf):
    """Every trained buffer: weights AND biases AND momentum state —
    misrouted per-stage hyper-parameters (e.g. a bias lr reading
    another layer's slot) must not hide behind weights-only checks."""
    out = []
    for fwd in wf.forwards:
        fwd.weights.map_read()
        out.append(numpy.array(fwd.weights.mem))
        fwd.bias.map_read()
        out.append(numpy.array(fwd.bias.mem))
    for gd in wf.gds:
        gd.gradient_weights.map_read()
        out.append(numpy.array(gd.gradient_weights.mem))
        gd.gradient_bias.map_read()
        out.append(numpy.array(gd.gradient_bias.mem))
    return out


# -- construction -----------------------------------------------------------

def test_segments_cover_forward_and_gd_chains(stitch_config):
    wf = build(CPUDevice())
    report = wf.stitch_report()
    assert report["enabled"]
    # exactly two segments: [loader, forwards..., evaluator] (the
    # device-resident input pipeline heads the first program) and
    # [gd chain]; decision / plumbing stay barriers
    assert len(report["segments"]) == 2
    fwd_names = [wf.loader.name] + [u.name for u in wf.forwards] \
        + [wf.evaluator.name]
    gd_names = [u.name for u in wf.gds]
    assert report["segments"][0] == fwd_names
    assert report["segments"][1] == gd_names
    assert report["loader_headed"] == [True, False]
    flat = [n for names in report["segments"] for n in names]
    assert wf.decision.name not in flat
    # gd members share the head's TRAIN skip gate (the eligibility rule)
    head_gate = wf.gds[0].gate_skip
    assert all(gd.gate_skip is head_gate for gd in wf.gds)


def test_loader_stays_barrier_under_host_mode(stitch_config):
    """engine.loader=host restores the PR 3 segment shape: the loader
    drops out of the first program and serves host-side."""
    saved = root.common.engine.get("loader", "auto")
    root.common.engine.loader = "host"
    try:
        wf = build(CPUDevice())
        report = wf.stitch_report()
        assert len(report["segments"]) == 2
        assert report["segments"][0][0] == wf.forwards[0].name
        assert report["loader_headed"] == [False, False]
        wf.run()
        assert wf.stopped
        assert wf.stitch_report()["dispatches"] > 0
    finally:
        root.common.engine.loader = saved


def test_stitch_on_flip_after_off_initialize_engages(stitch_config):
    """The switch is honored per run in BOTH directions: initialize
    under off, flip on, run — segments build once and engage."""
    stitch_config.stitch = "off"
    wf = build(CPUDevice(), max_epochs=2)
    assert wf.stitch_report()["segments"] == []
    stitch_config.stitch = "on"
    wf.run()
    assert len(wf.stitch_report()["segments"]) == 2
    assert wf.stitch_report()["dispatches"] > 0


def test_interpret_device_builds_no_segments(stitch_config):
    wf = build(NumpyDevice())
    assert wf.stitch_report()["segments"] == []
    wf.run()        # the plain path still trains to completion
    assert wf.stopped


def test_stitch_off_restores_per_unit_path(stitch_config, monkeypatch):
    stitch_config.stitch = "off"
    wf = build(CPUDevice(), max_epochs=2)
    assert wf.stitch_report()["segments"] == []
    calls = {"fwd": 0}
    from veles_tpu.znicz.all2all import All2All
    orig = All2All.tpu_run

    def counting(self):
        calls["fwd"] += 1
        return orig(self)

    monkeypatch.setattr(All2All, "tpu_run", counting)
    wf.run()
    assert wf.stopped
    assert calls["fwd"] > 0     # the seed per-unit dispatch path ran


# -- dispatch counts --------------------------------------------------------

def test_dispatches_are_per_segment_not_per_unit(stitch_config,
                                                 monkeypatch):
    """Per minibatch the scheduler launches O(segments) programs: ONE
    for the forward+evaluator chain (every minibatch) and ONE for the
    gd chain (TRAIN minibatches only — the Decision barrier and the
    shared skip gate are untouched); the stitched units' own per-unit
    programs never run."""
    wf = build(CPUDevice(), max_epochs=2)
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.znicz.all2all import All2All
    from veles_tpu.znicz.evaluator import EvaluatorSoftmax
    from veles_tpu.znicz.gd import GradientDescent
    for klass in (All2All, EvaluatorSoftmax, GradientDescent):
        monkeypatch.setattr(
            klass, "tpu_run",
            lambda self: pytest.fail(
                "%s.tpu_run dispatched per-unit during a stitched "
                "run" % type(self).__name__))
    served = {"total": 0, "train": 0}
    orig_serve = type(wf.loader).serve_next_minibatch

    def counting_serve(self, consumer, **kwargs):
        orig_serve(self, consumer, **kwargs)
        served["total"] += 1
        if int(self.minibatch_class) == TRAIN:
            served["train"] += 1

    monkeypatch.setattr(type(wf.loader), "serve_next_minibatch",
                        counting_serve)
    wf.run()
    assert wf.stopped
    fwd_seg, gd_seg = wf._stitch_segments_
    assert served["total"] > 0 and served["train"] > 0
    assert fwd_seg.dispatches == served["total"]
    assert gd_seg.dispatches == served["train"]
    assert wf.stitch_report()["dispatches"] == \
        served["total"] + served["train"]


# -- numerical parity -------------------------------------------------------

#: deliberately DISTINCT hyper-parameters per layer and per bias: a
#: stitched stage reading a neighbour stage's (or its weight slot's)
#: scalar cannot alias into a passing run
_ASYMMETRIC_LAYERS = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 32},
     "<-": {"learning_rate": 0.05, "learning_rate_bias": 0.02,
            "gradient_moment": 0.9, "gradient_moment_bias": 0.5,
            "weights_decay": 0.0005, "weights_decay_bias": 0.002}},
    {"type": "softmax", "->": {"output_sample_shape": 10},
     "<-": {"learning_rate": 0.01, "learning_rate_bias": 0.07,
            "gradient_moment": 0.3, "gradient_moment_bias": 0.8,
            "weights_decay": 0.003, "weights_decay_bias": 0.0001}},
]


def test_stitched_matches_unstitched_weights_and_metrics(stitch_config):
    def build_asym():
        prng.seed_all(5)
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: BlobLoader(w, minibatch_size=48),
            layers=[{**s} for s in _ASYMMETRIC_LAYERS],
            decision_config={"max_epochs": 3,
                             "fail_iterations": 10 ** 6})
        wf.launcher = DummyLauncher()
        wf.initialize(device=CPUDevice())
        return wf

    stitch_config.stitch = "on"
    wf_on = build_asym()
    wf_on.run()
    stitch_config.stitch = "off"
    wf_off = build_asym()
    wf_off.run()
    assert wf_on.stitch_report()["dispatches"] > 0
    assert wf_off.stitch_report()["dispatches"] == 0
    for w_on, w_off in zip(_params(wf_on), _params(wf_off)):
        numpy.testing.assert_allclose(w_on, w_off, atol=5e-3)
    # epoch metrics flushed to plain host floats, and they agree
    for cls in (1, 2):
        a = wf_on.decision.epoch_n_err_pt[cls]
        b = wf_off.decision.epoch_n_err_pt[cls]
        assert isinstance(a, float) and abs(a - b) < 1.0
    assert abs(wf_on.decision.best_n_err_pt
               - wf_off.decision.best_n_err_pt) < 1.0
    # the stitched confusion matrix (device-accumulated) matches up to
    # argmax boundary flips from float drift (<2% of samples moved)
    cm_on = numpy.array(wf_on.evaluator.confusion_matrix.mem)
    cm_off = numpy.array(wf_off.evaluator.confusion_matrix.mem)
    assert cm_on.sum() == cm_off.sum() > 0
    assert numpy.abs(cm_on - cm_off).sum() <= 0.02 * cm_on.sum()


def test_unstitched_chain_is_given_no_host_array(stitch_config):
    """A Vector with no device answers ``devmem`` with its host array
    itself; the eager chain's jitted evaluator then read the loader's
    labels while the next fill rewrote them (memory.py's rule).  What a
    loader serves has the workflow's device, stitched or not."""
    stitch_config.stitch = "off"
    wf = build(CPUDevice())
    assert wf.stitch_report()["dispatches"] == 0
    wf.loader.run()
    for vec in (wf.loader.minibatch_data, wf.loader.minibatch_labels,
                wf.evaluator.labels, wf.forwards[0].input):
        assert vec.device is wf.device
        assert not isinstance(vec.devmem, numpy.ndarray)


def test_deferred_metrics_are_device_scalars_until_flush(stitch_config):
    wf = build(CPUDevice(), max_epochs=2)
    wf.run()
    # per-minibatch metric stayed a device scalar (no per-step float())
    assert not isinstance(wf.evaluator.n_err, (int, float))
    assert hasattr(wf.evaluator.n_err, "dtype")
    # ...but every epoch close flushed to plain host numbers (the
    # close also resets the bucket to int 0), nothing left pending
    assert all(isinstance(v, (int, float))
               for v in wf.decision.epoch_n_err)
    assert all(not p for p in wf.decision._pending_metrics_)


def test_metrics_every_cadence_matches_boundary_flush(stitch_config):
    stitch_config.metrics_every = 1      # flush every minibatch
    wf_k1 = build(CPUDevice(), max_epochs=3)
    wf_k1.run()
    stitch_config.metrics_every = 0      # epoch-boundary only
    wf_k0 = build(CPUDevice(), max_epochs=3)
    wf_k0.run()
    assert wf_k1.decision.best_n_err_pt == \
        pytest.approx(wf_k0.decision.best_n_err_pt, abs=1e-9)


# -- gate semantics regressions ---------------------------------------------

def test_repeater_refires_stitched_loop_to_max_epochs(stitch_config):
    wf = build(CPUDevice(), max_epochs=4)
    wf.run()
    assert wf.stopped
    # decision completes when epoch_number+1 reaches max_epochs, so the
    # Repeater's back edge re-fired the stitched loop through 3 full
    # epoch wraps (the seed loop semantics, unchanged)
    assert wf.loader.epoch_number == 3
    assert bool(wf.decision.complete)


def test_manual_unit_run_keeps_per_unit_semantics(stitch_config):
    """Direct unit.run() calls (how tests and debuggers drive the
    graph) bypass segments entirely — the fuzz/parity harnesses keep
    their exact seed semantics."""
    wf = build(CPUDevice(), max_epochs=1)
    wf.loader.run()
    from veles_tpu.loader.base import TRAIN
    while int(wf.loader.minibatch_class) != TRAIN:
        wf.loader.run()
    for fwd in wf.forwards:
        fwd.run()
    wf.evaluator.run()
    before = numpy.array(wf.forwards[1].weights.mem)
    wf.gds[0].run()
    wf.forwards[1].weights.map_read()
    after = numpy.array(wf.forwards[1].weights.mem)
    assert not numpy.allclose(before, after)
    assert wf._stitch_segments_[0].dispatches == 0   # never engaged


def test_mse_evaluator_device_matches_host():
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.memory import Vector
    from veles_tpu.znicz.evaluator import EvaluatorMSE
    rng = numpy.random.default_rng(4)
    out = rng.standard_normal((8, 3)).astype(numpy.float32)
    target = rng.standard_normal((8, 3)).astype(numpy.float32)

    def run(device, batch):
        wf = DummyWorkflow()
        ev = EvaluatorMSE(wf)
        ev.output = Vector(out.copy())
        ev.target = Vector(target.copy())
        ev.err_output = Vector(numpy.zeros((8, 3), numpy.float32))
        ev.batch_size = batch
        for vec in (ev.output, ev.target, ev.err_output):
            vec.initialize(device)
        ev.device = device
        ev.run()
        return numpy.array(ev.err_output.mem), float(ev.mse)

    for batch in (8, 5):        # full and short (masked tail) batches
        err_host, mse_host = run(NumpyDevice(), batch)
        err_dev, mse_dev = run(CPUDevice(), batch)
        numpy.testing.assert_allclose(err_dev, err_host, atol=1e-6)
        assert mse_dev == pytest.approx(mse_host, abs=1e-5)

    # unnormalized-activation regime: err² overflows float32 — the host
    # squares in f64, the device rescales per row; both must agree
    out *= numpy.float32(1e22)
    target *= 0.0
    err_host, mse_host = run(NumpyDevice(), 8)
    err_dev, mse_dev = run(CPUDevice(), 8)
    assert numpy.isfinite(mse_dev)
    assert mse_dev == pytest.approx(mse_host, rel=1e-5)


def test_softmax_evaluator_device_matches_host():
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.memory import Vector
    from veles_tpu.znicz.evaluator import EvaluatorSoftmax
    rng = numpy.random.default_rng(7)
    logits = rng.standard_normal((6, 4)).astype(numpy.float32)
    sm = numpy.exp(logits) / numpy.exp(logits).sum(1, keepdims=True)
    labels = numpy.array([0, 3, 2, -1, 1, -1], numpy.int32)
    max_idx = logits.argmax(1).astype(numpy.int32)

    def run(device):
        wf = DummyWorkflow()
        ev = EvaluatorSoftmax(wf)
        ev.output = Vector(sm.copy())
        ev.labels = Vector(labels.copy())
        ev.max_idx = Vector(max_idx.copy())
        ev.err_output = Vector(numpy.zeros((6, 4), numpy.float32))
        ev.confusion_matrix.reset(numpy.zeros((4, 4), numpy.int64))
        ev.batch_size = 6
        for vec in (ev.output, ev.labels, ev.max_idx, ev.err_output,
                    ev.confusion_matrix):
            vec.initialize(device)
        ev.device = device
        ev.run()
        return (numpy.array(ev.err_output.mem), int(ev.n_err),
                float(ev.loss), numpy.array(ev.confusion_matrix.mem))

    err_h, n_h, loss_h, cm_h = run(NumpyDevice())
    err_d, n_d, loss_d, cm_d = run(CPUDevice())
    numpy.testing.assert_allclose(err_d, err_h, atol=1e-6)
    assert n_d == n_h
    assert loss_d == pytest.approx(loss_h, abs=1e-6)
    numpy.testing.assert_array_equal(cm_d, cm_h)


def test_job_layer_slave_trains_through_segments(stitch_config):
    """The elastic job layer — the path the eager chain exists for —
    dispatches O(segments) programs per job: slave-mode graph surgery
    re-stitches, the JobClient handshake reports it, and the master
    still converges on the merged deltas."""
    from veles_tpu.parallel.jobs import JobClient, JobServer

    def mk(device, **flags):
        prng.seed_all(1234)
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: BlobLoader(w, minibatch_size=50),
            layers=_layers(),
            decision_config={"max_epochs": 3,
                             "fail_iterations": 10 ** 6},
            launcher=DummyLauncher(**flags))
        wf.initialize(device=device)
        return wf

    master = mk(NumpyDevice(), is_master=True)
    slave = mk(CPUDevice(), is_slave=True)
    server = JobServer(master).start()
    try:
        client = JobClient(slave, server.endpoint)
        client.handshake()
        assert len(slave.stitch_report()["segments"]) == 2
        assert client.run()
        client.close()
    finally:
        server.stop()
    assert client.jobs_done > 0
    assert slave.stitch_report()["dispatches"] > client.jobs_done
    assert master.decision.best_n_err_pt < 10.0


# -- throughput floor -------------------------------------------------------

@pytest.mark.slow
def test_stitched_throughput_floor_cpu(stitch_config):
    """In-process CPU JAX: a dispatch-bound eager config (tiny layers,
    batch 16) must run ≥ 1.5× faster stitched than unstitched —
    locally measured ~2.5×; the floor leaves CI headroom."""

    def measure(stitch):
        stitch_config.stitch = stitch
        prng.seed_all(5)
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: BlobLoader(
                w, n_train=640, n_valid=160, dim=32,
                minibatch_size=16),
            layers=_layers(hidden=16),
            decision_config={"max_epochs": 2,
                             "fail_iterations": 10 ** 6})
        wf.launcher = DummyLauncher()
        wf.initialize(device=CPUDevice())
        wf.run()                          # warm: compiles included
        wf.decision.complete <<= False
        wf.decision.max_epochs = 8
        tic = time.perf_counter()
        wf.run()                          # six warm epochs
        elapsed = time.perf_counter() - tic
        assert wf.stopped
        return elapsed

    t_on = measure("on")
    t_off = measure("off")
    assert t_off / t_on >= 1.5, \
        "stitched %.3fs vs unstitched %.3fs (%.2fx < 1.5x floor)" % (
            t_on, t_off, t_off / t_on)
