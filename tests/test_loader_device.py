"""Device-resident input pipeline (ISSUE 4 acceptance): device-loader ↔
host-loader parity (identical minibatch streams, identical end-of-epoch
metrics incl. confusion matrix, short-final-batch masking), the
loader-headed segment in ``wf.stitch_report()``, zero per-step
``device_put`` on the FullBatch fast path (transfer-intercept fixture
over ``Device.put`` — the Vector/staging upload seam), slave jobs
re-using the resident dataset, and the ``-m slow`` ≥ 1.3× floor over
the host-loader stitched path."""

import time

import numpy
import pytest

from veles_tpu import prng
from veles_tpu.backends import CPUDevice, NumpyDevice
from veles_tpu.config import root
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN
from veles_tpu.loader.fullbatch import FullBatchLoader, FullBatchLoaderMSE
from veles_tpu.znicz.standard_workflow import StandardWorkflow


class BlobLoader(FullBatchLoader):
    """Separable 10-class blobs; default sizes leave SHORT final
    batches in both the validation and the train span (100 % 48,
    400 % 48 != 0) so tail masking is always exercised."""

    def __init__(self, workflow, n_train=400, n_valid=100, dim=32,
                 **kwargs):
        self._cfg = (n_train, n_valid, dim)
        self.serve_record = []
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

    def serve_next_minibatch(self, consumer, **kwargs):
        super(BlobLoader, self).serve_next_minibatch(consumer, **kwargs)
        self.minibatch_indices.map_read()
        self.serve_record.append((
            int(self.minibatch_class), int(self.minibatch_offset),
            int(self.minibatch_size),
            tuple(int(i) for i in
                  self.minibatch_indices.mem[:self.minibatch_size])))


@pytest.fixture
def loader_mode():
    """Snapshot/restore the engine.loader knob."""
    saved = root.common.engine.get("loader", "auto")

    def set_mode(mode):
        root.common.engine.loader = mode
    yield set_mode
    root.common.engine.loader = saved


def _layers(hidden=32):
    return [
        {"type": "all2all_tanh", "->": {"output_sample_shape": hidden},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
    ]


def _build(device=None, minibatch_size=48, max_epochs=3, seed=5,
           **loader_kw):
    prng.seed_all(seed)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: BlobLoader(
            w, minibatch_size=minibatch_size, **loader_kw),
        layers=_layers(),
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 10 ** 6})
    wf.launcher = DummyLauncher()
    wf.initialize(device=device or CPUDevice())
    return wf


class SynthMSE(FullBatchLoaderMSE):
    def load_data(self):
        rng = numpy.random.default_rng(3)
        data = rng.standard_normal((120, 12)).astype(numpy.float32)
        self.original_data.mem = data
        self.original_targets.mem = (
            data[:, :4] * 0.5).astype(numpy.float32)
        self.class_lengths[:] = [0, 40, 80]


def _build_mse():
    prng.seed_all(7)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: SynthMSE(w, minibatch_size=32),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 8},
                 "<-": {"learning_rate": 0.05}},
                {"type": "all2all",
                 "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05}}],
        loss_function="mse",
        decision_config={"max_epochs": 3, "fail_iterations": 10 ** 6})
    wf.launcher = DummyLauncher()
    wf.initialize(device=CPUDevice())
    return wf


# -- segment shape ----------------------------------------------------------

def test_loader_heads_first_segment_in_report(loader_mode):
    set_mode = loader_mode
    set_mode("device")
    wf = _build()
    report = wf.stitch_report()
    assert report["segments"][0][0] == wf.loader.name
    assert report["loader_headed"] == [True, False]
    assert wf.loader.device_fast_path_active
    # auto resolves the same way on a jit device
    set_mode("auto")
    assert wf.loader.device_fast_path_active
    # host (and interpret devices) keep the loader a barrier
    set_mode("host")
    assert not wf.loader.device_fast_path_active
    wf_host = _build()
    assert wf_host.stitch_report()["loader_headed"] == [False, False]
    set_mode("auto")
    wf_np = _build(device=NumpyDevice())
    assert not wf_np.loader.device_fast_path_active


def test_store_in_device_memory_off_disables_fast_path(loader_mode):
    loader_mode("device")
    wf = _build(store_in_device_memory=False)
    assert not wf.loader.device_fast_path_active
    wf.run()    # the host path still trains to completion
    assert wf.stopped


# -- gather correctness -----------------------------------------------------

def test_in_program_gather_matches_host_reference(loader_mode):
    """Drive the loader-headed segment for a full epoch-and-a-half and
    verify EVERY dispatch against a host reference gather: values,
    label mapping, short-final-batch zero/-1 masking, epoch-wrap
    reshuffle pickup."""
    loader_mode("device")
    wf = _build(max_epochs=100)
    loader = wf.loader
    seg = wf._stitch_segments_[0]
    assert seg.head is loader
    for _ in range(18):     # > one epoch of ceil(500/48)=11 serves
        seg.execute()
        size = loader.minibatch_size
        start = loader.minibatch_offset - size
        loader.shuffled_indices.map_read()
        idx = numpy.array(loader.shuffled_indices.mem[start:start + size])
        loader.minibatch_data.map_read()
        data = loader.minibatch_data.mem
        loader.original_data.map_read()
        numpy.testing.assert_array_equal(
            data[:size], loader.original_data.mem[idx])
        assert (data[size:] == 0).all()
        loader.minibatch_labels.map_read()
        labels = loader.minibatch_labels.mem
        expect = numpy.asarray(loader._mapped_labels)[idx]
        numpy.testing.assert_array_equal(labels[:size], expect)
        assert (labels[size:] == -1).all()
        # the host index mirror agrees (fill_indices -1 tail included)
        loader.minibatch_indices.map_read()
        numpy.testing.assert_array_equal(
            loader.minibatch_indices.mem[:size], idx)
        assert (loader.minibatch_indices.mem[size:] == -1).all()


# -- parity -----------------------------------------------------------------

def test_device_host_parity_streams_metrics_confusion(loader_mode):
    """Identical minibatch streams (class/offset/size/indices per
    serve), end-of-epoch error metrics and confusion matrix between
    the device fast path and the host loader."""
    loader_mode("device")
    wf_dev = _build()
    wf_dev.run()
    loader_mode("host")
    wf_host = _build()
    wf_host.run()
    assert wf_dev.stopped and wf_host.stopped
    # the device run really went through the loader-headed segment
    assert wf_dev.stitch_report()["loader_headed"][0]
    assert wf_dev._stitch_segments_[0].dispatches == \
        len(wf_dev.loader.serve_record)
    # identical serve streams
    assert wf_dev.loader.serve_record == wf_host.loader.serve_record
    # identical end-of-epoch metrics
    for cls in (1, 2):
        a = wf_dev.decision.epoch_n_err_pt[cls]
        b = wf_host.decision.epoch_n_err_pt[cls]
        assert abs(a - b) < 0.5, (cls, a, b)
    assert abs(wf_dev.decision.best_n_err_pt
               - wf_host.decision.best_n_err_pt) < 0.5
    # identical confusion matrices (device-accumulated vs host-fed)
    cm_dev = numpy.array(wf_dev.evaluator.confusion_matrix.mem)
    cm_host = numpy.array(wf_host.evaluator.confusion_matrix.mem)
    assert cm_dev.sum() == cm_host.sum() > 0
    assert numpy.abs(cm_dev - cm_host).sum() <= 0.02 * cm_dev.sum()
    # and the trained parameters agree
    for f_dev, f_host in zip(wf_dev.forwards, wf_host.forwards):
        f_dev.weights.map_read()
        f_host.weights.map_read()
        numpy.testing.assert_allclose(
            f_dev.weights.mem, f_host.weights.mem, atol=5e-3)


def test_mse_targets_ride_the_device_stage(loader_mode):
    """FullBatchLoaderMSE extends the in-program gather with targets —
    an MSE workflow trains through the loader-headed segment and
    matches the host path."""
    loader_mode("device")
    wf_dev = _build_mse()
    assert wf_dev.stitch_report()["loader_headed"][0]
    assert "minibatch_targets" in [
        name for name, *_rest in wf_dev.loader._device_stage_plan()]
    wf_dev.run()
    loader_mode("host")
    wf_host = _build_mse()
    wf_host.run()
    assert wf_dev.decision.best_mse == pytest.approx(
        wf_host.decision.best_mse, rel=1e-3)


def _trained(wf):
    out = []
    for fwd in wf.forwards:
        for vec in (fwd.weights, fwd.bias):
            vec.map_read()
            out.append(numpy.array(vec.mem))
    return out


@pytest.mark.parametrize("loss", ["softmax", "mse"])
def test_host_path_trains_on_the_rows_it_served(loss, loader_mode,
                                                aligned):
    """The host loader's gather takes its indices from a Vector whose
    host array ``fill_indices`` rewrites for the NEXT minibatch; jax's
    CPU backend takes an aligned argument without a copy and runs the
    gather later.  With that array aligned, the host path must still
    end, every time, where the device path (in-program gather, no such
    argument) ends."""
    def build(mode):
        loader_mode(mode)
        return _build() if loss == "softmax" else _build_mse()

    wf_dev = build("device")
    wf_dev.run()
    for _ in range(5):
        wf_host = build("host")
        indices = wf_host.loader.minibatch_indices
        indices.reset(aligned(indices.shape, indices.dtype))
        wf_host.run()
        for dev, host in zip(_trained(wf_dev), _trained(wf_host)):
            numpy.testing.assert_array_equal(host, dev)
        if loss == "mse":
            assert wf_host.decision.best_mse == wf_dev.decision.best_mse


# -- transfer elimination ---------------------------------------------------

@pytest.fixture
def put_counter(monkeypatch):
    """Transfer-intercept fixture: counts every Device.put — the seam
    every Vector upload and staging upload goes through."""
    calls = []
    orig = CPUDevice.put

    def counting(self, array):
        calls.append(int(numpy.asarray(array).nbytes))
        return orig(self, array)

    monkeypatch.setattr(CPUDevice, "put", counting)
    return calls


def test_zero_per_step_device_put_on_fast_path(loader_mode,
                                               put_counter):
    loader_mode("device")
    wf = _build(max_epochs=2)
    wf.run()        # warm: one-time dataset/labels/index/param uploads
    steps_before = len(wf.loader.serve_record)
    puts_before = len(put_counter)
    wf.decision.complete <<= False
    wf.decision.max_epochs = wf.loader.epoch_number + 1 + 3
    wf.run()        # three more warm epochs
    steps = len(wf.loader.serve_record) - steps_before
    puts = len(put_counter) - puts_before
    assert steps >= 30
    # the only allowed uploads are the per-epoch-wrap re-uploads of
    # the (small) shuffled-index buffer — nothing per step
    assert puts <= 4, (puts, steps)


def test_host_loader_pays_per_step_uploads(loader_mode, put_counter):
    """The contrast line for the fixture: the host path uploads at
    least the label buffer every serve."""
    loader_mode("host")
    wf = _build(max_epochs=2)
    wf.run()
    puts_before = len(put_counter)
    steps_before = len(wf.loader.serve_record)
    wf.decision.complete <<= False
    wf.decision.max_epochs = wf.loader.epoch_number + 1 + 1
    wf.run()
    steps = len(wf.loader.serve_record) - steps_before
    puts = len(put_counter) - puts_before
    assert puts >= steps


# -- job layer --------------------------------------------------------------

def _mk_distributed(loader_mode_value, prefetch=False, **flags):
    root.common.engine.loader = loader_mode_value
    prng.seed_all(1234)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: BlobLoader(
            w, minibatch_size=50, prefetch=prefetch),
        layers=_layers(),
        decision_config={"max_epochs": 2, "fail_iterations": 10 ** 6},
        launcher=DummyLauncher(**flags))
    device = NumpyDevice() if flags.get("is_master") else CPUDevice()
    wf.initialize(device=device)
    return wf


def test_slave_jobs_reuse_resident_dataset(loader_mode, put_counter):
    """Across a whole multi-job slave session the dataset uploads
    exactly ONCE; per job only weights and the index span move."""
    from veles_tpu.parallel.jobs import JobClient, JobServer

    loader_mode("device")
    master = _mk_distributed("device", is_master=True)
    slave = _mk_distributed("device", is_slave=True)
    assert slave.stitch_report()["loader_headed"][0]
    dataset_nbytes = int(slave.loader.original_data.nbytes)
    server = JobServer(master).start()
    try:
        client = JobClient(slave, server.endpoint)
        client.handshake()
        assert client.run()
        client.close()
    finally:
        server.stop()
    assert client.jobs_done > 3
    dataset_puts = [n for n in put_counter if n == dataset_nbytes]
    assert len(dataset_puts) == 1, dataset_puts
    assert master.decision.best_n_err_pt < 50.0


def test_run_prefetch_stages_next_job_index_span(loader_mode):
    """Under the double-buffered job loop the device-path loader
    stages the NEXT job's index span (merge + background upload) and
    apply_data_from_master installs the staged buffer."""
    from veles_tpu.parallel.jobs import JobClient, JobServer

    loader_mode("device")
    master = _mk_distributed("device", is_master=True)
    slave = _mk_distributed("device", prefetch=True, is_slave=True)
    hits = []
    loader = slave.loader
    orig_apply = type(loader).apply_data_from_master

    def spy_apply(self, data):
        key = (int(data["minibatch_offset"]),
               int(data["minibatch_size"]))
        hits.append(key in self._staged_indices_)
        return orig_apply(self, data)

    type(loader).apply_data_from_master = spy_apply
    server = JobServer(master).start()
    try:
        client = JobClient(slave, server.endpoint)
        client.handshake()
        assert client.run_prefetch()
        client.close()
    finally:
        server.stop()
        type(loader).apply_data_from_master = orig_apply
    assert client.jobs_done > 3
    assert any(hits), "no job consumed a staged index span"
    assert not loader._staged_indices_      # nothing leaked
    assert master.decision.best_n_err_pt < 50.0


# -- throughput floor -------------------------------------------------------

@pytest.mark.slow
def test_devloader_throughput_floor_cpu(loader_mode):
    """In-process CPU JAX, MNIST784-shaped data: the device-resident
    input pipeline must run ≥ 1.3× faster than the PR 3 stitched eager
    path with the host loader (same stitched segments otherwise)."""

    def measure(mode):
        root.common.engine.loader = mode
        wf = _build(minibatch_size=16, max_epochs=2, seed=5,
                    n_train=1280, n_valid=320, dim=784)
        wf.run()                          # warm: compiles included
        wf.decision.complete <<= False
        wf.decision.max_epochs = 8
        tic = time.perf_counter()
        wf.run()                          # six warm epochs
        elapsed = time.perf_counter() - tic
        assert wf.stopped
        return elapsed

    t_dev = measure("device")
    t_host = measure("host")
    assert t_host / t_dev >= 1.3, \
        "devloader %.3fs vs host loader %.3fs (%.2fx < 1.3x floor)" % (
            t_dev, t_host, t_host / t_dev)
