"""The resident set's rows-major device form (``ops/gather.py``): the rule
that chooses it, the upload that builds it, and the three gathers that
read it (``fill_minibatch``, the stitched head, the epoch scan) against
plain numpy indexing, bit for bit.  What the chip's compiler makes of the
form is ``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veles_tpu import prng, trace
from veles_tpu.backends import CPUDevice, NumpyDevice
from veles_tpu.dummy import DummyLauncher, DummyWorkflow
from veles_tpu.loader.fullbatch import FullBatchLoader, FullBatchLoaderMSE
from veles_tpu.memory import Vector, Watcher
from veles_tpu.ops import gather
from veles_tpu.ops.gather import ResidentRows, resident_shape, upload_rows
from veles_tpu.znicz.standard_workflow import StandardWorkflow

ROWS = 7
#: row sizes that are and are not multiples of 128, and whether the rule
#: gives them the form
ROW_ELEMS = [(3, False), (784, False), (3072, True), (154587, True)]
DTYPES = [numpy.uint8, numpy.float32, numpy.int32]
#: valid rows, a repeat, and the empty slots of a short final batch
INDICES = numpy.array([5, 0, -1, 6, 5, -1, -1], numpy.int32)


def _host(elems, dtype, rows=ROWS, seed=0):
    rng = numpy.random.default_rng([seed, elems])
    return rng.integers(1, 200, (rows, elems)).astype(dtype)


def _put(array):
    return jax.device_put(array, jax.devices()[0])


def _numpy_rows(host, indices):
    mask = (indices >= 0).reshape((-1,) + (1,) * (host.ndim - 1))
    return numpy.where(mask, host[numpy.maximum(indices, 0)], 0)


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("sample_shape, expected", [
    ((227, 227, 3), (1208, 128)),      # AlexNet: 37 bytes of padding
    ((32, 32, 3), (24, 128)),          # CIFAR: none
    ((96, 96, 3), (216, 128)),
    ((1000,), (8, 128)),               # 2.4% of padding
    ((28, 28), None),                  # MNIST: 784 -> 1024 is too much
    ((784,), None),
    ((3,), None),
    ((), None),                        # labels
])
def test_rule_pads_rows_to_whole_tiles_or_leaves_them(sample_shape,
                                                      expected):
    assert resident_shape(sample_shape) == expected
    if expected is not None:
        elems = int(numpy.prod(sample_shape))
        padded = expected[0] * expected[1]
        assert expected[0] % 8 == 0 and 0 <= padded - elems < 1024
        assert (padded - elems) * 8 <= elems


# -- upload + gather against numpy --------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("elems, formed", ROW_ELEMS)
def test_take_rows_over_the_form_equals_numpy(elems, formed, dtype):
    host = _host(elems, dtype)
    dev = upload_rows(host, _put)
    assert isinstance(dev, ResidentRows) == formed
    assert dev.shape == host.shape and dev.dtype == host.dtype
    if formed:
        assert dev.form.shape == (ROWS,) + resident_shape((elems,))
        # the padding is never visible
        assert (numpy.asarray(dev) == host).all()
    out = numpy.asarray(gather.take_rows(dev, INDICES))
    assert out.dtype == host.dtype and out.shape == (len(INDICES), elems)
    assert (out == _numpy_rows(host, INDICES)).all()


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("elems, formed", ROW_ELEMS)
def test_take_rows_norm_over_the_form_equals_numpy(elems, formed, dtype):
    host = _host(elems, dtype, seed=1)
    dev = upload_rows(host, _put)
    rng = numpy.random.default_rng(elems)
    for scale, shift in (
            (numpy.float32(1.0 / 255.0), numpy.float32(-0.5)),
            (rng.standard_normal(elems).astype(numpy.float32),
             rng.standard_normal(elems).astype(numpy.float32))):
        out = numpy.asarray(gather.take_rows_norm(
            dev, INDICES, (scale, shift)))
        # bit for bit what the gather gives on the plain array
        today = numpy.asarray(gather._gather_norm_jnp(
            jnp.asarray(host), jnp.asarray(INDICES),
            jnp.asarray(scale), jnp.asarray(shift)))
        assert out.dtype == numpy.float32 and (out == today).all()
        ref = host[numpy.maximum(INDICES, 0)].astype(numpy.float32) \
            * scale + shift
        numpy.testing.assert_allclose(
            out, numpy.where((INDICES >= 0)[:, None], ref, 0),
            rtol=1e-6, atol=1e-6)
        assert (out[INDICES < 0] == 0).all()


@pytest.mark.parametrize("sample_shape, dtype", [
    ((32, 32, 3), numpy.float32), ((227, 227, 3), numpy.uint8)])
def test_both_heads_read_the_form_at_a_sample_shape(sample_shape, dtype):
    rng = numpy.random.default_rng(4)
    host = rng.integers(0, 255, (6,) + sample_shape).astype(dtype)
    dev = upload_rows(host, _put)
    idx = numpy.array([4, -1, 0, 5], numpy.int32)
    out = numpy.asarray(gather.take_rows(dev, idx))
    normed = numpy.asarray(gather.take_rows_norm(dev, idx, (0.5, 1.0)))
    ref = _numpy_rows(host, idx)
    assert out.dtype == dtype and (out == ref).all()
    mask = (idx >= 0).reshape(-1, 1, 1, 1)
    numpy.testing.assert_allclose(normed, numpy.where(
        mask, ref.astype(numpy.float32) * 0.5 + 1.0, 0), rtol=1e-6)


@pytest.mark.parametrize("rows", [1, 128, 300, 513])
def test_upload_in_chunks_overlaps_the_last_and_loses_nothing(
        rows, monkeypatch):
    """Chunks of at least 128 rows; the last is moved back over rows
    already written, so every count of rows takes one program."""
    monkeypatch.setattr(gather, "CHUNK_BYTES", 128 * 1024 * 4)
    host = _host(1024, numpy.float32, rows=rows)
    puts = []

    def put(array):
        puts.append(array.shape[0])
        return _put(array)

    dev = upload_rows(host, put)
    assert puts == [min(rows, 128)] * -(-rows // 128)
    assert (numpy.asarray(dev) == host).all()
    # a slice of the set is a slice of its rows, still in the form
    part = dev[rows // 2:]
    assert isinstance(part, ResidentRows)
    assert (numpy.asarray(part) == host[rows // 2:]).all()
    with pytest.raises(TypeError):
        dev[numpy.array([0])]


def test_upload_under_a_pod_sharding_builds_each_devices_rows():
    mesh = Mesh(numpy.array(jax.devices()[:4]), ("data",))
    host = _host(3072, numpy.uint8, rows=64).reshape(64, 32, 32, 3)
    by_rows = upload_rows(host, _put,
                          NamedSharding(mesh, P("data", None, None, None)))
    assert by_rows.form.sharding.spec == P("data")
    shards = by_rows.form.addressable_shards
    assert sorted(s.data.shape for s in shards) == [(16, 24, 128)] * 4
    assert (numpy.asarray(by_rows) == host).all()
    idx = numpy.array([63, 0, -1, 17], numpy.int32)
    assert (numpy.asarray(gather.take_rows(by_rows, idx))
            == _numpy_rows(host, idx)).all()
    whole = upload_rows(host, _put, NamedSharding(mesh, P()))
    assert all(s.data.shape == (64, 24, 128)
               for s in whole.form.addressable_shards)
    assert (numpy.asarray(whole) == host).all()
    with pytest.raises(ValueError, match="by rows"):
        upload_rows(host, _put, NamedSharding(
            mesh, P(None, "data", None, None)))


def test_form_passes_through_jit_and_scan_as_the_set_it_stands_for():
    host = _host(2048, numpy.int32, rows=32)
    dev = upload_rows(host, _put)
    batches = jnp.arange(32, dtype=jnp.int32).reshape(4, 8)[::-1]

    @jax.jit
    def sums(data, batches):
        return jax.lax.scan(lambda carry, idx: (
            carry + gather.take_rows(data, idx).sum(), None),
            jnp.int32(0), batches)[0]

    assert int(sums(dev, batches)) == int(host.sum())


# -- the Vector owns the device form ------------------------------------------

@pytest.mark.traced
def test_vector_owns_the_form_and_the_upload_is_one_span():
    Watcher.reset()
    host = _host(3072, numpy.uint8, rows=40).reshape(40, 32, 32, 3)
    vec = Vector(host, category="dataset", rows_major=True)
    vec.initialize(CPUDevice())
    dev = vec.devmem
    assert isinstance(dev, ResidentRows)
    assert vec.devmem is dev                       # uploaded once
    # the host side is what it was
    assert vec.shape == host.shape and vec.dtype == host.dtype
    assert vec.mem.shape == host.shape
    assert vec.map_read().mem is vec.mem
    assert (vec.mem == host).all()
    ledger = Watcher.hbm_ledger()
    assert ledger["by_category"]["dataset"]["bytes"] == 40 * 24 * 128
    assert Watcher.h2d_bytes == host.nbytes
    spans = [event for event in trace.recorder.events()
             if event[1:3] == ("loader", "upload")]
    assert len(spans) == 1
    assert spans[0][6] == {
        "rows": 40, "row_elems": 3072, "padded_elems": 3072,
        "bytes": 40 * 3072, "form": "(40, 24, 128)"}
    vec.reset(None)                                # the benchmark's release
    assert vec._devmem_ is None and not vec
    assert Watcher.hbm_ledger()["by_category"]["dataset"]["bytes"] == 0
    # an interpret device keeps the host's array
    plain = Vector(host, category="dataset", rows_major=True)
    plain.initialize(NumpyDevice())
    assert plain.devmem is plain.mem


def test_vector_pickles_without_its_device_form():
    import pickle
    host = _host(1024, numpy.float32, rows=9)
    vec = Vector(host, category="dataset", rows_major=True)
    vec.initialize(CPUDevice())
    vec.devmem
    back = pickle.loads(pickle.dumps(vec))
    assert back.rows_major and back._devmem_ is None
    assert (back.mem == host).all()
    old = Vector(host)
    del old.__dict__["rows_major"]                 # a pickle from before
    assert pickle.loads(pickle.dumps(old)).rows_major is False


# -- the loader's three gathers -----------------------------------------------

class ImageLoader(FullBatchLoader):
    """Images whose rows take the form (16 x 16 x 4 = 1024), uint8 where
    the set keeps its storage dtype, float32 where it is normalised at
    initialize; 50 validation and 130 train samples leave short final
    batches."""

    def load_data(self):
        rng = numpy.random.default_rng(8)
        self.original_data.mem = rng.integers(
            0, 256, (180, 16, 16, 4)).astype(
                numpy.uint8 if self.native_device_dtype
                else numpy.float32)
        self.original_labels = [int(i) % 10 for i in range(180)]
        self.class_lengths[:] = [0, 50, 130]


def _loader(cls=ImageLoader, **kwargs):
    loader = cls(DummyWorkflow(), minibatch_size=32, **kwargs)
    loader.initialize(device=CPUDevice())
    return loader


def test_fill_minibatch_gathers_from_the_form_short_batches_too():
    loader = _loader(normalization_type="none")
    data = loader.original_data
    assert isinstance(data.devmem, ResidentRows)
    assert data.mem.shape == (180, 16, 16, 4) == data.shape
    assert loader.minibatch_data.shape == (32, 16, 16, 4)
    seen_short = 0
    for _ in range(9):                 # a validation and a train pass
        loader.run()
        size = int(loader.minibatch_size)
        loader.minibatch_indices.map_read()
        idx = numpy.array(loader.minibatch_indices.mem[:32])
        assert (idx[size:] == -1).all() and (idx[:size] >= 0).all()
        loader.minibatch_data.map_read()
        assert (loader.minibatch_data.mem
                == _numpy_rows(data.mem, idx)).all()
        seen_short += size < 32
    assert seen_short >= 2


def _workflow(native, fused=False, max_epochs=3):
    prng.seed_all(11)
    kwargs = {"fused": True, "fused_config": {"epoch_mode": True}} \
        if fused else {}
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: ImageLoader(
            w, minibatch_size=32, native_device_dtype=native,
            normalization_type="scale"),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16},
                 "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 10},
                 "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}}],
        decision_config={"max_epochs": max_epochs,
                         "fail_iterations": 10 ** 6}, **kwargs)
    wf.launcher = DummyLauncher()
    wf.initialize(device=CPUDevice())
    return wf


@pytest.fixture
def todays_form(monkeypatch):
    """``todays_form(True)``: every set keeps its own shape on the
    device, as before the form existed."""
    def switch(on):
        if on:
            monkeypatch.setattr(gather, "resident_shape",
                                lambda sample_shape: None)
        else:
            monkeypatch.undo()
    return switch


@pytest.mark.parametrize("native", [False, True],
                         ids=["take_rows", "take_rows_norm"])
def test_stitched_head_gives_the_minibatches_numpy_gives(native):
    wf = _workflow(native)
    loader = wf.loader
    segment = wf._stitch_segments_[0]
    assert segment.head is loader
    assert isinstance(loader.original_data.devmem, ResidentRows)
    host = loader.original_data.mem
    assert host.dtype == (numpy.uint8 if native else numpy.float32)
    short = 0
    for _ in range(9):
        segment.execute()
        size = int(loader.minibatch_size)
        start = int(loader.minibatch_offset) - size
        loader.shuffled_indices.map_read()
        idx = numpy.full(32, -1, numpy.int32)
        idx[:size] = loader.shuffled_indices.mem[start:start + size]
        ref = _numpy_rows(host, idx)
        loader.minibatch_data.map_read()
        if native:
            scale, shift = loader.input_norm
            ref = numpy.where((idx >= 0).reshape(-1, 1, 1, 1),
                              ref.astype(numpy.float32) * scale + shift,
                              numpy.float32(0))
            numpy.testing.assert_allclose(loader.minibatch_data.mem, ref,
                                          rtol=1e-6, atol=1e-6)
        else:
            assert (loader.minibatch_data.mem == ref).all()
        short += size < 32
    assert short >= 2


@pytest.mark.parametrize("path", ["stitched", "stitched_native",
                                  "fused_epoch_scan"])
def test_training_from_the_form_is_bit_for_bit_todays(path, todays_form):
    """The stitched head and the one-program epoch give the minibatches
    they gave: the same run with every set in its own shape ends in the
    same weights, to the bit."""
    weights = {}
    for today in (False, True):
        todays_form(today)
        wf = _workflow(native=path != "stitched",
                       fused=path == "fused_epoch_scan")
        formed = isinstance(wf.loader.original_data.devmem, ResidentRows)
        assert formed != today
        wf.run()
        if path == "fused_epoch_scan":
            data = wf.fused_trainer._epoch_data_
            assert isinstance(data, ResidentRows) != today
            assert data.shape == (130, 16, 16, 4)
            wf.fused_trainer.sync_weights()
        weights[today] = [numpy.array(f.weights.mem) for f in wf.forwards]
        assert all(numpy.isfinite(w).all() for w in weights[today])
    for formed, plain in zip(weights[False], weights[True]):
        assert (formed == plain).all()


def test_epoch_scan_slices_rows_of_the_form_and_trains_as_per_step():
    """The one-program epoch takes the TRAIN rows as a slice of the
    form; with the shuffle off it walks the rows in order."""
    from veles_tpu.znicz.fused_graph import epoch_runner
    loader = _loader(normalization_type="none")
    data = loader.original_data.devmem[50:]
    assert isinstance(data, ResidentRows) and data.shape[0] == 130
    labels = jnp.asarray(loader._mapped_labels[50:])
    seen = []

    def step_fn(params, x, y):
        return params + 1, {"x": x, "y": y}

    _count, out = jax.jit(epoch_runner(step_fn, 130, 32, shuffle=False))(
        jnp.int32(0), data, labels, jax.random.PRNGKey(0))
    seen = numpy.asarray(out["x"]).reshape(128, 16, 16, 4)
    assert (seen == loader.original_data.mem[50:178]).all()
    assert (numpy.asarray(out["y"]).reshape(-1)
            == loader._mapped_labels[50:178]).all()


def test_mse_targets_take_the_form_too():
    class Pairs(FullBatchLoaderMSE):
        def load_data(self):
            rng = numpy.random.default_rng(2)
            self.original_data.mem = rng.standard_normal(
                (60, 1024)).astype(numpy.float32)
            self.original_targets.mem = rng.standard_normal(
                (60, 2048)).astype(numpy.float32)
            self.class_lengths[:] = [0, 20, 40]

    loader = _loader(Pairs, normalization_type="none")
    assert isinstance(loader.original_targets.devmem, ResidentRows)
    assert loader.original_targets in loader.resident_vectors()
    for _ in range(3):
        loader.run()
        loader.minibatch_indices.map_read()
        idx = numpy.array(loader.minibatch_indices.mem[:32])
        idx[int(loader.minibatch_size):] = -1
        loader.minibatch_targets.map_read()
        assert (loader.minibatch_targets.mem == _numpy_rows(
            loader.original_targets.mem, idx)).all()
