"""Vector coherence-protocol tests (ref ``veles/tests/`` Array coverage:
map/unmap semantics, pickling device data transparently)."""

import pickle

import numpy
import pytest

from veles_tpu.backends import CPUDevice, NumpyDevice
from veles_tpu.memory import Vector, Watcher


def test_empty_vector():
    v = Vector()
    assert not v
    assert v.shape is None and v.size == 0


def test_reset_and_host_access():
    v = Vector(numpy.arange(6, dtype=numpy.float32).reshape(2, 3))
    assert v.shape == (2, 3)
    assert v.dtype == numpy.float32
    assert len(v) == 2
    assert v.mem[1, 2] == 5


def test_device_upload_download():
    dev = CPUDevice()
    v = Vector(numpy.ones((4, 4), dtype=numpy.float32))
    v.initialize(dev)
    d = v.devmem
    assert hasattr(d, "devices")           # a jax.Array
    # mutate on device (reassign — jax arrays are immutable)
    v.devmem = d * 3.0
    assert (v.mem == 3.0).all()            # implicit D2H on read


def test_host_edit_republish():
    dev = CPUDevice()
    v = Vector(numpy.zeros((2, 2), dtype=numpy.float32))
    v.initialize(dev)
    _ = v.devmem                           # uploaded
    v.map_write()
    v.mem[...] = 7.0
    v.unmap()
    assert float(numpy.asarray(v.devmem)[0, 0]) == 7.0


def _map_write_edit(v, host):
    v.map_write()
    v.mem[...] = 7.0


def _publish_host(v, host):
    v.publish(host_array=numpy.full(v.shape, 7.0, numpy.float32))


def _write_installed_array(v, host):
    host[...] = 7.0       # the array `v.mem = host` installed, no copy


@pytest.mark.parametrize("write", [
    _map_write_edit, _publish_host, _write_installed_array])
def test_device_copy_is_not_the_host_array(write, aligned):
    """The rule of memory.py: what ``devmem`` returned keeps its values
    whatever the host side does afterwards.  On the CPU backend jax
    would alias an aligned host buffer, and the write would show in a
    "device copy" that a queued step still reads."""
    host = aligned((8, 16), numpy.float32)
    host[...] = 1.0
    v = Vector()
    v.mem = host
    v.initialize(CPUDevice())
    before = v.devmem
    write(v, host)
    assert (v.mem == 7.0).all()
    assert (numpy.asarray(before) == 1.0).all()


def test_interpret_device_passthrough():
    dev = NumpyDevice()
    v = Vector(numpy.arange(4.0))
    v.initialize(dev)
    assert isinstance(v.devmem, numpy.ndarray)
    v.devmem = v.devmem * 2
    assert (v.mem == numpy.arange(4.0) * 2).all()


def test_pickle_syncs_device_to_host():
    dev = CPUDevice()
    v = Vector(numpy.zeros((3,), dtype=numpy.float32))
    v.initialize(dev)
    v.devmem = v.devmem + 5.0              # freshest data on device only
    blob = pickle.dumps(v)
    restored = pickle.loads(blob)
    assert (restored.mem == 5.0).all()
    # restored vector re-uploads lazily on a fresh device attach
    restored.initialize(CPUDevice())
    assert float(numpy.asarray(restored.devmem)[0]) == 5.0


def test_watcher_accounting():
    Watcher.reset()
    dev = CPUDevice()
    v = Vector(numpy.zeros((1024,), dtype=numpy.float32))
    v.initialize(dev)
    _ = v.devmem
    assert Watcher.bytes_in_use >= 4096
    v.reset(None)
    assert Watcher.bytes_in_use == 0
    assert Watcher.peak_bytes >= 4096
