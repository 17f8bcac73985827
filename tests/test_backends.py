"""Backend registry + device tests (ref ``veles/tests/`` backend coverage;
runs on the virtual 8-device CPU mesh from conftest)."""

import numpy
import pytest

from veles_tpu.backends import (
    AutoDevice, BackendRegistry, CPUDevice, DeviceInfo, NumpyDevice,
    TPUDevice, make_device)


def test_registry_has_all_backends():
    for name in ("tpu", "cpu", "numpy", "auto"):
        assert name in BackendRegistry.backends


def test_numpy_device_roundtrip():
    dev = NumpyDevice()
    assert dev.exists and dev.is_interpret
    arr = numpy.arange(6, dtype=numpy.float32)
    assert (dev.get(dev.put(arr)) == arr).all()


def test_cpu_device_mesh():
    dev = CPUDevice()
    assert dev.exists
    assert dev.num_devices == 8      # conftest forces 8 virtual devices
    mesh = dev.mesh                  # default: data axis absorbs all
    assert mesh.shape["data"] == 8


def test_custom_mesh_axes():
    dev = CPUDevice()
    mesh = dev.make_mesh({"data": 2, "model": 4})
    assert mesh.shape == {"data": 2, "model": 4}
    mesh2 = dev.make_mesh({"data": -1, "model": 2})
    assert mesh2.shape == {"data": 4, "model": 2}


def test_cpu_put_get_sync():
    dev = CPUDevice()
    arr = numpy.random.rand(4, 4).astype(numpy.float32)
    dev_arr = dev.put(arr)
    dev.sync()
    assert numpy.allclose(dev.get(dev_arr), arr)


def test_cpu_put_hands_jax_a_private_copy(aligned):
    """jax's CPU backend takes a 64-byte aligned numpy buffer without a
    copy; CPUDevice.put must not let the "device copy" be the array
    the host goes on writing."""
    host = aligned((32,), numpy.int32)
    dev = CPUDevice().put(host)
    assert not numpy.shares_memory(numpy.asarray(dev), host)
    host[:] = 5
    assert (numpy.asarray(dev) == 0).all()


def test_auto_device_is_the_cpu_when_the_process_asked_for_it():
    # conftest pins jax_platforms="cpu" (what JAX_PLATFORMS=cpu does):
    # the one case in which "auto" means the CPU
    dev = AutoDevice()
    assert isinstance(dev, CPUDevice) and dev.exists


def test_make_device_by_name():
    assert make_device("numpy").is_interpret
    with pytest.raises(ValueError):
        make_device("opencl")


def test_tpu_device_absent_under_cpu_env_raises():
    """No such platform is an error at construction, not a device
    object over an empty list that fails later in put()."""
    with pytest.raises(RuntimeError, match="tpu"):
        TPUDevice()


def test_device_pickle_roundtrip():
    import pickle
    dev = CPUDevice()
    restored = pickle.loads(pickle.dumps(dev))
    assert restored.exists
    assert restored.num_devices == 8


def test_device_info_db_roundtrip(tmp_path):
    info = DeviceInfo("TPU v5e")
    info.ratings = {"gemm": {"float32": {"time": 0.01,
                                         "tiles": [256, 512, 256]}}}
    path = str(tmp_path / "device_infos.json")
    DeviceInfo.save_db({"TPU v5e": info}, path)
    db = DeviceInfo.load_db(path)
    assert db["TPU v5e"].get_kernel_tiles("gemm", "float32") == \
        [256, 512, 256]
    assert db["TPU v5e"].get_kernel_tiles("gemm", "bfloat16",
                                          default=[128, 128, 128]) == \
        [128, 128, 128]


def test_device_info_load_db_unwraps_autotune_envelope(tmp_path):
    # scripts.autotune prints a {"devices": ..., "_this_run": ...}
    # envelope; a DB file saved from that stdout must load as if it
    # were the flat table, with _this_run treated as provenance only
    import json
    path = str(tmp_path / "device_infos.json")
    envelope = {
        "devices": {"TPU v5e": {"gemm": {"float32": {
            "tiles": [256, 512, 256]}}}},
        "_this_run": {"device_kind": "TPU v5e", "ts": 0.0, "argv": []},
    }
    with open(path, "w") as fout:
        json.dump(envelope, fout)
    db = DeviceInfo.load_db(path)
    assert "_this_run" not in db
    assert db["TPU v5e"].get_kernel_tiles("gemm", "float32") == \
        [256, 512, 256]
    # a flat DB that happens to contain a model named "devices" plus
    # another real model is NOT an envelope and must load untouched
    flat = {"devices": {"gemm": {}}, "TPU v4": {"gemm": {}}}
    with open(path, "w") as fout:
        json.dump(flat, fout)
    assert set(DeviceInfo.load_db(path)) == {"devices", "TPU v4"}


def test_autotune_sweep_merges_per_device_model(tmp_path):
    # re-running a sweep on a SECOND device kind must not clobber the
    # first's ratings, even when the DB file is a redirected stdout
    # envelope (_this_run stays last-run-only, never a device entry)
    import json

    from veles_tpu.ops.benchmark import autotune_gd

    path = str(tmp_path / "device_infos.json")
    first = DeviceInfo("TPU v4")
    first.ratings["gemm"] = {"float32": [256, 256, 256]}
    with open(path, "w") as fout:
        json.dump({"devices": {"TPU v4": first.ratings},
                   "_this_run": {"device_kind": "TPU v4", "ts": 1.0}},
                  fout)
    autotune_gd(shape=(16, 128, 64), runs=1, db_path=path)
    db = DeviceInfo.load_db(path)
    assert "_this_run" not in db
    assert db["TPU v4"].ratings["gemm"] == {"float32": [256, 256, 256]}
    others = [m for m in db if m != "TPU v4"]
    assert others and any("gd_v2" in db[m].ratings for m in others)
