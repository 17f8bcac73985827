"""Test configuration: force an 8-device virtual CPU platform so mesh /
sharding tests exercise real multi-device code paths without TPU hardware
(mirrors the reference's NumpyDevice-as-universal-fake strategy,
``veles/tests/accelerated_test.py:47-80``)."""

import os

# Preserved for tests that deliberately escape the CPU pin via a
# subprocess (test_accuracy_parity.py trains on the real accelerator).
ORIG_JAX_PLATFORMS = os.environ.get("JAX_PLATFORMS")
ORIG_XLA_FLAGS = os.environ.get("XLA_FLAGS", "")

# Hard-set: the tests run on the virtual CPU mesh whatever accelerator
# the session's environment points at.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the env var alone loses to an earlier jax.config.update; pin the
# config too, before backend init
jax.config.update("jax_platforms", "cpu")

import numpy  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed_prng():
    """Deterministic named streams per test (ref: multi_device re-seeds
    between backends, accelerated_test.py:47-80)."""
    from veles_tpu import prng
    prng.seed_all(1234)
    yield


@pytest.fixture(autouse=True)
def _force_trace(request):
    """The ``traced`` marker: force-enable the trace recorder around
    the test — via the CONFIG knob (not by poking the recorder), so a
    ``Workflow.initialize()`` inside the test (which re-reads the knob
    through ``trace.configure()``) keeps it on.  The ring starts empty
    and the default off-state is restored afterwards, so unmarked
    tests see the stock single-attribute-check disabled path."""
    if request.node.get_closest_marker("traced") is None:
        yield
        return
    from veles_tpu import trace
    from veles_tpu.config import root
    saved = root.common.engine.get("trace", "off")
    root.common.engine.trace = "on"
    trace.recorder.clear()
    trace.configure()
    yield
    root.common.engine.trace = saved
    trace.configure()
    trace.recorder.clear()


@pytest.fixture(autouse=True)
def _pin_synthetic_data(request, tmp_path, monkeypatch):
    """Short sample runs everywhere in the suite were calibrated on the
    synthetic stand-ins; a machine provisioned with real datasets (for
    test_accuracy_parity.py, which opts out) must not silently switch
    them onto real data."""
    if request.module.__name__ == "test_accuracy_parity":
        yield
        return
    from veles_tpu.config import root
    monkeypatch.delenv("VELES_DATASETS", raising=False)
    saved = root.common.dirs.get("datasets")
    root.common.dirs.datasets = str(tmp_path / "no-datasets-here")
    yield
    root.common.dirs.datasets = saved


@pytest.fixture
def aligned():
    """``aligned(shape, dtype)``: a zeroed numpy array on a 64-byte
    boundary, the kind of host buffer jax's CPU backend takes WITHOUT a
    copy (numpy's own allocator gives one in some runs and not in
    others, which is how an aliasing defect comes to flip)."""
    def make(shape, dtype):
        nbytes = int(numpy.prod(shape)) * numpy.dtype(dtype).itemsize
        raw = numpy.zeros(nbytes + 64, numpy.uint8)
        start = -raw.ctypes.data % 64
        return raw[start:start + nbytes].view(dtype).reshape(shape)
    return make


@pytest.fixture
def mix_lists(monkeypatch):
    """The kernel's form of ``ops.grouped.expert_mix`` in interpret mode
    with a spy on it: the list this returns gains, for every RUN of it,
    the length of the list of touched experts it walked (read it after
    ``jax.effects_barrier()``)."""
    import jax

    from veles_tpu.config import root
    from veles_tpu.ops import grouped
    lengths = []
    real = grouped._mix_pallas

    def spy(x, weights, into, back, ids, count, interpret):
        jax.debug.callback(lambda n: lengths.append(int(n)), count)
        return real(x, weights, into, back, ids, count, interpret)

    monkeypatch.setattr(grouped, "_mix_pallas", spy)
    monkeypatch.setattr(root.common.engine, "interpret", True,
                        raising=False)
    return lengths

