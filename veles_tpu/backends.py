"""Device/backend abstraction: TPU-first device registry.

Parity target: reference ``veles/backends.py`` — ``Device`` base (``:184``)
with ``BackendRegistry`` metaclass (``:166``), concrete ``OpenCLDevice``
(``:426``) / ``CUDADevice`` (``:745``) / ``NumpyDevice`` (``:918``) and
``AutoDevice`` (``:406-424``; here: the TPU or an error, never a silent
walk down to the CPU); per-device performance database ``DeviceInfo``
(``:63-164``) loaded from ``devices/device_infos.json``.

TPU re-design (BASELINE.json north star: "TPU as a first-class Device"):

* ``TPUDevice`` owns the set of local TPU chips AND the logical
  ``jax.sharding.Mesh`` over them — the mesh is part of the device
  abstraction because on TPU "the device" a workflow trains on is a slice,
  not a chip.
* ``CPUDevice`` is the XLA-on-CPU twin (used by the virtual multi-device
  test mesh); ``NumpyDevice`` is the pure-interpret debug backend, the
  universal fake of the reference's test strategy
  (``tests/accelerated_test.py:47-80``).
* The reference's autotune DB (measured matmul block sizes per device,
  ``backends.py:623-744``) survives as :class:`DeviceInfo` — a per-TPU-
  generation Pallas tile-size table filled by
  :mod:`veles_tpu.ops.benchmark` and persisted to the same JSON shape.
"""

import json
import os
import re

import numpy

from veles_tpu.config import root
from veles_tpu.distributable import Pickleable

DEVICE_INFOS_JSON = os.path.join(
    os.path.dirname(__file__), "devices", "device_infos.json")

#: peak dense bf16 FLOP/s per *jax device* (v2/v3 devices are single
#: TensorCores = half a chip; v4+ are whole chips/megacores) — consumed
#: by bench.py's MFU gate and scripts/profile_step.py
PEAK_BF16_FLOPS = (
    ("v6", 918e12),     # Trillium ("TPU v6 lite"/"TPU v6e")
    ("v5p", 459e12),
    ("v5", 197e12),     # "TPU v5 lite" / v5e
    ("v4", 275e12),
    ("v3", 61.5e12),
    ("v2", 22.5e12),
)

#: peak dense int8 OP/s per *jax device* — the honest MFU denominator
#: for the quantized serving programs (``veles_tpu.quant``): v5e/v5p/
#: v6e double their bf16 rate at int8, v2–v4 have no int8 fast path
#: (the MXU runs the same passes, so the bf16 number stands)
PEAK_INT8_OPS = (
    ("v6", 1836e12),
    ("v5p", 918e12),
    ("v5", 394e12),
    ("v4", 275e12),
    ("v3", 61.5e12),
    ("v2", 22.5e12),
)

#: HBM bytes per *jax device* (same core-vs-chip granularity as the
#: peak table: v2/v3 devices are single TensorCores owning half the
#: chip's memory) — the generative preflight's KV-footprint budget
#: (analyzer rule V-S01); CPU/unknown kinds return None and the check
#: degrades to plan sanity only
DEVICE_HBM_BYTES = (
    ("v6", 32 << 30),
    ("v5p", 95 << 30),
    ("v5", 16 << 30),
    ("v4", 32 << 30),
    ("v3", 16 << 30),
    ("v2", 8 << 30),
)


#: the checkout this package lives in
CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the ONE compile-cache location when the environment names none: a
#: fixed directory inside the checkout (git-ignored).  The path is part
#: of the cache key, so it never carries a home directory, a temporary
#: name, a pid or a timestamp.
COMPILE_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".cache", "xla")


def _requested_platforms():
    """The platform list this process was started for: JAX's
    ``jax_platforms`` (what ``JAX_PLATFORMS`` feeds), lower-cased;
    ``""`` when JAX is left to pick."""
    import jax
    return str(jax.config.jax_platforms or "").lower()


def enable_compilation_cache(platform=None):
    """Turn on XLA's persistent executable cache; returns the directory
    in use, or None on the CPU.

    The TPU analogue of the reference's kernel binary cache keyed on
    source SHA + defines (``accelerated_units.py:605-674``): every tool
    that compiles through this framework (devices, the timing harness,
    the autotuner, the profiler, ``chip_smoke.py``, ``bench.py``)
    shares one on-disk cache.  ONE rule: if ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and this function sets no directory in
    code; otherwise the cache lives at :data:`COMPILE_CACHE_DIR`.  Safe
    to call any number of times, before or after backend init (only
    programs compiled afterwards are cached).

    The cache is keyed on the program WITH its metadata: the scope and
    kernel names a device trace shows (``docs/observability.md``) are
    HLO metadata, JAX's default key leaves metadata out, and an
    executable loaded under such a key shows the names it was compiled
    with (my chip run, PR 26: none at all, for the step compiled before
    the scopes existed).  The checkout's own path is taken out of the
    source files first, so two checkouts of one tree share entries.

    Non-CPU platforms only: CPU compiles are cheap, and an AOT CPU
    executable cached under one machine-feature detection can SIGILL
    under another.  ``platform`` is the caller's RESOLVED platform
    (e.g. ``jax.devices()[0].platform``) — prefer passing it; with
    ``None`` the *requested* ``jax_platforms`` string is checked.
    """
    import jax
    if platform is None:
        platform = _requested_platforms()
    if str(platform).lower() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(CHECKOUT_DIR + os.sep))
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def assert_backend_untouched(what):
    """Raise unless this process can still hand the accelerator to a
    child: a chip belongs to ONE process at a time, so a parent that
    has initialised a JAX accelerator backend holds it, and a child
    that needs it then fails or hangs.  Called at every site that
    spawns a ``python -m veles_tpu`` child (``what`` names it).  A
    process that never imported JAX, never initialised a backend, or
    runs on the CPU (which children can share) passes."""
    import sys
    if "jax" not in sys.modules:
        return
    import jax
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    platform = jax.default_backend()
    if platform != "cpu":
        raise RuntimeError(
            "%s: this process has already initialised the %r JAX "
            "backend and holds the device — a child that needs it "
            "would fail or hang.  Spawn before touching JAX (or run "
            "the parent with -d numpy / JAX_PLATFORMS=cpu)"
            % (what, platform))


def peak_bf16_flops(device_kind):
    """Peak dense bf16 FLOP/s for a jax device kind, or None."""
    kind = (device_kind or "").lower()
    for tag, peak in PEAK_BF16_FLOPS:
        if tag in kind:
            return peak
    return None


def peak_int8_ops(device_kind):
    """Peak dense int8 OP/s for a jax device kind, or None."""
    kind = (device_kind or "").lower()
    for tag, peak in PEAK_INT8_OPS:
        if tag in kind:
            return peak
    return None


def device_hbm_bytes(device_kind):
    """HBM bytes for a jax device kind, or None (CPU/unknown)."""
    kind = (device_kind or "").lower()
    for tag, nbytes in DEVICE_HBM_BYTES:
        if tag in kind:
            return nbytes
    return None


class BackendRegistry(type):
    """name → Device class registry (ref ``backends.py:166``)."""

    backends = {}

    def __init__(cls, name, bases, namespace):
        super(BackendRegistry, cls).__init__(name, bases, namespace)
        backend = namespace.get("BACKEND")
        if backend:
            BackendRegistry.backends[backend] = cls


class DeviceInfo(Pickleable):
    """Per-device-model performance knowledge (ref ``backends.py:63-164``).

    Maps ``(kernel, dtype)`` → best tile sizes as measured by the
    benchmark autotuner; shipped/persisted as JSON in the reference's
    ``device_infos.json`` schema spirit: ``{model: {kernel: {dtype:
    {"time": s, "tiles": [bm, bk, bn]}}}}``.
    """

    def __init__(self, model):
        super(DeviceInfo, self).__init__()
        self.model = model
        self.ratings = {}

    @classmethod
    def load_db(cls, path=DEVICE_INFOS_JSON):
        if not os.path.exists(path):
            return {}
        with open(path, "r") as fin:
            raw = json.load(fin)
        if (isinstance(raw, dict) and "devices" in raw
                and set(raw) <= {"devices", "_this_run"}):
            # scripts.autotune's stdout envelope ({"devices": ...,
            # "_this_run": ...}) saved verbatim as a DB file — unwrap
            # the devices table; _this_run is last-run provenance only
            raw = raw["devices"]
        db = {}
        for model, ratings in raw.items():
            info = cls(model)
            info.ratings = ratings
            db[model] = info
        return db

    @staticmethod
    def save_db(db, path=DEVICE_INFOS_JSON):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as fout:
            json.dump({m: i.ratings for m, i in db.items()}, fout, indent=2,
                      sort_keys=True)

    def get_kernel_tiles(self, kernel, dtype, default=None):
        """The autotuned tile sizes for (kernel, dtype) — the TPU analogue
        of ``get_kernel_bs_vo`` (ref ``backends.py:88``)."""
        entry = self.ratings.get(kernel, {}).get(str(dtype))
        return entry["tiles"] if entry else default


class Device(Pickleable, metaclass=BackendRegistry):
    """Abstract backend device."""

    BACKEND = None

    def __init__(self, **kwargs):
        super(Device, self).__init__(**kwargs)
        self.device_info = DeviceInfo(self.model)

    def init_unpickled(self):
        super(Device, self).init_unpickled()

    # -- capability flags ---------------------------------------------------
    @property
    def is_interpret(self):
        """True when compute runs as plain numpy (no jit)."""
        return False

    @property
    def exists(self):
        return True

    @property
    def model(self):
        return self.BACKEND

    @property
    def backend_name(self):
        return self.BACKEND

    # -- array placement ----------------------------------------------------
    def put(self, array):
        """Place a host array on this device; returns the device array."""
        raise NotImplementedError

    def get(self, devarray):
        """Fetch a device array back to host numpy."""
        raise NotImplementedError

    def sync(self):
        """Block until all dispatched work completes (ref
        ``backends.py:568,902``)."""

    # -- dtype policy -------------------------------------------------------
    @property
    def compute_dtype(self):
        """Dtype for matmul/conv operands — set precision_type to
        "bfloat16" to keep the MXU fed (precision_level is the separate
        robustness knob, see config.py)."""
        from veles_tpu.dtypes import dtype_by_name
        return dtype_by_name(
            root.common.engine.get("precision_type", "float32"))

    @property
    def storage_dtype(self):
        """Dtype for persistent params (master copy)."""
        from veles_tpu.dtypes import dtype_by_name
        return dtype_by_name(
            root.common.engine.get("precision_type", "float32"))

    def __repr__(self):
        return "<%s model=%s>" % (type(self).__name__, self.model)


class _JaxDevice(Device):
    """Shared machinery for XLA-backed devices (TPU and CPU)."""

    PLATFORM = None

    def __init__(self, **kwargs):
        import jax
        enable_compilation_cache(platform=self.PLATFORM)
        # no such platform raises here (jax names what it found): a
        # device object over an empty list would only fail later, in put()
        self._jax_devices = list(kwargs.pop("devices", ())) \
            or jax.devices(self.PLATFORM)
        super(_JaxDevice, self).__init__(**kwargs)
        self._mesh = None

    def __getstate__(self):
        state = super(_JaxDevice, self).__getstate__()
        # jax device handles and meshes are process-local.
        state.pop("_jax_devices", None)
        state.pop("_mesh", None)
        return state

    def __setstate__(self, state):
        import jax
        super(_JaxDevice, self).__setstate__(state)
        try:
            self._jax_devices = jax.devices(self.PLATFORM)
        except RuntimeError:
            self._jax_devices = []
        self._mesh = None

    @property
    def exists(self):
        return bool(self._jax_devices)

    @property
    def jax_devices(self):
        return self._jax_devices

    @property
    def num_devices(self):
        return len(self._jax_devices)

    @property
    def model(self):
        if self._jax_devices:
            return getattr(self._jax_devices[0], "device_kind",
                           self.BACKEND)
        return self.BACKEND

    # -- mesh ---------------------------------------------------------------
    @property
    def mesh(self):
        """The logical device mesh (ref north star: mesh handle on the
        Device).  Axes come from ``root.common.engine.mesh.axes``; an axis
        size of -1 absorbs all remaining devices."""
        if self._mesh is None:
            self._mesh = self.make_mesh()
        return self._mesh

    def make_mesh(self, axes=None):
        import jax
        axes = dict(axes or root.common.engine.mesh.axes.to_dict())
        n = max(1, len(self._jax_devices))
        fixed = 1
        wild = None
        for name, size in axes.items():
            if size == -1:
                wild = name
            else:
                fixed *= size
        if wild is not None:
            axes[wild] = max(1, n // fixed)
        names = tuple(axes)
        shape = tuple(axes[name] for name in names)
        count = int(numpy.prod(shape)) if shape else 1
        devices = numpy.array(self._jax_devices[:count]).reshape(shape)
        return jax.sharding.Mesh(devices, names)

    # -- placement ----------------------------------------------------------
    def put(self, array):
        import jax
        return jax.device_put(array, self._jax_devices[0])

    def get(self, devarray):
        return numpy.asarray(devarray)

    def sync(self):
        import jax
        # Drains all dispatched computations on this backend.
        (jax.device_put(0.0, self._jax_devices[0]) + 0).block_until_ready()


class TPUDevice(_JaxDevice):
    """First-class TPU backend (the point of this framework)."""

    BACKEND = "tpu"
    PLATFORM = "tpu"


class CPUDevice(_JaxDevice):
    """XLA-on-CPU backend; hosts the virtual multi-device test mesh."""

    BACKEND = "cpu"
    PLATFORM = "cpu"

    def put(self, array):
        """Upload a PRIVATE copy of a numpy ``array`` (:func:`upload`).
        This device's memory IS host memory: ``jax.device_put`` takes a
        64-byte aligned numpy buffer without a copy and returns at once,
        so the "device copy" of a Vector would be the very array the
        host goes on writing (``mem[...] =``, ``publish``), under a step
        that is still queued.  A chip's upload is a copy by nature, so
        :class:`TPUDevice` keeps the plain put."""
        return upload(array, self._jax_devices[0])


def upload(array, placement):
    """``jax.device_put(array, placement)`` (a jax device or a sharding)
    under the one rule: a host buffer that jax has been handed is never
    written again.  Numpy arrays (``array`` may be a pytree of them)
    bound for CPU devices, where jax would alias their buffers
    (:meth:`CPUDevice.put`), are copied first; anything else goes as it
    is."""
    import jax
    devices = getattr(placement, "device_set", (placement,))
    if all(d.platform == "cpu" for d in devices):
        array = jax.tree.map(
            lambda a: a.copy() if isinstance(a, numpy.ndarray) else a,
            array)
    return jax.device_put(array, placement)


class NumpyDevice(Device):
    """Pure-numpy interpret backend (ref ``backends.py:918``): the debug /
    universal-fake device — unit ``numpy_run`` bodies execute eagerly with
    no jit, so pdb and printf work."""

    BACKEND = "numpy"

    @property
    def is_interpret(self):
        return True

    def put(self, array):
        return numpy.asarray(array)

    def get(self, devarray):
        return numpy.asarray(devarray)


class AutoDevice(Device):
    """The TPU, or an error (ref ``backends.py:406-424`` walked down a
    PRIORITY list; a training platform that lands on the CPU without
    saying so is worse than one that stops).  The CPU is chosen only
    when the process was started for it — ``JAX_PLATFORMS=cpu`` (what
    the tests set) / ``jax_platforms="cpu"`` — and the numpy backend
    only by name (``-d numpy``)."""

    BACKEND = "auto"

    def __new__(cls, **kwargs):
        requested = _requested_platforms()
        if requested == "cpu":
            return CPUDevice(**kwargs)
        try:
            return TPUDevice(**kwargs)
        except RuntimeError as exc:
            raise RuntimeError(
                "backend 'auto' looked for a TPU (jax.devices('tpu'), "
                "jax_platforms=%r) and found none: %s — to run on the "
                "CPU ask for it: -d cpu, root.common.engine.backend="
                "'cpu', or JAX_PLATFORMS=cpu" % (
                    requested,
                    str(exc).strip().splitlines()[0])) from exc


def make_device(backend=None, **kwargs):
    """CLI-style backend selection (ref ``Device.init_parser``
    ``backends.py:352``): ``backend`` is "auto"/"tpu"/"cpu"/"numpy"."""
    backend = (backend or root.common.engine.get("backend", "auto")).lower()
    klass = BackendRegistry.backends.get(backend)
    if klass is None:
        raise ValueError(
            "unknown backend %r (have: %s)" %
            (backend, ", ".join(sorted(BackendRegistry.backends))))
    return klass(**kwargs)
