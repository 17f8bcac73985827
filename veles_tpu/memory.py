"""Vector: the universal buffer bridging host numpy and device HBM.

Parity target: reference ``veles/memory.py`` — ``Array`` (``:110``): a
numpy mirror + device buffer with an explicit
``map_read/map_write/map_invalidate/unmap`` coherence protocol
(``:371-383``), transparent device→host sync when pickling
(``__getstate__`` ``:284-299``) and a ``Watcher`` accounting peak device
allocation (``:56-107``).

TPU re-design: the device buffer is a ``jax.Array``.  JAX arrays are
immutable, so the mutable-buffer protocol becomes *generation tracking*:
the Vector knows whether host or device holds the freshest data and
converts lazily.  ``map_write → unmap`` round-trips still work (host edit
then re-upload), but the idiomatic fast path for jitted units is
``v.devmem`` in / reassign ``v.devmem`` out — no copies, donation-friendly.
The protocol takes host copy and device copy for two memories, and on the
CPU backend jax would make them one (an aligned numpy buffer is taken
without a copy, by ``device_put`` and by a jitted call alike, and the
call returns before it has run).  So the rule: **a host buffer that jax
has been handed is never written again** — uploads to CPU devices hand
over a private copy (:meth:`veles_tpu.backends.CPUDevice.put`,
:func:`~veles_tpu.backends.upload`), a Vector that a jitted unit
reads has a device (the owner attaches it), and no jitted call is given
``mem`` or a view of it.
Pickling syncs device→host exactly like the reference, so whole-workflow
snapshots capture weights regardless of where they live.
"""

import threading

import numpy

from veles_tpu import trace
from veles_tpu.backends import upload
from veles_tpu.distributable import Pickleable


class Watcher(object):
    """Device-memory accounting (ref ``memory.py:56-107``).

    Besides the reference's peak-allocation bookkeeping, the Watcher
    counts **transfer traffic in both directions**: every Vector
    upload and staging-ring upload reports ``h2d_bytes`` /
    ``h2d_transfers``, and every device→host fetch (``map_read``
    coherence syncs, the deferred-metrics ``device_get_all`` batch)
    reports ``d2h_bytes`` / ``d2h_transfers`` — so the bench ladder
    records ``h2d_bytes_per_step`` AND ``d2h_bytes_per_step`` and the
    input-pipeline / deferred-metrics work shows up as eliminated
    transfer bytes, not just img/s.  Each accounting call also samples
    a ``veles_tpu.trace`` counter track ("h2d" category) when tracing
    is on, so Perfetto shows the cumulative byte curves on the
    timeline.

    The Watcher is also the **live HBM ledger** behind
    ``veles_tpu.prof``: tracked bytes carry a *category* (the
    Vector's ``category`` tag — ``params`` / ``dataset`` / ``staging``
    / ``kv`` / ``other``) with current + peak accounting per category,
    and a per-Vector registry of resident buffers, so
    ``perf_report()`` can say not just *how much* HBM is in use but
    *whose* it is and what the headroom was."""

    lock = threading.Lock()
    bytes_in_use = 0
    peak_bytes = 0
    #: bumped by reset(); holds taken before the current generation
    #: were already wiped from the ledger, so their releases must be
    #: no-ops (GC can finalize a Vector long after a reset)
    generation = 0
    h2d_bytes = 0
    h2d_transfers = 0
    d2h_bytes = 0
    d2h_transfers = 0
    #: per-category current/peak resident bytes ({category: int})
    bytes_by_category = {}
    peak_by_category = {}
    #: id(owner) -> (shape, dtype str, nbytes, category) for every
    #: live tracked device buffer — the per-Vector ledger detail
    _vectors = {}

    @classmethod
    def track(cls, nbytes, category=None, owner=None):
        cat = category or "other"
        with cls.lock:
            cls.bytes_in_use += nbytes
            cls.peak_bytes = max(cls.peak_bytes, cls.bytes_in_use)
            total = cls.bytes_by_category.get(cat, 0) + nbytes
            cls.bytes_by_category[cat] = total
            cls.peak_by_category[cat] = max(
                cls.peak_by_category.get(cat, 0), total)
            if owner is not None:
                cls._vectors[id(owner)] = (
                    getattr(owner, "shape", None),
                    str(getattr(owner, "dtype", None)), nbytes, cat)

    @classmethod
    def untrack(cls, nbytes, category=None, owner=None):
        cat = category or "other"
        with cls.lock:
            cls.bytes_in_use -= nbytes
            cls.bytes_by_category[cat] = \
                cls.bytes_by_category.get(cat, 0) - nbytes
            if owner is not None:
                cls._vectors.pop(id(owner), None)

    @classmethod
    def hbm_ledger(cls, top=8):
        """JSON-able residency snapshot: totals, per-category
        current/peak, and the ``top`` biggest resident buffers."""
        with cls.lock:
            by_category = {
                cat: {"bytes": cls.bytes_by_category.get(cat, 0),
                      "peak": peak}
                for cat, peak in cls.peak_by_category.items()}
            vectors = sorted(cls._vectors.values(),
                             key=lambda v: -v[2])[:top]
        return {
            "bytes_in_use": cls.bytes_in_use,
            "peak_bytes": cls.peak_bytes,
            "by_category": by_category,
            "top_vectors": [
                {"shape": list(shape) if shape else None,
                 "dtype": dtype, "nbytes": nbytes, "category": cat}
                for shape, dtype, nbytes, cat in vectors],
        }

    @classmethod
    def track_h2d(cls, nbytes):
        with cls.lock:
            cls.h2d_bytes += int(nbytes)
            cls.h2d_transfers += 1
            total = cls.h2d_bytes
        trace.counter("h2d", "h2d_bytes", total)

    @classmethod
    def track_d2h(cls, nbytes):
        with cls.lock:
            cls.d2h_bytes += int(nbytes)
            cls.d2h_transfers += 1
            total = cls.d2h_bytes
        trace.counter("h2d", "d2h_bytes", total)

    @classmethod
    def reset(cls):
        with cls.lock:
            cls.generation += 1
            cls.bytes_in_use = 0
            cls.peak_bytes = 0
            cls.h2d_bytes = 0
            cls.h2d_transfers = 0
            cls.d2h_bytes = 0
            cls.d2h_transfers = 0
            cls.bytes_by_category = {}
            cls.peak_by_category = {}
            cls._vectors = {}


class Vector(Pickleable):
    """Host-mirrored device buffer.

    ``category`` tags the buffer for the Watcher's HBM ledger
    (``params`` / ``dataset`` / ``staging`` / ``kv``; ``None`` groups
    under ``other``) — set it at construction (weights, resident
    datasets and minibatch staging buffers already are), it rides
    pickling and is read at device-upload time.

    ``rows_major`` marks a resident data set, ``[samples, ...]``: its
    device copy is made by :func:`veles_tpu.ops.gather.upload_rows`, in
    the form a row gather reads cheaply (a
    :class:`~veles_tpu.ops.gather.ResidentRows` of this Vector's
    ``shape``, or the plain array where the form does not help).  The
    host side is untouched, and the copy is this Vector's like any
    other: ``reset`` drops it, the Watcher counts it once."""

    def __init__(self, data=None, category=None, rows_major=False):
        super(Vector, self).__init__()
        self._mem = None          # host numpy array (may be stale)
        self._device = None
        self.category = category
        self.rows_major = rows_major
        if data is not None:
            self.reset(data)

    def init_unpickled(self):
        super(Vector, self).init_unpickled()
        self._devmem_ = None       # jax.Array (transient)
        self._host_fresh_ = True   # host copy up to date
        self._dev_fresh_ = False   # device copy up to date
        self._tracked_bytes_ = 0
        self._tracked_category_ = None
        self._tracked_gen_ = 0
        #: pod-mesh placement (NamedSharding); process-local like the
        #: device handle, installed by PodRuntime via set_sharding()
        self._sharding_ = None
        # pre-category pickles (and bare __new__ construction paths)
        # lack the attribute entirely
        if not hasattr(self, "category"):
            self.category = None
        if not hasattr(self, "rows_major"):
            self.rows_major = False

    # -- basic properties ---------------------------------------------------
    def reset(self, data):
        """Install new host contents (ref ``Array.reset`` semantics)."""
        self._mem = numpy.ascontiguousarray(data) \
            if data is not None else None
        self._drop_devmem()
        self._host_fresh_ = True
        self._dev_fresh_ = False
        return self

    @property
    def shape(self):
        ref = self._devmem_ if self._devmem_ is not None else self._mem
        return tuple(ref.shape) if ref is not None else None

    @property
    def size(self):
        shape = self.shape
        if shape is None:
            return 0
        return int(numpy.prod(shape)) if shape else 1

    @property
    def dtype(self):
        ref = self._devmem_ if self._devmem_ is not None else self._mem
        return numpy.dtype(str(ref.dtype)) if ref is not None else None

    @property
    def nbytes(self):
        ref = self._devmem_ if self._devmem_ is not None else self._mem
        if ref is None:
            return 0
        return int(numpy.prod(ref.shape)) * ref.dtype.itemsize

    def __bool__(self):
        return self.shape is not None

    def __len__(self):
        shape = self.shape
        return shape[0] if shape else 0

    def __repr__(self):
        where = "dev" if (self._devmem_ is not None
                          and not self._host_fresh_) else "host"
        return "<Vector %s %s @%s>" % (self.shape, self.dtype, where)

    # -- device attachment --------------------------------------------------
    def initialize(self, device):
        """Attach to a device; uploads lazily on first devmem access."""
        self._device = device
        return self

    @property
    def device(self):
        return self._device

    # -- the coherence protocol --------------------------------------------
    @property
    def mem(self):
        """Host view.  Always safe to *read*; call :meth:`unmap` after
        in-place writes to publish them to the device."""
        self.map_read()
        return self._mem

    @mem.setter
    def mem(self, value):
        self.reset(value)

    @property
    def devmem(self):
        """The ``jax.Array``; uploads the host copy if it is fresher."""
        if self._device is None or self._device.is_interpret:
            return self.mem
        if self._devmem_ is None or not self._dev_fresh_:
            if self._mem is None:
                raise ValueError("empty Vector has no device memory")
            if self.rows_major:
                from veles_tpu.ops.gather import upload_rows
                self._set_devmem(upload_rows(
                    self._mem, self._device.put, self._sharding_))
            elif self._sharding_ is not None:
                # pod placement: EVERY upload of this Vector (epoch
                # reshuffles included) lands with its mesh sharding,
                # so the AOT pod executables never see a drifted
                # single-device array
                self._set_devmem(upload(self._mem, self._sharding_))
            else:
                self._set_devmem(self._device.put(self._mem))
            Watcher.track_h2d(self._mem.nbytes)
            self._dev_fresh_ = True   # host and device now agree
        return self._devmem_

    @devmem.setter
    def devmem(self, value):
        """Publish a new device array (the jitted-unit fast path)."""
        if self._device is not None and self._device.is_interpret:
            self._mem = numpy.asarray(value)
            self._host_fresh_ = True
            self._dev_fresh_ = False
            return
        self._set_devmem(value)
        self._dev_fresh_ = True
        self._host_fresh_ = False

    def map_read(self):
        """Ensure the host copy reflects device state (implicit D2H sync
        point, ref ``memory.py:371``)."""
        if not self._host_fresh_ and self._devmem_ is not None:
            self._mem = numpy.asarray(self._devmem_)
            self._host_fresh_ = True   # copies agree; device stays fresh
            Watcher.track_d2h(self._mem.nbytes)
        return self

    def map_write(self):
        """Declare intent to edit the host copy in place: next devmem
        access re-uploads."""
        self.map_read()
        if self._mem is not None and not self._mem.flags.writeable:
            # numpy views of jax arrays are read-only — materialize.
            self._mem = numpy.array(self._mem)
        self._dev_fresh_ = False
        return self

    def publish(self, host_array=None, device_array=None):
        """Install matching host and device copies in ONE step — the
        consume half of the prefetch staging ring: a background worker
        prepared both representations (host fill + async upload), so
        neither side needs a transfer here.  The previous device
        minibatch is released first (its buffer returns to the
        allocator — the donation analogue for a producer that cannot
        alias into jit's donate_argnums).

        Passing only ``host_array`` behaves like an in-place
        ``map_write`` edit; passing both marks BOTH sides fresh."""
        if host_array is not None:
            if self._mem is None or self._mem.shape != host_array.shape \
                    or not self._mem.flags.writeable:
                self._mem = numpy.array(host_array)
            else:
                self._mem[...] = host_array
            self._host_fresh_ = True
            self._dev_fresh_ = False
        if device_array is not None:
            self._set_devmem(device_array)
            self._dev_fresh_ = True
            if host_array is None:
                self._host_fresh_ = False
        return self

    @property
    def sharding(self):
        """The pinned pod-mesh placement (None = plain single-device
        puts through ``device.put``)."""
        return self._sharding_

    def set_sharding(self, sharding):
        """Pin (or clear, with None) this Vector's device placement to
        a ``jax.sharding.Sharding`` — the pod runtime's reshard
        primitive.  The freshest contents are preserved: a live device
        copy syncs to host first, then the device side drops so the
        next ``devmem`` access re-places it under the new sharding
        (chip-kill reshard = set a smaller mesh's shardings and touch
        ``devmem``).  No-op when the sharding is unchanged."""
        if sharding is self._sharding_:
            return self
        if self._devmem_ is not None:
            self.map_read()
        self._sharding_ = sharding
        self._drop_devmem()
        self._dev_fresh_ = False
        return self

    def map_invalidate(self):
        """Declare the host copy garbage (device will be overwritten)."""
        self._host_fresh_ = True
        self._dev_fresh_ = False
        self._drop_devmem()
        return self

    def unmap(self):
        """Compatibility no-op: publishing host edits is what
        :meth:`map_write` declares; the upload itself is lazy."""
        return self

    # -- pickling (snapshots) ----------------------------------------------
    def __getstate__(self):
        self.map_read()   # device → host sync (ref memory.py:284-299)
        return super(Vector, self).__getstate__()

    # -- helpers ------------------------------------------------------------
    def _set_devmem(self, value):
        self._untrack_devmem()
        self._devmem_ = value
        self._tracked_bytes_ = (
            int(value.nbytes)
            if value is not None and value.shape else 0)
        if self._tracked_bytes_:
            self._tracked_category_ = getattr(self, "category", None)
            Watcher.track(self._tracked_bytes_,
                          self._tracked_category_, owner=self)
            self._tracked_gen_ = Watcher.generation

    def _untrack_devmem(self):
        if self._tracked_bytes_:
            # a Watcher.reset() since the hold was taken already
            # wiped these bytes; releasing them again would drive
            # the ledger (and its category) negative
            if getattr(self, "_tracked_gen_", 0) == Watcher.generation:
                Watcher.untrack(self._tracked_bytes_,
                                self._tracked_category_, owner=self)
            self._tracked_bytes_ = 0

    def _drop_devmem(self):
        self._untrack_devmem()
        self._devmem_ = None

    def __del__(self):
        try:
            self._drop_devmem()
        except Exception:  # pragma: no cover - interpreter shutdown
            pass


class StagingRing(object):
    """Double-buffered host staging for the loader prefetch ring.

    A fixed ring of reusable host staging buffers (allocated ONCE —
    the seed prefetch path allocated a fresh ``zeros_like`` per
    background fill) plus a non-blocking upload helper: a background
    worker ``acquire()``\\ s the next slot, fills/normalizes/pads in
    place, then ``upload()``\\ s it so the device copy is in flight
    while the consumer still computes on the previous minibatch.

    Slot-reuse contract: a slot may be overwritten once ``depth``
    newer acquisitions happened — the caller picks ``depth`` ≥ its
    maximum fills-in-flight plus buffers still being read (the loader
    ring uses 3: ≤ 2 in-flight fills + 1 slot the consumer may still
    be publish-copying).
    """

    def __init__(self, shape, dtype, depth=2):
        self.depth = int(depth)
        self._slots = [numpy.zeros(shape, dtype=dtype)
                       for _ in range(self.depth)]
        self._pos = 0
        self._lock = threading.Lock()

    def acquire(self):
        """Next reusable staging buffer (round-robin).  The span
        covers the slot-lock wait — contention here means the ring is
        too shallow for the fills in flight."""
        with trace.span("loader", "ring_acquire"):
            with self._lock:
                slot = self._slots[self._pos]
                self._pos = (self._pos + 1) % self.depth
        return slot

    @staticmethod
    def upload(device, array):
        """Kick a host→device copy of a staged buffer and return the
        device array (``None`` when there is no jit device).  The put
        runs on the CALLING (background) thread — the scheduler thread
        never blocks on it — and the traffic is accounted so
        ``h2d_bytes_per_step`` bench records see staged uploads too."""
        if device is None or getattr(device, "is_interpret", True):
            return None
        with trace.span("loader", "staging_upload"):
            out = device.put(array)
        Watcher.track_h2d(array.nbytes)
        return out


def device_get_all(values):
    """Fetch a mixed list of device scalars / arrays / host numbers in
    ONE batched ``jax.device_get`` (a single transfer+sync instead of
    one per value) — the deferred-metrics fetch the device-resident
    evaluators rely on: per-minibatch metrics stay async device
    scalars, and epoch accounting pays exactly one round-trip.

    Host values (ints, floats, numpy) pass through untouched, so
    callers may mix eager (interpret) and device metrics freely."""
    device_idx = [i for i, v in enumerate(values)
                  if not isinstance(v, (int, float, numpy.number))
                  and not isinstance(v, numpy.ndarray)]
    out = list(values)
    if device_idx:
        import jax
        fetched = jax.device_get([values[i] for i in device_idx])
        Watcher.track_d2h(sum(getattr(v, "nbytes", 0)
                              for v in fetched))
        for i, val in zip(device_idx, fetched):
            out[i] = val
    return out


#: Reference-compatible alias (the reference class is ``Array``,
#: ``memory.py:110``; "Vector" is what Znicz unit attributes call theirs).
Array = Vector
