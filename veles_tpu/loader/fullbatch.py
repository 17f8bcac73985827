"""FullBatchLoader: whole dataset resident in memory (optionally on HBM).

Parity target: reference ``veles/loader/fullbatch.py`` —
``FullBatchLoader`` (``:79``) keeps ``original_data`` / ``original_labels``
resident and fills minibatches on-device via the ``fullbatch_loader``
gather kernel (``ocl/fullbatch_loader.cl:5-30``); ``FullBatchLoaderMSE``
(``:563``) adds ``original_targets`` for regression.

TPU re-design: the dataset Vectors live on HBM once (one upload), the
minibatch fill is :func:`veles_tpu.ops.gather.take_rows` on the shuffled
index slice — the jitted consumer (forward unit / fused train step) reads
``minibatch_data.devmem`` so the gather fuses into the step and nothing
round-trips to host during training.  The device copy of
``original_data`` (and ``original_targets``) is NOT held in the samples'
shape: the chip lays an image-shaped array out with the sample dimension
innermost, so a gather of 256 rows first copied the whole set, every
minibatch.  The Vectors are ``rows_major``: at upload each row is padded
to whole tiles and split into lanes (``ops.gather.resident_shape``; rows
that would grow by more than an eighth, and labels, keep their shape),
which the chip lays out rows-major, and the three gathers (``fill_minibatch``,
the stitched head, the epoch scan) take rows of that form and reshape
only those.  The host side (``original_data.mem``, ``shape``,
``analyze_dataset``, the normaliser) sees the samples' shape as before.
Normalization is applied to the
resident data once at initialize (the reference normalizes per-minibatch
on host; one-shot is equivalent for stateless/TRAIN-fit normalizers and
removes a per-step host pass).

Stitched-eager device fast path (``root.common.engine.loader``,
default ``auto``): when a jit device is attached the loader HEADS the
first stitched segment — :meth:`FullBatchLoader.stitch_stage` keeps
the serving bookkeeping as a host prelude and turns per-step minibatch
selection into an in-program ``jnp.take`` over the device-resident
shuffled-index buffer with traced ``minibatch_size`` masking.  The
gather fuses into the first forward program, ``pad_minibatch`` /
``normalize_minibatch`` stay no-ops, and a training step moves ZERO
per-step host→device bytes (the index buffer re-uploads once per
epoch shuffle; slaves re-use the resident dataset across jobs and
``prefetch_job_data`` stages the next job's index span concurrently
with the current compute).

Epoch-scan windows (``root.common.engine.epoch_scan``) build on the
same stage: the traced ``(offset, size)`` pair becomes one ROW of the
window's stacked per-step index scalars, so K consecutive gathers
lower to in-scan index arithmetic over the resident shuffled-index
buffer and a whole class pass dispatches once
(``docs/engine_fast_path.md`` § Epoch mode).
"""

import numpy

from veles_tpu.config import root
from veles_tpu.loader.base import (
    INDEX_DTYPE, Loader, LoaderError, TRAIN)
from veles_tpu.memory import StagingRing, Vector
from veles_tpu.ops.gather import take_rows


class FullBatchLoader(Loader):
    """Subclasses implement ``load_data()`` filling ``original_data``,
    ``original_labels`` (list or array) and ``class_lengths``."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        self.original_data = Vector(category="dataset", rows_major=True)
        self.original_labels = []
        #: keep the dataset on device and gather there (default on)
        self.store_in_device_memory = kwargs.get(
            "store_in_device_memory", True)
        #: keep the resident dataset in its NATIVE storage dtype (e.g.
        #: uint8 pixels) and publish the fitted normalizer as an affine
        #: ``input_norm=(scale, shift)`` for the fused train step
        #: instead of materializing normalized float32.  An HBM-bound
        #: step reads the batch twice (forward + weight gradient), so
        #: u8 residency quarters its dominant traffic term.  Requires
        #: an affine normalizer (``NormalizerBase.as_affine``).
        self.native_device_dtype = kwargs.get(
            "native_device_dtype", False)
        #: (scale, shift) for the jitted consumer; None unless
        #: native_device_dtype is active
        self.input_norm = None
        #: the pre-mapped labels as a device-residable Vector (int32),
        #: built at initialize when the dataset is labeled
        self.resident_labels = Vector(category="dataset")
        super(FullBatchLoader, self).__init__(workflow, **kwargs)

    def init_unpickled(self):
        super(FullBatchLoader, self).init_unpickled()
        #: staged device index buffers for the NEXT job's span
        #: (prefetch_job_data → apply_data_from_master hand-off):
        #: {(offset, size): (new_host_indices, Future[device array])}
        self._staged_indices_ = {}

    @property
    def has_labels(self):
        return len(self.original_labels) > 0

    @property
    def device_fast_path_active(self):
        """True when minibatch selection can run as an in-program
        gather over the HBM-resident dataset (the loader-headed
        stitched segment).  Resolution of ``root.common.engine.loader``:
        ``host`` disables; ``device``/``auto`` engage whenever a jit
        device is attached and the dataset is resident.  A
        ``native_device_dtype`` loader rides the same path with the
        gather+normalize HEAD (``ops.gather.take_rows_norm``): the raw
        storage-dtype rows are read once and the first forward program
        receives normalized float32."""
        mode = str(root.common.engine.get("loader", "auto")).lower()
        if mode == "host":
            return False
        return (self.device is not None
                and not self.device.is_interpret
                and self.store_in_device_memory
                and bool(self.original_data))

    def create_minibatch_data(self):
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size,) + self.original_data.shape[1:],
            dtype=self.original_data.dtype))

    def initialize(self, device=None, **kwargs):
        # device resolution (explicit arg → workflow.device) lives in
        # ONE place: the base Loader.initialize
        super(FullBatchLoader, self).initialize(device=device, **kwargs)
        if len(self.original_data) != self.total_samples:
            raise LoaderError(
                "original_data has %d samples, class_lengths say %d" %
                (len(self.original_data), self.total_samples))
        if self.has_labels and \
                len(self.original_labels) != self.total_samples:
            raise LoaderError("original_labels length mismatch")
        if self.native_device_dtype:
            # the normalizer stays symbolic: the fused step applies it
            # in-program and the dataset keeps its storage dtype
            self.input_norm = self.normalizer.as_affine()
            if self.input_norm is None:
                raise LoaderError(
                    "native_device_dtype needs an affine normalizer "
                    "(as_affine() returned None for %s)"
                    % type(self.normalizer).__name__)
        else:
            # One-shot normalization of the resident dataset (see
            # module doc).
            self.normalizer.normalize(self.original_data.mem)
            self.original_data.map_write()
        if self.has_labels:
            # None = unlabeled sample (e.g. a split without labels) → -1
            mapped = [-1 if raw is None
                      else self.labels_mapping.get(raw, raw)
                      for raw in self.original_labels]
            self._mapped_labels = numpy.asarray(mapped, dtype=numpy.int32)
            self.resident_labels.reset(self._mapped_labels)
        else:
            self._mapped_labels = None
        self._staged_indices_.clear()
        if self.device is not None and not self.device.is_interpret \
                and self.store_in_device_memory:
            self.original_data.initialize(self.device)
            self.original_data.devmem  # upload once
            if self.resident_labels:
                self.resident_labels.initialize(self.device)

    def analyze_dataset(self):
        """The dataset is fully resident: analyze directly instead of
        streaming minibatches (faster, same statistics)."""
        if self.class_lengths[TRAIN] == 0:
            if not self.normalizer.is_initialized:
                raise LoaderError(
                    "no train samples and uninitialized normalizer")
            return
        start = self.class_end_offsets[TRAIN - 1]
        self.normalizer.analyze(self.original_data.mem[start:])
        if self.has_labels and not self.labels_mapping:
            uniques = sorted(set(
                raw for raw in self.original_labels if raw is not None))
            self.labels_mapping = {raw: i for i, raw in enumerate(uniques)}

    def fill_minibatch(self):
        """Gather the minibatch rows (device-side when resident);
        returns the indices it took them by, for a subclass with further
        rows to take."""
        count = self.minibatch_size
        if count < self.max_minibatch_size:
            # short batch: -1 the tail for DIRECT fill_minibatch
            # callers (_iterate_class) — the serve path already did
            # this in fill_indices.  A full batch has no tail: skip
            # the write entirely (the fast-skip satellite)
            self.minibatch_indices.map_write()
            self.minibatch_indices.mem[count:] = -1
        # a COPY: fill_indices rewrites the Vector's host array for the
        # next minibatch while this one's gather may still be queued
        indices = self.minibatch_indices.mem[
            :self.max_minibatch_size].copy()
        if self.device is not None and not self.device.is_interpret \
                and self.store_in_device_memory:
            self.minibatch_data.devmem = take_rows(
                self.original_data.devmem, indices)
        else:
            self.minibatch_data.map_write()
            data = self.original_data.mem
            idx = numpy.asarray(indices)
            valid = idx >= 0
            gathered = data[numpy.where(valid, idx, 0)]
            mask = valid.reshape((-1,) + (1,) * (data.ndim - 1))
            self.minibatch_data.mem[...] = numpy.where(mask, gathered, 0)
        if self.has_labels:
            self.minibatch_labels.map_write()
            idx = numpy.asarray(indices)
            valid = idx >= 0
            self.minibatch_labels.mem[...] = numpy.where(
                valid, self._mapped_labels[numpy.where(valid, idx, 0)],
                -1)
            if not self.labels_mapping:
                # raw labels only feed mapping analysis; per-step python
                # loops here would host-bound the serving pipeline
                for i, index in enumerate(indices[:count]):
                    self.raw_minibatch_labels[i] = \
                        self.original_labels[index] if index >= 0 \
                        else None
        return indices

    def pad_minibatch(self, minibatch_size):
        """No-op: fill_minibatch gathers with -1 markers which zero/-1
        fill the tail already."""

    def normalize_minibatch(self):
        """No-op: the resident dataset was normalized once at
        initialize."""

    def map_minibatch_labels(self):
        """No-op: labels were mapped in fill_minibatch from the
        pre-mapped resident array."""

    # -- the loader-headed stitched segment (device fast path) --------------
    def _device_stage_plan(self):
        """``(name, source Vector, output Vector, pad value)`` rows the
        in-program gather produces; :class:`FullBatchLoaderMSE` extends
        with targets."""
        plan = [("minibatch_data", self.original_data,
                 self.minibatch_data, 0)]
        if self.has_labels:
            plan.append(("minibatch_labels", self.resident_labels,
                         self.minibatch_labels, -1))
        return plan

    def stitch_stage(self):
        """Head stage of the stitched eager chain: the host serving
        bookkeeping rides as the segment prelude
        (:meth:`veles_tpu.loader.base.Loader.stitch_prelude`) and the
        fill becomes an in-program ``take_rows`` over the resident
        dataset —
        the served span of the device-resident shuffled-index buffer is
        selected by the traced (offset, size) scalars, so one trace
        serves every batch of every class, short epoch tails included,
        and the gather fuses into the first forward program.  With
        ``native_device_dtype`` the data row instead goes through the
        fused gather+normalize head
        (:func:`veles_tpu.ops.gather.take_rows_norm`): the raw
        storage-dtype bytes are read once and the segment's consumers
        see normalized float32 — the affine normalizer never
        materializes a float copy of the resident dataset."""
        from veles_tpu.stitch import StitchStage
        if not self.device_fast_path_active:
            return None
        import jax
        import jax.numpy as jnp

        from veles_tpu.ops.gather import take_rows_norm
        max_mb = int(self.max_minibatch_size)
        plan = self._device_stage_plan()
        pads = {name: pad for name, _src, _out, pad in plan}
        norm = self.input_norm if self.native_device_dtype else None

        @jax.named_scope("veles.loader.take_rows")
        def fn(t):
            offset = t["offset"].astype(jnp.int32)
            size = t["size"].astype(jnp.int32)
            pos = jnp.arange(max_mb, dtype=jnp.int32)
            valid = pos < size
            idx = jnp.take(t["indices"],
                           jnp.where(valid, offset + pos, 0))
            out = {}
            for name in pads:
                if norm is not None and name == "minibatch_data":
                    # gather + affine normalize in one head kernel;
                    # -1 rows zero AFTER the normalize, so the short-
                    # batch padding contract (zeros) is unchanged
                    out[name] = take_rows_norm(
                        t["src_" + name],
                        jnp.where(valid, idx, -1), norm)
                    continue
                # rows out of the resident set (its device form or,
                # the labels, a plain array), the tail's rows zero
                rows = take_rows(t["src_" + name],
                                 jnp.where(valid, idx, -1))
                if pads[name]:
                    mask = valid.reshape((-1,) + (1,) * (rows.ndim - 1))
                    rows = jnp.where(mask, rows, pads[name])
                out[name] = rows
            return out

        params = {"indices": self.shuffled_indices}
        produces = {}
        for name, src, out_vec, _pad in plan:
            params["src_" + name] = src
            produces[name] = out_vec
        loader = self
        return StitchStage(
            self, fn, produces=produces, params=params,
            # ints, not floats: the segment passes python ints through
            # to the trace as int32, keeping offsets exact for
            # datasets beyond 2**24 samples
            scalars=lambda: {
                "offset": int(loader.minibatch_offset
                              - loader.minibatch_size),
                "size": int(loader.minibatch_size)},
            prelude=self.stitch_prelude)

    def resident_vectors(self):
        """The HBM-resident dataset family (pod sharding surface): the
        raw sample rows, the pre-mapped labels and the shuffled-index
        buffer — each sharded row-wise over the pod's ``data`` axis so
        one chip holds ``1/shards`` of the dataset and the stitched
        in-program gather partitions with it."""
        vectors = super(FullBatchLoader, self).resident_vectors()
        vectors.append(self.original_data)
        if self.resident_labels:
            vectors.append(self.resident_labels)
        return vectors

    # -- distribution: job-spanning residency -------------------------------
    def prefetch_job_data(self, data):
        """Slave-side lookahead on the device fast path: merge the NEXT
        job's index span into a private copy of the shuffled-index
        buffer and upload it in the background, so the next job's only
        H2D bytes overlap the current job's compute (the dataset itself
        never re-uploads — it is resident across jobs).  Host-path
        loaders keep the base fill-prefetch ring; like that ring,
        background staging is opt-in via the loader's ``prefetch``
        flag — an operator who disabled prefetch gets no background
        threads on ANY path."""
        if not self.device_fast_path_active:
            return super(FullBatchLoader, self).prefetch_job_data(data)
        if not self.prefetch:
            return
        key = (int(data["minibatch_offset"]),
               int(data["minibatch_size"]))
        if self._staged_indices_:
            # one staged span at a time: a second merge would snapshot
            # shuffled_indices BEFORE the first span lands, so its
            # buffer is stale by construction and apply_data_from_master
            # would discard it anyway — don't pay the copy + upload
            return
        self.shuffled_indices.map_read()
        merged = numpy.array(self.shuffled_indices.mem)
        merged[key[0] - key[1]:key[0]] = numpy.asarray(
            data["indices"], dtype=INDEX_DTYPE)
        from veles_tpu import thread_pool
        fut = thread_pool.submit(StagingRing.upload, self.device, merged)
        self._staged_indices_[key] = (merged, fut)

    def apply_data_from_master(self, data):
        key = (int(data["minibatch_offset"]),
               int(data["minibatch_size"]))
        staged = self._staged_indices_.pop(key, None)
        if self._staged_indices_:
            # a miss (or pipeline reorder) means the remaining
            # lookahead is stale — 2-deep job pipeline, same policy as
            # the base prefetch ring
            self._staged_indices_.clear()
        if staged is None:
            return super(FullBatchLoader, self).apply_data_from_master(
                data)
        for attr in ("minibatch_class", "minibatch_size",
                     "minibatch_offset", "epoch_number"):
            setattr(self, attr, data[attr])
        self.last_minibatch <<= False
        self.epoch_ended <<= False
        self.train_ended <<= False
        if numpy.asarray(data["indices"]).size != self.minibatch_size:
            raise LoaderError("minibatch size mismatch in job payload")
        merged, fut = staged
        try:
            dev = fut.result()
        except Exception:
            self.exception("staged index upload failed — re-uploading "
                           "on demand")
            dev = None
        self.shuffled_indices.publish(merged, dev)


class FullBatchLoaderMSE(FullBatchLoader):
    """Adds per-sample regression targets (ref ``fullbatch.py:563``)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        self.original_targets = Vector(category="dataset",
                                       rows_major=True)
        self.minibatch_targets = Vector(category="staging")
        super(FullBatchLoaderMSE, self).__init__(workflow, **kwargs)

    def _device_stage_plan(self):
        plan = super(FullBatchLoaderMSE, self)._device_stage_plan()
        plan.append(("minibatch_targets", self.original_targets,
                     self.minibatch_targets, 0))
        return plan

    def resident_vectors(self):
        vectors = super(FullBatchLoaderMSE, self).resident_vectors()
        vectors.append(self.original_targets)
        return vectors

    def initialize(self, device=None, **kwargs):
        super(FullBatchLoaderMSE, self).initialize(device=device, **kwargs)
        if len(self.original_targets) != self.total_samples:
            raise LoaderError("original_targets length mismatch")
        self.minibatch_targets.reset(numpy.zeros(
            (self.max_minibatch_size,) + self.original_targets.shape[1:],
            dtype=self.original_targets.dtype))
        if self.device is not None and not self.device.is_interpret \
                and self.store_in_device_memory:
            self.original_targets.initialize(self.device)
            self.original_targets.devmem

    def fill_minibatch(self):
        indices = super(FullBatchLoaderMSE, self).fill_minibatch()
        if self.device is not None and not self.device.is_interpret \
                and self.store_in_device_memory:
            self.minibatch_targets.devmem = take_rows(
                self.original_targets.devmem, indices)
        else:
            self.minibatch_targets.map_write()
            targets = self.original_targets.mem
            idx = numpy.asarray(indices)
            valid = idx >= 0
            gathered = targets[numpy.where(valid, idx, 0)]
            mask = valid.reshape((-1,) + (1,) * (targets.ndim - 1))
            self.minibatch_targets.mem[...] = numpy.where(
                mask, gathered, 0)
