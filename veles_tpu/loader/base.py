"""Loader: 3-set dataset model and minibatch serving.

Parity target: reference ``veles/loader/base.py`` — ``Loader`` (``:120``)
with the ``ILoader`` contract ``load_data / create_minibatch_data /
fill_minibatch`` (``:100-112``); TEST/VALID/TRAIN 3-set model over one
concatenated index space (``:352-366``), per-epoch serving order
test→validation→train with flags ``last_minibatch`` / ``epoch_ended`` /
``train_ended`` (``:862-899``), train-set shuffling with ``shuffle_limit``
(``:711-731``), the failed-minibatch retry queue + per-slave pending
accounting that gives elastic fault tolerance (``:733-751``, ``:679-687``),
label mapping, normalizer hookup (``analyze_dataset`` ``:755``), and
master-side index distribution (``:631-687``).

TPU re-design notes: serving stays a host-side unit (it is control flow);
the device-side minibatch *fill* lives in
:class:`veles_tpu.loader.fullbatch.FullBatchLoader` where the dataset is
HBM-resident and gathering rides :func:`veles_tpu.ops.gather.take_rows`
— or, on the stitched eager path, fuses into the first forward segment
as an in-program gather (``root.common.engine.loader``, see
:meth:`Loader.stitch_prelude` and ``docs/engine_fast_path.md``).
For on-pod data parallelism the same index partitioning used for slaves
feeds per-device shards (see :mod:`veles_tpu.parallel`).

Loaders that cannot be fully resident (streaming/image) get a
double-buffered async prefetch ring instead: a background worker runs
``fill_minibatch_into`` for batch k+1 into a reusable
:class:`veles_tpu.memory.StagingRing` buffer — normalize + label-map +
pad included — and kicks a non-blocking host→device upload while the
stitched segments for batch k execute; the serve thread just publishes
the prepared pair (:meth:`veles_tpu.memory.Vector.publish`), releasing
the previous device minibatch for allocator reuse.
"""

import collections

import numpy

from veles_tpu import prng, trace
from veles_tpu.memory import Vector
from veles_tpu.mutable import Bool
from veles_tpu.normalization import normalizer_factory
from veles_tpu.units import Unit

TARGET = 3
TRAIN = 2
VALID = 1
TEST = 0
CLASS_NAME = ["test", "validation", "train"]

INDEX_DTYPE = numpy.int32
LABEL_DTYPE = numpy.int32


class LoaderError(Exception):
    pass


class Loader(Unit):
    """Base loader.  Subclasses implement ``load_data`` (fill
    ``class_lengths``), ``create_minibatch_data`` (allocate
    ``minibatch_data``) and ``fill_minibatch`` (fill data+raw labels for
    ``minibatch_indices``)."""

    hide_from_registry = True

    def __init__(self, workflow, **kwargs):
        self.class_lengths = [0, 0, 0]
        self.class_end_offsets = [0, 0, 0]
        self._effective_class_end_offsets = [0, 0, 0]
        self.max_minibatch_size = kwargs.get("minibatch_size", 100)
        self.minibatch_class = TRAIN
        self.minibatch_offset = 0
        self.minibatch_size = 0
        self.minibatch_data = Vector(category="staging")
        self.minibatch_labels = Vector(category="staging")
        self.minibatch_indices = Vector(category="staging")
        self.raw_minibatch_labels = []
        self.labels_mapping = {}
        self.shuffled_indices = Vector(category="dataset")
        self.shuffle_limit = kwargs.get("shuffle_limit", 2 ** 31)
        # ensemble members train on a subset; the manager communicates
        # the ratio via config (ref loader/base.py:524 train_ratio)
        if "train_ratio" in kwargs:
            self.train_ratio = kwargs["train_ratio"]
        else:
            from veles_tpu.config import root
            self.train_ratio = float(
                root.common.ensemble.get("train_ratio", 1.0) or 1.0)
        #: LoaderWithValidationRatio (ref docs): a (0, 1) ratio carves
        #: a validation set out of an all-train dataset at initialize.
        #: Validated HERE so a bad config fails before any data loads.
        ratio = kwargs.get("validation_ratio", None)
        if ratio is not None:
            try:
                ratio = float(ratio)
            except (TypeError, ValueError):
                raise LoaderError(
                    "validation_ratio must be a number in (0, 1), "
                    "got %r" % (kwargs["validation_ratio"],))
            if not 0.0 < ratio < 1.0:
                raise LoaderError(
                    "validation_ratio must be in (0, 1), got %r"
                    % ratio)
        self.validation_ratio = ratio
        self.testing = kwargs.get("testing", False)
        #: overlap next-minibatch IO with downstream compute (needs a
        #: subclass providing ``fill_minibatch_into``)
        self.prefetch = kwargs.get("prefetch", False)
        self.global_offset = 0
        self.samples_served = 0
        self.epoch_number = 0
        self.last_minibatch = Bool(False)
        self.epoch_ended = Bool(False)
        self.train_ended = Bool(False)
        self.test_ended = Bool(False)
        self.failed_minibatches = []
        self._total_failed = 0
        self._normalization_type = kwargs.get("normalization_type", "none")
        self._normalization_parameters = kwargs.get(
            "normalization_parameters", {})
        self._prng_name = kwargs.get("prng_name", "loader")
        #: the attached device (captured at initialize; None/interpret
        #: means host-only serving — no staging uploads)
        self.device = None
        super(Loader, self).__init__(workflow, **kwargs)
        self._normalizer = None

    def init_unpickled(self):
        import threading
        super(Loader, self).init_unpickled()
        #: outstanding minibatches per consumer: {slave_id: [(off, size)]}
        self.pending_minibatches_ = collections.defaultdict(list)
        #: pending background fills: {(offset, size): Future}
        self._prefetch_futures_ = {}
        #: serializes fill_minibatch vs background fill_minibatch_into —
        #: subclasses may share file handles between them
        self._fill_lock_ = threading.Lock()
        #: reusable staging buffers for the prefetch ring (lazy: needs
        #: minibatch_data's shape, known after initialize)
        self._staging_ring_ = None

    # -- configuration ------------------------------------------------------
    @property
    def prng(self):
        return prng.get(self._prng_name)

    @property
    def normalizer(self):
        if self._normalizer is None:
            self._normalizer = normalizer_factory(
                self._normalization_type, **self._normalization_parameters)
        return self._normalizer

    @property
    def has_labels(self):
        """Subclasses set ``_has_labels = True`` in ``load_data()`` when
        the dataset is labeled (ref determines this from the minibatch
        labels vector, ``base.py:258``)."""
        return getattr(self, "_has_labels", False) \
            or bool(self.labels_mapping)

    @property
    def total_samples(self):
        return sum(self.class_lengths)

    @property
    def effective_total_samples(self):
        return self._effective_class_end_offsets[TRAIN]

    @property
    def effective_class_end_offsets(self):
        return self._effective_class_end_offsets

    @property
    def total_failed(self):
        return self._total_failed

    @property
    def pending_minibatches_count(self):
        return sum(len(v) for v in self.pending_minibatches_.values())

    @property
    def class_ended(self):
        for offset in self.effective_class_end_offsets:
            if self.global_offset == offset:
                return True
            if self.global_offset < offset:
                return False
        raise LoaderError(
            "global_offset %d out of bounds %s" %
            (self.global_offset, self.effective_class_end_offsets))

    @property
    def shape(self):
        if not self.minibatch_data:
            raise AttributeError("minibatch_data not yet allocated")
        return self.minibatch_data.shape[1:]

    # -- ILoader contract ---------------------------------------------------
    def load_data(self):
        raise NotImplementedError

    def create_minibatch_data(self):
        raise NotImplementedError

    def fill_minibatch(self):
        raise NotImplementedError

    #: True when the subclass provides a pure, thread-safe
    #: ``fill_minibatch_into`` — enables :attr:`prefetch`
    supports_prefetch = False

    def fill_minibatch_into(self, indices, data_out, raw_labels_out):
        """Pure fill: write samples for ``indices`` into the given numpy
        buffers WITHOUT touching ``self.minibatch_*`` state.  Must be
        safe to call from a background thread while downstream units
        consume the previously served minibatch."""
        raise NotImplementedError

    # -- lifecycle ----------------------------------------------------------
    def initialize(self, **kwargs):
        super(Loader, self).initialize(**kwargs)
        device = kwargs.get("device", None)
        if device is None:
            device = getattr(self.workflow, "device", None)
        if device is not None:
            self.device = device
        # a re-initialize reshuffles the index space: any buffered
        # background fill belongs to the OLD shuffle and a later serve
        # with a matching (offset, size) key would silently publish the
        # stale buffer — drop everything in flight
        self._prefetch_futures_.clear()
        self._staging_ring_ = None
        if self.testing:
            self.shuffle_limit = 0
            self.global_offset = 0
            del self.failed_minibatches[:]
        self.load_data()
        if sum(self.class_lengths) == 0:
            raise LoaderError("there is no data to serve")
        if self.validation_ratio is not None and \
                self.class_lengths[VALID] == 0 and \
                self.class_lengths[TRAIN] > 0:
            # the reference's LoaderWithValidationRatio: a RANDOM
            # subset of the train span becomes validation.  The index
            # space stays contiguous ([test | valid | train]); one
            # prng permutation of the train span before the carve
            # makes the leading block a random sample — a label-sorted
            # dataset would otherwise send whole classes to validation
            k = int(self.class_lengths[TRAIN] * self.validation_ratio)
            if k > 0:
                start = self.class_lengths[0] + self.class_lengths[VALID]
                idx = numpy.arange(self.total_samples,
                                   dtype=INDEX_DTYPE)
                self.prng.shuffle(idx[start:])
                self.shuffled_indices.mem = idx
                self.class_lengths[VALID] = k
                self.class_lengths[TRAIN] -= k
                self.info(
                    "extracted %d random validation samples from "
                    "train (validation_ratio %.3f)", k,
                    self.validation_ratio)
        self._calc_class_end_offsets()
        self.info(
            "samples: test: %d, validation: %d, train: %d",
            *self.class_lengths)
        self.minibatch_labels.reset(numpy.zeros(
            self.max_minibatch_size, dtype=LABEL_DTYPE))
        self.raw_minibatch_labels = [None] * self.max_minibatch_size
        self.minibatch_indices.reset(numpy.zeros(
            self.max_minibatch_size, dtype=INDEX_DTYPE))
        self.create_minibatch_data()
        if not self.minibatch_data:
            raise LoaderError(
                "minibatch_data MUST be allocated in "
                "create_minibatch_data()")
        # a Vector with no device gives a jitted consumer its host
        # array itself, which the next fill rewrites under a step that
        # is still queued: what a loader serves (data, labels, a
        # subclass's targets) uploads like any unit's Vectors (a Loader
        # is not an AcceleratedUnit)
        for name, vec in vars(self).items():
            if name.startswith("minibatch_") and isinstance(vec, Vector):
                vec.initialize(self.device)
        self.analyze_dataset()
        self.shuffle()

    def run(self):
        """Serve one minibatch (standalone mode)."""
        self.pending_minibatches_.pop(None, None)
        self.serve_next_minibatch(None)
        self._on_successful_serve()
        self._start_prefetch()

    def stitch_prelude(self):
        """Host half of a loader-headed stitched dispatch (the device
        fast path): advance the serving state — offset/class, epoch
        flags, retry + pending accounting, the index window — WITHOUT
        filling any host minibatch buffer; the stitched segment
        gathers the batch in-program from the resident dataset."""
        self.pending_minibatches_.pop(None, None)
        self.serve_next_minibatch(None, fill=False)
        self._on_successful_serve()

    def scan_window_step(self):
        """One serving step of an epoch-scan window
        (:mod:`veles_tpu.epoch_scan`): byte-identical bookkeeping to
        :meth:`stitch_prelude`, called K times back-to-back while the
        window is planned — the K per-step preludes collapsed into one
        host loop before the single scan dispatch.  The served
        ``(minibatch_offset, minibatch_size)`` pair becomes that
        step's row of the scan's stacked index scalars."""
        self.stitch_prelude()

    # -- serving ------------------------------------------------------------
    def shuffle(self):
        """Shuffle the TRAIN span of the index space (ref ``:711-731``)."""
        if not self.shuffled_indices:
            self.shuffled_indices.mem = numpy.arange(
                self.total_samples, dtype=INDEX_DTYPE)
        if self.shuffle_limit <= 0 or self.class_lengths[TRAIN] == 0:
            return
        self.shuffle_limit -= 1
        self.shuffled_indices.map_write()
        self.prng.shuffle(
            self.shuffled_indices.mem[self.class_end_offsets[VALID]:])

    def class_index_by_sample_index(self, index):
        for class_index, offset in enumerate(
                self.effective_class_end_offsets):
            if index < offset:
                return class_index, offset - index
        raise LoaderError("sample index %d out of range" % index)

    def serve_next_minibatch(self, consumer_id, fill=True):
        """Pick the next (offset, size) — retrying failed minibatches
        first — and fill data (ref ``:726-752``).  ``fill=False`` is the
        loader-headed stitched dispatch: serving state advances but no
        host buffer is touched — the segment gathers in-program."""
        with trace.span("loader", "serve_minibatch"):
            retried = False
            try:
                minibatch_def = self.failed_minibatches.pop()
                retried = True
            except IndexError:
                minibatch_def = self._advance_global_offset()
            minibatch_offset, minibatch_size = minibatch_def
            self.pending_minibatches_[consumer_id].append(minibatch_def)
            self.minibatch_offset, self.minibatch_size = minibatch_def
            if retried:
                # a requeued batch keeps ITS class, not whatever class
                # the already-advanced global_offset is in; epoch flags
                # were signaled when the batch was first advanced
                self.minibatch_class, _ = \
                    self.class_index_by_sample_index(
                        minibatch_offset - minibatch_size)
                self.last_minibatch <<= False
                self.epoch_ended <<= False
            else:
                self._update_flags()

            self.fill_indices(minibatch_offset - minibatch_size,
                              minibatch_size)
            if self.is_master or not fill:
                return
            if self._consume_prefetched(minibatch_def):
                return      # fully prepared (normalized/mapped/padded)
            with trace.span("loader", "sync_fill"):
                with self._fill_lock_:
                    self.fill_minibatch()
                self.normalize_minibatch()
                self.map_minibatch_labels()
                if minibatch_size < self.max_minibatch_size:
                    self.pad_minibatch(minibatch_size)

    def pad_minibatch(self, minibatch_size):
        """Zero/-1-fill the tail of a short final batch (indices are
        already -1-padded by :meth:`fill_indices`).  Only ever called
        for a SHORT batch — a full batch skips the tail ``map_write``
        churn entirely.  Loaders whose ``fill_minibatch`` already pads
        (device-side gather) override with a no-op."""
        self.minibatch_data.map_write()
        self.minibatch_data.mem[minibatch_size:] = 0.0
        if self.has_labels:
            self.minibatch_labels.map_write()
            self.minibatch_labels.mem[minibatch_size:] = -1

    def fill_indices(self, start_offset, count):
        """Copy the served span of shuffled indices into
        ``minibatch_indices`` (ref ``:823-838``); a short batch gets a
        ``-1`` tail here so EVERY serving path (host fill, prefetch
        ring, in-program device gather) sees sane empty-slot markers."""
        self.minibatch_indices.map_write()
        self.shuffled_indices.map_read()
        self.minibatch_indices.mem[:count] = \
            self.shuffled_indices.mem[start_offset:start_offset + count]
        if count < self.max_minibatch_size:
            self.minibatch_indices.mem[count:] = -1
        return False

    def normalize_minibatch(self):
        self.normalizer.normalize(
            self.minibatch_data.mem[:self.minibatch_size])
        self.minibatch_data.map_write()

    def map_minibatch_labels(self):
        if not self.has_labels:
            return
        self.minibatch_labels.map_write()
        self._map_labels_into(self.minibatch_labels.mem,
                              self.raw_minibatch_labels,
                              self.minibatch_size)

    def _map_labels_into(self, labels_out, raw_labels, count):
        """raw → mapped labels for the first ``count`` slots — the ONE
        implementation both serving paths use (the synchronous
        :meth:`map_minibatch_labels` and the prefetch ring's
        :meth:`_prepare_staged`), so a hit and a miss can never map
        differently."""
        for i, raw in enumerate(raw_labels[:count]):
            labels_out[i] = self.labels_mapping.get(raw, -1) \
                if self.labels_mapping else raw

    def _calc_class_end_offsets(self):
        total = 0
        for i, n in enumerate(self.class_lengths):
            if not isinstance(n, (int, numpy.integer)):
                raise TypeError("class_lengths must be integers")
            total += n
            self.class_end_offsets[i] = total
        self._effective_class_end_offsets = list(self.class_end_offsets)
        self._effective_class_end_offsets[TRAIN] -= int(
            (1.0 - self.train_ratio) * self.class_lengths[TRAIN])

    def _advance_global_offset(self):
        """(ref ``:881-899``)"""
        if self.is_slave:
            return self.minibatch_offset, self.minibatch_size
        if self.global_offset >= self.effective_total_samples:
            self.global_offset = 0
            self.epoch_number += 1
            self.shuffle()
        self.minibatch_class, remainder = self.class_index_by_sample_index(
            self.global_offset)
        minibatch_size = min(remainder, self.max_minibatch_size)
        self.global_offset += minibatch_size
        self.train_ended <<= \
            self.global_offset >= self.effective_total_samples
        self.test_ended <<= \
            self.global_offset >= self.class_end_offsets[TEST]
        return self.global_offset, minibatch_size

    def _update_flags(self):
        """(ref ``:862-879``)"""
        if self.is_slave:
            return
        last_mb = (
            self.class_ended and
            (not self.pending_minibatches_count or not self.is_master) and
            not self.failed_minibatches)
        self.last_minibatch <<= last_mb
        self.epoch_ended <<= last_mb and (
            self.minibatch_class == VALID or
            (self.minibatch_class == TEST and
             self.class_lengths[TRAIN] == self.class_lengths[VALID] == 0) or
            (self.minibatch_class == TEST and self.testing) or
            (self.minibatch_class == TRAIN and
             self.class_lengths[VALID] == 0))

    # -- prefetch (double-buffered next-minibatch IO) -----------------------
    def _peek_next_minibatch(self):
        """The (offset, size) the NEXT standalone serve will pick, or
        None when it cannot be predicted side-effect-free (retry queue
        non-empty, epoch wrap pending — the wrap reshuffles — or
        master/slave mode)."""
        if (self.is_slave or self.is_master or self.failed_minibatches
                or self.global_offset >= self.effective_total_samples):
            return None
        _cls, remainder = self.class_index_by_sample_index(
            self.global_offset)
        size = min(remainder, self.max_minibatch_size)
        return self.global_offset + size, size

    def _staging(self):
        """Lazy staging ring sized like ``minibatch_data`` (allocated
        once; the worker fills slots in rotation).  Depth 3 = the ≤ 2
        fills ever in flight (:meth:`prefetch_job_data`) plus the slot
        the single consumer thread may still be publish-copying after
        popping its future — a recycled slot is therefore never
        refilled while it is being read."""
        if self._staging_ring_ is None:
            from veles_tpu.memory import StagingRing
            self._staging_ring_ = StagingRing(
                self.minibatch_data.shape, self.minibatch_data.dtype,
                depth=3)
        return self._staging_ring_

    def _prepare_staged(self, data_out, labels_out, raw_labels, size):
        """Worker-side minibatch prep: the normalize + label-map + pad
        the serve thread used to pay AFTER the fill — done here so a
        prefetch hit publishes a finished batch.  Label mapping is
        shared with the sync path (:meth:`_map_labels_into`); a loader
        that overrides :meth:`normalize_minibatch` or
        :meth:`pad_minibatch` with non-default semantics must override
        this too."""
        self.normalizer.normalize(data_out[:size])
        if size < self.max_minibatch_size:
            data_out[size:] = 0.0
        if self.has_labels:
            self._map_labels_into(labels_out, raw_labels, size)

    def _submit_fill(self, key, indices, size):
        """Queue a background fill of ``indices`` into a staging-ring
        slot under ``key`` (the (offset, size) the matching serve will
        present).  The worker does the WHOLE prep — fill, normalize,
        label-map, pad — then kicks a non-blocking device upload, so
        the serve thread's share of a hit is one ``publish()``.
        ``_fill_lock_`` serializes against synchronous fills
        (subclasses may share file handles)."""
        from veles_tpu.memory import StagingRing
        data_out = self._staging().acquire()
        labels_out = numpy.full(self.max_minibatch_size, -1,
                                dtype=LABEL_DTYPE)
        raw_labels = [None] * self.max_minibatch_size
        device = self.device

        def work():
            # the WHOLE body under the fill lock: it serializes shared
            # file handles AND ring-slot access — a dropped worker
            # still prepping a recycled slot must never overlap a
            # newer worker's fill of the same buffer
            with trace.span("loader", "prefetch_fill"):
                with self._fill_lock_:
                    self.fill_minibatch_into(indices, data_out[:size],
                                             raw_labels)
                    self._prepare_staged(data_out, labels_out,
                                         raw_labels, size)
                    dev_data = StagingRing.upload(device, data_out)
                    dev_labels = StagingRing.upload(device, labels_out) \
                        if self.has_labels else None
            return data_out, labels_out, raw_labels, dev_data, dev_labels

        from veles_tpu import thread_pool
        self._prefetch_futures_[key] = thread_pool.submit(work)

    def _start_prefetch(self):
        """Kick a background fill of the predicted next minibatch into
        private buffers (the IO-overlap half of the reference's threaded
        unit execution, ``veles/thread_pool.py:71``)."""
        if not (self.prefetch and self.supports_prefetch):
            return
        if self.is_slave or self.is_master:
            # distributed prefetch is driven by prefetch_job_data (the
            # next job's payload) — do NOT clobber its bookkeeping here
            return
        nxt = self._peek_next_minibatch()
        if nxt is None:
            # unpredictable (retry queued / epoch wrap → reshuffle):
            # anything buffered may be wrong for a same-offset later
            # serve — drop it (the lock keeps still-running work safe)
            self._prefetch_futures_.clear()
            return
        offset, size = nxt
        self.shuffled_indices.map_read()
        indices = numpy.array(
            self.shuffled_indices.mem[offset - size:offset])
        self._submit_fill(nxt, indices, size)

    def prefetch_job_data(self, data):
        """Slave-side IO overlap (the reference's async double-buffering
        one level deeper, ``client.py:293-296``): the job client hands
        us the NEXT job's loader payload while the CURRENT job still
        computes; start filling those exact indices into private
        buffers so ``apply_data_from_master`` + serve find them ready."""
        if not (self.prefetch and self.supports_prefetch):
            return
        key = (int(data["minibatch_offset"]),
               int(data["minibatch_size"]))
        # ≤ 2 in flight (the job pipeline is 2-deep); an identical key
        # keeps the OLDER future — jobs are served in order, so it
        # matches first and the newer duplicate simply refills
        if len(self._prefetch_futures_) >= 2 \
                or key in self._prefetch_futures_:
            return
        self._submit_fill(key, numpy.array(data["indices"]), key[1])

    def _consume_prefetched(self, minibatch_def):
        """Publish the prepared staging pair when a background fill
        matches the minibatch being served; ``False`` → the caller
        falls back to the synchronous fill+prep path.  A worker
        exception propagates here (never lost in the pool) and demotes
        to the sync path with the full traceback logged."""
        key = (int(minibatch_def[0]), int(minibatch_def[1]))
        fut = self._prefetch_futures_.pop(key, None)
        if fut is None:
            if self._prefetch_futures_ and not self.is_slave:
                # stale standalone predictions: drop (slave mode keeps
                # the map — a mismatch there just means the future
                # belongs to the NEXT job, racing the current serve)
                self._prefetch_futures_.clear()
            return False
        try:
            data, labels, raw_labels, dev_data, dev_labels = fut.result()
        except Exception:
            self.exception("prefetch failed — refilling synchronously")
            return False
        # both representations land fresh: the host copy for host
        # consumers, the already-uploaded device copy for the jitted
        # chain — and the PREVIOUS device minibatch is released for
        # allocator reuse (Vector.publish)
        with trace.span("loader", "publish"):
            self.minibatch_data.publish(data, dev_data)
            self.raw_minibatch_labels[:] = raw_labels
            if self.has_labels:
                self.minibatch_labels.publish(labels, dev_labels)
        return True

    def _on_successful_serve(self):
        self.samples_served += self.minibatch_size
        if self.last_minibatch:
            self.debug(
                "last minibatch of class %s served in epoch %d",
                CLASS_NAME[self.minibatch_class], self.epoch_number)

    # -- normalization analysis --------------------------------------------
    def analyze_dataset(self):
        """Stream the TRAIN set through the normalizer once
        (ref ``:755-803``); also collects the label mapping when the
        subclass provides raw labels."""
        if self.class_lengths[TRAIN] == 0:
            if not self.normalizer.is_initialized:
                raise LoaderError(
                    "no train samples and the normalizer is uninitialized; "
                    "derive_from() an existing loader or set "
                    "normalizer.state")
            return
        labels_seen = {}

        def callback():
            if self.has_labels and not self.labels_mapping:
                for raw in self.raw_minibatch_labels[:self.minibatch_size]:
                    if raw is not None and raw not in labels_seen:
                        labels_seen[raw] = len(labels_seen)
            self.normalizer.analyze(
                self.minibatch_data.mem[:self.minibatch_size])

        self._iterate_class(TRAIN, callback)
        if self.has_labels and not self.labels_mapping and labels_seen:
            # integer raw labels keep their numeric order
            try:
                ordered = sorted(labels_seen)
            except TypeError:
                ordered = list(labels_seen)
            self.labels_mapping = {raw: i for i, raw in enumerate(ordered)}

    def _iterate_class(self, class_index, fn):
        if not self.shuffled_indices:
            self.shuffled_indices.mem = numpy.arange(
                self.total_samples, dtype=INDEX_DTYPE)
        length = self.class_lengths[class_index]
        start = self.class_end_offsets[class_index - 1] \
            if class_index > 0 else 0
        n_batches = int(numpy.ceil(length / self.max_minibatch_size))
        for i in range(n_batches):
            offset = i * self.max_minibatch_size
            self.minibatch_size = min(self.max_minibatch_size,
                                      length - offset)
            self.minibatch_indices.map_write()
            self.minibatch_indices.mem[:self.minibatch_size] = \
                self.shuffled_indices.mem[
                    start + offset:start + offset + self.minibatch_size]
            self.fill_minibatch()
            fn()

    def derive_from(self, other):
        """Reuse another loader's normalization statistics + label
        mapping (ref ``:249``) — the test/inference-time path."""
        self._normalization_type = other._normalization_type
        self._normalization_parameters = other._normalization_parameters
        self._normalizer = normalizer_factory(
            self._normalization_type, **self._normalization_parameters)
        self._normalizer.state = other.normalizer.state
        self.labels_mapping = dict(other.labels_mapping)
        return self

    # -- distribution (ref :631-687) ---------------------------------------
    def resident_vectors(self):
        """Dataset-category Vectors that stay device-resident for the
        whole run — the buffers the pod runtime (:mod:`veles_tpu.pod`)
        shards over its ``data`` axis and re-places on an elastic
        reshard.  Base loaders expose the shuffled-index buffer;
        FullBatch subclasses add the resident dataset/labels/targets."""
        return [self.shuffled_indices]

    def generate_data_for_master(self):
        return True

    def generate_data_for_slave(self, slave=None):
        sid = getattr(slave, "id", slave)
        self.serve_next_minibatch(sid)
        data = {"indices": numpy.array(
            self.minibatch_indices.mem[:self.minibatch_size])}
        for attr in ("minibatch_class", "minibatch_size",
                     "minibatch_offset", "epoch_number"):
            data[attr] = getattr(self, attr)
        return data

    def apply_data_from_master(self, data):
        for attr in ("minibatch_class", "minibatch_size",
                     "minibatch_offset", "epoch_number"):
            setattr(self, attr, data[attr])
        self.last_minibatch <<= False
        self.epoch_ended <<= False
        self.train_ended <<= False
        indices = data["indices"]
        if indices.size != self.minibatch_size:
            raise LoaderError("minibatch size mismatch in job payload")
        if not self.shuffled_indices:
            self.shuffled_indices.mem = numpy.arange(
                self.total_samples, dtype=INDEX_DTYPE)
        self.shuffled_indices.map_write()
        self.shuffled_indices.mem[
            self.minibatch_offset - self.minibatch_size:
            self.minibatch_offset] = indices

    def apply_data_from_slave(self, data, slave=None):
        sid = getattr(slave, "id", slave)
        if not self.pending_minibatches_.get(sid):
            raise LoaderError("no pending minibatches for slave %r" % sid)
        self.minibatch_offset, self.minibatch_size = \
            self.pending_minibatches_[sid].pop()
        self._on_successful_serve()

    def drop_slave(self, slave=None):
        sid = getattr(slave, "id", slave)
        if sid in self.pending_minibatches_:
            failed = self.pending_minibatches_.pop(sid)
            self._total_failed += len(failed)
            self.failed_minibatches.extend(failed)
            self.info("requeued %d failed minibatches (total failed: %d)",
                      len(failed), self._total_failed)

    # -- master crash-recovery (checkpoint protocol) -------------------------
    def checkpoint_state(self):
        """Serving-cursor snapshot for master crash-recovery: epoch,
        global offset, the shuffled index permutation and the retry
        queue.  In-flight (pending) minibatches are folded into the
        retry queue — after a resume their slaves' updates are
        stale-rejected by the job layer, so the work MUST be
        re-served or those samples would silently vanish from the
        epoch."""
        state = {
            "epoch_number": int(self.epoch_number),
            "global_offset": int(self.global_offset),
            "minibatch_class": int(self.minibatch_class or 0),
            "samples_served": int(self.samples_served),
            "failed": [(int(o), int(s))
                       for o, s in self.failed_minibatches],
            "pending": [(int(o), int(s))
                        for defs in self.pending_minibatches_.values()
                        for o, s in defs],
        }
        if self.shuffled_indices:
            self.shuffled_indices.map_read()
            state["shuffled_indices"] = numpy.array(
                self.shuffled_indices.mem)
        return state

    def restore_checkpoint_state(self, state):
        self.epoch_number = int(state.get("epoch_number", 0))
        self.global_offset = int(state.get("global_offset", 0))
        self.minibatch_class = int(state.get("minibatch_class", 0))
        self.samples_served = int(state.get("samples_served", 0))
        if state.get("shuffled_indices") is not None:
            self.shuffled_indices.reset(numpy.asarray(
                state["shuffled_indices"], dtype=INDEX_DTYPE))
        requeue = [(int(o), int(s))
                   for o, s in (state.get("failed") or ())]
        requeue += [(int(o), int(s))
                    for o, s in (state.get("pending") or ())]
        self.failed_minibatches = requeue
        self.pending_minibatches_.clear()
        # epoch-edge flags recompute at the next serve
        self.last_minibatch <<= False
        self.epoch_ended <<= False
        self.train_ended <<= False
        if requeue:
            self.info("resume requeued %d in-flight/failed "
                      "minibatch(es) from the checkpoint",
                      len(requeue))

    # -- results ------------------------------------------------------------
    def get_metric_values(self):
        return {"Total epochs": self.epoch_number}
