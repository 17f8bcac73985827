"""PodRuntime: one pod, one program.

Turns an initialized, *stitched* workflow (:mod:`veles_tpu.stitch`)
into shards of a single pjit'd program per segment over a
:func:`veles_tpu.parallel.mesh.mesh_from_topology` mesh:

* the device-resident FullBatch dataset, the pre-mapped labels and the
  shuffled-index buffer shard row-wise over the ``data`` axis (each
  chip holds ``1/shards`` of the dataset), and the PR 4 traced
  ``(offset, size)`` gather partitions with them — GSPMD lowers the
  global ``jnp.take`` into per-shard index arithmetic with one
  combine, so minibatch selection never funnels through a host;
* parameters (and their momentum/solver state — every donated Vector)
  stay replicated (or TP-shard via ``param_rules``, the
  :func:`veles_tpu.parallel.dp.tp_rules` /
  :func:`~veles_tpu.parallel.dp.fsdp_rules` recipes), so the gradient
  contractions XLA partitions over the batch end in an in-program
  ``psum`` — the ICI all-reduce that replaces per-step ZMQ gradient
  frames — and the optimizer step runs sharded-in-program on donated
  HBM buffers;
* metric scalars come out replicated (already globally reduced), so
  Decision's host accounting is byte-compatible with the
  single-device run.

Nothing about the workflow's control graph changes: the loader prelude
still advances the serving cursor, Decision still closes epochs, the
segments just dispatch mesh-wide programs.  ``install()`` is therefore
reversible (:meth:`uninstall`) and must be re-run after any
``rebuild_stitching()``.

Elastic membership: :meth:`pre_dispatch` (called by every bound
segment before it gathers arguments) consults the chaos controller's
``pod_chip`` site — a scheduled ``chip_kill`` drops one chip from the
mesh, :meth:`reshard` shrinks the ``data`` axis to the largest size
the global batch still divides over the survivors, re-places every
resident buffer (params sync device→host→new-mesh: the run resumes
from the last in-HBM-consistent step), BUMPS the generation (the PR 7
staleness token the membership layer reports upstream) and recompiles
each segment once.  The reshard lands in the trace as a
``pod:reshard`` instant next to the chaos injection that provoked it.
"""

import numpy

from veles_tpu import chaos, prof, trace
from veles_tpu.config import root
from veles_tpu.logger import Logger
from veles_tpu.memory import Vector
from veles_tpu.parallel.mesh import MeshTopologyError, mesh_from_topology
from veles_tpu.prof.ledger import _fmt_bytes


class PodError(RuntimeError):
    """The workflow cannot run as a pod program (not stitched, no
    divisible batch, no mesh) — raised by :meth:`PodRuntime.install`
    with the remedy in the message."""


def spec_for_vector(vec, batch, shards, data_axis="data",
                    param_rules=None, donated=False):
    """THE per-Vector pod sharding rule — shared by
    :meth:`PodRuntime._spec_for` and the analyzer's V-P02 preflight
    (:func:`veles_tpu.analyze.shapes.check_pod`), so the residency
    estimate and the installed plan can never drift:

    * parameters — the ``params`` category, or ANY donated slot —
      replicate, unless ``param_rules`` returns a spec for the leaf
      (a raising rule raises here, identically at preflight and at
      install);
    * resident dataset rows and minibatch-sized staging tensors shard
      their leading dim over ``data_axis`` — but only when the row
      count divides the shard count: an uneven dataset replicates
      transparently instead of crashing ``jax.device_put`` (the
      preflight warns, so the lost sharding is not silent);
    * everything else replicates.
    """
    from jax.sharding import PartitionSpec as P
    shape = vec.shape or ()
    if donated or getattr(vec, "category", None) == "params":
        if param_rules is not None and shape:
            spec = param_rules(numpy.empty(shape, dtype=numpy.int8))
            if spec is not None:
                return spec
        return P()
    leading = shape[0] if shape else 0
    if leading and (getattr(vec, "category", None) == "dataset"
                    or leading == batch):
        if leading % max(1, shards) == 0:
            # the leading dimension alone: the spec then fits a resident
            # set's device form as it fits the array it stands for
            return P(data_axis)
        return P()
    return P()


class PodRuntime(Logger):
    """Compiles a stitched workflow's segments for a device mesh with
    in-program gradient aggregation.

    ``mesh``: a ``jax.sharding.Mesh`` with a ``data`` axis; default
    :func:`mesh_from_topology` (the ``root.common.engine.pod.topology``
    knob).  ``param_rules``: optional callable ``leaf_shape_array ->
    PartitionSpec | None`` applied to parameter/donated buffers (TP /
    FSDP sharding); ``None`` → fully replicated; ``"auto"`` → the
    static planner (:func:`veles_tpu.analyze.plan.auto_param_rules`)
    picks replicated/fsdp/tp for this mesh at ``install()`` (also
    spellable as the ``root.common.engine.pod.param_rules`` knob).
    ``data_axis`` names the batch axis ("data").

    ``preflight``: ``off`` | ``warn`` | ``fail`` — run the analyzer's
    V-P02 pod preflight at install (default: the
    ``root.common.engine.pod.preflight`` knob, else ``warn``).
    """

    def __init__(self, workflow, mesh=None, param_rules=None,
                 data_axis="data", preflight=None, **kwargs):
        super(PodRuntime, self).__init__(**kwargs)
        self.workflow = workflow
        self.data_axis = data_axis
        self.param_rules = param_rules
        if param_rules is None:
            node = root.common.engine.get("pod")
            knob = node.get("param_rules") if node else None
            if knob:
                # a knob can only spell a mode ("auto"); callables
                # come through the constructor
                self.param_rules = str(knob)
        #: the planner's winning candidate (dict) when param_rules
        #: resolved via "auto" at install()
        self.auto_plan = None
        self.mesh = mesh if mesh is not None else mesh_from_topology(
            require=(data_axis,))
        if data_axis not in self.mesh.shape:
            raise MeshTopologyError(
                "pod mesh %r has no %r axis" % (dict(self.mesh.shape),
                                                data_axis))
        if preflight is None:
            node = root.common.engine.get("pod")
            preflight = str((node.get("preflight") if node else None)
                            or "warn").lower()
        self.preflight = preflight
        self.generation = 1
        self.installed = False
        #: chips lost to chip_kill faults so far (reshard count)
        self.reshards = 0
        #: id(segment) -> analytic per-dispatch psum bytes (the ring
        #: all-reduce estimate over the segment's donated buffers)
        self._psum_bytes = {}
        #: id(segment) -> analytic per-dispatch expert all_to_all
        #: bytes (non-zero only under an ``expert`` mesh axis)
        self._a2a_bytes = {}
        #: pipeline microbatches per step (the
        #: ``root.common.engine.pod.microbatches`` knob; default =
        #: the planner's PP_MICRO_PER_STAGE × stage count)
        node = root.common.engine.get("pod")
        self.microbatches = int((node.get("microbatches") if node
                                 else 0) or 0) or None
        self._segments = []
        self._sharded_vecs = []
        #: membership hook: called as on_reshard(runtime) after an
        #: elastic reshard so the control plane can report the bumped
        #: generation on its next epoch sync
        self.on_reshard = None

    # -- properties ---------------------------------------------------------
    @property
    def shards(self):
        """Lockstep shards on the data axis."""
        return int(self.mesh.shape[self.data_axis])

    @property
    def devices(self):
        return [d for d in self.mesh.devices.flat]

    @property
    def pipe_stages(self):
        """Pipeline stages on the ``pipe`` axis (1 = no pipelining)."""
        return int(dict(self.mesh.shape).get("pipe", 1))

    @property
    def expert_shards(self):
        """Expert shards on the ``expert`` axis (1 = dense)."""
        return int(dict(self.mesh.shape).get("expert", 1))

    def _microbatches(self):
        if self.microbatches:
            return int(self.microbatches)
        from veles_tpu.analyze.plan import PP_MICRO_PER_STAGE
        return PP_MICRO_PER_STAGE * self.pipe_stages

    def describe(self):
        from veles_tpu.analyze.pricing import pipeline_bubble
        return {
            "shards": self.shards,
            "axes": dict(self.mesh.shape),
            "generation": self.generation,
            "reshards": self.reshards,
            "segments": [
                "+".join(s.names) for s in self._segments],
            "psum_bytes_per_step": sum(self._psum_bytes.values()),
            "all_to_all_bytes_per_step": sum(self._a2a_bytes.values()),
            "bubble_fraction": pipeline_bubble(self.pipe_stages,
                                               self._microbatches())
            if self.pipe_stages > 1 else 0.0,
            "auto_plan": (self.auto_plan or {}).get("name"),
        }

    # -- install ------------------------------------------------------------
    def install(self):
        """Shard the resident state and swap every stitched segment's
        program for its mesh-wide twin.  Idempotent; re-run after
        ``rebuild_stitching()``."""
        wf = self.workflow
        segments = list(getattr(wf, "_stitch_segments_", ()))
        if not segments:
            raise PodError(
                "workflow has no stitched segments — pod training "
                "rides the stitched fast path (initialize with "
                "root.common.engine.stitch=on on a jit device; "
                "interpret/NumpyDevice workflows cannot shard)")
        batch = int(wf.loader.max_minibatch_size)
        if batch % self.shards:
            raise PodError(
                "global batch %d does not divide over %d data shards "
                "— pick a batch a multiple of the data axis (or a "
                "smaller topology)" % (batch, self.shards))
        self._resolve_param_rules()
        self._run_preflight()
        self._segments = segments
        self._apply_shardings()
        self.installed = True
        self.info(
            "pod installed: %d segment(s) compiled for %d shard(s) "
            "%r, ~%s psum/step",
            len(segments), self.shards, dict(self.mesh.shape),
            _fmt_bytes(sum(self._psum_bytes.values())))
        return self

    def uninstall(self):
        """Back to single-device segments (clears vector shardings)."""
        for segment in self._segments:
            segment.clear_shardings()
            segment.prof_entry.shards = 1
        for vec in self._sharded_vecs:
            vec.set_sharding(None)
        self._sharded_vecs = []
        self._segments = []
        self._psum_bytes = {}
        self._a2a_bytes = {}
        self.installed = False
        self._invalidate_scan()
        return self

    def _invalidate_scan(self):
        """Drop the workflow's compiled epoch-scan window programs (a
        placement change is a new program by definition — the next
        window recompiles once, counted warmup, never flagged)."""
        runner = getattr(self.workflow, "_epoch_runner_", None)
        if runner is not None:
            runner.invalidate_programs()

    def _resolve_param_rules(self):
        """A string ``param_rules`` is a mode: ``auto`` hands the
        choice to the static planner (replicated / fsdp / tp over
        THIS mesh, priced with the shared pricing core); the winner's
        callable (or None) replaces the string before preflight and
        sharding, so everything downstream sees an explicit rule —
        same programs, same parity, zero extra recompiles."""
        if not isinstance(self.param_rules, str):
            return
        mode = self.param_rules.strip().lower()
        if mode in ("", "none", "off"):
            self.param_rules = None
            return
        if mode != "auto":
            raise PodError(
                "unknown param_rules mode %r (None | callable | "
                "'auto')" % (self.param_rules,))
        from veles_tpu.analyze.plan import auto_param_rules
        rules, name, row = auto_param_rules(
            self.workflow, self.mesh, data_axis=self.data_axis)
        self.param_rules = rules
        self.auto_plan = row
        self.info(
            "pod auto plan: %s (%s) — predicted %s/shard, %s "
            "psum/step",
            name, row.get("rule", "?"),
            _fmt_bytes(int(row.get("per_shard_bytes", 0))),
            _fmt_bytes(int(row.get("psum_bytes_per_step", 0))))

    def _run_preflight(self):
        if self.preflight == "off":
            return
        from veles_tpu.analyze import PreflightError
        from veles_tpu.analyze.shapes import check_pod
        report = check_pod(self.workflow, self.mesh,
                           data_axis=self.data_axis,
                           param_rules=self.param_rules)
        if report.has_errors and self.preflight == "fail":
            raise PreflightError(report)
        for finding in report:
            (self.warning if finding.severity == "error"
             else self.info)("pod preflight %s: %s", finding.rule,
                             finding.message)

    # -- sharding plan ------------------------------------------------------
    def _named(self, spec):
        from jax.sharding import NamedSharding
        return NamedSharding(self.mesh, spec)

    def _spec_for(self, vec, donated=False):
        """The shared per-Vector rule (:func:`spec_for_vector`) bound
        to this runtime's mesh/batch/rules."""
        return spec_for_vector(
            vec, int(self.workflow.loader.max_minibatch_size),
            self.shards, data_axis=self.data_axis,
            param_rules=self.param_rules, donated=donated)

    def _segment_shardings(self, segment):
        from jax.sharding import PartitionSpec as P
        don_ids = set(id(v) for v in segment._don_vecs)

        def spec(vec):
            return self._spec_for(vec, donated=id(vec) in don_ids)

        in_s = (tuple(self._named(spec(v))
                      for v in segment._input_vecs),
                tuple(self._named(spec(v)) for v in segment._ro_vecs),
                tuple(self._named(spec(v)) for v in segment._don_vecs),
                None)                      # traced python scalars
        out_s = ([self._named(spec(v)) for v in segment._output_vecs],
                 [self._named(spec(v)) for v in segment._don_vecs],
                 # metrics are globally-reduced device scalars
                 self._named(P()))
        return in_s, out_s

    def _segment_psum_estimate(self, segment):
        """Analytic per-dispatch ICI traffic: every donated buffer that
        replicates while the segment consumes batch-sharded tensors is
        all-reduced in-program — a ring moves ``2·(n−1)/n`` of the
        reduced bytes (XLA's cost model does not expose collective
        traffic, so the ledger carries this estimate, clearly labeled
        next to the measured ``h2d_bytes``).  The formula lives in the
        shared pricing core (:func:`veles_tpu.analyze.pricing
        .segment_psum_bytes`), so the static planner's prediction and
        this ledger entry cannot drift."""
        from veles_tpu.analyze.pricing import segment_psum_bytes
        return segment_psum_bytes(
            segment, int(self.workflow.loader.max_minibatch_size),
            self.shards, data_axis=self.data_axis,
            param_rules=self.param_rules)

    def _segment_a2a_estimate(self, segment):
        """Analytic per-dispatch expert-dispatch traffic (zero without
        an ``expert`` mesh axis) — the shared pricing-core formula
        (:func:`veles_tpu.analyze.pricing.segment_all_to_all_bytes`),
        carried in the ledger's ``all_to_all_bytes`` column next to
        (never mixed into) the ring-reduce ``psum_bytes``."""
        from veles_tpu.analyze.pricing import segment_all_to_all_bytes
        return segment_all_to_all_bytes(
            segment, int(self.workflow.loader.max_minibatch_size),
            self.expert_shards)

    def _apply_shardings(self):
        """Pin every plan Vector's placement and swap every segment's
        jit wrapper — placements land eagerly so the first dispatch
        lowers against mesh-resident arguments (and the AOT
        executables can then enforce them)."""
        # fresh estimates: a re-install after rebuild_stitching (or a
        # reshard) must not accumulate entries keyed by dead segments
        self._psum_bytes = {}
        self._a2a_bytes = {}
        seen = set()
        sharded = []
        for segment in self._segments:
            in_s, out_s = self._segment_shardings(segment)
            segment.set_shardings(in_s, out_s)
            segment.pod = self
            # the ledger's axis dimension: this entry's program now
            # runs N-wide (updated again on reshard)
            segment.prof_entry.shards = self.shards
            self._psum_bytes[id(segment)] = \
                self._segment_psum_estimate(segment)
            self._a2a_bytes[id(segment)] = \
                self._segment_a2a_estimate(segment)
            don_ids = set(id(v) for v in segment._don_vecs)
            # output Vectors are pinned too: per-step programs only
            # WRITE them (already mesh-placed), but an epoch-scan
            # window passes them back in as carry placeholders — a
            # single-device host re-upload would then reject against
            # the window program's explicit shardings
            for vec in (segment._input_vecs + segment._ro_vecs
                        + segment._don_vecs + segment._output_vecs):
                if id(vec) in seen or not isinstance(vec, Vector):
                    continue
                seen.add(id(vec))
                vec.set_sharding(self._named(self._spec_for(
                    vec, donated=id(vec) in don_ids)))
                sharded.append(vec)
        # resident loader buffers outside any current plan (targets of
        # a future segment rebuild) re-place with the dataset rule too
        for vec in self.workflow.loader.resident_vectors():
            if isinstance(vec, Vector) and vec and id(vec) not in seen:
                seen.add(id(vec))
                vec.set_sharding(self._named(self._spec_for(vec)))
                sharded.append(vec)
        self._sharded_vecs = sharded
        # eager re-place: devmem under the new sharding NOW, so the
        # first dispatch (and its AOT lower) sees mesh-resident args
        for vec in sharded:
            if vec and vec.device is not None \
                    and not vec.device.is_interpret:
                vec.devmem
        # epoch-scan window programs compiled for the OLD placement
        # (or none) must rebuild against this mesh
        self._invalidate_scan()

    def segment_psum_bytes(self, segment):
        """Per-dispatch collective bytes for ``segment`` (the ledger
        hook the stitched dispatch path calls).  Per STEP: an
        epoch-scan window multiplies by its K (every scanned step runs
        the same in-program psum on the data axis)."""
        return self._psum_bytes.get(id(segment), 0)

    def segment_all_to_all_bytes(self, segment):
        """Per-dispatch expert all_to_all bytes for ``segment`` — the
        ledger hook twin of :meth:`segment_psum_bytes`; an epoch-scan
        window multiplies by its K the same way."""
        return self._a2a_bytes.get(id(segment), 0)

    def scan_shardings(self, plan, with_verdict=False, n_pred=0):
        """Explicit mesh shardings for an epoch-scan window program
        over ``plan`` (:class:`veles_tpu.epoch_scan.ScanPlan`) — the
        SAME per-Vector rule as the per-step segment programs
        (:func:`spec_for_vector`), so a window compiled over the pod
        is the per-step pod program with the step loop folded in:
        carry params/momentum replicate (or TP/FSDP-shard via
        ``param_rules``), batch-shaped outputs and the resident
        dataset shard the data axis, stacked per-step scalars / the
        metric accumulator / the verdict replicate."""
        from jax.sharding import PartitionSpec as P
        rep = self._named(P())

        def spec(vec, donated=False):
            return self._named(self._spec_for(vec, donated=donated))

        in_s = (tuple(spec(v, True) for v in plan.don_vecs),
                tuple(spec(v) for v in plan.out_vecs),
                tuple(spec(v) for v in plan.ext_vecs),
                tuple(rep for _ in range(plan.n_scalars)),
                rep,
                tuple(rep for _ in range(n_pred)))
        out_s = (tuple(spec(v, True) for v in plan.don_vecs),
                 tuple(spec(v) for v in plan.out_vecs),
                 tuple(rep for _ in plan.metric_spec),
                 rep,
                 {"improved": rep, "stop": rep} if with_verdict
                 else ())
        return in_s, out_s

    # -- elastic membership -------------------------------------------------
    def pre_dispatch(self, segment):
        """The chaos ``pod_chip`` site, consulted before every sharded
        dispatch: a scheduled ``chip_kill`` loses one chip and
        triggers the elastic reshard.  Unarmed chaos costs one
        attribute check."""
        if not chaos.controller.armed:
            return
        fault = chaos.controller.process("pod_chip", role="pod")
        if fault is not None and fault.action == "chip_kill":
            self.warning("chaos: chip killed under a %d-shard pod",
                         self.shards)
            self.reshard(lost=1)

    def reshard(self, lost=1, devices=None):
        """Shrink the mesh after losing ``lost`` chips (or rebuild
        over an explicit ``devices`` list) and resume from the last
        in-HBM-consistent step.

        The surviving ``data`` axis is the largest size that (a) fits
        the survivors and (b) still divides the global batch — with
        power-of-two batches this halves the axis, the documented
        shrink policy.  Every resident buffer re-places (params sync
        device→host first, so the exact post-last-step values carry
        over), every segment recompiles once against the new mesh, and
        the generation bumps so the control plane can tell pre-reshard
        state from post."""
        import jax

        survivors = list(devices) if devices is not None \
            else self.devices[:max(1, len(self.devices) - int(lost))]
        batch = int(self.workflow.loader.max_minibatch_size)
        other = 1
        for name, size in self.mesh.shape.items():
            if name != self.data_axis:
                other *= int(size)
        if len(survivors) < other:
            # only the data axis is elastic: model/pipeline shards
            # hold DIFFERENT parameter slices, so a pod cannot lose
            # below its non-data extent — fail with the remedy, not a
            # reshape traceback mid-dispatch
            raise PodError(
                "cannot reshard: %d surviving chip(s) cannot carry "
                "the mesh's non-data axes (product %d) — a TP/PP-"
                "sharded pod cannot shrink below its model extent; "
                "restore the chips or redeploy with a smaller "
                "topology" % (len(survivors), other))
        # non-data axes keep their extent; data absorbs what remains
        new_n = max(1, len(survivors) // other)
        while new_n > 1 and batch % new_n:
            new_n -= 1
        axes = {name: (new_n if name == self.data_axis else int(size))
                for name, size in self.mesh.shape.items()}
        names = tuple(axes)
        shape = tuple(axes[n] for n in names)
        count = int(numpy.prod(shape))
        grid = numpy.array(survivors[:count]).reshape(shape)
        old_shards = self.shards
        self.mesh = jax.sharding.Mesh(grid, names)
        self.generation += 1
        self.reshards += 1
        self._psum_bytes = {}
        self._apply_shardings()
        trace.instant("pod", "reshard",
                      {"generation": self.generation,
                       "shards": self.shards,
                       "was": old_shards}, role="pod")
        from veles_tpu import watch
        if watch.enabled():
            watch.publish("reshard", generation=self.generation,
                          shards=self.shards, was=old_shards,
                          reshards=self.reshards)
        self.warning(
            "pod resharded %d -> %d shard(s) (generation %d): "
            "dataset + params re-placed, %d segment program(s) "
            "recompiling, training resumes from the last "
            "in-HBM-consistent step", old_shards, self.shards,
            self.generation, len(self._segments))
        hook = self.on_reshard
        if hook is not None:
            try:
                hook(self)
            except Exception:
                self.exception("on_reshard hook failed")
        return self
