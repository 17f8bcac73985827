"""Workflow: the container unit executing a graph of units.

Parity target: reference ``veles/workflow.py`` —

* ``Workflow`` (``workflow.py:87``): unit container with
  ``start_point``/``end_point``, per-unit ``add_ref`` registration
  (``:402``), initialization in dependency order with partial-init requeue
  (``:303-336``), run/stop lifecycle (``:351-377``), run-time statistics
  (``:767-826``), result gathering (``:827-851``), content checksum
  (``:852-866``), graphviz export (``:628``) and the master–slave job
  protocol (``generate_data_for_slave`` ``:478``,
  ``apply_data_from_slave`` ``:533``, ``do_job`` ``:558``).

TPU re-design: execution is an iterative FIFO work-queue (see
:mod:`veles_tpu.units` module docstring) — single-threaded and
deterministic by default, with an optional background executor for
host-blocking units.  Device work inside unit ``run()`` bodies is
asynchronously dispatched by JAX, so the queue loop overlaps host
scheduling with TPU compute naturally.
"""

import collections
import hashlib
import inspect
import os
import threading
import time

from veles_tpu.plumbing import EndPoint, StartPoint
from veles_tpu.units import Unit


class ChecksumError(Exception):
    """A unit's defining code cannot be content-addressed — master/slave
    code-mismatch detection would be unsound, so checksum() fails closed."""


class NoMoreJobs(Exception):
    """Master has no further jobs for slaves (ref ``workflow.py:498``)."""


class NoJobYet(Exception):
    """Master has nothing to hand out *right now* but more jobs may
    appear (e.g. a GA generation waiting on in-flight evaluations); the
    slave should retry shortly instead of quitting."""


class Workflow(Unit):
    """Container unit holding and executing a unit graph."""

    hide_from_registry = True

    #: seconds _drain waits for in-flight background units before
    #: raising — run() returning means the graph IS quiescent, never a
    #: silent shrug (warnings escalate every 60 s until then)
    QUIESCENCE_TIMEOUT = 600.0

    def __init__(self, workflow=None, **kwargs):
        self._units = []
        self._sync_ = None
        self.result_file = kwargs.get("result_file")
        super(Workflow, self).__init__(workflow, **kwargs)
        self._launcher = None
        if kwargs.get("launcher") is not None:
            self.launcher = kwargs["launcher"]  # setter → add_ref
        self.stopped = False
        self._run_time = 0.0
        self.start_point = StartPoint(self)
        self.end_point = EndPoint(self)
        self.negotiates_on_connect = True

    def init_unpickled(self):
        super(Workflow, self).init_unpickled()
        self._queue_ = collections.deque()
        self._queue_lock_ = threading.Lock()
        self._queue_cond_ = threading.Condition(self._queue_lock_)
        self._inflight_ = 0
        self._finished_event_ = threading.Event()
        self._job_callback_ = None
        # stitched segments hold jitted programs → transient; rebuilt by
        # initialize() (which re-runs after every unpickle-and-resume)
        self._stitch_segments_ = []
        self._epoch_runner_ = None
        self._stitch_active_ = False
        #: was the switch on when segments were last (re)built?  run()
        #: uses this to honor an off→on flip without re-walking the
        #: graph on every call (slaves run() once per job)
        self._stitch_built_enabled_ = False

    def __setstate__(self, state):
        super(Workflow, self).__setstate__(state)
        # workflow back-references are weakrefs (transient) — re-link.
        for unit in self._units:
            unit.workflow = self

    # -- membership ---------------------------------------------------------
    def add_ref(self, unit):
        """Units self-register on construction (ref ``workflow.py:402``)."""
        if unit is self:
            raise ValueError("a workflow cannot contain itself")
        if unit not in self._units:
            self._units.append(unit)
        unit.workflow = self

    def del_ref(self, unit):
        if unit in self._units:
            self._units.remove(unit)

    @property
    def units(self):
        return list(self._units)

    def __iter__(self):
        return iter(self._units)

    def __len__(self):
        return len(self._units)

    def __getitem__(self, key):
        if isinstance(key, str):
            for unit in self._units:
                if unit.name == key:
                    return unit
            raise KeyError(key)
        return self._units[key]

    # -- mode flags ---------------------------------------------------------
    @property
    def launcher(self):
        return self._launcher

    @launcher.setter
    def launcher(self, value):
        old = getattr(self, "_launcher", None)
        if old is not None and old is not value:
            del_ref = getattr(old, "del_ref", None)
            if del_ref is not None:
                del_ref(self)
        self._launcher = value
        if value is not None:
            add_ref = getattr(value, "add_ref", None)
            if add_ref is not None:
                add_ref(self)

    @property
    def is_master(self):
        return getattr(self._launcher, "is_master", False)

    @property
    def is_slave(self):
        return getattr(self._launcher, "is_slave", False)

    @property
    def is_standalone(self):
        return getattr(self._launcher, "is_standalone", True)

    # -- initialization ----------------------------------------------------
    def units_in_dependency_order(self):
        """BFS from start_point over control edges; unreachable units are
        appended afterwards in insertion order (ref ``workflow.py:269``)."""
        seen = []
        seen_set = set()
        frontier = collections.deque([self.start_point])
        while frontier:
            unit = frontier.popleft()
            if id(unit) in seen_set:
                continue
            seen_set.add(id(unit))
            seen.append(unit)
            for dst in unit.links_to:
                if id(dst) not in seen_set:
                    frontier.append(dst)
        appended = []
        for unit in self._units:
            if id(unit) not in seen_set:
                seen_set.add(id(unit))
                seen.append(unit)
                appended.append(unit)
        if appended and not getattr(self, "_warned_unreachable_",
                                    False):
            # one-time structured downgrade of the analyzer's V-G02
            # finding: standalone runs see WHICH units silently ride
            # in insertion order (master/slave payload fragility).
            # Same detection helper as the analyzer pass, so the two
            # cannot disagree (an unreachable end_point is appended
            # for ordering but excluded from the finding, both here
            # and there).
            self._warned_unreachable_ = True
            from veles_tpu.analyze.graph import unreachable_units
            flagged = unreachable_units(
                self.start_point, self._units,
                exclude=(self.end_point,))
            if flagged:
                self.warning(
                    "V-G02: %d unit(s) unreachable from start_point, "
                    "appended in insertion order: %s — they "
                    "initialize but never run; `python -m "
                    "veles_tpu.analyze` has the full pre-flight "
                    "report",
                    len(flagged), ", ".join(u.name for u in flagged))
        return seen

    def initialize(self, device=None, **kwargs):
        """Initialize all units in dependency order with partial-init
        requeue (ref ``workflow.py:303-336``): a unit whose demanded
        attributes are not yet produced is retried after its producers.
        Only :class:`~veles_tpu.units.MissingDemandedAttributes` requeues —
        each unit at most once per remaining peer — so genuine
        AttributeError bugs in ``initialize()`` bodies surface immediately."""
        from veles_tpu import trace, watch
        from veles_tpu.obs import blackbox
        from veles_tpu.units import MissingDemandedAttributes
        # honor the root.common.engine.trace knob per initialize (the
        # natural "a run starts here" boundary — off records nothing
        # in any hook); the flight-recorder knob
        # (root.common.obs.blackbox_dir) and the telemetry-bus knob
        # (root.common.watch.endpoint) arm at the same boundary
        trace.configure()
        blackbox.configure()
        watch.configure()
        self.device = device
        pending = collections.deque(self.units_in_dependency_order())
        retries = {}
        limit = len(pending)
        while pending:
            unit = pending.popleft()
            try:
                if device is not None and _accepts_kwarg(
                        unit.initialize, "device"):
                    unit.initialize(device=device, **kwargs)
                else:
                    unit.initialize(**kwargs)
            except MissingDemandedAttributes:
                retries[id(unit)] = retries.get(id(unit), 0) + 1
                if retries[id(unit)] > limit:
                    raise
                pending.append(unit)
        self._is_initialized = True
        self.stopped = False
        self.rebuild_stitching()
        return self

    # -- segment stitching (the eager fast path, veles_tpu.stitch) ----------
    def rebuild_stitching(self):
        """(Re)walk the unit chain and compile maximal runs of pure
        jitted units into single XLA programs (see
        :mod:`veles_tpu.stitch`).  Called at the end of
        :meth:`initialize` and again after any graph surgery (e.g. the
        slave-mode back-edge removal)."""
        from veles_tpu import stitch, trace
        with trace.span("segment", "rebuild_stitching"):
            for segment in self._stitch_segments_:
                segment.detach()
            self._stitch_segments_ = stitch.build_segments(self)
            self._stitch_built_enabled_ = stitch.enabled()
            # the epoch-scan runner rides the stitched shape: rebuilt
            # with it so its cycle analysis and compiled K-step window
            # programs can never outlive the segments they fold
            if self._stitch_segments_:
                from veles_tpu import epoch_scan
                self._epoch_runner_ = epoch_scan.build_runner(self)
            else:
                self._epoch_runner_ = None
        return self._stitch_segments_

    @property
    def stitch_active(self):
        """True while run() executes with stitched segments live."""
        return self._stitch_active_

    def stitch_report(self):
        """Observability: segment composition + dispatch counts (the
        compile/dispatch-count tests and the job layer's slave log
        read this).  ``loader_headed`` marks segments whose head runs a
        host prelude — i.e. the device-resident input pipeline fused
        the minibatch gather into that program."""
        from veles_tpu import stitch
        runner = self._epoch_runner_
        return {
            "enabled": stitch.enabled(),
            "segments": [segment.names
                         for segment in self._stitch_segments_],
            "loader_headed": [segment.has_prelude
                              for segment in self._stitch_segments_],
            "dispatches": sum(segment.dispatches
                              for segment in self._stitch_segments_),
            # the epoch-scan view: eligibility (with the blocking
            # reason when not), windows executed and steps they
            # covered — `dispatches` above stays the PER-STEP count
            "epoch_scan": runner.describe() if runner is not None
            else None,
        }

    def perf_report(self):
        """Text summary of the performance ledger
        (:mod:`veles_tpu.prof`): per-segment (and per-serve-bucket)
        flops / bytes / dispatch wall-time / achieved FLOP/s — MFU
        when the attached device has a peak-table entry — plus
        compile/recompile totals and the per-category HBM ledger.
        Always available (dispatch accounting has no knob); pair with
        ``trace_report()`` for the where-did-the-time-go view."""
        from veles_tpu import prof
        return prof.report_text()

    def trace_report(self, top=10):
        """Text summary of the in-memory trace ring (per-category
        totals, top-K spans by total time, segment dispatch vs
        host-gap split) — :func:`veles_tpu.trace.report_text` over the
        process-wide recorder.  Enable recording with
        ``root.common.engine.trace=on`` (or a ``.json`` path to also
        get the Perfetto timeline)."""
        from veles_tpu import trace
        return trace.report_text(top=top)

    # -- execution ----------------------------------------------------------
    def schedule(self, unit, src):
        """Enqueue a gate check for ``unit`` triggered by ``src``."""
        with self._queue_cond_:
            self._queue_.append((unit, src))
            self._queue_cond_.notify_all()

    def run(self):
        """Run the graph to completion (ref ``workflow.py:351-377``).

        The master never executes the graph body — job generation drives it
        instead (ref ``workflow.py:350-354``)."""
        if not self._is_initialized:
            raise RuntimeError("initialize() the workflow before run()")
        if self.is_master:
            return
        from veles_tpu import stitch
        # honored per run in BOTH directions: off after initialize
        # restores the per-unit path; on after an off-initialize builds
        # the missed segments now (once — not a graph re-walk per job)
        if stitch.enabled() and not self._stitch_segments_ \
                and not self._stitch_built_enabled_:
            self.rebuild_stitching()
        self._stitch_active_ = (bool(self._stitch_segments_)
                                and stitch.enabled())
        for segment in self._stitch_segments_:
            # an interrupted previous run may have left members
            # unconsumed — stale pass state must not suppress the
            # eager fallback
            segment.reset_pass()
        if self._epoch_runner_ is not None:
            # same hazard, Decision half: a window dispatched but the
            # decision never fired — its absorb flag must not skip a
            # real minibatch on this run
            self._epoch_runner_.reset_pass()
        self.stopped = False
        self._finished_event_.clear()
        tic = time.time()
        self.event("run", "begin")
        from veles_tpu import watch
        if watch.enabled():
            watch.publish("run", phase="begin",
                          workflow=type(self).__name__)
        self.schedule(self.start_point, None)
        self._drain()
        self._run_time += time.time() - tic
        self.event("run", "end")
        if watch.enabled():
            watch.publish("run", phase="end",
                          workflow=type(self).__name__,
                          run_time=round(self._run_time, 3),
                          results=self.gather_results())
            watch.publish("perf", self._perf_event())

    def _perf_event(self):
        """The compact perf digest a run's end publishes onto the
        telemetry bus: ledger counters + the HBM ledger peak — the
        live twin of ``perf_report()``'s headline numbers."""
        from veles_tpu import prof
        from veles_tpu.memory import Watcher
        totals = prof.ledger.summary()["totals"]
        hbm = Watcher.hbm_ledger()
        event = {key: totals.get(key) for key in
                 ("compiles", "recompiles", "flops_dispatched",
                  "achieved_flops", "mfu", "psum_bytes_moved")}
        event["hbm_peak_bytes"] = hbm.get("peak_bytes", 0)
        event["hbm_bytes"] = {
            cat: info["bytes"] for cat, info in
            hbm.get("by_category", {}).items() if info}
        report = self.stitch_report()
        event["dispatches"] = report.get("dispatches", 0)
        scan = report.get("epoch_scan") or {}
        event["scan_windows"] = scan.get("windows", 0)
        event["scan_steps"] = scan.get("steps", 0)
        return event

    def _drain(self):
        """Pop-and-run until the queue is empty AND no background unit is
        in flight.  ``wants_thread`` units execute on the shared host
        thread pool (ref ``veles/units.py:496-505`` ran *every* unit
        there); their downstream units are only scheduled from the
        worker after ``run()`` completes, so control-graph ordering is
        preserved — but units NOT downstream keep draining concurrently."""
        queue = self._queue_
        cond = self._queue_cond_
        while True:
            with cond:
                while not queue and self._inflight_ and not self.stopped:
                    cond.wait(0.05)
                if self.stopped or (not queue and not self._inflight_):
                    break
                unit, src = queue.popleft()
            if unit.wants_thread:
                self._spawn(unit, src)
            else:
                unit._check_gate_and_run(src)
        # join stragglers: run() returning MUST mean the graph is
        # quiescent — a wedged background unit would otherwise race
        # snapshot/teardown.  Escalate with warnings, then fail loudly
        # instead of silently violating the contract.
        with cond:
            start = time.time()
            next_warn = 60.0
            while self._inflight_:
                cond.wait(0.5)
                if not self._inflight_:   # finished at the boundary
                    break
                elapsed = time.time() - start
                if elapsed >= self.QUIESCENCE_TIMEOUT:
                    raise RuntimeError(
                        "workflow not quiescent: %d background unit(s) "
                        "still running %.0fs after drain" % (
                            self._inflight_, elapsed))
                if elapsed >= next_warn:
                    self.warning(
                        "%d background unit(s) still running %.0fs "
                        "after drain; waiting (timeout %.0fs)",
                        self._inflight_, elapsed, self.QUIESCENCE_TIMEOUT)
                    next_warn += 60.0
            queue.clear()

    def _spawn(self, unit, src):
        from veles_tpu import thread_pool
        with self._queue_cond_:
            self._inflight_ += 1
        thread_pool.submit(self._run_background, unit, src)

    def _run_background(self, unit, src):
        try:
            unit._check_gate_and_run(src)
        except Exception:
            self.exception("background unit %r failed", unit)
        finally:
            with self._queue_cond_:
                self._inflight_ -= 1
                self._queue_cond_.notify_all()

    def stop(self):
        self.stopped = True
        with self._queue_cond_:
            self._queue_cond_.notify_all()
        for unit in self._units:
            unit.stop()

    def on_workflow_finished(self):
        self.stopped = True
        self._finished_event_.set()
        cb, self._job_callback_ = self._job_callback_, None
        if cb is not None:
            cb(self.generate_data_for_master())
        if self.result_file:
            self.write_results()
        notify = getattr(self._launcher, "on_workflow_finished", None)
        if notify is not None:
            notify()

    def on_unit_failed(self, unit):
        self.warning("unit %r failed; stopping workflow", unit)
        self.stopped = True
        self._finished_event_.set()

    @property
    def run_time(self):
        return self._run_time

    # -- master/slave job protocol (ref workflow.py:478-617) ----------------
    def generate_data_for_slave(self, slave=None):
        """Per-unit payload list in dependency order; ``None`` entries for
        units that only negotiate on connect (ref ``workflow.py:478-510``)."""
        data = []
        for unit in self.units_in_dependency_order():
            if unit is self:
                continue
            data.append(unit.generate_data_for_slave(slave))
        return data

    def apply_data_from_master(self, data):
        units = [u for u in self.units_in_dependency_order() if u is not self]
        if len(data) != len(units):
            raise ValueError(
                "job payload has %d entries for %d units — master/slave "
                "workflow checksum mismatch?" % (len(data), len(units)))
        for unit, payload in zip(units, data):
            if payload is not None:
                unit.apply_data_from_master(payload)

    def prefetch_job(self, data):
        """Slave-side lookahead: offer the NEXT job's per-unit payloads
        to units exposing ``prefetch_job_data`` (the loader starts its
        minibatch IO) while the current job still computes.  Read-only
        with respect to serving state — ``apply_data_from_master``
        still happens when the job is actually processed."""
        units = [u for u in self.units_in_dependency_order()
                 if u is not self]
        if len(data) != len(units):
            return
        for unit, payload in zip(units, data):
            hook = getattr(unit, "prefetch_job_data", None)
            if hook is not None and payload is not None:
                try:
                    hook(payload)
                except Exception:
                    self.exception("prefetch_job_data failed on %r",
                                   unit)

    def generate_data_for_master(self):
        return [u.generate_data_for_master()
                for u in self.units_in_dependency_order() if u is not self]

    def apply_data_from_slave(self, data, slave=None):
        units = [u for u in self.units_in_dependency_order() if u is not self]
        if len(data) != len(units):
            raise ValueError(
                "update payload has %d entries for %d units — master/slave "
                "workflow checksum mismatch?" % (len(data), len(units)))
        for unit, payload in zip(units, data):
            if payload is not None:
                unit.apply_data_from_slave(payload, slave)

    def drop_slave(self, slave=None):
        for unit in self._units:
            unit.drop_slave(slave)

    def do_job(self, data, callback):
        """Slave side: install payload, run, send update via ``callback``
        (ref ``workflow.py:558-576``)."""
        self.apply_data_from_master(data)
        self._job_callback_ = callback
        self.run()

    # -- master crash-recovery (checkpoint protocol) ------------------------
    def _checkpoint_key(self, index, unit):
        """Stable per-unit key: dependency-order index + sanitized
        name.  The handshake checksum guarantees a restarted master
        rebuilds the same graph, so the index is reproducible; the
        name makes a mismatch loudly visible in the checkpoint dir."""
        safe = "".join(c if c.isalnum() else "_" for c in unit.name)
        return "u%03d_%s" % (index, safe)

    def capture_train_state(self):
        """Gather ``(train, meta)`` for a
        :class:`veles_tpu.checkpoint.TrainCheckpointer` — the master
        crash-recovery snapshot (docs/robustness.md).

        Every unit exposing ``checkpoint_state()`` contributes a dict;
        ndarray values go into the sharded ``train`` pytree, everything
        else into the JSON ``meta`` side.  The split is reassembled in
        :meth:`restore_train_state`, so units never see it."""
        import numpy
        train, meta = {}, {}
        for i, unit in enumerate(self.units_in_dependency_order()):
            if unit is self:
                continue
            hook = getattr(unit, "checkpoint_state", None)
            if hook is None:
                continue
            try:
                state = hook()
            except Exception:
                self.exception("checkpoint_state failed on %r", unit)
                continue
            if not state:
                continue
            key = self._checkpoint_key(i, unit)
            arrays = {k: v for k, v in state.items()
                      if isinstance(v, numpy.ndarray)}
            small = {k: v for k, v in state.items()
                     if not isinstance(v, numpy.ndarray)}
            if arrays:
                train[key] = arrays
            if small:
                meta[key] = small
        return train, meta

    def restore_train_state(self, train, meta):
        """Install a checkpoint captured by :meth:`capture_train_state`
        into this (freshly built and initialized) workflow: each
        contributing unit's ``restore_checkpoint_state(state)`` gets
        its reassembled dict back."""
        train = train or {}
        meta = meta or {}
        restored = 0
        for i, unit in enumerate(self.units_in_dependency_order()):
            if unit is self:
                continue
            hook = getattr(unit, "restore_checkpoint_state", None)
            if hook is None:
                continue
            key = self._checkpoint_key(i, unit)
            state = {}
            state.update(meta.get(key) or {})
            state.update(train.get(key) or {})
            if not state:
                continue
            try:
                hook(state)
                restored += 1
            except Exception:
                self.exception("restore_checkpoint_state failed on %r",
                               unit)
        self.info("restored checkpoint state into %d unit(s)", restored)
        return restored

    # -- results / stats ----------------------------------------------------
    def gather_results(self):
        """Collect metrics from IResultProvider units
        (ref ``workflow.py:827-851``)."""
        results = {}
        for unit in self._units:
            get = getattr(unit, "get_metric_values", None)
            if callable(get):
                try:
                    results.update(get())
                except Exception:
                    self.exception("result provider %r failed", unit)
        return results

    def write_results(self, path=None):
        import json
        path = path or self.result_file
        if not path:
            return

        def _default(obj):
            try:
                return float(obj)
            except (TypeError, ValueError):
                return repr(obj)
        with open(path, "w") as fout:
            json.dump(self.gather_results(), fout, indent=2,
                      default=_default)

    def get_unit_run_time_stats(self):
        """(unit, seconds) sorted descending (ref ``workflow.py:767-826``)."""
        stats = [(unit, unit.run_time) for unit in self._units]
        stats.sort(key=lambda pair: -pair[1])
        return stats

    def print_stats(self, top=10):
        total = sum(t for _, t in self.get_unit_run_time_stats()) or 1e-12
        self.info("unit run-time stats (top %d):", top)
        for unit, seconds in self.get_unit_run_time_stats()[:top]:
            self.info("  %6.2f%%  %8.3f s  %s",
                      100.0 * seconds / total, seconds, unit.name)

    # -- identity / export --------------------------------------------------
    def checksum(self):
        """Content-address the workflow definition so master and slave can
        verify they run the same code (ref ``workflow.py:852-866``, which
        hashes the workflow *file* bytes).

        Hashes (a) the graph structure (class + unit names in dependency
        order) and (b) the bytes of every module file defining a unit
        class.  A unit whose code cannot be located (REPL/exec-defined
        with no retrievable source) raises :class:`ChecksumError` —
        failing closed instead of letting two different workflows
        checksum equal."""
        sha = hashlib.sha256()
        files = {}      # module name → file path (module name, not the
        # path, goes into the hash: master and slave may hold the same
        # code at different absolute install locations)
        for unit in self.units_in_dependency_order():
            sha.update(type(unit).__name__.encode())
            sha.update(unit.name.encode())
            mod = inspect.getmodule(type(unit))
            fname = getattr(mod, "__file__", None)
            if fname and os.path.isfile(fname):
                files[mod.__name__] = fname
                continue
            try:
                sha.update(inspect.getsource(type(unit)).encode())
            except (OSError, TypeError):
                raise ChecksumError(
                    "cannot content-address %r (class %s: no module file "
                    "and no retrievable source) — master/slave checksum "
                    "would be unsound" % (unit, type(unit).__name__))
        for modname in sorted(files):
            sha.update(modname.encode())
            with open(files[modname], "rb") as fin:
                sha.update(fin.read())
        return sha.hexdigest()

    def package_export(self, path, precision=32, with_stablehlo=True):
        """Write a native-inference package (ref ``workflow.py:868-975``).

        Requires the workflow (or a subclass) to expose ``forwards`` —
        the forward units in execution order (StandardWorkflow does).
        """
        from veles_tpu.package import export_package
        return export_package(self, path, precision=precision,
                              with_stablehlo=with_stablehlo,
                              name=self.name)

    def generate_graph(self):
        """DOT text of the control graph (ref ``workflow.py:628``)."""
        lines = ["digraph %s {" % type(self).__name__.replace(" ", "_")]
        idx = {id(u): "u%d" % i for i, u in enumerate(self._units)}
        for unit in self._units:
            lines.append('  %s [label="%s\\n%s"];' % (
                idx[id(unit)], type(unit).__name__, unit.name))
        for unit in self._units:
            for dst in unit.links_to:
                if id(dst) in idx:
                    lines.append("  %s -> %s;" % (idx[id(unit)],
                                                  idx[id(dst)]))
        lines.append("}")
        return "\n".join(lines)


def _accepts_kwarg(fn, name):
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return False
    if name in sig.parameters:
        return True
    return any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in sig.parameters.values())
