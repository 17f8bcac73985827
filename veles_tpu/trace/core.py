"""The span recorder: a process-wide, lock-light ring of trace events.

Reference parity target: the platform's live observability pair — the
ZeroMQ plotting stream and the MongoDB-backed web status service
(``veles/graphics_server.py``, ``veles/web_status.py``) — whose job was
answering *what is the run doing right now*.  The TPU re-design asks a
sharper question — *where did the step time go* — and answers it the
way Pathways-style systems do: a timeline of spans across every
subsystem (segment dispatch, loader serving, H2D/D2H traffic, serve
request lifecycle, master–slave jobs), exported in the standard Chrome
trace-event format so Perfetto and ``chrome://tracing`` just work.

One API, two sinks.  :func:`span` writes to this module's ring when
``recorder.enabled`` says so, and ALWAYS emits a
``jax.profiler.TraceAnnotation`` named ``veles:<cat>/<name>``: a
TraceMe is inert without a profiler session, and during one (the
benchmark's, or :func:`device_trace`) the program's own spans land on
the host plane of the device trace, on the device's clock, with their
scalar arguments as the event's stats.  :func:`instant`,
:func:`counter` and :func:`complete` are ring-only: the profiler takes
no event after the fact.

Design constraints, in order:

1. **Ring off, a span is one inert annotation.**  ``span()`` reads
   ``recorder.enabled`` and, when it is False, returns the bare
   annotation: nothing is recorded, no timestamp is taken and no lock
   is held in Python (0.4 us against 0.26 us for a no-op context
   manager, measured on the CPU host).  ``instant`` / ``counter`` /
   ``complete`` cost one attribute check.  ``root.common.engine.trace
   = off`` (the default) therefore costs well under a microsecond a
   hook.
2. **Recording is allocation-light and lock-light.**  One
   ``perf_counter_ns`` pair per span, one small tuple, one slot store
   in a preallocated ring under a plain lock held for a few
   instructions.  No I/O ever happens on the recording path; export
   reads a snapshot.
3. **Fixed capacity, wraparound.**  The ring keeps the NEWEST
   ``capacity`` events; ``dropped`` counts what wrapped away, so a
   report can say "last N events of a longer run" instead of lying.

Event phases mirror the Chrome trace-event vocabulary: ``X`` complete
spans (begin + duration), ``i`` instants, ``C`` counter samples.
"""

import threading
import time

from veles_tpu.config import root

#: default ring capacity (events); override via
#: ``root.common.engine.trace_capacity``
DEFAULT_CAPACITY = 65536

#: the default process role; export maps each role to its own pid
#: (trainer / server / master / slave-<sid>)
DEFAULT_ROLE = "trainer"


#: prefix of every span's name in a profiler trace
ANNOTATION_PREFIX = "veles:"

_annotation_class = []


def _annotation(cat, name, args):
    """The ``jax.profiler.TraceAnnotation`` of one span (the class is
    looked up once: ``import jax`` stays lazy, as everywhere here)."""
    if not _annotation_class:
        from jax.profiler import TraceAnnotation
        _annotation_class.append(TraceAnnotation)
    label = "%s%s/%s" % (ANNOTATION_PREFIX, cat, name)
    if args:
        return _annotation_class[0](label, **args)
    return _annotation_class[0](label)


class _Span(object):
    """A live span: records one ``X`` event on exit, around the
    profiler annotation of the same span."""

    __slots__ = ("_rec", "cat", "name", "args", "role", "_begin",
                 "_annotation")

    def __init__(self, rec, cat, name, args, role, annotation):
        self._rec = rec
        self.cat = cat
        self.name = name
        self.args = args
        self.role = role
        self._annotation = annotation

    def set_metadata(self, **kwargs):
        """Arguments known only inside the span (a count of what it
        did), on both sinks; the bare annotation of the ring-off path
        has the same method."""
        self._annotation.set_metadata(**kwargs)
        self.args = dict(self.args or (), **kwargs)

    def __enter__(self):
        self._annotation.__enter__()
        self._begin = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._rec.record("X", self.cat, self.name, self._begin,
                         end - self._begin, self.args, self.role)
        self._annotation.__exit__(*exc)
        return False


class TraceRecorder(object):
    """Process-wide ring of trace events.

    Events are ``(phase, cat, name, ts_ns, dur_ns, tid, args, role)``
    tuples; ``ts_ns`` is ``time.perf_counter_ns`` (monotonic, arbitrary
    epoch — viewers only need relative time).
    """

    def __init__(self, capacity=DEFAULT_CAPACITY):
        #: THE hot-path switch: every instrumentation hook reads this
        #: one attribute and bails when False
        self.enabled = False
        #: export path armed by :func:`configure` (``trace=<p.json>``)
        self.path = None
        #: default role stamped on events recorded without an explicit
        #: one (set_role("server") etc. re-labels the whole process)
        self.role = DEFAULT_ROLE
        self.capacity = int(capacity)
        self._ring = [None] * self.capacity
        self._pos = 0
        self._lock = threading.Lock()
        #: (cat, name) -> count since clear(); survives ring wraparound
        #: so dispatch/compile counts stay exact on long runs (bench
        #: reads deltas of these)
        self._counts = {}

    # -- recording (hot) ----------------------------------------------------
    def record(self, phase, cat, name, ts_ns, dur_ns, args=None,
               role=None, tid=None):
        """``tid`` defaults to the recording thread's ident; an explicit
        value labels synthetic lanes — the pod runtime's per-shard
        dispatch spans use shard indices so one pod renders as ONE pid
        with a lane per chip in Perfetto."""
        event = (phase, cat, name, ts_ns, dur_ns,
                 threading.get_ident() if tid is None else int(tid),
                 args, role or self.role)
        key = (cat, name)
        with self._lock:
            self._ring[self._pos % self.capacity] = event
            self._pos += 1
            self._counts[key] = self._counts.get(key, 0) + 1

    # -- reading ------------------------------------------------------------
    def events(self):
        """Snapshot of the ring, oldest recorded → newest.  Indexing
        uses the SNAPSHOT's own length — a concurrent resize() (a
        configure() on another thread) must not skew the modulo into
        unwritten slots."""
        with self._lock:
            pos = self._pos
            ring = list(self._ring)
        n = min(pos, len(ring))
        return [ring[i % len(ring)] for i in range(pos - n, pos)]

    @property
    def recorded(self):
        """Total events ever recorded since the last clear()."""
        return self._pos

    @property
    def dropped(self):
        """Events that wrapped out of the ring."""
        return max(0, self._pos - self.capacity)

    def count(self, cat=None, name=None):
        """Exact event count by category and/or name (wraparound-proof
        — kept as running counters, not derived from the ring)."""
        with self._lock:
            items = list(self._counts.items())
        total = 0
        for (c, n), k in items:
            if cat is not None and c != cat:
                continue
            if name is not None and n != name:
                continue
            total += k
        return total

    def category_counts(self):
        """{category: event count} (wraparound-proof)."""
        with self._lock:
            items = list(self._counts.items())
        out = {}
        for (c, _n), k in items:
            out[c] = out.get(c, 0) + k
        return out

    # -- lifecycle ----------------------------------------------------------
    def clear(self):
        """Drop every recorded event (keeps enabled/role/path)."""
        with self._lock:
            self._ring = [None] * self.capacity
            self._pos = 0
            self._counts = {}

    def resize(self, capacity):
        """Install a new ring capacity (drops recorded events)."""
        capacity = int(capacity)
        if capacity < 1:
            raise ValueError("trace capacity must be >= 1")
        with self._lock:
            self.capacity = capacity
            self._ring = [None] * capacity
            self._pos = 0
            self._counts = {}


#: THE process-wide recorder every hook and exporter shares
recorder = TraceRecorder()


# -- the hot-path API -------------------------------------------------------

def span(cat, name, args=None, role=None):
    """Context manager timing a span, on both sinks: always a profiler
    annotation ``veles:<cat>/<name>`` carrying ``args`` (a dict of
    scalars the call site has at hand; one built only under
    :func:`enabled` is None when the ring is off, and so stays
    ring-only), and an ``X`` event in the ring when it is on.  Ring
    off: the bare, inert annotation; nothing recorded."""
    annotation = _annotation(cat, name, args)
    rec = recorder
    if not rec.enabled:
        return annotation
    return _Span(rec, cat, name, args, role, annotation)


def instant(cat, name, args=None, role=None):
    """Record a point event (Chrome phase ``i``)."""
    rec = recorder
    if not rec.enabled:
        return
    rec.record("i", cat, name, time.perf_counter_ns(), 0, args, role)


def counter(cat, name, value, role=None):
    """Record a counter sample (Chrome phase ``C``) — Perfetto renders
    consecutive samples of one name as a counter track."""
    rec = recorder
    if not rec.enabled:
        return
    rec.record("C", cat, name, time.perf_counter_ns(), 0,
               {"value": value}, role)


def complete(cat, name, begin_ns, dur_ns, args=None, role=None,
             tid=None):
    """Record a span retroactively from caller-held timestamps (the
    serve request lifecycle measures enqueue→reply with its own
    ``perf_counter`` stamps — same clock as ``perf_counter_ns``).
    ``tid`` labels a synthetic lane (pod per-shard spans)."""
    rec = recorder
    if not rec.enabled:
        return
    rec.record("X", cat, name, int(begin_ns), int(dur_ns), args, role,
               tid=tid)


def enabled():
    """The hot-path switch, for call sites that want to skip building
    args dicts entirely when tracing is off."""
    return recorder.enabled


def set_role(role):
    """Re-label events recorded by this process from here on (export
    gives each role its own pid: trainer/server/master/slave-<sid>)."""
    recorder.role = str(role)


# -- configuration ----------------------------------------------------------

_atexit_armed = [False]


def configure(value=None):
    """Apply the ``root.common.engine.trace`` knob (read fresh when
    ``value`` is None): ``off`` disables recording, ``on`` records to
    the in-memory ring, any other string is a path — record AND write
    a Perfetto-loadable Chrome trace-event JSON there at process exit
    (or via :func:`veles_tpu.trace.save`).  Returns the enabled state.

    ``root.common.engine.trace_capacity`` resizes the ring (only when
    it actually changes — a resize drops recorded events)."""
    if value is None:
        value = root.common.engine.get("trace", "off")
    path = None
    if isinstance(value, str):
        low = value.strip().lower()
        if low in ("", "off", "0", "false", "no", "none"):
            on = False
        elif low in ("on", "1", "true", "yes"):
            on = True
        else:
            on = True
            path = value
    else:
        on = bool(value)
    capacity = root.common.engine.get("trace_capacity", None)
    if capacity and int(capacity) != recorder.capacity:
        recorder.resize(int(capacity))
    recorder.enabled = on
    recorder.path = path
    if path is not None and not _atexit_armed[0]:
        import atexit

        from veles_tpu.trace import export
        _atexit_armed[0] = True
        atexit.register(export.save_at_exit)
    return on


# -- one trace with the device and the program's spans in it ---------------

class _DeviceTrace(object):
    """Context manager around ``jax.profiler.start_trace`` /
    ``stop_trace`` when a REAL accelerator is present; a no-op on the
    CPU (the XLA CPU profile would drown the host spans).  The
    profiler's options are the benchmark harness's (host tracer level
    1: TraceMe annotations, no Python call tracer), so every
    :func:`span` lands on the ``/host:CPU`` plane beside the device's
    planes.  What the profiler raises is raised: no hidden fallback.
    ``bool(ctx)`` inside the block tells whether the profiler runs."""

    def __init__(self, logdir=None):
        self._logdir = logdir
        self._started = False

    def __bool__(self):
        return self._started

    def __enter__(self):
        import jax
        if jax.devices()[0].platform == "cpu":
            return self
        logdir = self._logdir
        if logdir is None:
            import os
            logdir = os.path.join(
                root.common.dirs.get("cache") or ".", "jax_trace")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=options)
        self._started = True
        return self

    def __exit__(self, *exc):
        if self._started:
            self._started = False
            import jax
            jax.profiler.stop_trace()
        return False


def device_trace(logdir=None):
    """The operator's way to ONE trace holding the device's timeline
    and the program's own spans: wraps ``jax.profiler.start_trace`` /
    ``stop_trace`` when a non-CPU device is present, no-op otherwise.
    Use around a few warm steps; the ``.xplane.pb`` lands under
    ``<logdir>/plugins/profile/`` (default ``<cache dir>/jax_trace``)
    and opens in XProf / TensorBoard or Perfetto."""
    return _DeviceTrace(logdir)
