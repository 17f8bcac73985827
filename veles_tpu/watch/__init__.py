"""veles_tpu.watch — training-health telemetry + the live bus.

PR 12 (:mod:`veles_tpu.obs`) instrumented the *serving* side; this
package closes the *training* side and replaces the reference
platform's live-plotting operator surface (PAPER.md §0):

1. **In-program health telemetry** (:mod:`~veles_tpu.watch.health`) —
   ``root.common.engine.health = off|on|strict`` folds per-param-group
   grad-norm / weight-norm / update-ratio / non-finite counts into the
   stitched segment (and epoch-scan window) programs as a handful of
   device scalars riding the deferred-metrics fetch: zero extra
   dispatches, ``off`` bitwise identical, ``strict`` raising a typed
   :class:`~veles_tpu.watch.health.HealthError` naming the first
   non-finite parameter leaf at the window boundary.
2. **A live telemetry bus** (:mod:`~veles_tpu.watch.bus`) — a
   drop-tolerant ZMQ PUB socket (bounded HWM; a slow or dead
   subscriber can never backpressure a train or decode step) that
   workflows, the Decision's epoch closes, ``PodMaster``/``PodRuntime``
   and the generative scheduler publish periodic JSON snapshots onto;
   ``python -m veles_tpu.watch <endpoint>`` renders a live terminal
   dashboard and ``--record file.ndjson`` persists a session.
3. **A perf-regression watchdog** — ``scripts/bench_diff.py``
   compares a fresh ``bench.py`` run against a named bank of
   earlier records per metric and exits non-zero on regression.

Disabled path contract (the PR 5 rule): with no bus configured,
:func:`publish` is one attribute check; with ``health=off`` the
stitched programs are byte-identical to an unwatched build.

See ``docs/observability.md`` § Training health & live watch.
"""

from veles_tpu.watch import bus as _bus_mod, health  # noqa: F401
from veles_tpu.watch.bus import (  # noqa: F401
    TelemetryBus, TelemetryReader, load_events, record_events)
from veles_tpu.watch.health import (  # noqa: F401
    HealthError, HealthMonitor, health_mode, monitor)
from veles_tpu.config import root

#: the process-wide bus (None = disabled; publish() is then a no-op)
_bus = None


def enabled():
    """True when a telemetry bus is live in this process."""
    return _bus is not None


def bus():
    """The live :class:`TelemetryBus`, or ``None``."""
    return _bus


def start(endpoint=None, **kwargs):
    """Start (or return) the process bus.  ``endpoint`` default: the
    ``root.common.watch.endpoint`` knob, else a random local port."""
    global _bus
    if _bus is not None:
        return _bus
    node = root.common.get("watch")
    if endpoint is None:
        endpoint = (node.get("endpoint") if node else None) \
            or "tcp://127.0.0.1:0"
    if node is not None:
        kwargs.setdefault("hwm", int(node.get("hwm", 64) or 64))
        kwargs.setdefault("history",
                          int(node.get("history", 256) or 256))
        kwargs.setdefault("conflate",
                          bool(node.get("conflate", False)))
    _bus = TelemetryBus(endpoint, **kwargs)
    return _bus


def shutdown():
    """Close and forget the process bus (test hygiene)."""
    global _bus
    if _bus is not None:
        _bus.close()
        _bus = None


def configure():
    """Apply the ``root.common.watch.endpoint`` knob (called from
    ``Workflow.initialize`` at the same boundary trace/obs re-read
    theirs): a non-empty endpoint starts the bus once per process;
    empty/unset leaves publishing a no-op."""
    node = root.common.get("watch")
    endpoint = node.get("endpoint") if node else None
    if endpoint and _bus is None:
        start(str(endpoint))
    return _bus


def publish(kind, payload=None, **kwargs):
    """Publish one event onto the process bus; a single attribute
    check when no bus is configured.  Keyword args merge into (and
    override) ``payload``."""
    live = _bus
    if live is None:
        return None
    data = dict(payload or {})
    data.update(kwargs)
    return live.publish(kind, data)


def latest(kind=None):
    """Newest event per kind (host-side conflation), or one kind's —
    copied under the bus lock."""
    live = _bus
    if live is None:
        return None if kind else {}
    return live.latest_events(kind)


def recent_events(limit=64):
    """The newest ``limit`` published events (the blackbox tail),
    copied under the bus lock; ``[]`` with no bus."""
    live = _bus
    if live is None:
        return []
    return live.recent(limit)


def metrics_text():
    """Prometheus exposition for the per-role scrape endpoints
    (:func:`veles_tpu.obs.scrape.default_sources`): the latest cached
    health snapshot as ``veles_health_*`` gauges plus the bus's
    publish/drop counters.  Empty string when neither the health knob
    nor the bus is armed (the source contributes nothing to a scrape
    then — families stay contiguous by construction)."""
    lines = []
    snap = monitor.last_snapshot
    if snap:
        lines.append("# HELP veles_health_stat latest in-program "
                     "training-health stats by param group")
        lines.append("# TYPE veles_health_stat gauge")
        for group in sorted(snap.get("groups", {})):
            entry = snap["groups"][group]
            for stat in sorted(entry):
                if stat == "leaves":
                    continue
                lines.append(
                    'veles_health_stat{group="%s",stat="%s"} %g'
                    % (group, stat, entry[stat]))
        lines.append("# HELP veles_health_nonfinite non-finite "
                     "elements per donated param leaf (latest)")
        lines.append("# TYPE veles_health_nonfinite gauge")
        for group in sorted(snap.get("groups", {})):
            for leaf in sorted(snap["groups"][group]["leaves"]):
                lines.append(
                    'veles_health_nonfinite{group="%s",leaf="%s"} %d'
                    % (group, leaf,
                       snap["groups"][group]["leaves"][leaf]))
        lines.append("# TYPE veles_health_step gauge")
        lines.append("veles_health_step %d" % snap.get("step", 0))
    live = _bus
    if live is not None:
        info = live.describe()
        lines.append("# TYPE veles_watch_published_total counter")
        lines.append("veles_watch_published_total %d"
                     % info["published"])
        lines.append("# TYPE veles_watch_dropped_total counter")
        lines.append("veles_watch_dropped_total %d" % info["dropped"])
    return "\n".join(lines) + "\n" if lines else ""


def last_health():
    """The latest host-side health snapshot (cached by
    ``HealthMonitor.snapshot`` — populated whenever the health knob is
    armed, bus or no bus), or ``None``."""
    return monitor.last_snapshot
