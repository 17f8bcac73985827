"""veles_tpu.trace — unified low-overhead tracing & observability.

One span recorder threaded through every hot path the platform has: a
process-wide lock-light ring of spans/instants/counters
(:mod:`~veles_tpu.trace.core`) with Chrome trace-event / Perfetto
export, a text ``trace_report()`` summary and a ``python -m
veles_tpu.trace <trace.json>`` summarizer CLI
(:mod:`~veles_tpu.trace.export`).

Instrumented categories (see ``docs/observability.md``):

=========  ==========================================================
category   spans / counters
=========  ==========================================================
segment    stitched-program dispatches + first-dispatch compiles +
           ``rebuild_stitching`` walks (:mod:`veles_tpu.stitch`)
unit       per-unit ``run_wrapped`` on the UNstitched path
           (:mod:`veles_tpu.units`)
loader     minibatch serving, prefetch fills, staging-ring
           acquire/upload, publishes (:mod:`veles_tpu.loader.base`)
h2d        cumulative ``h2d_bytes`` / ``d2h_bytes`` counter tracks
           from every accounted transfer (:mod:`veles_tpu.memory`)
serve      request enqueue→reply, batched device calls, AOT bucket
           compiles (:mod:`veles_tpu.serve`)
jobs       master job generate/apply, slave request/compute/update,
           heartbeat gaps (:mod:`veles_tpu.parallel.jobs`)
watch      training-health boundary fetches: ``health_check``
           (strict-mode non-finite sweep) and ``health_snapshot``
           (full stat fetch) instants — the ONLY host syncs the
           health telemetry ever adds (:mod:`veles_tpu.watch`)
=========  ==========================================================

The knob: ``root.common.engine.trace = off | on | <path.json>`` —
``off`` (default) records nothing; ``on`` records into the
fixed-capacity ring (wraparound keeps the newest spans); a path
additionally writes the Perfetto-loadable JSON at process exit.

One API, two sinks: whatever the knob says, every :func:`span` is also
a profiler annotation ``veles:<cat>/<name>`` (inert without a profiler
session), so a ``jax.profiler`` trace — the benchmark's, or the one
:func:`device_trace` takes for an operator — holds the program's own
spans on the device's clock.
"""

from veles_tpu.trace.core import (  # noqa: F401
    ANNOTATION_PREFIX, DEFAULT_CAPACITY, TraceRecorder, complete, configure,
    counter, device_trace, enabled, instant, recorder, set_role, span)
from veles_tpu.trace.export import (  # noqa: F401
    chrome_events, load, metrics_text, report_text, save, summary)
