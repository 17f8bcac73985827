"""What every run shares: finding a cell's files by name, the tracer,
the comparison of each number with its limit, the result line.

Holds no model, no traffic and no metric of its own: a configuration is
``configs/<name>.json`` (named by ``BENCHMARK.json``), a cell is
``workloads/<cell>.json``, a path is ``drivers/<driver>.py``, a
per-layer metric is ``layer_metrics/<name>.json`` over
``readers/<reader>.py``.
"""

import contextlib
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def start_jax():
    """JAX with no lower limit on what the persistent cache keeps: the
    LM's programs compile in under JAX's default second."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def enable_compile_cache(platform):
    """The program's ONE compile-cache rule
    (``backends.enable_compilation_cache``): the directory the
    environment names, else the fixed ``.cache/xla`` inside the checkout;
    nothing on the CPU.  The benchmark sets no directory of its own."""
    from veles_tpu import backends
    return backends.enable_compilation_cache(platform=platform)


def load_json(*parts):
    with open(os.path.join(*parts)) as handle:
        return json.load(handle)


def manifest():
    return load_json(ROOT, "BENCHMARK.json")


def merge(base, override):
    """``override`` over ``base``; nested objects merge key by key."""
    out = dict(base)
    for key, value in override.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merge(out[key], value)
        else:
            out[key] = value
    return out


def load_cell(name, rehearse=False, cell_file=None):
    """``(cell entry, cell parameters, configuration)`` of one cell.
    ``cell_file`` names a workload file outside the manifest (a
    rehearsal of a cell that is not listed yet)."""
    spec = manifest()
    if cell_file:
        params = load_json(cell_file)
        entry = {"name": name, "config": params["config"],
                 "traffic": params.get("traffic", name), "chips":
                 params.get("chips", 1)}
    else:
        entries = [w for w in spec["workloads"] if w["name"] == name]
        if not entries:
            raise SystemExit("no workload %r in BENCHMARK.json" % name)
        entry = entries[0]
        params = load_json(HERE, "workloads", name + ".json")
    configs = [c for c in spec["configs"] if c["name"] == entry["config"]]
    if not configs:
        raise SystemExit("no configuration %r in BENCHMARK.json"
                         % entry["config"])
    config = load_json(ROOT, configs[0]["file"])
    if rehearse:
        config = merge(config, config.get("rehearsal", {}))
        params = merge(params, params.get("rehearsal", {}))
    return entry, params, config


def load_driver(config):
    return importlib.import_module("benchmarks.drivers." + config["driver"])


def load_reference(config):
    return importlib.import_module(
        "benchmarks.reference." + config["reference"])


def peaks_for(kind):
    table = load_json(HERE, "peaks.json")
    if kind not in table:
        raise SystemExit("device kind %r is not in benchmarks/peaks.json"
                         % kind)
    return table[kind]


class Tracer(object):
    """The profiler around a part of the window, and the driver's own
    host spans.  Off (``enabled`` false) it does nothing, so a
    ``--trace 0`` run pays for none of it."""

    def __init__(self, enabled, log_dir):
        self.enabled = bool(enabled)
        self.log_dir = log_dir
        self.running = False
        self.done = False
        self.started_at = None
        self.stopped_at = None
        self._window = None

    def start(self):
        if not self.enabled or self.running or self.done:
            return
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self.running = True
        self.started_at = time.perf_counter()
        self._window = jax.profiler.TraceAnnotation("bench:window")
        self._window.__enter__()

    def stop(self):
        if not self.running:
            return
        import jax
        self._window.__exit__(None, None, None)
        self.stopped_at = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False
        self.done = True

    @contextlib.contextmanager
    def span(self, name):
        if not self.running:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield

    def wrap(self, owner, method, name):
        """Put a span around ``owner.method`` (an instance attribute, so
        only this object is touched).  A no-op when tracing is off."""
        if not self.enabled:
            return
        inner = getattr(owner, method)
        tracer = self

        def spanned(*args, **kwargs):
            with tracer.span(name):
                return inner(*args, **kwargs)

        setattr(owner, method, spanned)


class Context(object):
    """One run's arguments and files, handed to the driver."""

    def __init__(self, entry, params, config, seed, seconds, trace,
                 rehearse):
        self.entry = entry
        self.params = params
        self.config = config
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.rehearse = bool(rehearse)
        self.tracer = Tracer(trace, os.path.join(
            ROOT, ".cache", "bench_trace"))
        self.trace_seconds = min(
            float(params.get("trace_seconds", 4.0)), self.seconds)

    def log(self, message):
        print("[bench] " + message, file=sys.stderr, flush=True)


def judge(checks, limits):
    """``checks``: name -> number.  Returns ``(correct, compared)`` with
    ``compared`` name -> ``{"value", "limit"}``; a number with no limit
    in the cell's file is an error, not a pass."""
    compared = {}
    correct = True
    for name, value in checks.items():
        if name not in limits:
            raise SystemExit("no limit for %r in the cell's file" % name)
        limit = limits[name]
        ok = value is not None and value == value and value <= limit
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit}
    return correct, compared
