"""Auto-vivifying configuration tree (the ``root.*`` namespace).

Capability parity with the reference's config system
(``veles/config.py:60-147`` — auto-vivifying dotted namespace; defaults at
``:178-290``; ``site_config.py`` override chain ``:294-307``; protected keys
``:79-85``), re-designed for the TPU build:

* the tree is a plain nested-attribute namespace, printable and
  pickle/JSON-able, so whole-run configuration snapshots ride along with
  checkpoints;
* genetic search-range markers (``Tuneable``/``Range`` — see
  :mod:`veles_tpu.genetics.config`) may be embedded as *values* anywhere in
  the tree, exactly like the reference embeds them
  (``veles/genetics/config.py:45-110``);
* TPU-relevant defaults live under ``root.common.engine`` (backend name,
  precision policy incl. bfloat16, mesh axes) instead of the reference's
  OpenCL/CUDA block-size knobs.
"""

import json
import os


class Config(object):
    """A node in the auto-vivifying config tree.

    Attribute access on a missing key creates a child ``Config`` node, so
    ``root.a.b.c = 1`` works with no prior declarations (reference
    ``veles/config.py:101``).
    """

    __slots__ = ("__dict__", "__path__")

    def __init__(self, path="root"):
        object.__setattr__(self, "__path__", path)

    # -- vivification ------------------------------------------------------
    def __getattr__(self, name):
        if name.startswith("__") and name.endswith("__"):
            raise AttributeError(name)
        child = Config("%s.%s" % (self.path, name))
        self.__dict__[name] = child
        return child

    def __setattr__(self, name, value):
        if (id(self), name) in _PROTECTED:
            raise AttributeError(
                "config key %s.%s is protected" % (self.path, name))
        self.__dict__[name] = value

    # -- niceties ----------------------------------------------------------
    @property
    def path(self):
        return object.__getattribute__(self, "__path__")

    def __contains__(self, name):
        return name in self.__dict__

    def __iter__(self):
        return iter(sorted(self.__dict__.items()))

    def __bool__(self):
        return bool(self.__dict__)

    def __repr__(self):
        return "<Config %s: %d keys>" % (self.path, len(self.__dict__))

    def get(self, name, default=None):
        """Non-vivifying lookup."""
        return self.__dict__.get(name, default)

    def update(self, tree):
        """Deep-merge a nested dict (or another Config) into this node.

        Mirrors the reference's ``Config.update`` used by every
        ``<name>_config.py`` (``veles/config.py:118-140``).
        """
        if isinstance(tree, Config):
            tree = tree.to_dict()
        if not isinstance(tree, dict):
            raise TypeError("Config.update expects a dict, got %r" % tree)
        for key, value in tree.items():
            if isinstance(value, dict):
                node = self.__dict__.get(key)
                if not isinstance(node, Config):
                    node = Config("%s.%s" % (self.path, key))
                    self.__dict__[key] = node
                node.update(value)
            else:
                setattr(self, key, value)
        return self

    def to_dict(self):
        out = {}
        for key, value in self.__dict__.items():
            out[key] = value.to_dict() if isinstance(value, Config) else value
        return out

    def protect(self, *names):
        """Forbid reassignment of direct children (ref ``config.py:79-85``)."""
        for name in names:
            _PROTECTED.add((id(self), name))

    def print_(self, indent=0, file=None):
        import sys
        file = file or sys.stdout
        for key, value in sorted(self.__dict__.items()):
            if isinstance(value, Config):
                print("%s%s:" % ("  " * indent, key), file=file)
                value.print_(indent + 1, file)
            else:
                print("%s%s: %r" % ("  " * indent, key, value), file=file)


_PROTECTED = set()

#: The global configuration tree — the singular ``root`` every module imports.
root = Config("root")


def _default_dirs():
    base = os.environ.get("VELES_TPU_HOME",
                          os.path.join(os.path.expanduser("~"), ".veles_tpu"))
    return {
        "base": base,
        "datasets": os.path.join(base, "datasets"),
        "snapshots": os.path.join(base, "snapshots"),
        "cache": os.path.join(base, "cache"),
        "results": os.path.join(base, "results"),
    }


# Platform defaults (reference analogue: veles/config.py:178-290).
root.common.update({
    "dirs": _default_dirs(),
    "engine": {
        # "auto" | "tpu" | "cpu" | "numpy"; auto = the TPU or an error
        # (the CPU only under JAX_PLATFORMS=cpu).
        "backend": "auto",
        # Compute dtype for operands: "float32" or "bfloat16" (MXU-native).
        "precision_type": "float32",
        # Numerical-robustness knob, same direction as the reference's
        # precision levels (0 fast, 1 Kahan, 2 multipartial —
        # veles/config.py:246-249).  On TPU it selects MXU pass counts
        # for float32 matmuls: 0 → DEFAULT (bf16 passes), 1 → HIGH
        # (bf16_3x), 2 → HIGHEST (full f32).
        "precision_level": 0,
        "mesh": {
            # Logical mesh axes for pjit sharding; data-parallel by default.
            "axes": {"data": -1},   # -1 = all devices
        },
        # Eager unit-chain fast path: stitch maximal runs of pure jitted
        # units into ONE XLA program each at Workflow.initialize()
        # ("on" | "off"; honored by Workflow.run() and the job-layer
        # slave path — "off" restores the per-unit dispatch path).
        "stitch": "on",
        # Input pipeline for the eager/stitched trainer
        # ("auto" | "device" | "host"):  "device" (and "auto" when a
        # jit device is attached and the dataset is HBM-resident)
        # heads the first stitched segment with the loader — minibatch
        # selection becomes an in-program gather over the resident
        # dataset, with ZERO per-step host fill / host→device bytes.
        # "host" restores the seed per-step host fill.  Read at
        # Workflow.initialize()/rebuild_stitching() time.
        "loader": "auto",
        # Deferred-metric fetch cadence for the device-resident
        # evaluators: 0 = one batched fetch per epoch/class boundary;
        # K > 0 additionally flushes every K minibatches (bounds the
        # async dispatch queue on very long epochs).
        "metrics_every": 0,
        # Unified tracing (veles_tpu.trace): "off" (default — the ring
        # records nothing; a span is still one inert profiler
        # annotation, 0.4 us), "on" (record spans into
        # the in-memory ring), or a *.json path (record AND write a
        # Perfetto-loadable Chrome trace-event file at process exit).
        # Read fresh at Workflow.initialize() via trace.configure().
        "trace": "off",
        # Trace ring capacity in events; wraparound keeps the newest.
        "trace_capacity": 65536,
        # In-program training-health telemetry (veles_tpu.watch):
        # "off" (default — stitched programs byte-identical to an
        # unwatched build), "on" (per-param-group grad/weight/update
        # norms + non-finite counts ride the deferred-metrics fetch as
        # device scalars, zero extra dispatches), "strict" (non-finite
        # params raise watch.health.HealthError naming the first bad
        # leaf at the window boundary).  Read at
        # Workflow.initialize()/rebuild_stitching() time.
        "health": "off",
        "interpret": False,         # run Pallas kernels in interpret mode
        # Master crash-recovery (veles_tpu.parallel.jobs.JobServer):
        # "dir" non-empty → the master checkpoints the workflow's
        # train state there (async TrainCheckpointer) every
        # "every_jobs" applied updates AND at every epoch boundary;
        # a restarted master resumes with `--resume` (launcher) /
        # JobServer.resume_from_checkpoint().
        "checkpoint": {"dir": "", "every_jobs": 0},
    },
    # Deterministic fault injection (veles_tpu.chaos; read at
    # chaos.configure() — the launcher calls it at initialize).  See
    # docs/robustness.md for the fault model; "schedule" is a list of
    # fault dicts (or a path to a JSON file of them), every run is
    # replayable from (seed, schedule).
    "chaos": {
        "enabled": False,
        "seed": 1234,
        "schedule": [],
        "drop_probability": 0.0,
        "dup_probability": 0.0,
        "delay_probability": 0.0,
        "delay_ms": 50.0,
        "corrupt_probability": 0.0,
        # the reference's --slave-death-probability (client.py:303)
        "slave_death_probability": 0.0,
    },
    # Fleet observability (veles_tpu.obs).  "slo" declares windowed
    # objectives per signal name: {"max"|"min": bound, "window_s",
    # "fast_window_s", "target", "burn_threshold"} — evaluated by the
    # serving SLO engine with multi-window burn rates; the three
    # autoscaling signals (queue depth, batch fill, TTFT p99 burn
    # rate) export on /metrics regardless.  "blackbox_dir" non-empty
    # arms the flight recorder: fatal exits (unhandled exception,
    # SIGTERM, chaos kills) dump the live trace ring + ledger summary
    # there as a loadable post-mortem (obs.blackbox.load).
    "obs": {
        "slo": {
            "ttft_p99_ms": {"max": 500.0, "window_s": 60.0,
                            "fast_window_s": 5.0, "target": 0.99,
                            "burn_threshold": 2.0},
        },
        "blackbox_dir": "",
    },
    # The live telemetry bus (veles_tpu.watch.bus): a non-empty
    # "endpoint" (e.g. "tcp://127.0.0.1:9461", or ":0" for a random
    # port) starts the drop-tolerant ZMQ PUB bus at
    # Workflow.initialize(); workflows, Decision epoch closes,
    # PodMaster/PodRuntime and the generative scheduler then publish
    # JSON snapshots onto it.  "hwm" bounds the per-subscriber send
    # queue (overflow drops frames — a slow viewer never backpressures
    # training); "history" sizes the host-side ring the blackbox
    # post-mortems embed; "conflate" opts into ZMQ keep-only-last wire
    # semantics.  Watch live: python -m veles_tpu.watch <endpoint>.
    "watch": {
        "endpoint": "",
        "hwm": 64,
        "history": 256,
        "conflate": False,
    },
    # Serving robustness: a batched `infer` exceeding this deadline
    # fails the batch's futures with serve.batcher.InferDeadlineExceeded
    # (HTTP 500) instead of blocking every queued client forever.
    # 0 = off (the direct, zero-overhead path).
    "serve": {"infer_deadline_ms": 0},
    "thread_pool": {"max_workers": 8},
    "network_compression": "snappy",
    "timings": set(),
    "trace": {"run": False},
    "web": {"host": "localhost", "port": 8090},
    "api": {"port": 8180},
    "forge": {"port": 8188, "service_name": "forge"},
    "warnings": {"numpy_run": True},
})


def apply_site_config():
    """Reference ``site_config.py`` chain (``veles/config.py:294-307``):
    look for ``site_config.py`` next to the package, in ``~/.veles_tpu`` and
    in ``$VELES_TPU_SITE_CONFIG``, exec each against ``root``."""
    candidates = [
        os.path.join(os.path.dirname(__file__), "site_config.py"),
        os.path.join(_default_dirs()["base"], "site_config.py"),
        os.environ.get("VELES_TPU_SITE_CONFIG", ""),
    ]
    for path in candidates:
        if path and os.path.exists(path):
            with open(path, "r") as fin:
                code = compile(fin.read(), path, "exec")
            exec(code, {"root": root})


def update_from_arguments(pairs):
    """Apply ``key=value`` CLI overrides (ref ``__main__.py:474-482``).

    ``key`` is a dotted path below ``root``; ``value`` is parsed as JSON when
    possible, else kept as a string.
    """
    for pair in pairs:
        key, _, raw = pair.partition("=")
        if not _:
            raise ValueError("override %r is not key=value" % pair)
        try:
            value = json.loads(raw)
        except ValueError:
            value = raw
        node = root
        parts = key.split(".")
        if parts[0] == "root":
            parts = parts[1:]
        if not parts:
            raise ValueError(
                "override %r names no key below root" % pair)
        for part in parts[:-1]:
            node = getattr(node, part)
        setattr(node, parts[-1], value)
