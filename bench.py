"""Benchmark stages: prints JSON lines
``{"metric": ..., "value": N, "unit": ..., "platform": ...,
"device_kind": ..., "n_devices": N, ...}``.

``python bench.py`` runs the selected stages **in this one process**
(a chip belongs to one process at a time), one after the other, on the
accelerator JAX finds:

1. **Chip or fail.**  The run refuses to start on a non-TPU platform
   unless the caller set ``JAX_PLATFORMS=cpu`` for a rehearsal.  A CPU
   number is never printed under a device metric's name: every record
   carries the ``platform``, ``device_kind`` and device count it ran on.
2. **Loud failures.**  A stage that raises (or is cut at its cap) is
   reported on stderr, the remaining stages still run, and the process
   exits non-zero.  Nothing is re-emitted from an earlier run.
3. **One compile cache**, enabled through
   ``veles_tpu.backends.enable_compilation_cache`` (the environment's
   ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.cache/xla``).
4. **MFU reported** alongside throughput: XLA's own
   ``compiled.cost_analysis()`` flop count / measured step time / peak
   bf16 FLOPs for the detected TPU generation (no peak is assumed for a
   device kind the table does not know).

Headline metric (BASELINE.json): Znicz ImageNet AlexNet images/sec/chip
on the fused train step (forward+backward+update in one XLA program,
bf16 compute / fp32 master weights).  ``vs_baseline`` compares against
1500 images/sec — a generous single-V100 AlexNet training throughput
(the reference's own OpenCL backend was slower); driver target is
v5e-8 ≥ 4× single-V100, i.e. vs_baseline ≥ 0.5 per chip.

Env knobs: ``BENCH_BUDGET_SEC`` (default 2600) total wall-clock budget;
``BENCH_STAGES`` comma list to restrict stages (an unknown name is an
error); ``BENCH_TIMEOUT_SCALE`` stretches the per-stage caps.

The stage table is what earlier rounds left; turning it into benchmark
cells is ROADMAP A0/C1.

Reference discipline mirrored: the in-situ benchmark unit
``/root/reference/veles/accelerated_units.py:706-825`` (min-of-N timed
kernel chain rating the device) — here the "chain" is the real fused
train step and the rating is images/sec + MFU.
"""

import functools
import json
import os
import sys
import time

V100_ALEXNET_IMG_PER_SEC = 1500.0


@functools.lru_cache(maxsize=None)
def _device_fields():
    """What every record says about where it ran."""
    import jax
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "n_devices": jax.device_count()}


def _dumps(rec):
    """json.dumps for stdout *records*: stamps a measurement timestamp
    and the platform / device kind / device count the process runs on
    (a stage that measured something else — the native C++ engine —
    names its own ``device_kind`` and keeps it)."""
    if isinstance(rec, dict) and "metric" in rec:
        rec = dict(rec)
        rec.setdefault("ts", int(time.time()))
        for key, value in _device_fields().items():
            rec.setdefault(key, value)
    return json.dumps(rec)


def _peak_flops(device_kind):
    # ONE peak-table resolution for the whole repo: the performance
    # ledger owns it (prof.peak_flops), bench just forwards — a
    # dtype-aware or multi-device peak change lands once
    from veles_tpu import prof
    return prof.peak_flops(device_kind)


def _measure(step_fn, params, x, labels, steps, flops_override=None):
    """Honest (sec_per_step, flops_per_step): ONE compiled program
    loops the step with a runtime trip count and is timed at two trip
    counts; the marginal cancels per-program dispatch/fetch overhead
    exactly, and the sync is a host fetch of result-derived bytes (see
    ops/timing.py).
    ``flops_override``: analytic count for steps whose inner lax.scan
    bodies XLA's cost analysis counts only once (LSTM)."""
    from veles_tpu.ops.timing import measure_fused_step
    return measure_fused_step(step_fn, params, x, labels, k=steps,
                              flops_override=flops_override)


# --------------------------------------------------------------------------
# stages (run in child processes; each prints ONE json line on stdout)
# --------------------------------------------------------------------------

def stage_probe():
    import jax
    dev = jax.devices()[0]
    import jax.numpy as jnp
    x = jnp.ones((256, 256), jnp.bfloat16)
    y = jax.jit(lambda a: a @ a)(x)
    assert float(jax.device_get(y[0, 0])) == 256.0  # real bytes, real sync
    from veles_tpu.samples.datasets import (cifar10_available,
                                            mnist_available)
    datasets = {"mnist": mnist_available(),
                "cifar10": cifar10_available()}
    # accuracy parity is a SEPARATE claim from throughput parity —
    # state it loudly so no reader mistakes one for the other
    # (VERDICT r3 item 8)
    if datasets and all(datasets.values()):
        parity = ("data present - run tests/test_accuracy_parity.py "
                  "for the strict gates")
    else:
        parity = "unproven (real datasets absent from this image)"
    probe = {"platform": dev.platform,
             "device_kind": dev.device_kind,
             "n_devices": jax.device_count(),
             # accuracy-parity gates (test_accuracy_parity.py)
             # need the real files; throughput stages use
             # synthetic batches either way
             "real_datasets_present": datasets,
             "accuracy_parity": parity}
    print(_dumps(probe))
    return probe


def _batch_tag(batch, default):
    """Metric-name suffix for non-default batch sizes: every stage
    that reads a batch env knob must key its metric by batch, or a
    scaling-sweep line is read as the canonical measurement."""
    return "" if batch == default else " (batch %d)" % batch


def _device_kind():
    import jax
    return jax.devices()[0].device_kind


#: hard physics gates — a measurement outside these is a broken
#: stopwatch, not a fast chip, and must NOT be published (round-2
#: post-mortem: MFU 54.58 and vs_baseline 1177 went out unchecked)
MAX_MFU = 1.0
MAX_VS_BASELINE = 200.0


class _StageTimeout(Exception):
    """Raised by the ladder's per-stage SIGALRM watchdog.  Module
    scope: stage-level fallbacks (the remat retries) must re-raise it
    instead of treating the watchdog as an ordinary stage failure."""


def _emit(metric, sec_per_step, batch, flops, vs=None, extra=None):
    kind = _device_kind()
    # no train step on any hardware completes in under a microsecond —
    # catches broken stopwatches even where no peak-FLOPs entry exists
    if sec_per_step <= 1e-6:
        rec = {
            "metric": metric, "value": 0.0, "unit": "images/sec",
            "vs_baseline": None,
            "error": "timing failed physics check: sec_per_step "
                     "%.3e below plausibility floor" % sec_per_step,
            "raw_sec_per_step": sec_per_step,
            "device_kind": kind,
        }
        rec.update(extra or {})   # the diagnosis matters MOST here
        print(_dumps(rec))
        return
    ips = batch / sec_per_step
    peak = _peak_flops(kind)
    mfu = (flops / sec_per_step / peak) if (flops and peak) else None
    vs_baseline = (ips / vs) if vs else None
    problems = []
    if mfu is not None and not (0.0 < mfu <= MAX_MFU):
        problems.append("MFU %.4f outside (0, %.1f]" % (mfu, MAX_MFU))
    if vs_baseline is not None and not (
            0.0 < vs_baseline <= MAX_VS_BASELINE):
        problems.append("vs_baseline %.1f outside (0, %.0f]"
                        % (vs_baseline, MAX_VS_BASELINE))
    if problems:
        rec = {
            "metric": metric, "value": 0.0, "unit": "images/sec",
            "vs_baseline": None,
            "error": "timing failed physics check: " + "; ".join(problems),
            "raw_sec_per_step": sec_per_step, "raw_mfu": mfu,
            "device_kind": kind,
        }
        rec.update(extra or {})   # the diagnosis matters MOST here
        print(_dumps(rec))
        return
    rec = {
        "metric": metric,
        "value": round(ips, 1),
        "unit": "images/sec",
        "vs_baseline": (round(vs_baseline, 3) if vs_baseline else None),
        "mfu": (round(mfu, 4) if mfu is not None else None),
        "sec_per_step": round(sec_per_step, 6),
        "batch": batch,
        "device_kind": kind,
    }
    if extra:
        rec.update(extra)
    print(_dumps(rec))


def stage_mnist():
    import numpy

    import jax
    from veles_tpu import prng
    from veles_tpu.znicz.fused import init_mlp_params, make_train_step
    from __graft_entry__ import MNIST_LAYERS

    prng.seed_all(1234)
    batch = 8192
    params = init_mlp_params(784, MNIST_LAYERS)
    rng = numpy.random.default_rng(0)
    x = jax.device_put(
        rng.standard_normal((batch, 784)).astype(numpy.float32))
    labels = jax.device_put(
        rng.integers(0, 10, batch).astype(numpy.int32))
    sec, flops = _measure(make_train_step(MNIST_LAYERS),
                          params, x, labels, steps=100)
    _emit("MNIST784 MLP fused train throughput", sec, batch, flops)


def stage_mnist_bf16():
    """bf16 compute (fp32 master weights): halves the HBM bytes of a
    step the thin 784→100→10 matmul chain is bound by — the TPU-native
    mixed-precision mode vs stage_mnist's f32 (the reference-comparable
    line)."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.znicz.fused import init_mlp_params, make_train_step
    from __graft_entry__ import MNIST_LAYERS

    prng.seed_all(1234)
    batch = 8192
    params = init_mlp_params(784, MNIST_LAYERS)
    rng = numpy.random.default_rng(0)
    x = jax.device_put(
        rng.standard_normal((batch, 784)).astype(numpy.float32))
    labels = jax.device_put(
        rng.integers(0, 10, batch).astype(numpy.int32))
    sec, flops = _measure(
        make_train_step(MNIST_LAYERS, compute_dtype=jnp.bfloat16),
        params, x, labels, steps=100)
    _emit("MNIST784 MLP fused train throughput (bf16)", sec, batch,
          flops)


def stage_mnist_u8():
    """Device-resident NATIVE-dtype dataset: x stays uint8 in HBM
    (MNIST's storage dtype) and normalization fuses into the step
    (``fused.mlp_apply input_norm``).  The step is HBM-bound and reads
    x twice (forward + weight gradient), so quartering its bytes is the
    single biggest lever on the flagship line — the TPU-first upgrade
    of the reference's device-resident fullbatch data
    (``loader/fullbatch.py:79``)."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.znicz.fused import init_mlp_params, make_train_step
    from __graft_entry__ import MNIST_LAYERS

    prng.seed_all(1234)
    batch = 8192
    params = init_mlp_params(784, MNIST_LAYERS)
    rng = numpy.random.default_rng(0)
    x = jax.device_put(
        rng.integers(0, 256, (batch, 784)).astype(numpy.uint8))
    labels = jax.device_put(
        rng.integers(0, 10, batch).astype(numpy.int32))
    step = make_train_step(MNIST_LAYERS, compute_dtype=jnp.bfloat16,
                           input_norm=(1.0 / 255.0, 0.0))
    sec, flops = _measure(step, params, x, labels, steps=100)
    _emit("MNIST784 MLP fused train throughput (u8-resident)", sec,
          batch, flops)


def _conv_stage(metric, layers, input_shape, n_classes, batch, steps,
                vs=None, compute_dtype="bfloat16", extra=None):
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    params, step_fn, _eval, _apply = lower_specs(
        layers, input_shape, compute_dtype=jnp.dtype(compute_dtype).type)
    rng = numpy.random.default_rng(0)
    x = jax.device_put(rng.standard_normal(
        (batch,) + tuple(input_shape)).astype(numpy.float32))
    labels = jax.device_put(
        rng.integers(0, n_classes, batch).astype(numpy.int32))
    sec, flops = _measure(step_fn, params, x, labels, steps=steps)
    _emit(metric, sec, batch, flops, vs=vs, extra=extra)


def _wf_stage(metric, fused_config=None, sample=None, fused=True,
              vs=None, extra=None, loader_mode=None, epoch_scan=None,
              health=None):
    """The WHOLE framework path: StandardWorkflow(fused=True) — graph
    scheduling, loader epoch bookkeeping, Decision accounting, and the
    fused step — timed over full epochs via wf.run().  Every minibatch
    host-fetches its metrics (unless epoch_mode batches the fetches),
    so the wall clock is honest by construction.  Returns the measured
    images/sec so ratio lines (eager vs fused) can chain stages.

    ``loader_mode`` pins ``root.common.engine.loader`` for the stage
    (the eager line runs "host" so its number stays the PR 3 baseline;
    the devloader line runs "device").  Every record carries
    ``h2d_bytes_per_step`` AND ``d2h_bytes_per_step`` —
    Watcher-accounted transfer traffic per train-equivalent step over
    the timed region, both directions — so BENCH_*.json tracks
    transfer ELIMINATION, not just img/s; plus the timed region's
    span counts from the trace recorder (``trace_dispatches`` =
    stitched-segment programs dispatched, ``trace_compiles`` =
    first-dispatch compiles — a nonzero value here means warmup leaked
    into the timed region).  The recorder is force-enabled for the
    stage (its per-event cost is a ring write, orders below the step
    time); the ``engine.trace=off`` <1% criterion is about the
    DEFAULT state and is asserted by tests, not this ladder."""
    from veles_tpu import chaos, prng, prof, trace
    from veles_tpu.backends import AutoDevice
    from veles_tpu.config import root
    from veles_tpu.memory import Watcher
    from veles_tpu.samples import mnist

    saved_loader = root.common.engine.get("loader", "auto")
    saved_trace = root.common.engine.get("trace", "off")
    saved_scan = root.common.engine.get("epoch_scan", "off")
    saved_health = root.common.engine.get("health", "off")
    if loader_mode is not None:
        root.common.engine.loader = loader_mode
    if epoch_scan is not None:
        root.common.engine.epoch_scan = epoch_scan
    if health is not None:
        root.common.engine.health = health
    root.common.engine.trace = "on"    # initialize() → trace.configure
    try:
        prng.seed_all(1234)
        batch = 2048
        # max_epochs=1 ends after the initial validation pass with ZERO
        # train steps, so the train-step (or epoch-program) compile would
        # land inside the timed region — warm through epoch 2 (the first
        # REAL train epoch) instead
        wf = (sample or mnist).create_workflow(
            device=AutoDevice(), max_epochs=2, minibatch_size=batch,
            fused=fused, fused_config=dict(fused_config or {}))
        wf.run()                           # epochs 1-2: compiles included
        wf.decision.complete <<= False
        wf.decision.max_epochs = 4
        h2d_before = Watcher.h2d_bytes
        d2h_before = Watcher.d2h_bytes
        dispatches_before = trace.recorder.count("segment", "dispatch")
        compiles_before = trace.recorder.count("segment", "compile")
        flops_before = prof.ledger.flops_dispatched
        recompiles_before = prof.ledger.recompiles
        faults_before = chaos.controller.faults_injected
        # per-entry (dispatches, steps) snapshot: the steps_per_dispatch
        # column (epoch-scan windows fold K steps into one dispatch;
        # per-step entries count each dispatch as one step)
        ledger_before = {(e.kind, e.name): (e.dispatches, e.steps)
                         for e in prof.ledger.entries("segment")}
        tic = time.perf_counter()
        wf.run()                           # epochs 3-4, warm
        elapsed = time.perf_counter() - tic
        h2d_delta = Watcher.h2d_bytes - h2d_before
        d2h_delta = Watcher.d2h_bytes - d2h_before
        dispatches = trace.recorder.count("segment", "dispatch") \
            - dispatches_before
        compiles = trace.recorder.count("segment", "compile") \
            - compiles_before
        # performance-ledger columns: XLA-cost-analysis FLOPs
        # dispatched over the timed wall clock vs the device peak
        # (None where no peak entry exists — e.g. the CPU), recompile
        # count (nonzero = the sentinel flagged a steady-state
        # retrace inside the timed region), and absolute peak HBM
        flops_delta = prof.ledger.flops_dispatched - flops_before
        recompiles = prof.ledger.recompiles - recompiles_before
        # chaos injections inside the timed region: 0 on every normal
        # run — a line from a fault-injection session can never be
        # mistaken for a clean throughput sample
        faults_injected = chaos.controller.faults_injected \
            - faults_before
        peak = _peak_flops(_device_kind())
        wf_mfu = (round(flops_delta / elapsed / peak, 4)
                  if peak and flops_delta else None)
        peak_hbm = Watcher.peak_bytes
        seg_dispatches = seg_steps = 0
        for e in prof.ledger.entries("segment"):
            d0, s0 = ledger_before.get((e.kind, e.name), (0, 0))
            dd, sd = e.dispatches - d0, e.steps - s0
            seg_dispatches += dd
            seg_steps += sd if sd else dd
        steps_per_dispatch = round(seg_steps / seg_dispatches, 2) \
            if seg_dispatches else None
    finally:
        root.common.engine.loader = saved_loader
        root.common.engine.trace = saved_trace
        root.common.engine.epoch_scan = saved_scan
        root.common.engine.health = saved_health
        trace.configure()
    # train-only images over the wall clock (which includes the eval
    # passes): comparable to the fused synthetic-batch line — counting
    # eval minibatches as served images made this neither a train
    # throughput nor an epoch time (VERDICT r3 item 7)
    from veles_tpu.loader.base import TRAIN
    train_samples = 2 * int(wf.loader.class_lengths[TRAIN])
    sec_per_step = batch * elapsed / train_samples
    extra = dict(extra or {})
    extra.setdefault("h2d_bytes_per_step",
                     round(h2d_delta * batch / train_samples, 1))
    extra.setdefault("d2h_bytes_per_step",
                     round(d2h_delta * batch / train_samples, 1))
    extra.setdefault("trace_dispatches", dispatches)
    extra.setdefault("trace_compiles", compiles)
    extra.setdefault("mfu", wf_mfu)
    extra.setdefault("peak_hbm_bytes", peak_hbm)
    extra.setdefault("recompiles", recompiles)
    extra.setdefault("faults_injected", faults_injected)
    extra.setdefault("steps_per_dispatch", steps_per_dispatch)
    if loader_mode is not None:
        extra.setdefault("loader", loader_mode)
    if epoch_scan is not None:
        extra.setdefault("epoch_scan", epoch_scan)
    if health is not None:
        extra.setdefault("health", health)
    _emit(metric, sec_per_step, batch, None, vs=vs, extra=extra)
    return batch / sec_per_step


#: fused mnist_wf images/sec from THIS ladder run — the eager stage's
#: vs= denominator, so BENCH_*.json tracks the eager↔fused ratio per
#: round instead of two unrelated absolutes (the whole ladder runs in
#: one child process, mnist_wf before mnist_wf_eager in every order)
_WF_FUSED_IPS = [None]


def stage_mnist_wf():
    _WF_FUSED_IPS[0] = _wf_stage(
        "MNIST784 full StandardWorkflow(fused) train throughput "
        "(epoch wall-clock incl. eval)")


def stage_mnist_wf_epoch():
    """The same full framework path with
    ``fused_config={'epoch_mode': True}``: each TRAIN epoch is ONE
    XLA program (one dispatch + one metric fetch), quantifying how
    much of the per-minibatch framework overhead epoch_mode removes
    vs the ``mnist_wf`` line."""
    _wf_stage("MNIST784 full StandardWorkflow(fused, epoch_mode) "
              "train throughput (epoch wall-clock incl. eval)",
              fused_config={"epoch_mode": True})


#: eager (host-loader) mnist_wf_eager images/sec from THIS ladder run —
#: the devloader stage's vs= denominator (same-run ratio line, like
#: _WF_FUSED_IPS for the eager↔fused ratio)
_WF_EAGER_IPS = [None]


def stage_mnist_wf_eager():
    """The EAGER unit-chain trainer (fused=False): what elastic
    master–slave jobs train through today (fused raises under the job
    layer, fused_unit.py initialize).  Emits ``vs=`` the fused
    ``mnist_wf`` line measured in the SAME ladder run, so the recorded
    ``vs_baseline`` IS the eager↔fused throughput ratio the stitched
    fast path (root.common.engine.stitch) is closing; re-measures the
    fused twin in-process when BENCH_STAGES skipped ``mnist_wf``.
    Pins ``engine.loader=host`` so the line stays the PR 3 baseline
    the ``mnist_wf_eager_devloader`` stage compares against."""
    fused_ips = _WF_FUSED_IPS[0]
    if fused_ips is None:
        fused_ips = _wf_stage(
            "MNIST784 full StandardWorkflow(fused) train throughput "
            "(epoch wall-clock incl. eval)")
        _WF_FUSED_IPS[0] = fused_ips
    from veles_tpu.config import root
    _WF_EAGER_IPS[0] = _wf_stage(
        "MNIST784 full StandardWorkflow(eager unit chain) train "
        "throughput (epoch wall-clock incl. eval)", fused=False,
        vs=fused_ips, loader_mode="host",
        extra={"stitch": root.common.engine.get("stitch", "on"),
               "vs_metric": "mnist_wf (fused, same run)"})


#: per-step stitched devloader images/sec from THIS ladder run — the
#: epoch-scan stage's vs= denominator (the true apples-to-apples:
#: same device-resident loader, same stitched programs, only the
#: K-step window folding differs)
_WF_DEVLOADER_IPS = [None]


def stage_mnist_wf_eager_devloader():
    """The stitched eager trainer with the DEVICE-RESIDENT input
    pipeline (``engine.loader=device``): the loader heads the first
    stitched segment, minibatch selection is an in-program gather over
    the HBM-resident dataset, and per-step H2D drops to zero (watch
    ``h2d_bytes_per_step`` vs the eager line).  Emits ``vs=`` the
    host-loader ``mnist_wf_eager`` line from the SAME ladder run, so
    ``vs_baseline`` IS the input-pipeline speedup; re-measures the
    eager twin in-process when BENCH_STAGES skipped it."""
    eager_ips = _WF_EAGER_IPS[0]
    if eager_ips is None:
        stage_mnist_wf_eager()
        eager_ips = _WF_EAGER_IPS[0]
    from veles_tpu.config import root
    _WF_DEVLOADER_IPS[0] = _wf_stage(
        "MNIST784 full StandardWorkflow(eager, device-resident "
        "loader) train throughput (epoch wall-clock incl. eval)",
        fused=False, vs=eager_ips, loader_mode="device",
        extra={"stitch": root.common.engine.get("stitch", "on"),
               "vs_metric": "mnist_wf_eager (host loader, "
                            "same run)"})


def stage_mnist_wf_eager_epoch():
    """One-dispatch epochs on the stitched eager trainer
    (``engine.epoch_scan=auto``): K consecutive steps — the in-program
    gather, the forward/evaluator chain AND the GD chain — fold into
    ONE ``lax.scan`` dispatch with donated weight/momentum carry and
    the Decision metric accumulated in-program, so a class pass is one
    host dispatch.  Emits ``vs=`` the per-step stitched devloader line
    from the SAME ladder run (identical programs, only the window
    folding differs) — ``vs_baseline`` IS the host-dispatch-
    elimination speedup the fused path's ``epoch_mode`` banked ~28%
    for — plus the ``steps_per_dispatch`` ledger column; re-measures
    the per-step twin in-process when BENCH_STAGES skipped it."""
    devloader_ips = _WF_DEVLOADER_IPS[0]
    if devloader_ips is None:
        stage_mnist_wf_eager_devloader()
        devloader_ips = _WF_DEVLOADER_IPS[0]
    _wf_stage("MNIST784 full StandardWorkflow(eager, epoch-scan "
              "windows) train throughput (epoch wall-clock incl. "
              "eval)",
              fused=False, vs=devloader_ips, loader_mode="device",
              epoch_scan="auto",
              extra={"vs_metric": "mnist_wf_eager_devloader "
                                  "(per-step stitched, same run)"})


def stage_mnist_wf_health():
    """In-program health telemetry on the stitched devloader trainer
    (``engine.health=on``, veles_tpu.watch): per-param-group
    grad/weight/update norms + non-finite counts ride the SAME
    stitched programs as extra deferred-metric outputs — ZERO extra
    dispatches by construction.  Emits ``vs=`` the health-off
    devloader line from the SAME ladder run, so ``vs_baseline`` IS
    the telemetry overhead ratio (the acceptance line: ~1.0x), and
    ``trace_dispatches`` must match the baseline's count exactly
    (asserted by tests/test_watch.py; the bench line makes it visible
    per round).  Re-measures the health-off twin in-process when
    BENCH_STAGES skipped it."""
    devloader_ips = _WF_DEVLOADER_IPS[0]
    if devloader_ips is None:
        stage_mnist_wf_eager_devloader()
        devloader_ips = _WF_DEVLOADER_IPS[0]
    _wf_stage("MNIST784 full StandardWorkflow(eager, device loader, "
              "health telemetry) train throughput (epoch wall-clock "
              "incl. eval)",
              fused=False, vs=devloader_ips, loader_mode="device",
              health="on",
              extra={"vs_metric": "mnist_wf_eager_devloader "
                                  "(health off, same run)"})


def stage_mnist_wf_slave():
    """The elastic job layer END-TO-END with a FUSED slave (round-5
    capability: fused training under master–slave): master + slave in
    ONE process over real localhost ZMQ sockets, per-minibatch jobs —
    indices + weights out, update deltas back, double-buffered
    (JobClient.run_prefetch).  Vs the ``mnist_wf`` line this prices
    the whole job protocol: serve_next_minibatch, pickled payloads,
    per-job weight install (refresh_from_forwards), delta extraction
    and master-side merge."""
    from veles_tpu import prng
    from veles_tpu.backends import AutoDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.parallel.jobs import JobClient, JobServer
    from veles_tpu.samples import mnist

    from veles_tpu.backends import NumpyDevice
    from veles_tpu.loader.base import TRAIN

    batch = 2048

    def mk(device, **flags):
        prng.seed_all(1234)
        wf = mnist.create_workflow(
            launcher=DummyLauncher(**flags), max_epochs=2,
            minibatch_size=batch, fused=True)
        wf.initialize(device=device)
        return wf

    # the master never runs kernels — NumpyDevice keeps the dataset
    # out of HBM (per-host device config does not enter the checksum)
    master = mk(NumpyDevice(), is_master=True)
    slave = mk(AutoDevice(), is_slave=True)
    server = JobServer(master).start()
    try:
        client = JobClient(slave, server.endpoint)
        client.handshake()
        client.run_prefetch()      # epochs 1-2: compiles included
        client.close()
    finally:
        server.stop()
    # the server latches no_more_jobs once Decision completes — fresh
    # server+client for the warm timed epochs (the slave's jitted step
    # and params stay warm); connect + checksum handshake are inside
    # the timed window.  Prefetch blurs the epoch boundary by up to
    # one in-flight job, so the denominator counts the train samples
    # the master ACTUALLY merged during the window, not 2×epoch.
    master.decision.complete <<= False
    master.decision.max_epochs = 4
    counted = {"train": 0}
    inner_apply = master.decision.apply_data_from_slave

    def counting_apply(data, slave=None):
        if data and data.get("cls") == TRAIN:
            counted["train"] += int(data.get("size", 0))
        return inner_apply(data, slave)

    master.decision.apply_data_from_slave = counting_apply
    server = JobServer(master).start()
    try:
        tic = time.perf_counter()
        client = JobClient(slave, server.endpoint)
        client.handshake()
        client.run_prefetch()      # epochs 3-4, warm
        elapsed = time.perf_counter() - tic
        client.close()
    finally:
        server.stop()
    _emit("MNIST784 full StandardWorkflow(fused) master+slave jobs "
          "throughput (epoch wall-clock incl. eval, localhost ZMQ)",
          batch * elapsed / max(counted["train"], 1), batch, None)


def stage_mnist_pod():
    """One pod, one program (veles_tpu.pod): the stitched EAGER
    trainer compiled over the whole local device mesh — dataset +
    shuffled indices sharded on the ``data`` axis, params replicated,
    gradient aggregation an in-program ``psum`` — vs the SAME-RUN
    ZMQ master–slave eager session it replaces (per-minibatch jobs:
    indices + weights out, update deltas back over localhost
    sockets).  ``vs_baseline`` IS therefore the wire-elimination
    speedup; ``psum_bytes_per_step`` prices what the gradients cost
    on ICI instead (the ledger's analytic ring-all-reduce estimate).
    The pod side trains through PodRuntime directly — the membership
    control plane adds O(epochs) frames, nothing to a throughput
    line.  On the virtual CPU mesh all shards share one host's cores,
    so ``vs_baseline`` there prices partitioning overhead, not the
    ICI win — the TPU line is the one that matters."""
    import jax

    from veles_tpu import prng, prof
    from veles_tpu.backends import AutoDevice, NumpyDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.parallel.jobs import JobClient, JobServer
    from veles_tpu.parallel.mesh import mesh_from_topology
    from veles_tpu.pod import PodRuntime, train_epochs
    from veles_tpu.samples import mnist

    batch = 2048

    def mk(device, **flags):
        prng.seed_all(1234)
        wf = mnist.create_workflow(
            launcher=DummyLauncher(**flags), max_epochs=2,
            minibatch_size=batch, fused=False)
        wf.initialize(device=device)
        return wf

    # ---- the ZMQ per-minibatch baseline (eager, stitched slave)
    master = mk(NumpyDevice(), is_master=True)
    slave = mk(AutoDevice(), is_slave=True)
    server = JobServer(master).start()
    try:
        client = JobClient(slave, server.endpoint)
        client.handshake()
        client.run_prefetch()      # epochs 1-2: compiles included
        client.close()
    finally:
        server.stop()
    master.decision.complete <<= False
    master.decision.max_epochs = 4
    counted = {"train": 0}
    inner_apply = master.decision.apply_data_from_slave

    def counting_apply(data, slave_desc=None):
        if data and data.get("cls") == TRAIN:
            counted["train"] += int(data.get("size", 0))
        return inner_apply(data, slave_desc)

    master.decision.apply_data_from_slave = counting_apply
    server = JobServer(master).start()
    try:
        tic = time.perf_counter()
        client = JobClient(slave, server.endpoint)
        client.handshake()
        client.run_prefetch()      # epochs 3-4, warm
        zmq_elapsed = time.perf_counter() - tic
        client.close()
    finally:
        server.stop()
    zmq_ips = max(counted["train"], 1) / zmq_elapsed

    # ---- the pod path: same eager stitched graph, ONE pjit'd
    #      program per segment over every local device
    wf = mk(AutoDevice())
    pod = PodRuntime(wf, mesh=mesh_from_topology(
        {"data": -1}, require=("data",)))
    pod.install()
    for _ in train_epochs(wf, 2):      # epochs 1-2: compiles included
        pass
    train_samples = 2 * int(wf.loader.class_lengths[TRAIN])
    psum_before = prof.ledger.psum_bytes_moved
    recompiles_before = prof.ledger.recompiles
    tic = time.perf_counter()
    for _ in train_epochs(wf, 4, already=2):   # epochs 3-4, warm
        pass
    elapsed = time.perf_counter() - tic
    # per-step = the runtime's static estimate for ONE train
    # minibatch (every sharded segment's ring-all-reduce bytes); the
    # measured ledger delta also covers the eval-class dispatches
    # inside the timed epochs, so it rides along as the total instead
    # of being laundered into a per-train-step figure
    _emit("MNIST784 full StandardWorkflow(eager, pod) one-program "
          "train throughput (epoch wall-clock incl. eval, %d-shard "
          "mesh)" % pod.shards,
          batch * elapsed / train_samples, batch, None, vs=zmq_ips,
          extra={"psum_bytes_per_step":
                 pod.describe()["psum_bytes_per_step"],
                 "psum_bytes_moved":
                 prof.ledger.psum_bytes_moved - psum_before,
                 "shards": pod.shards,
                 "recompiles": prof.ledger.recompiles
                 - recompiles_before,
                 "devices": len(jax.devices()),
                 "vs_metric": "ZMQ master+slave eager jobs "
                              "(same run)"})


def stage_mnist_pod_epoch():
    """One-dispatch POD epochs: the PodRuntime-sharded stitched
    trainer with ``engine.epoch_scan=auto`` — the K-step scan folds
    into the pjit'd window program, gradient aggregation stays an
    in-scan ``psum`` on the data axis, and a pod epoch is ONE dispatch
    per class pass.  Self-baselined: the SAME warmed pod workflow is
    timed per-step (knob off) then windowed (knob auto), so
    ``vs_baseline`` IS the pod host-dispatch-elimination ratio;
    ``dispatches_per_epoch`` records the trace-counted dispatch rate
    of the windowed region (the pod smoke asserts the same bound in
    CI)."""
    import jax

    from veles_tpu import prng, prof, trace
    from veles_tpu.backends import AutoDevice
    from veles_tpu.config import root
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.base import TRAIN
    from veles_tpu.parallel.mesh import mesh_from_topology
    from veles_tpu.pod import PodRuntime, train_epochs
    from veles_tpu.samples import mnist

    batch = 2048
    saved_scan = root.common.engine.get("epoch_scan", "off")
    saved_trace = root.common.engine.get("trace", "off")
    root.common.engine.trace = "on"
    try:
        prng.seed_all(1234)
        wf = mnist.create_workflow(
            launcher=DummyLauncher(), max_epochs=2,
            minibatch_size=batch, fused=False)
        wf.initialize(device=AutoDevice())
        pod = PodRuntime(wf, mesh=mesh_from_topology(
            {"data": -1}, require=("data",)))
        pod.install()
        root.common.engine.epoch_scan = "off"
        for _ in train_epochs(wf, 2):       # warm: compiles included
            pass
        train_samples = 2 * int(wf.loader.class_lengths[TRAIN])
        tic = time.perf_counter()
        for _ in train_epochs(wf, 4, already=2):    # per-step, warm
            pass
        per_step_ips = train_samples / (time.perf_counter() - tic)
        root.common.engine.epoch_scan = "auto"
        for _ in train_epochs(wf, 5, already=4):    # window compiles
            pass
        dispatches_before = trace.recorder.count("segment", "dispatch")
        recompiles_before = prof.ledger.recompiles
        psum_before = prof.ledger.psum_bytes_moved
        tic = time.perf_counter()
        for _ in train_epochs(wf, 7, already=5):    # windowed, warm
            pass
        elapsed = time.perf_counter() - tic
        dispatches = trace.recorder.count("segment", "dispatch") \
            - dispatches_before
        _emit("MNIST784 full StandardWorkflow(eager, pod, epoch-scan "
              "windows) one-dispatch-epoch train throughput (epoch "
              "wall-clock incl. eval, %d-shard mesh)" % pod.shards,
              batch * elapsed / train_samples, batch, None,
              vs=per_step_ips,
              extra={"dispatches_per_epoch": round(dispatches / 2, 1),
                     "shards": pod.shards,
                     "psum_bytes_moved":
                     prof.ledger.psum_bytes_moved - psum_before,
                     "recompiles": prof.ledger.recompiles
                     - recompiles_before,
                     "devices": len(jax.devices()),
                     "vs_metric": "same pod workflow, per-step "
                                  "stitched (same run)"})
    finally:
        root.common.engine.epoch_scan = saved_scan
        root.common.engine.trace = saved_trace
        trace.configure()


def stage_mnist_pod_pp():
    """Pipeline-parallel pod epochs: a homogeneous stacked-stage
    model trained through :func:`veles_tpu.parallel.pp.pipeline_apply`
    over a dp×pp mesh, each epoch ONE jitted scan over minibatches
    (one dispatch per class pass), vs the SAME-RUN dp twin running the
    identical stages as a sequential ``lax.scan`` with params
    replicated — ``vs_baseline`` therefore prices what pipelining the
    stages costs/buys on THIS device set (on the virtual CPU mesh the
    bubble is pure overhead; on real chips the stage weights stop
    being replicated).  ``bubble_fraction`` carries the analytic GPipe
    ramp/drain idle share the planner prices, ``dispatches_per_epoch``
    the host-dispatch bound the pod smoke asserts."""
    import jax
    import jax.numpy as jnp
    import numpy
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veles_tpu.analyze.pricing import pipeline_bubble
    from veles_tpu.parallel.mesh import make_mesh, replicated
    from veles_tpu.parallel.pp import pipeline_apply

    n_dev = len(jax.devices())
    stages = 4 if n_dev % 4 == 0 else 2
    if n_dev < 2 * stages:
        print(_dumps({
            "metric": "MLP stacked-stage pipeline-parallel pod epoch "
                      "train throughput",
            "value": 0.0, "unit": "images/sec", "vs_baseline": None,
            "error": "needs a dp×pp mesh: %d device(s) < %d"
                     % (n_dev, 2 * stages),
            "device_kind": _device_kind()}))
        return
    dim, batch, n_micro, steps_per_epoch, epochs = 128, 1024, 8, 16, 3
    mesh = make_mesh({"data": n_dev // stages, "pipe": stages})
    rng = numpy.random.default_rng(11)
    params = {
        "w": jnp.asarray(rng.standard_normal(
            (stages, dim, dim)).astype(numpy.float32) * 0.3),
        "b": jnp.zeros((stages, dim), numpy.float32),
    }
    pp_shard = {"w": NamedSharding(mesh, P("pipe", None, None)),
                "b": NamedSharding(mesh, P("pipe", None))}
    dp_shard = {"w": replicated(mesh), "b": replicated(mesh)}
    data = jnp.asarray(rng.standard_normal(
        (steps_per_epoch, batch, dim)).astype(numpy.float32))
    target = jnp.asarray(rng.standard_normal(
        (steps_per_epoch, batch, dim)).astype(numpy.float32))

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def seq_forward(p, x):
        def body(h, leaf):
            return stage_fn(leaf, h), None
        h, _ = jax.lax.scan(body, x, p)
        return h

    def pp_forward(p, x):
        return pipeline_apply(stage_fn, p, x, mesh, n_micro=n_micro,
                              batch_axis="data")

    def epoch_fn(forward, shard):
        def loss_fn(p, x, y):
            return ((forward(p, x) - y) ** 2).mean()

        def step(p, xs):
            x, y = xs
            grads = jax.grad(loss_fn)(p, x, y)
            return jax.tree.map(lambda a, g: a - 0.1 * g, p,
                                grads), None

        def epoch(p):
            p, _ = jax.lax.scan(step, p, (data, target))
            return p
        # pinned in/out shardings: every epoch call lands on ONE
        # compiled program — zero steady-state recompiles
        return jax.jit(epoch, in_shardings=(shard,),
                       out_shardings=shard)

    seq_epoch = epoch_fn(seq_forward, dp_shard)
    pp_epoch = epoch_fn(pp_forward, pp_shard)
    p_seq = jax.device_put(params, dp_shard)
    p_pp = jax.device_put(params, pp_shard)
    p_seq = seq_epoch(p_seq)           # warm: compiles included
    p_pp = pp_epoch(p_pp)
    jax.block_until_ready((p_seq, p_pp))
    tic = time.perf_counter()
    for _ in range(epochs):
        p_seq = seq_epoch(p_seq)
    jax.block_until_ready(p_seq)
    dp_ips = epochs * steps_per_epoch * batch \
        / (time.perf_counter() - tic)
    tic = time.perf_counter()
    for _ in range(epochs):
        p_pp = pp_epoch(p_pp)
    jax.block_until_ready(p_pp)
    elapsed = time.perf_counter() - tic
    _emit("MLP stacked-stage pipeline-parallel pod epoch train "
          "throughput (one-dispatch epochs, %dx%d dp×pp mesh)"
          % (n_dev // stages, stages),
          elapsed / (epochs * steps_per_epoch), batch, None,
          vs=dp_ips,
          extra={"dispatches_per_epoch": 1,
                 "bubble_fraction": round(
                     pipeline_bubble(stages, n_micro), 4),
                 "stages": stages, "microbatches": n_micro,
                 "shards": n_dev,
                 "recompiles": (seq_epoch._cache_size() - 1)
                 + (pp_epoch._cache_size() - 1),
                 "devices": n_dev,
                 "vs_metric": "same stages as a sequential dp scan, "
                              "params replicated (same run)"})


def stage_moe_pod():
    """Expert-parallel pod steps: the switch-MoE sample routed by
    ``all_to_all`` over a dp×ep mesh vs its SAME-RUN dense reference
    (one-program jit, no mesh) — at the drop-free capacity
    (``capacity_factor = n_experts``) the two are token-for-token
    equal, so ``vs_baseline`` prices exactly what expert routing
    costs/buys; ``all_to_all_bytes_per_step`` carries the analytic
    exchange traffic the prof ledger's new column meters (tokens out
    to their experts and back)."""
    import jax
    import numpy
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veles_tpu.analyze.pricing import all_to_all_bytes
    from veles_tpu.parallel.mesh import make_mesh
    from veles_tpu.samples import moe

    n_dev = len(jax.devices())
    experts = 4
    if n_dev < 2 * experts:
        print(_dumps({
            "metric": "Switch-MoE expert-parallel pod train "
                      "throughput",
            "value": 0.0, "unit": "images/sec", "vs_baseline": None,
            "error": "needs a dp×ep mesh: %d device(s) < %d"
                     % (n_dev, 2 * experts),
            "device_kind": _device_kind()}))
        return
    cfg = {"vocab": 512, "dim": 64, "ffn": 128, "experts": experts,
           "seq_len": 32}
    batch, steps = 32, 10
    mesh = make_mesh({"data": n_dev // experts, "expert": experts})
    # correctness first: drop-free routing must match the dense
    # reference token for token (the ep smoke leg's parity anchor)
    params = moe.init_params(cfg, seed=1)
    probe = moe.synthetic_tokens(cfg, 8, seed=2)
    diff = float(numpy.abs(
        numpy.asarray(moe.apply_fn(params, probe, cfg, mesh=None))
        - numpy.asarray(moe.apply_fn(params, probe, cfg,
                                     mesh=mesh))).max())
    if diff > 1e-5:
        print(_dumps({
            "metric": "Switch-MoE expert-parallel pod train "
                      "throughput",
            "value": 0.0, "unit": "images/sec", "vs_baseline": None,
            "error": "routed MoE diverged %.2e from the dense "
                     "reference at drop-free capacity" % diff,
            "device_kind": _device_kind()}))
        return
    tokens = moe.synthetic_tokens(cfg, batch, seed=3)

    def timed(p, v, step, toks):
        for _ in range(2):             # warm: compiles included
            p, v, metrics = step(p, v, toks)
        jax.block_until_ready(metrics["loss"])
        warm_compiles = step._cache_size()
        tic = time.perf_counter()
        for _ in range(steps):
            p, v, metrics = step(p, v, toks)
        jax.block_until_ready(metrics["loss"])
        return (time.perf_counter() - tic,
                step._cache_size() - warm_compiles)

    p, v, dense_step = moe.build_train(cfg, mesh=None, seed=1)
    dense_elapsed, dense_rec = timed(p, v, dense_step, tokens)
    dense_ips = steps * batch / dense_elapsed
    p, v, ep_step = moe.build_train(cfg, mesh=mesh, seed=1)
    shard = {name: NamedSharding(mesh, spec)
             for name, spec in moe.param_specs(p).items()}
    p = jax.device_put(p, shard)
    v = jax.device_put(v, shard)
    toks = jax.device_put(tokens,
                          NamedSharding(mesh, P("data", "expert")))
    elapsed, ep_rec = timed(p, v, ep_step, toks)
    # the routed activation [B, T, D] crosses the expert axis out and
    # back each step — the ledger's all_to_all column meters the same
    act_bytes = batch * cfg["seq_len"] * cfg["dim"] * 4
    _emit("Switch-MoE expert-parallel pod train throughput "
          "(all_to_all routing, %dx%d dp×ep mesh, seq/sec)"
          % (n_dev // experts, experts),
          elapsed / steps, batch,
          moe.train_step_flops(cfg, batch), vs=dense_ips,
          extra={"all_to_all_bytes_per_step":
                 all_to_all_bytes(act_bytes, experts),
                 "experts": experts, "expert_shards": experts,
                 "max_token_diff": diff,
                 "recompiles": dense_rec + ep_rec,
                 "devices": n_dev,
                 "vs_metric": "dense MoE reference, one-program jit "
                              "(same run)"})


def stage_ae_wf_epoch():
    """The AE family through the full framework path with epoch_mode:
    StandardWorkflow(fused, epoch_mode) + MSE loss — the regression
    epoch program gathers resident float TARGETS in-program (VERDICT
    r4 item 5: AE epoch-mode bench stage)."""
    from veles_tpu.samples import mnist_ae
    _wf_stage("MNIST784-AE full StandardWorkflow(fused, epoch_mode, "
              "mse) train throughput (epoch wall-clock incl. eval)",
              fused_config={"epoch_mode": True}, sample=mnist_ae)


def stage_cifar():
    from veles_tpu.samples import cifar10
    _conv_stage("CIFAR-10 convnet fused train throughput",
                cifar10.LAYERS, (32, 32, 3), 10, batch=1024, steps=20)


def stage_stl10():
    """STL-10 convnet (96x96x3) — the last BASELINE.md config ladder
    member without its own throughput line."""
    from veles_tpu.samples import stl10
    batch = int(os.environ.get("BENCH_STL10_BATCH", "256"))
    # labeled synthetic: samples/stl10.py substitutes a stand-in when
    # the real binaries are absent, and this line must never read as a
    # real-data result (VERDICT r4 weak item 5).  Every conv stage
    # uses synthetic batches; STL-10 carries the label because its
    # BASELINE config is the one defined by a real dataset.
    _conv_stage("STL-10 convnet fused train throughput "
                "(synthetic batch)" + _batch_tag(batch, 256),
                stl10.LAYERS, (96, 96, 3), 10, batch=batch, steps=12)


def _e2e_loop(metric, loader, params, step, label_dtype="int32",
              min_seconds=4.0, flops=None, extra=None):
    """Drive the REAL loader (shuffling, epoch bookkeeping,
    device-resident gather, prefetch hooks) into the fused step and
    measure whole-pipeline images/sec.  Long run + single final host
    fetch: the fixed sync overhead amortizes instead of inflating.
    The e2e number proves the input pipeline keeps up with the
    synthetic-batch line (ref: the in-workflow benchmark unit,
    ``/root/reference/veles/accelerated_units.py:706-825``)."""
    import numpy as np

    import jax
    from veles_tpu.ops.timing import host_fetch, probe_of

    host = {"serve": 0.0, "dispatch": 0.0}

    def serve():
        tic = time.perf_counter()
        loader.run()
        x = loader.minibatch_data.devmem
        labels = jax.device_put(np.ascontiguousarray(
            loader.minibatch_labels.mem.astype(label_dtype)))
        host["serve"] += time.perf_counter() - tic
        return x, labels

    x, labels = serve()                    # warm: compile + first fill
    params, m = step(params, x, labels)
    host_fetch(probe_of(params, m))
    host["serve"] = 0.0
    served = iters = 0
    tic = time.perf_counter()
    while True:
        x, labels = serve()
        t0 = time.perf_counter()
        params, m = step(params, x, labels)
        host["dispatch"] += time.perf_counter() - t0
        served += int(loader.minibatch_size)
        iters += 1
        if time.perf_counter() - tic >= min_seconds:
            break
    t_drain = time.perf_counter()
    host_fetch(probe_of(params, m))        # real bytes end the clock
    now = time.perf_counter()
    elapsed = now - tic
    # throughput normalizes by equivalent FULL batches (short tails
    # count pro-rata); the per-batch diagnostics divide by the ACTUAL
    # loop iterations they were accumulated over
    n_batches = served / loader.max_minibatch_size
    # provenance: where the wall-clock went, so a pathological line
    # carries its own diagnosis — host serve work vs step-dispatch blocking vs the
    # final queue drain
    _emit(metric, elapsed / n_batches,
          loader.max_minibatch_size, flops, extra=dict({
              "batches_served": iters,
              "host_serve_ms_per_batch": round(
                  1e3 * host["serve"] / iters, 3),
              "dispatch_ms_per_batch": round(
                  1e3 * host["dispatch"] / iters, 3),
              "drain_s": round(now - t_drain, 3)}, **(extra or {})))


def stage_mnist_e2e():
    """End-to-end framework stage: MnistSimple through the REAL
    StandardWorkflow loader feeding the fused step."""
    import jax
    from veles_tpu import prng
    from veles_tpu.samples import mnist
    from veles_tpu.znicz.fused import lower_workflow

    from veles_tpu.ops.timing import cost_flops

    prng.seed_all(1234)
    batch = 8192
    wf = mnist.create_workflow(max_epochs=10 ** 6,
                               minibatch_size=batch)
    params, step_fn = lower_workflow(wf)
    # ONE compile serves both the flops readout and the timed loop
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        params, wf.loader.minibatch_data.mem,
        wf.loader.minibatch_labels.mem.astype("int32")).compile()
    params = jax.device_put(params)
    _e2e_loop("MNIST784 MLP end-to-end workflow throughput "
              "(loader+prefetch+fused step)", wf.loader, params,
              compiled, flops=cost_flops(compiled))


def stage_mnist_e2e_u8():
    """End-to-end with the NATIVE-dtype resident dataset: the loader
    keeps u8 pixels in HBM, gathers u8 minibatches, and the fused step
    scales in-program (``MnistLoader(native_device_dtype=True)``).
    Compare against the ``mnist_u8`` synthetic line the way
    ``mnist_e2e`` compares against ``mnist``."""
    import jax
    from veles_tpu import prng
    from veles_tpu.samples import mnist
    from veles_tpu.znicz.fused import lower_workflow

    from veles_tpu.ops.timing import cost_flops

    prng.seed_all(1234)
    batch = 8192
    wf = mnist.create_workflow(max_epochs=10 ** 6,
                               minibatch_size=batch, native=True,
                               fused=True)
    params, step_fn = lower_workflow(wf)
    compiled = jax.jit(step_fn, donate_argnums=(0,)).lower(
        params, wf.loader.minibatch_data.mem,
        wf.loader.minibatch_labels.mem.astype("int32")).compile()
    params = jax.device_put(params)
    _e2e_loop("MNIST784 MLP end-to-end workflow throughput "
              "(u8-resident loader + fused step)", wf.loader, params,
              compiled, flops=cost_flops(compiled))


def stage_ae():
    """MNIST autoencoder (BASELINE.json.configs[2]): 784→100→784
    sigmoid MLP, MSE reconstruction loss, fused train step."""
    import numpy

    import jax
    from veles_tpu import prng
    from veles_tpu.samples.mnist_ae import make_layers
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    batch = 8192
    params, step_fn, _eval, _apply = lower_specs(make_layers(), (784,),
                                                 loss="mse")
    rng = numpy.random.default_rng(0)
    x = jax.device_put(
        rng.standard_normal((batch, 784)).astype(numpy.float32))
    sec, flops = _measure(step_fn, params, x, x, steps=100)
    _emit("MNIST784 autoencoder fused train throughput", sec, batch,
          flops)


def stage_kohonen():
    """Kohonen SOM (BASELINE.json.configs[4]): non-gradient training —
    the random + matrix_reduce substrate.  32×32 map over 784-d data."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu.ops.timing import inprogram_marginal
    from veles_tpu.znicz.kohonen import _som_step

    side, dim, batch = 32, 784, 4096
    n = side * side
    rng = numpy.random.default_rng(0)
    weights = jax.device_put(
        rng.standard_normal((n, dim)).astype(numpy.float32))
    grid = jax.device_put(numpy.stack(numpy.meshgrid(
        numpy.arange(side), numpy.arange(side)),
        axis=-1).reshape(n, 2).astype(numpy.float32))
    x = jax.device_put(
        rng.standard_normal((batch, dim)).astype(numpy.float32))
    radius = jnp.float32(side / 4.0)

    def unit(w):
        new_w, _winners = _som_step(w, grid, x, radius,
                                    jnp.float32(0.1), (side, side))
        return new_w
    sec = inprogram_marginal(unit, weights, k1=2, k2=16)
    # distance cross-term + neighborhood-weighted update matmuls
    # dominate: 2·B·N·D each; elementwise terms ~B·N
    flops = 4.0 * batch * n * dim + 10.0 * batch * n
    _emit("Kohonen SOM 32x32 train throughput", sec, batch, flops)


def stage_lstm():
    """Sequential-MNIST LSTM (the recurrent family): 28-step fused
    scan, gates as one matmul per step, backward through the scan."""
    import numpy

    import jax
    from veles_tpu import prng
    from veles_tpu.samples.mnist_rnn import LAYERS
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    batch = 2048
    params, step_fn, _eval, _apply = lower_specs(LAYERS, (28, 28))
    rng = numpy.random.default_rng(0)
    x = jax.device_put(
        rng.standard_normal((batch, 28, 28)).astype(numpy.float32))
    labels = jax.device_put(
        rng.integers(0, 10, batch).astype(numpy.int32))
    # cost_analysis counts the 28-step sequence scan body ONCE —
    # analytic FLOPs, or MFU underreports ~28×
    from veles_tpu.znicz.rnn import lstm_train_flops
    h = int(LAYERS[0]["->"]["hidden_units"])
    flops_lstm = lstm_train_flops(batch, 28, 28, h, head_classes=10)
    sec, flops = _measure(step_fn, params, x, labels, steps=50,
                          flops_override=flops_lstm)
    _emit("Sequential-MNIST LSTM fused train throughput", sec, batch,
          flops)
    # bf16 A/B: the f32 LSTM is HBM-bound at these shapes
    # (docs/performance.md roofline) — halving the activation bytes is
    # the one lever the roofline allows; measure it so the claim is a
    # number, not a prediction.  Chip-only (or forced): doubling the
    # stage's work would blow a CPU rehearsal's cap for a number that
    # only means something on HBM.
    if _device_kind().lower().find("tpu") >= 0 \
            or os.environ.get("BENCH_LSTM_BF16") == "1":
        import jax.numpy as jnp
        params16, step16, _e16, _a16 = lower_specs(
            LAYERS, (28, 28), compute_dtype=jnp.bfloat16)
        sec16, _f = _measure(step16, params16, x, labels, steps=50,
                             flops_override=flops_lstm)
        _emit("Sequential-MNIST LSTM fused train throughput (bf16)",
              sec16, batch, flops_lstm)


def stage_transformer():
    """GPT-style LM train step on one chip (flash attention consults
    the autotune DB; bf16 compute; remat OFF + chunked CE by default —
    see the knob comment below): the long-context substrate's
    single-chip number.  Metric = tokens/sec."""
    import numpy

    import jax
    from veles_tpu.samples import transformer

    if os.environ.get("BENCH_LM_TINY"):      # CPU smoke of the path
        cfg = dict(transformer.TINY, seq_len=64)
    else:
        cfg = {"vocab": 32000, "dim": 512, "heads": 8, "layers": 8,
               "mlp_ratio": 4, "seq_len": 1024}
    # batch 32 = 32k tokens/step: the chunked-CE readout (transformer.
    # make_train_step ce_chunk) keeps logits memory at O(B·128·V), so
    # the old full-[B,S,V]-logits batch ceiling no longer applies
    batch = int(os.environ.get("BENCH_LM_BATCH", "32"))
    # remat trades a full block-forward recompute (~25% extra FLOPs)
    # for HBM the single-chip config (batch 32, d=512, ~1.3 GB of
    # activations) does not need — off by default here; chunked CE
    # stays on (its recompute is only the readout, ~10%, and it keeps
    # logits memory O(B·chunk·V)).  Both remain env knobs, and remat
    # stays the default in the deep/sharded regimes that need it.
    remat = os.environ.get("BENCH_LM_REMAT", "0") == "1"
    ce_chunk = int(os.environ.get("BENCH_LM_CE_CHUNK", "128"))
    params = transformer.init_params(cfg, seed=0)
    velocity = jax.tree.map(numpy.zeros_like, params)
    tokens = jax.device_put(transformer.synthetic_tokens(cfg, batch))
    labels = numpy.zeros((batch,), numpy.int32)

    def measure(remat_mode):
        raw_step = transformer.make_train_step(cfg, remat=remat_mode,
                                               ce_chunk=ce_chunk)

        def step(state, x, _labels):
            p, v = state
            p, v, metrics = raw_step(p, v, x)
            return (p, v), metrics

        # the blocks are scanned: cost analysis counts the body once,
        # so FLOPs/MFU come from the analytic closed form (~L× higher)
        return _measure(
            step, (params, velocity), tokens, labels, steps=12,
            flops_override=transformer.train_step_flops(cfg, batch))

    fell_back = False
    try:
        sec, flops = measure(remat)
    except _StageTimeout:
        raise                 # the ladder watchdog, never a fallback
    except Exception as exc:
        if remat:
            raise
        # the no-recompute step outgrew HBM on this generation —
        # degrade to the remat build rather than losing the LM line
        print("transformer: remat-off failed (%s); retrying with "
              "remat" % type(exc).__name__, file=sys.stderr)
        remat = True
        fell_back = True
    if fell_back:
        # retry OUTSIDE the except block (traceback pins the failed
        # attempt's device buffers); stage_profile_lm (same child,
        # later in the order) reads the same env knob — keep it
        # profiling the config that WORKED
        os.environ["BENCH_LM_REMAT"] = "1"
        sec, flops = measure(True)
    name = ("GPT-512x8 LM fused train throughput (tokens basis)"
            + _batch_tag(batch, 32))
    if os.environ.get("BENCH_LM_TINY"):
        name += " [tiny-smoke]"
    _emit(name, sec, batch * cfg["seq_len"], flops,
          extra={"remat": remat, "ce_chunk": ce_chunk})


def stage_transformer_lm_train():
    """The MFU line: the fused-kernel LM train step (flash-attention
    fwd+bwd custom_vjp + chunked CE) vs the SAME-RUN XLA-kernel
    baseline — dense materialized attention (no custom_vjp, AD
    rebuilds the [B,H,S,S] scores in the backward) + full-logits CE.
    Both arms are measured in this process on this chip, so ``vs=`` is
    a kernel-for-kernel ratio, not a cross-session absolute.  Emits
    tokens/sec, MFU, steps_per_dispatch (the multi-step loop's trip
    count — K steps ride one dispatch) and recompiles (jit cache
    entries beyond the first across repeated same-shape calls)."""
    import numpy

    import jax
    from veles_tpu.config import root
    from veles_tpu.samples import transformer

    # off-TPU the stage runs a thin LONG-SEQUENCE config: both arms
    # are the dense fast path there (interpret-mode Pallas is not a
    # throughput claim), so the A/B isolates what the fused step is
    # FOR — the blockwise custom_vjp backward vs AD rebuilding the
    # materialized [B,H,S,S] scores.  The crossover on CPU is S≈2-4k
    # (below that the score matrix fits cache and recompute loses);
    # measured ratios: 0.75x @ S=1k, 1.3x @ S=4-6k, 1.5x @ S=8k.
    # S=6144 keeps the A/B inside the stage budget on one CPU core.
    tiny = bool(os.environ.get("BENCH_LM_TINY")) \
        or jax.default_backend() != "tpu"
    if tiny:
        cfg = {"vocab": 512, "dim": 64, "heads": 2, "layers": 1,
               "mlp_ratio": 2,
               "seq_len": int(os.environ.get("BENCH_LM_SEQ", "6144"))}
        batch = int(os.environ.get("BENCH_LM_BATCH", "1"))
    else:
        cfg = {"vocab": 32000, "dim": 512, "heads": 8, "layers": 8,
               "mlp_ratio": 4, "seq_len": 1024}
        batch = int(os.environ.get("BENCH_LM_BATCH", "32"))
    remat = os.environ.get("BENCH_LM_REMAT", "0") == "1"
    ce_chunk = int(os.environ.get("BENCH_LM_CE_CHUNK", "128"))
    steps = 4 if tiny else 12
    params = transformer.init_params(cfg, seed=0)
    velocity = jax.tree.map(numpy.zeros_like, params)
    tokens = jax.device_put(transformer.synthetic_tokens(cfg, batch))
    labels = numpy.zeros((batch,), numpy.int32)
    flops = transformer.train_step_flops(cfg, batch)

    def measure(kernels, chunk):
        # the kernels knob is resolved at TRACE time (samples.
        # transformer._attend, znicz.gd stage build), so each arm
        # builds its own program under its own mode — nothing leaks
        # across arms through a compile cache keyed only on shapes
        saved = root.common.engine.get("kernels", "auto")
        root.common.engine.kernels = kernels
        try:
            raw_step = transformer.make_train_step(
                cfg, remat=remat, ce_chunk=chunk)

            def step(state, x, _labels):
                p, v = state
                p, v, metrics = raw_step(p, v, x)
                return (p, v), metrics

            sec, _ = _measure(step, (params, velocity), tokens,
                              labels, steps=steps,
                              flops_override=flops)
            # recompile probe: repeated same-shape dispatches of the
            # plain jitted step must hit ONE cache entry — a weak-type
            # flip or python-scalar bake-in would grow the cache
            jitted = jax.jit(step)
            state = (jax.device_put(params), jax.device_put(velocity))
            for _ in range(3):
                out_state, metrics = jitted(state, tokens, labels)
            jax.block_until_ready(metrics)
            recompiles = max(0, jitted._cache_size() - 1)
        finally:
            root.common.engine.kernels = saved
        return sec, recompiles

    base_sec, base_recompiles = measure("xla", 0)
    sec, recompiles = measure(
        str(root.common.engine.get("kernels", "auto")) if
        str(root.common.engine.get("kernels", "auto")) != "xla"
        else "auto", ce_chunk)
    name = ("GPT-512x8 LM train step, fused kernels vs XLA baseline "
            "(tokens basis)" + _batch_tag(batch, 32))
    if tiny:
        name += " [tiny-smoke]"
    tokens_per_step = batch * cfg["seq_len"]
    _emit(name, sec, tokens_per_step, flops,
          vs=tokens_per_step / base_sec,
          extra={"remat": remat, "ce_chunk": ce_chunk,
                 "steps_per_dispatch": steps,
                 "recompiles": recompiles + base_recompiles,
                 "baseline_sec_per_step": round(base_sec, 6),
                 "kernels": "fused-vs-xla"})


def stage_transformer_gen():
    """Generative serving closed loop (the veles_tpu.gen subsystem):
    a seeded mixed-length request set pumped through the continuous-
    batching scheduler, then the SAME workload through the pad-to-
    slowest static batcher on a fresh engine — identical compiled
    programs, so the ratio isolates iteration-level admission.
    Metric = continuous tokens/sec; the record carries batch-fill %,
    p99 time-to-first-token under the closed-loop load, the
    vs-static speedup and the steady-state recompile count (must be
    0 after warmup)."""
    import numpy

    import jax.numpy as jnp
    from veles_tpu import prof
    from veles_tpu.gen import (GenerativeEngine, GenerativeScheduler,
                               TransformerGenModel, static_generate)
    from veles_tpu.samples import transformer

    kind = (_device_kind() or "").lower()
    tiny = os.environ.get("BENCH_GEN_TINY") or "tpu" not in kind
    if tiny:
        cfg = dict(transformer.TINY, seq_len=128)
        slots, max_seq, buckets = 4, 96, (8,)
        n_requests, long_new, dtype = 48, 64, None
    else:
        cfg = {"vocab": 32000, "dim": 512, "heads": 8, "layers": 8,
               "mlp_ratio": 4, "seq_len": 1024}
        slots, max_seq, buckets = 8, 768, (32, 64, 128)
        n_requests, long_new, dtype = 64, 512, jnp.bfloat16
    rng = numpy.random.default_rng(0)
    # the serving mix continuous batching exists for: mostly short
    # interactive generations with a long-form request interleaved
    # every slots-th — the static batcher pads each group to its
    # long member, the continuous scheduler backfills the idle rows
    workload = [
        (rng.integers(0, cfg["vocab"],
                      int(rng.integers(1, buckets[0] + 1))).tolist(),
         long_new if i % slots == 0
         else int(rng.integers(2, buckets[0] + 1)))
        for i in range(n_requests)]

    def build():
        model = TransformerGenModel(
            cfg, compute_dtype=dtype) if dtype else \
            TransformerGenModel(cfg)
        return GenerativeEngine(model, max_slots=slots,
                                max_seq=max_seq,
                                prefill_buckets=buckets,
                                seed=0).warmup()

    engine = build()
    recompiles0 = prof.ledger.recompiles
    scheduler = GenerativeScheduler(engine, name="bench")
    futures = [scheduler.submit(toks, max_new)
               for toks, max_new in workload]
    tic = time.perf_counter()
    scheduler.run_until_idle()
    cont_sec = time.perf_counter() - tic
    assert all(f.done() for f in futures)
    cont_tokens = scheduler.tokens_total
    recompiles = prof.ledger.recompiles - recompiles0
    fill = scheduler.batch_fill()
    ttft_p99_ms = scheduler.ttft.percentile(99) * 1e3
    engine.close()

    # tracing-on replay of the SAME workload on a fresh engine: the
    # observability tax banked next to tokens/s (the ISSUE 13 0.95x
    # gate reads this ratio), plus the trace-DERIVED queue-wait p99 —
    # measured from the scheduler's per-request queue_wait phase
    # spans, not a histogram, so it prices exactly what a waterfall
    # shows
    from veles_tpu import obs, trace
    from veles_tpu.config import root as _root
    from veles_tpu.trace import export as trace_export
    saved_trace = _root.common.engine.get("trace", "off")
    _root.common.engine.trace = "on"
    trace.configure()
    trace.recorder.clear()
    try:
        traced_engine = build()
        traced_scheduler = GenerativeScheduler(traced_engine,
                                               name="bench-traced")
        traced_futures = []
        tic = time.perf_counter()
        for toks, max_new in workload:
            with obs.activate(obs.mint()):
                traced_futures.append(
                    traced_scheduler.submit(toks, max_new))
        traced_scheduler.run_until_idle()
        traced_sec = time.perf_counter() - tic
        assert all(f.done() for f in traced_futures)
        traced_tokens = traced_scheduler.tokens_total
        waits = sorted(
            ev["dur_us"] / 1e3 for ev in trace_export.normalize()
            if ev["ph"] == "X" and ev["cat"] == "gen"
            and ev["name"] == "queue_wait")
        queue_wait_p99_ms = (
            waits[min(len(waits) - 1, int(0.99 * len(waits)))]
            if waits else None)
        traced_engine.close()
    finally:
        # restore BEFORE later stages run: a failure here must not
        # leave tracing armed under their timed regions
        _root.common.engine.trace = saved_trace
        trace.configure()
        trace.recorder.clear()
    traced_tps = traced_tokens / traced_sec if traced_sec else 0.0

    static_engine = build()
    tic = time.perf_counter()
    results, _steps = static_generate(static_engine, workload)
    static_sec = time.perf_counter() - tic
    static_tokens = sum(len(r) for r in results)
    static_engine.close()

    cont_tps = cont_tokens / cont_sec if cont_sec else 0.0
    static_tps = static_tokens / static_sec if static_sec else 0.0
    rec = {
        "metric": "transformer generative serving, continuous "
                  "batching (closed-loop mixed-length)"
                  + (" [tiny-smoke]" if tiny else ""),
        "value": round(cont_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "batch_fill": round(fill, 4),
        "ttft_p99_ms": round(ttft_p99_ms, 2),
        "queue_wait_p99_ms": round(queue_wait_p99_ms, 3)
                             if queue_wait_p99_ms is not None
                             else None,
        "tracing_overhead_x": round(traced_tps / cont_tps, 3)
                              if cont_tps else None,
        "tracing_on_tokens_per_sec": round(traced_tps, 1),
        "vs_static_x": round(cont_tps / static_tps, 3)
                       if static_tps else None,
        "static_tokens_per_sec": round(static_tps, 1),
        "recompiles": recompiles,
        "slots": slots,
        "requests": n_requests,
        "device_kind": _device_kind()}
    if recompiles:
        rec["error"] = ("%d steady-state recompile(s) — the AOT "
                        "bucket/decode plan missed the workload"
                        % recompiles)
    print(_dumps(rec))

    # -- long-tail phase: paged KV vs the same-run contiguous line --
    # mixed SHORT/LONG PROMPTS (not just budgets) — the mix paged KV
    # exists for: contiguous reserves max_seq rows per admission, the
    # pool pays per page; both engines run chunked admission over a
    # shared seed so the only variable is the KV layout.  The pool is
    # throttled to ~half the contiguous reservation so the preemption
    # path shows up in the record (lossless — token parity holds).
    if tiny:
        block_size, long_prompt = 8, 24
    else:
        block_size, long_prompt = 16, 512
    chunk = buckets[0]
    rng = numpy.random.default_rng(1)
    lt_new = min(long_new, max_seq - long_prompt - 1)
    lt_workload = [
        (rng.integers(0, cfg["vocab"],
                      long_prompt if i % slots == 0
                      else int(rng.integers(1, buckets[0] + 1))
                      ).tolist(),
         lt_new if i % slots == 0
         else int(rng.integers(2, buckets[0] + 1)))
        for i in range(n_requests)]
    max_blocks = max_seq // block_size

    def build_lt(kv, num_blocks=None):
        model = TransformerGenModel(
            cfg, compute_dtype=dtype) if dtype else \
            TransformerGenModel(cfg)
        return GenerativeEngine(
            model, max_slots=slots, max_seq=max_seq,
            prefill_buckets=buckets, seed=0, kv=kv,
            block_size=block_size if kv == "paged" else None,
            num_blocks=num_blocks, prefill_chunk=chunk).warmup()

    def run_lt(engine):
        scheduler = GenerativeScheduler(engine, name="bench-lt")
        futures = [scheduler.submit(toks, max_new)
                   for toks, max_new in lt_workload]
        hbm_sum = hbm_n = peak_conc = 0
        tic = time.perf_counter()
        while scheduler.queue_depth() or scheduler.active_requests():
            if scheduler.step() == 0:
                break
            per_req = engine.hbm_per_request_bytes()
            if per_req:
                hbm_sum += per_req
                hbm_n += 1
            peak_conc = max(peak_conc, scheduler.active_requests())
        sec = time.perf_counter() - tic
        tokens = [f.result(0) for f in futures]
        out = (scheduler.tokens_total, sec,
               hbm_sum // max(1, hbm_n), peak_conc,
               engine.preemptions_total, tokens)
        engine.close()
        return out

    recompiles0 = prof.ledger.recompiles
    (ct_tokens, ct_sec, ct_hbm, ct_conc, _zero,
     ct_streams) = run_lt(build_lt("contiguous"))
    (pg_tokens, pg_sec, pg_hbm, pg_conc, pg_preempt,
     pg_streams) = run_lt(build_lt(
         "paged", num_blocks=slots * max_blocks // 2 + 1))
    lt_recompiles = prof.ledger.recompiles - recompiles0
    ct_tps = ct_tokens / ct_sec if ct_sec else 0.0
    pg_tps = pg_tokens / pg_sec if pg_sec else 0.0
    rec = {
        "metric": "transformer generative serving, paged KV "
                  "(long-tail mixed prompts)"
                  + (" [tiny-smoke]" if tiny else ""),
        "value": round(pg_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "kv": "paged",
        "block_size": block_size,
        "prefill_chunk": chunk,
        "hbm_per_request_bytes": pg_hbm,
        "preemptions": pg_preempt,
        "max_concurrent": pg_conc,
        "vs_contiguous_x": round(pg_tps / ct_tps, 3)
                           if ct_tps else None,
        "contiguous_tokens_per_sec": round(ct_tps, 1),
        "contiguous_hbm_per_request_bytes": ct_hbm,
        "contiguous_max_concurrent": ct_conc,
        "token_parity": pg_streams == ct_streams,
        "recompiles": lt_recompiles,
        "slots": slots,
        "requests": n_requests,
        "device_kind": _device_kind()}
    if not rec["token_parity"]:
        rec["error"] = ("paged token streams diverge from the "
                        "same-run contiguous line — the parity "
                        "contract is bitwise")
    if lt_recompiles:
        rec["error"] = ("%d steady-state recompile(s) in the "
                        "long-tail phase" % lt_recompiles)
    print(_dumps(rec))

    # -- int8 phase: weight-only quantized serving vs the SAME-RUN --
    # float twin at the SAME compute dtype (bf16 on chip — so
    # vs_bf16_x is the on-chip quantization win; f32 on the tiny/CPU
    # path, where the column still isolates the int8 weights instead
    # of conflating a compute-dtype mismatch).  Both engines run the
    # phase-1 workload through the continuous scheduler;
    # hbm_per_request_bytes (params amortized over occupants) is the
    # capacity win — both regression-gated by scripts/bench_diff.py
    # from round one.
    def build_q(quantize):
        # BOTH engines share the phase-1 compute dtype (bf16 on chip,
        # f32 on the tiny/CPU path) so the ratio isolates the int8
        # weights, never a compute-dtype mismatch
        model = TransformerGenModel(
            cfg, compute_dtype=dtype) if dtype else \
            TransformerGenModel(cfg)
        engine = GenerativeEngine(model, max_slots=slots,
                                  max_seq=max_seq,
                                  prefill_buckets=buckets, seed=0)
        if quantize:
            # a random-/lightly-trained bench model legitimately
            # exceeds the 1e-2 production drift budget; the bench
            # measures throughput, not accuracy, so gate loosely
            engine.quantize_int8(calibration_tokens=workload[0][0],
                                 tol=0.2)
        return engine.warmup()

    def run_q(engine):
        scheduler = GenerativeScheduler(engine, name="bench-int8")
        futures = [scheduler.submit(toks, max_new)
                   for toks, max_new in workload]
        hbm_sum = hbm_n = 0
        tic = time.perf_counter()
        while scheduler.queue_depth() or scheduler.active_requests():
            if scheduler.step() == 0:
                break
            per_req = engine.hbm_per_request_bytes()
            if per_req:
                hbm_sum += per_req
                hbm_n += 1
        sec = time.perf_counter() - tic
        assert all(f.done() for f in futures)
        return (scheduler.tokens_total, sec,
                hbm_sum // max(1, hbm_n))

    recompiles0 = prof.ledger.recompiles
    bf16_engine = build_q(False)
    bf16_tokens, bf16_sec, _bf16_hbm = run_q(bf16_engine)
    bf16_params = bf16_engine.params_nbytes
    bf16_engine.close()
    int8_engine = build_q(True)
    q_tokens, q_sec, q_hbm = run_q(int8_engine)
    q_params = int8_engine.params_nbytes
    int8_engine.close()
    q_recompiles = prof.ledger.recompiles - recompiles0
    bf16_tps = bf16_tokens / bf16_sec if bf16_sec else 0.0
    q_tps = q_tokens / q_sec if q_sec else 0.0
    rec = {
        "metric": "transformer generative serving, int8 quantized "
                  "(weight-only)"
                  + (" [tiny-smoke]" if tiny else ""),
        "value": round(q_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "quantize": "int8",
        "vs_bf16_x": round(q_tps / bf16_tps, 3) if bf16_tps else None,
        "bf16_tokens_per_sec": round(bf16_tps, 1),
        "hbm_per_request_bytes": q_hbm,
        "params_bytes": q_params,
        "params_vs_float_x": round(q_params / float(bf16_params), 3),
        "recompiles": q_recompiles,
        "slots": slots,
        "requests": n_requests,
        "device_kind": _device_kind()}
    if q_recompiles:
        rec["error"] = ("%d steady-state recompile(s) in the int8 "
                        "phase" % q_recompiles)
    print(_dumps(rec))

    # -- prefix+spec phase: radix prefix cache + n-gram speculative --
    # decode vs the SAME shared-prefix workload on a plain paged
    # engine — the serving shape both levers exist for: every prompt
    # extends one common stem (the system-prompt pattern), and the
    # generations repeat prompt n-grams (the retrieval/template
    # pattern).  vs_nonspec_x is the compounding win per request;
    # prefix_hit_rate and spec_accept_rate are the per-lever gauges
    # bench_diff regression-gates as higher-is-better.
    sp_block = 8 if tiny else 16
    stem_len = 2 * sp_block if tiny else 8 * sp_block
    rng = numpy.random.default_rng(2)
    # a TEMPLATE stem (short token cycle), not noise: the decode
    # stream re-derives the cycle, which is exactly what the n-gram
    # proposer drafts from — random stems would still share pages
    # but leave speculation nothing to copy forward
    stem = (rng.integers(0, cfg["vocab"], 4).tolist()
            * stem_len)[:stem_len]
    sp_new = min(24 if tiny else 96, max_seq - stem_len - 9)
    sp_workload = [
        (stem + [int(t) for t in rng.integers(0, cfg["vocab"], 2)],
         sp_new)
        for _ in range(n_requests // 2)]
    sp_blocks = slots * (max_seq // sp_block) + 1

    def build_sp(**kw):
        model = TransformerGenModel(
            cfg, compute_dtype=dtype) if dtype else \
            TransformerGenModel(cfg)
        return GenerativeEngine(
            model, max_slots=slots, max_seq=max_seq,
            prefill_buckets=tuple(
                sorted({b for b in buckets} | {stem_len + sp_block})),
            seed=0, kv="paged", block_size=sp_block,
            num_blocks=sp_blocks, **kw).warmup()

    def run_sp(engine):
        scheduler = GenerativeScheduler(engine, name="bench-spec")
        futures = [scheduler.submit(toks, max_new)
                   for toks, max_new in sp_workload]
        tic = time.perf_counter()
        scheduler.run_until_idle()
        sec = time.perf_counter() - tic
        streams = [f.result(0) for f in futures]
        return (scheduler.tokens_total, sec,
                scheduler.ttft.percentile(99) * 1e3, streams)

    recompiles0 = prof.ledger.recompiles
    plain_engine = build_sp()
    (pl_tokens, pl_sec, _pl_ttft, pl_streams) = run_sp(plain_engine)
    plain_engine.close()
    sp_engine = build_sp(prefix_cache="on", speculative="ngram",
                         draft_k=4)
    (sp_tokens, sp_sec, sp_ttft, sp_streams) = run_sp(sp_engine)
    hit_rate = sp_engine.prefix_hit_rate()
    accept_rate = sp_engine.spec_accept_rate()
    tok_per_dispatch = sp_engine.spec_tokens_per_dispatch()
    sp_engine.close()
    sp_recompiles = prof.ledger.recompiles - recompiles0
    pl_tps = pl_tokens / pl_sec if pl_sec else 0.0
    sp_tps = sp_tokens / sp_sec if sp_sec else 0.0
    rec = {
        "metric": "transformer generative serving, prefix cache + "
                  "speculative decode (shared-prefix)"
                  + (" [tiny-smoke]" if tiny else ""),
        "value": round(sp_tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "prefix_cache": "on",
        "speculative": "ngram",
        "draft_k": 4,
        "ttft_p99_ms": round(sp_ttft, 2),
        "prefix_hit_rate": round(hit_rate, 4),
        "spec_accept_rate": round(accept_rate, 4),
        "spec_tokens_per_dispatch": round(tok_per_dispatch, 3),
        "vs_nonspec_x": round(sp_tps / pl_tps, 3) if pl_tps else None,
        "nonspec_tokens_per_sec": round(pl_tps, 1),
        "token_parity": sp_streams == pl_streams,
        "recompiles": sp_recompiles,
        "slots": slots,
        "requests": len(sp_workload),
        "device_kind": _device_kind()}
    if not rec["token_parity"]:
        rec["error"] = ("prefix+spec token streams diverge from the "
                        "same-run plain paged line — the parity "
                        "contract is bitwise")
    if sp_recompiles:
        rec["error"] = ("%d steady-state recompile(s) in the "
                        "prefix+spec phase" % sp_recompiles)
    print(_dumps(rec))

    # -- disagg phase: 2-role fleet (prefill role shipping KV pages --
    # over the job wire to decode replicas) vs the SAME bursty
    # open-loop workload on ONE paged engine — the ratio prices
    # disaggregation itself (wire + adoption overhead vs role
    # isolation).  Emits sustained req/s, TTFT p99 against the 500 ms
    # SLO, handoff bytes per request and the autoscaler's action
    # count — all regression-gated by scripts/bench_diff.py.
    from veles_tpu.fleet import Fleet

    block = 8 if tiny else 16
    paged_kw = dict(kv="paged", block_size=block,
                    num_blocks=slots * (max_seq // block) + 1,
                    prefill_chunk=buckets[0])

    def build_paged():
        model = TransformerGenModel(
            cfg, compute_dtype=dtype) if dtype else \
            TransformerGenModel(cfg)
        return GenerativeEngine(model, max_slots=slots,
                                max_seq=max_seq,
                                prefill_buckets=buckets, seed=0,
                                **paged_kw)

    def pump_bursty(submit, tick=None):
        """Open-loop: bursts of 8 with a think-time gap — the arrival
        pattern disaggregation exists for (prefill spikes must not
        stall in-flight decode)."""
        futures = []
        tic = time.perf_counter()
        for start in range(0, len(workload), 8):
            for toks, max_new in workload[start:start + 8]:
                futures.append(submit(toks, max_new))
            if tick is not None:
                tick()
            time.sleep(0.02)
        for future in futures:
            future.result(timeout=600.0)
        return time.perf_counter() - tic

    recompiles0 = prof.ledger.recompiles
    single = build_paged().warmup()
    s_sched = GenerativeScheduler(single, name="bench-single").start()
    s_sec = pump_bursty(s_sched.submit)
    s_ttft = s_sched.ttft.percentile(99) * 1e3
    s_sched.stop()
    single.close()
    s_rps = n_requests / s_sec if s_sec else 0.0

    fleet = Fleet(build_paged, decode_replicas=2, name="bench",
                  max_queue=4096).start()
    f_sec = pump_bursty(fleet.submit, tick=fleet.tick)
    f_ttft = fleet.ttft_p99_ms()
    actions = dict(fleet.autoscaler.actions_total)
    handoff_bpr = fleet.handoff_bytes_total // max(
        1, fleet.handoffs_total)
    fleet.stop(drain=True)
    fleet.close()
    d_recompiles = prof.ledger.recompiles - recompiles0
    f_rps = n_requests / f_sec if f_sec else 0.0
    rec = {
        "metric": "transformer generative serving, disaggregated "
                  "prefill/decode fleet"
                  + (" [tiny-smoke]" if tiny else ""),
        "value": round(f_rps, 2),
        "unit": "req/sec",
        "vs_baseline": None,
        "vs_single_engine_x": round(f_rps / s_rps, 3)
        if s_rps else None,
        "single_req_per_sec": round(s_rps, 2),
        "ttft_p99_ms": round(f_ttft, 1),
        "single_ttft_p99_ms": round(s_ttft, 1),
        "ttft_slo_ms": 500.0,
        "slo_met": bool(f_ttft <= 500.0),
        "handoff_bytes_per_request": handoff_bpr,
        "autoscaler_actions": int(sum(actions.values())),
        "autoscaler_actions_by_kind": actions,
        "decode_replicas": 2,
        "recompiles": d_recompiles,
        "slots": slots,
        "requests": n_requests,
        "device_kind": _device_kind()}
    if d_recompiles:
        rec["error"] = ("%d steady-state recompile(s) in the disagg "
                        "phase" % d_recompiles)
    print(_dumps(rec))


#: the reference DB's fastest recorded matmul: GTX TITAN, float,
#: precision 0 — 0.1642 s for ONE 3001² matmul (``backends.py:672-731``
#: stores dt/repeats of DeviceBenchmark(size=3001)), i.e. a measured
#: rate of 2·3001³/0.1642 ≈ 329 GFLOP/s.  The one absolute throughput
#: number the reference publishes (BASELINE.md row 8).
TITAN_MATMUL_GFLOPS = 2.0 * 3001.0 ** 3 / 0.1642 / 1e9

#: sustained-rate ratios vs a 2013 GPU decompose as ~42× hardware
#: (197 TFLOP/s bf16 vs 4.7 TFLOP/s fp32 peak) × the software
#: efficiency gap (TITAN measured 7 % of its peak through the OpenCL
#: tiling; the chip sustains ~98 % through XLA) — so the honest ceiling
#: is far above MAX_VS_BASELINE's throughput-ratio calibration
MAX_POWER_RATIO = 5000.0


def stage_power():
    """The reference's OWN in-situ rating workload — the 13× chained
    square matmul, min-of-runs (``accelerated_units.py:706-825``,
    ``ocl/benchmark.cl:1-11``) — reported as a sustained GFLOP/s rate
    and compared RATE-vs-RATE against the fastest entry in the
    reference's shipped DB (GTX TITAN ≈ 329 GFLOP/s fp32; see
    ``TITAN_MATMUL_GFLOPS``)."""
    from veles_tpu.ops.benchmark import (BENCH_CHAIN, BENCH_SIZE,
                                         estimate_device_power)

    kind = _device_kind()
    sec, gflops = estimate_device_power()
    peak = _peak_flops(kind)
    label = ("Device power rating (%dx%d^3 bf16 chain)"
             % (BENCH_CHAIN, BENCH_SIZE))
    # gflops IS the chain's sustained rate for these same constants, so
    # the physics gate needs no second flops derivation
    if sec <= 0 or (peak and gflops * 1e9 > peak * 1.05):
        print(_dumps({
            "metric": label,
            "value": 0.0, "unit": "GFLOP/s", "vs_baseline": None,
            "error": "timing failed physics check: %.3e s/chain"
                     % sec, "device_kind": kind}))
        return
    vs = gflops / TITAN_MATMUL_GFLOPS
    if not 0.0 < vs <= MAX_POWER_RATIO:
        print(_dumps({
            "metric": label,
            "value": 0.0, "unit": "GFLOP/s", "vs_baseline": None,
            "error": "vs_baseline %.1f outside (0, %.0f]"
                     % (vs, MAX_POWER_RATIO),
            "device_kind": kind}))
        return
    print(_dumps({
        "metric": label,
        "value": round(gflops, 1), "unit": "GFLOP/s",
        "vs_baseline": round(vs, 2),
        "sec_per_chain": round(sec, 6),
        "baseline": "GTX TITAN float P0, 3001^2 matmul in 0.1642 s "
                    "= %.0f GFLOP/s (reference devices/"
                    "device_infos.json) — rate-vs-rate comparison"
                    % TITAN_MATMUL_GFLOPS,
        "device_kind": kind}))


def stage_alexnet():
    from veles_tpu.samples import alexnet
    batch = int(os.environ.get("BENCH_ALEXNET_BATCH", "256"))
    # non-default batches get their own metric name (matching the
    # alexnet512 stage's convention) so a scaling point can never
    # be read as the canonical batch-256 headline
    if batch == 256:
        name = "AlexNet fused train throughput per chip (bf16)"
    else:
        name = ("AlexNet fused train throughput per chip "
                "(bf16, batch %d)" % batch)
    # the kernels= column: which backward-kernel mode the run used
    # (root.common.engine.kernels — the fused Pallas dW/db/dX family
    # vs the dense XLA reference), so AlexNet lines are only ever
    # compared against same-mode runs
    from veles_tpu.config import root
    _conv_stage(
        name, alexnet.LAYERS, alexnet.INPUT_SHAPE, 1000, batch=batch,
        steps=10, vs=V100_ALEXNET_IMG_PER_SEC,
        extra={"kernels": str(root.common.engine.get("kernels",
                                                     "auto"))})


def _epoch_loop(metric, step_fn, params, data, labels, n, batch,
                extra=None, shuffle=True):
    """Shared one-program-epoch stopwatch: jit(epoch_runner) with
    params donation, warm + real sync, then epochs paced by a per-epoch
    metric fetch — the honest cost a Decision-style consumer pays each
    epoch (async dispatch alone would enqueue thousands)."""
    import jax
    from veles_tpu.ops.timing import host_fetch, probe_of
    from veles_tpu.znicz.fused_graph import epoch_runner

    steps = n // batch
    epoch_fn = jax.jit(epoch_runner(step_fn, n, batch,
                                    shuffle=shuffle),
                       donate_argnums=(0,))
    # committed placement: uncommitted inputs + committed outputs
    # would re-key the jit cache on the second call (fused_unit._build
    # has the full story)
    params = jax.device_put(params, jax.devices()[0])
    params, m = epoch_fn(params, data, labels, jax.random.key(0))
    host_fetch(probe_of(params, m))              # warm + real sync
    epochs = 0
    tic = time.perf_counter()
    while True:
        params, m = epoch_fn(params, data, labels,
                             jax.random.key(epochs + 1))
        host_fetch(probe_of(m, m))   # paced on EXECUTED epochs
        epochs += 1
        if time.perf_counter() - tic >= 3.0:
            break
    host_fetch(probe_of(params, m))              # bytes end the clock
    elapsed = time.perf_counter() - tic
    _emit(metric, elapsed / (epochs * steps), batch, None,
          extra=dict({"epochs_timed": epochs,
                      "steps_per_epoch": steps}, **(extra or {})))


def stage_mnist_epoch():
    """Whole-epoch-in-ONE-program MNIST (fused_graph.epoch_runner):
    device-resident u8 dataset, in-program permutation + gather +
    scale-normalize + train step via lax.scan — a single dispatch per
    epoch, so the e2e number cannot be bounded by host round-trips.
    Compare against ``mnist_u8``
    (synthetic batch) and ``mnist_e2e_u8`` (host-driven loader)."""
    import numpy

    import jax
    from veles_tpu import prng
    from veles_tpu.samples import mnist
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    n, batch = 65536, 8192
    rng = numpy.random.default_rng(0)
    data = jax.device_put(rng.integers(0, 256, (n, 784),
                                       dtype=numpy.uint8))
    labels = jax.device_put(rng.integers(0, 10, n).astype(numpy.int32))
    params, step_fn, _e, _a = lower_specs(
        mnist.LAYERS, (784,),
        input_norm=(numpy.float32(1 / 255.0), numpy.float32(0.0)))
    _epoch_loop("MNIST784 MLP one-program-epoch train throughput "
                "(u8-resident, in-program permute+gather)",
                step_fn, params, data, labels, n, batch)


def stage_alexnet_epoch():
    """AlexNet whole-epoch-in-ONE-program (the conv leg of the
    one-program-epoch design): u8 ImageNet-shaped dataset resident in
    HBM, in-program permutation + gather + scale-normalize + bf16 fused
    train step via ``lax.scan``.  One dispatch per epoch, so — unlike
    ``alexnet_e2e``'s host-driven loop — per-dispatch transport latency
    amortizes across the whole epoch."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.samples import alexnet
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    shape = alexnet.INPUT_SHAPE
    batch = int(os.environ.get("BENCH_ALEXNET_BATCH", "256"))
    n = int(os.environ.get("BENCH_ALEXNET_EPOCH_SAMPLES", "4096"))
    if os.environ.get("BENCH_ALEXNET_E2E_TINY"):  # CPU smoke of the path
        shape, n, batch = (67, 67, 3), 64, 16
    rng = numpy.random.default_rng(0)
    data = jax.device_put(rng.integers(0, 256, (n,) + shape,
                                       dtype=numpy.uint8))
    labels = jax.device_put(
        rng.integers(0, 1000, n).astype(numpy.int32))
    # remat OFF: batch-256 AlexNet activations fit this chip, and the
    # ~30% forward recompute was most of the "e2e gap" vs the
    # (remat-free) synthetic stage — apples to apples now.  Knob for
    # generations/batches that need the memory back; OOM degrades to
    # the remat build (exporting the knob so the later e2e stage in
    # this child measures the same program — the LM-stage pattern).
    remat = os.environ.get("BENCH_ALEXNET_REMAT", "0") == "1"

    def run(remat_mode):
        params, step_fn, _e, _a = lower_specs(
            alexnet.LAYERS, shape, compute_dtype=jnp.bfloat16,
            remat=remat_mode,
            input_norm=(numpy.float32(1 / 255.0), numpy.float32(0.0)))
        _epoch_loop("AlexNet one-program-epoch train throughput "
                    "(u8-resident, in-program permute+gather, bf16)"
                    + _batch_tag(batch, 256),
                    step_fn, params, data, labels, n, batch,
                    extra={"remat": remat_mode})

    fell_back = False
    try:
        run(remat)
    except _StageTimeout:
        raise                 # the ladder watchdog, never a fallback
    except Exception as exc:
        if remat:
            raise
        print("alexnet_epoch: remat-off failed (%s); retrying with "
              "remat" % type(exc).__name__, file=sys.stderr)
        fell_back = True
    if fell_back:
        # retry OUTSIDE the except block: the traceback would pin the
        # failed attempt's device buffers through the rebuild.  Export
        # the knob so every later AlexNet stage in this child measures
        # the same (remat) program regardless of ladder order — the
        # stage_alexnet_e2e / stage_transformer pattern
        os.environ["BENCH_ALEXNET_REMAT"] = "1"
        run(True)


def stage_alexnet_epoch_ab():
    """Sequential-gather A/B for the epoch program: the SAME epoch as
    ``alexnet_epoch`` but with an iota index stream — the only
    difference is gather locality + the permutation op, so
    (shuffled − sequential) is the measured cost of permuted gather
    and (sequential − steps × synthetic step) is the residual
    scan/epoch overhead.  Adjudicates the unexplained ms of the
    epoch-vs-synthetic gap (VERDICT r4 item 3).  Its OWN stage, so a
    watchdog cut can never cost the canonical epoch line, and the
    canonical leg's params are long freed."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.samples import alexnet
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    shape = alexnet.INPUT_SHAPE
    batch = int(os.environ.get("BENCH_ALEXNET_BATCH", "256"))
    n = int(os.environ.get("BENCH_ALEXNET_EPOCH_SAMPLES", "4096"))
    if os.environ.get("BENCH_ALEXNET_E2E_TINY"):  # CPU smoke
        shape, n, batch = (67, 67, 3), 64, 16
    rng = numpy.random.default_rng(0)
    data = jax.device_put(rng.integers(0, 256, (n,) + shape,
                                       dtype=numpy.uint8))
    labels = jax.device_put(
        rng.integers(0, 1000, n).astype(numpy.int32))
    remat = os.environ.get("BENCH_ALEXNET_REMAT", "0") == "1"
    params, step_fn, _e, _a = lower_specs(
        alexnet.LAYERS, shape, compute_dtype=jnp.bfloat16,
        remat=remat,
        input_norm=(numpy.float32(1 / 255.0), numpy.float32(0.0)))
    _epoch_loop("AlexNet one-program-epoch train throughput "
                "(sequential gather A/B leg, bf16)"
                + _batch_tag(batch, 256),
                step_fn, params, data, labels, n, batch,
                extra={"remat": remat, "shuffle": False},
                shuffle=False)


def stage_native_infer():
    """Native C++ engine serving throughput (HOST CPU, no Python/JAX
    in the inference loop): the MNIST MLP exported as an int8 package
    (precision=8, 1/4 the fp32 bytes) and executed by the libVeles-
    equivalent runtime — the reference's C++ serving story, measured.
    Deliberately labeled host-cpu so it can never be mistaken for a
    chip number."""
    import tempfile
    import time as _time

    import numpy

    from veles_tpu import native
    from veles_tpu.backends import NumpyDevice
    from veles_tpu.dummy import DummyWorkflow
    from veles_tpu.memory import Vector
    from veles_tpu.package import export_package
    from veles_tpu.znicz.all2all import All2AllSoftmax, All2AllTanh

    from veles_tpu import prng
    prng.seed_all(1234)
    rng = numpy.random.default_rng(0)
    batch = 1024
    x = rng.standard_normal((batch, 784)).astype(numpy.float32)
    wf = DummyWorkflow()
    dev = NumpyDevice()
    fc = All2AllTanh(wf, output_sample_shape=(100,))
    fc.input = Vector(x.copy())
    fc.initialize(dev)
    fc.numpy_run()
    sm = All2AllSoftmax(wf, output_sample_shape=(10,))
    sm.input = fc.output
    sm.initialize(dev)
    sm.numpy_run()
    with tempfile.TemporaryDirectory() as tdir:
        path = os.path.join(tdir, "mlp8.zip")
        export_package([fc, sm], path, precision=8,
                       with_stablehlo=False)
        sm.output.map_read()
        golden = numpy.array(sm.output.mem)
        with native.NativeWorkflow(path) as nwf:
            warm = nwf.run(x)                       # warm (arena init)
            # never rate an engine with silently wrong numerics:
            # int8 quantization may flip a handful of near-tie argmaxes
            # on random inputs, but more than 1% disagreement with the
            # fp32 golden means the dequantize path is broken
            flips = float((warm.argmax(-1) != golden.argmax(-1)).mean())
            if flips > 0.01:
                raise RuntimeError(
                    "native int8 predictions diverge from the fp32 "
                    "golden on %.1f%% of samples — refusing to publish "
                    "a throughput number" % (100 * flips))
            k = 0
            tic = _time.perf_counter()
            while _time.perf_counter() - tic < 2.0:
                nwf.run(x)
                k += 1
            elapsed = _time.perf_counter() - tic
    print(_dumps({
        "metric": "MNIST784 MLP native C++ engine inference "
                  "(int8 package)",
        "value": round(batch * k / elapsed, 1), "unit": "images/sec",
        "vs_baseline": None,
        "sec_per_batch": round(elapsed / k, 6), "batch": batch,
        "device_kind": "host-cpu (native engine)"}))


def stage_alexnet_e2e():
    """AlexNet through the REAL framework data path (the conv leg of
    VERDICT r3 item 3): a u8 ImageNet-shaped dataset resident in HBM,
    the FullBatchLoader's device gather per minibatch, in-step scale
    normalization, feeding the StandardWorkflow(fused=True) trainer's
    own jitted bf16 step.  Compare images/sec against the synthetic-
    batch ``alexnet`` line to see what the input pipeline costs."""
    import numpy

    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.backends import AutoDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.samples import alexnet
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    shape = alexnet.INPUT_SHAPE
    n_samples = int(os.environ.get("BENCH_ALEXNET_E2E_SAMPLES", "4096"))
    if os.environ.get("BENCH_ALEXNET_E2E_TINY"):  # CPU smoke of the path
        shape, n_samples = (67, 67, 3), 32

    class SyntheticImageNetLoader(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.default_rng(0)
            self.original_data.mem = rng.integers(
                0, 256, (n_samples,) + shape, dtype=numpy.uint8)
            self.original_labels = [
                int(v) for v in rng.integers(0, 1000, n_samples)]
            self.class_lengths[:] = [0, 0, n_samples]

    prng.seed_all(1234)
    batch = int(os.environ.get("BENCH_ALEXNET_BATCH", "256"))
    if os.environ.get("BENCH_ALEXNET_E2E_TINY"):
        batch = 8

    def run(remat_mode):
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: SyntheticImageNetLoader(
                w, minibatch_size=batch, native_device_dtype=True,
                normalization_type="scale"),
            layers=[{**spec} for spec in alexnet.LAYERS],
            decision_config={"max_epochs": 10 ** 6},
            fused=True,
            # remat off for apples-to-apples with the synthetic stage
            # (see stage_alexnet_epoch's knob comment)
            fused_config={"compute_dtype": jnp.bfloat16,
                          "remat": remat_mode})
        wf.launcher = DummyLauncher()
        wf.initialize(device=AutoDevice())
        trainer = wf.fused_trainer
        trainer._build()
        _e2e_loop("AlexNet end-to-end workflow throughput "
                  "(u8-resident loader+gather+fused bf16 step)"
                  + _batch_tag(batch, 256),
                  wf.loader, trainer._params_, trainer._step_,
                  extra={"remat": remat_mode})

    remat = os.environ.get("BENCH_ALEXNET_REMAT", "0") == "1"
    fell_back = False
    try:
        run(remat)
    except _StageTimeout:
        raise                 # the ladder watchdog, never a fallback
    except Exception as exc:
        if remat:
            raise
        print("alexnet_e2e: remat-off failed (%s); retrying with "
              "remat" % type(exc).__name__, file=sys.stderr)
        fell_back = True
    if fell_back:
        # retry OUTSIDE the except block (traceback pins the failed
        # attempt's device buffers); the env export keeps the LATER
        # alexnet_epoch stage in this child on the same program
        os.environ["BENCH_ALEXNET_REMAT"] = "1"
        run(True)


def stage_alexnet512():
    """Batch sweep point: the same flagship at batch 512."""
    from veles_tpu.samples import alexnet
    _conv_stage(
        "AlexNet fused train throughput per chip (bf16, batch 512)",
        alexnet.LAYERS, alexnet.INPUT_SHAPE, 1000, batch=512,
        steps=10, vs=V100_ALEXNET_IMG_PER_SEC)


def stage_profile():
    """AlexNet step-time breakdown -> PROFILE.md.  The profiler's
    human-readable report goes to stdout next to the records; the JSON
    marker line records that the artifact was produced on this
    device."""
    from veles_tpu.scripts import profile_step
    args = ["--sample", "alexnet", "--batch", "256",
            "--out", "PROFILE.md"]
    # ~12 extra prefix compiles: opt-in, so a lean run still reaches
    # the final headline stage inside its budget
    if os.environ.get("BENCH_PER_LAYER") == "1":
        args.append("--per-layer")
    profile_step.main(args)
    print(_dumps({
        "metric": "AlexNet step profile artifact (PROFILE.md)",
        "value": 1.0, "unit": "artifact", "vs_baseline": None,
        "device_kind": _device_kind()}))


def stage_profile_lm():
    """GPT LM step-time breakdown -> PROFILE_LM.md (the fwd/bwd split
    + analytic-FLOPs table).  Profiles the SAME config the ``transformer`` stage measures
    (BENCH_LM_* knobs are read by profile_step's transformer build;
    the stage's OOM fallback exports its effective remat back into
    the env before this stage runs)."""
    if os.environ.get("BENCH_LM_TINY"):
        # the tiny smoke measures TINY; profiling the full 512x8
        # model here would describe a different program than the line
        print(_dumps({
            "metric": "GPT LM step profile artifact (PROFILE_LM.md)",
            "value": 0.0, "unit": "artifact", "vs_baseline": None,
            "skipped": "BENCH_LM_TINY measures the TINY config",
            "device_kind": _device_kind()}))
        return
    from veles_tpu.scripts import profile_step
    profile_step.main(["--sample", "transformer",
                       "--batch", os.environ.get("BENCH_LM_BATCH",
                                                 "32"),
                       "--out", "PROFILE_LM.md"])
    print(_dumps({
        "metric": "GPT LM step profile artifact (PROFILE_LM.md)",
        "value": 1.0, "unit": "artifact", "vs_baseline": None,
        "device_kind": _device_kind()}))


def stage_attn_bwd():
    """Flash-attention BACKWARD A/B, isolated: the Pallas two-kernel
    backward at several block sizes vs the XLA scan fallback, at the
    LM stage's attention shape — the direct evidence for VERDICT r5
    item 2 (the full-step LM line only shows the backward through a
    25/75 blend).  Emits the best-Pallas-vs-XLA speedup + TFLOP/s."""
    import jax.numpy as jnp
    from veles_tpu.config import root
    from veles_tpu.ops.benchmark import (_cand_key,
                                         _sweep_attention_bwd_shape)

    tiny = bool(os.environ.get("BENCH_ATTN_TINY"))
    if tiny:                # CPU smoke: interpret mode exercises the
        batch = 32          # keep the canonical un-suffixed metric
        shape = (1, 64, 2, 8)        # PALLAS leg too, not just XLA
        cands = ((8, 8), None)
        # the LM stage's attention shape, batch matched to the LM line
        # this stage exists to explain
    else:
        batch = int(os.environ.get("BENCH_LM_BATCH", "32"))
        shape = (batch, 1024, 8, 64)
        cands = ((128, 128), (256, 256), (256, 512), (512, 256), None)
    prior = root.common.engine.get("interpret", False)
    if tiny:
        root.common.engine.interpret = True
    try:
        out, failed, flops = _sweep_attention_bwd_shape(
            shape, jnp.bfloat16, cands, runs=2, causal=True,
            dtype_name="bfloat16")
    finally:
        root.common.engine.interpret = prior
    xla = out.get(None)
    pallas = {c: v for c, v in out.items() if c is not None}
    best = min(pallas, key=lambda c: pallas[c][0]) if pallas else None
    best_sec = pallas[best][0] if best else None
    rec = {
        "metric": "flash-attention backward A/B (Pallas vs XLA scan)"
                  + _batch_tag(batch, 32),
        "value": round(xla[0] / best_sec, 4)
                 if (xla and best_sec) else 0.0,
        "unit": "x", "vs_baseline": None,
        "shape": list(shape),
        "pallas_blocks": list(best) if best else None,
        "pallas_tflops": round(flops / best_sec / 1e12, 2)
                          if best_sec else None,
        "xla_scan_tflops": round(flops / xla[0] / 1e12, 2)
                            if xla else None,
        "device_kind": _device_kind()}
    # a failed leg must never read as a measured 0x: the sweep records
    # every candidate that raised, by name, with its error's first line
    if failed:
        rec["failed"] = {_cand_key(c): msg for c, msg in failed.items()}
    if not pallas and not xla:
        rec["error"] = "no candidate completed"
    elif not pallas:
        rec["error"] = "pallas leg never completed (XLA-only)"
    elif not xla:
        rec["error"] = "xla leg never completed (Pallas-only)"
    print(_dumps(rec))


def stage_s2d():
    """Space-to-depth conv1 A/B: the same
    stride-4 11x11 conv timed with and without the s2d rewrite, in one
    program each via the in-program marginal stopwatch."""
    from veles_tpu.ops.benchmark import measure_s2d_ab

    batch = 256
    flops = 2.0 * batch * 55 * 55 * 96 * 11 * 11 * 3
    secs = measure_s2d_ab(batch=batch)
    print(_dumps({
        "metric": "AlexNet conv1 space-to-depth speedup (A/B)",
        "value": round(secs["base_sec"] / secs["s2d_sec"], 4),
        "unit": "x",
        "vs_baseline": None,
        "base_ms": round(secs["base_sec"] * 1e3, 4),
        "s2d_ms": round(secs["s2d_sec"] * 1e3, 4),
        "tflops_effective_s2d": round(
            flops / secs["s2d_sec"] / 1e12, 2),
        "device_kind": _device_kind()}))


STAGES = {
    # name: (function, cap in seconds for the SIGALRM watchdog)
    "mnist": (stage_mnist, 150),
    "mnist_bf16": (stage_mnist_bf16, 150),
    "mnist_u8": (stage_mnist_u8, 150),
    "mnist_e2e": (stage_mnist_e2e, 240),
    "mnist_e2e_u8": (stage_mnist_e2e_u8, 240),
    "mnist_wf": (stage_mnist_wf, 240),
    "mnist_wf_epoch": (stage_mnist_wf_epoch, 240),
    "ae_wf_epoch": (stage_ae_wf_epoch, 240),
    "mnist_wf_eager": (stage_mnist_wf_eager, 300),
    "mnist_wf_eager_devloader": (stage_mnist_wf_eager_devloader, 300),
    "mnist_wf_eager_epoch": (stage_mnist_wf_eager_epoch, 300),
    "mnist_wf_health": (stage_mnist_wf_health, 300),
    "mnist_wf_slave": (stage_mnist_wf_slave, 300),
    "mnist_pod": (stage_mnist_pod, 420),
    "mnist_pod_epoch": (stage_mnist_pod_epoch, 420),
    "mnist_pod_pp": (stage_mnist_pod_pp, 300),
    "moe_pod": (stage_moe_pod, 300),
    "cifar": (stage_cifar, 210),
    "stl10": (stage_stl10, 240),
    "ae": (stage_ae, 150),
    "kohonen": (stage_kohonen, 150),
    "lstm": (stage_lstm, 180),
    "transformer": (stage_transformer, 240),
    "transformer_lm_train": (stage_transformer_lm_train, 400),
    "transformer_gen": (stage_transformer_gen, 300),
    "power": (stage_power, 240),
    "alexnet": (stage_alexnet, 600),
    "alexnet_e2e": (stage_alexnet_e2e, 450),
    "alexnet_epoch": (stage_alexnet_epoch, 450),
    "alexnet_epoch_ab": (stage_alexnet_epoch_ab, 450),
    "native_infer": (stage_native_infer, 180),
    "mnist_epoch": (stage_mnist_epoch, 180),
    "alexnet512": (stage_alexnet512, 600),
    "profile": (stage_profile, 600),
    "profile_lm": (stage_profile_lm, 600),
    "s2d": (stage_s2d, 300),
    "attn_bwd": (stage_attn_bwd, 400),
}


#: The one stage order: cheap -> heavy, the AlexNet headline LAST.
#: ``BENCH_STAGES`` selects a subset (run in this order).
STAGE_ORDER = ("mnist", "mnist_bf16", "mnist_u8", "mnist_e2e",
               "mnist_e2e_u8", "mnist_epoch", "mnist_wf",
               "mnist_wf_epoch", "ae_wf_epoch", "mnist_wf_eager",
               "mnist_wf_eager_devloader", "mnist_wf_eager_epoch",
               "mnist_wf_health",
               "mnist_wf_slave", "mnist_pod", "mnist_pod_epoch",
               "mnist_pod_pp", "moe_pod",
               "cifar", "stl10", "ae",
               "kohonen",
               "lstm", "transformer", "transformer_lm_train",
               "transformer_gen", "profile_lm",
               "attn_bwd", "power",
               "native_infer", "s2d", "alexnet512", "alexnet_e2e",
               "alexnet_epoch", "alexnet_epoch_ab", "profile", "alexnet")


def _selected_stages(spec):
    """``BENCH_STAGES`` value -> stage names in :data:`STAGE_ORDER`.
    Empty/None selects every stage; an unknown name raises (a typo
    must not read as "ran and passed")."""
    if not spec:
        return STAGE_ORDER
    only = {name.strip() for name in spec.split(",") if name.strip()}
    unknown = sorted(only - set(STAGE_ORDER))
    if unknown:
        raise ValueError("BENCH_STAGES: unknown stage(s) %s (have: %s)"
                         % (", ".join(unknown), ", ".join(STAGE_ORDER)))
    return tuple(name for name in STAGE_ORDER if name in only)


def run_stages(names, budget, scale=1.0):
    """Run ``names`` in this process, each under a best-effort SIGALRM
    watchdog at its (scaled) cap; every stage prints its own records.
    Returns ``{stage: reason}`` for each stage that raised, was cut at
    its cap, or never started because the budget ran out."""
    import signal

    deadline = time.monotonic() + budget

    def _alarm(_sig, _frame):
        raise _StageTimeout()

    # a stage stuck in *Python* gets cut at its cap so later stages
    # still run; a hang inside one blocking C call can defer the alarm
    # until that call returns
    can_alarm = hasattr(signal, "SIGALRM")
    if can_alarm:
        signal.signal(signal.SIGALRM, _alarm)
    failed = {}
    for name in names:
        remaining = deadline - time.monotonic()
        if remaining < 45:
            failed[name] = "not run: the %ds budget was spent" % budget
            continue
        cap = STAGES[name][1] * scale
        try:
            if can_alarm:
                signal.alarm(max(1, int(min(cap, remaining))))
            STAGES[name][0]()
        except _StageTimeout:
            failed[name] = "cut at its %ds cap" % min(cap, remaining)
        except Exception as exc:  # noqa: BLE001 - reported, exit != 0
            import traceback
            traceback.print_exc(file=sys.stderr)
            failed[name] = "%s: %s" % (type(exc).__name__, exc)
        finally:
            if can_alarm:
                signal.alarm(0)
        if name in failed:
            print("bench stage %s FAILED: %s" % (name, failed[name]),
                  file=sys.stderr)
        sys.stdout.flush()
    return failed


def main():
    """Run the selected stages; returns the process exit code."""
    import jax

    budget = float(os.environ.get("BENCH_BUDGET_SEC", "2600"))
    scale = float(os.environ.get("BENCH_TIMEOUT_SCALE", "1"))
    if scale <= 0:
        raise ValueError("BENCH_TIMEOUT_SCALE must be positive")
    names = _selected_stages(os.environ.get("BENCH_STAGES"))
    platform = jax.devices()[0].platform
    if platform != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        print("bench.py measures on a TPU and JAX found %r (%s); for a "
              "CPU rehearsal set JAX_PLATFORMS=cpu — its numbers are "
              "not device metrics" % (platform,
                                      jax.devices()[0].device_kind),
              file=sys.stderr)
        return 2
    from veles_tpu.backends import enable_compilation_cache
    cache = enable_compilation_cache(platform=platform)
    print("compile cache: %s" % (cache or "off (cpu)"), file=sys.stderr)
    stage_probe()
    failed = run_stages(names, budget, scale)
    if failed:
        print("bench.py: %d of %d selected stage(s) failed: %s"
              % (len(failed), len(names), ", ".join(failed)),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
