#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that veles_tpu still starts on the chip.

``python3 chip_smoke.py`` drives the system's two main paths once, in THIS
process, through the entry points a user would call, on one TPU chip:

* ``cli_train``     — the README's first command, in-process:
  ``veles_tpu.__main__.Main`` on ``veles_tpu.samples.mnist``, device left to
  the default ``auto``, a bounded run on the sample's synthetic stand-in data;
* ``alexnet_train`` — the published AlexNet stack (227x227x3, 96..384 kernels,
  4096-wide fc, 1000 classes) at batch 256, bf16 compute / f32 master weights,
  through ``StandardWorkflow(fused=True)`` over a device-resident u8
  ``FullBatchLoader``;
* ``lm_serve``      — ``samples.transformer.CONFIG`` (dim 1024 x 16 heads x 12
  layers, vocab 32000, seq 2048), seeded random bf16 weights, deployed as a
  ``GenerativeEngine`` through ``ModelRegistry.deploy_generative`` behind a
  real ``ServingServer`` on a loopback port, answering ``POST /generate/lm``
  requests in the contiguous and in the paged + chunked-prefill cache modes.

``python3 chip_smoke.py --chips 4`` runs ONLY the multi-chip path and what it
is compared with: the MNIST workflow under ``PodRuntime`` on a ``{"data": 4}``
mesh against the same seed and steps on one device.

It prints one JSON object per line: a ``setup`` line (device, peak-table row,
compile-cache directory), one line per phase (seconds, compile seconds, steps
or tokens, the backend each kernel family resolved to), and LAST
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Every time or rate it prints is a SMOKE value, not a measurement of record.
On any failure it prints the reason and exits non-zero without the last line.
There is no CPU mode: if JAX finds no TPU it fails at once.  (The phase
functions take sizes as arguments so that ``tests/test_chip_smoke.py`` can
rehearse them at tiny shapes on the CPU.)
"""

import argparse
import concurrent.futures
import json
import sys
import time
import urllib.request


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def check(condition, message):
    if not condition:
        raise SmokeFailure(message)


def emit(record):
    print(json.dumps(record), flush=True)


def device_summary():
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class CompileMeter(object):
    """Sums JAX's own compile events: seconds spent in (or fetching from
    the persistent cache instead of) backend compilation, and the
    persistent cache's hits and misses.  One per process — JAX keeps its
    listeners for good."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(name, fn, meter):
    """Run one phase, print its line; a failure prints the reason and
    re-raises as SystemExit(1) — the script never carries on."""
    before = meter.snapshot()
    tic = time.perf_counter()
    try:
        result = fn()
    except Exception as exc:  # noqa: BLE001 - reported, then exit 1
        import traceback
        traceback.print_exc(file=sys.stderr)
        emit({"phase": name, "ok": False,
              "error": "%s: %s" % (type(exc).__name__, exc)})
        raise SystemExit(1)
    after = meter.snapshot()
    record = {"phase": name, "ok": True,
              "smoke_seconds": round(time.perf_counter() - tic, 3),
              "smoke_compile_seconds": round(after[0] - before[0], 3),
              "compile_cache_hits": after[1] - before[1],
              "compile_cache_misses": after[2] - before[2]}
    record.update(result)
    emit(record)
    return record


def _finite(value):
    import math
    return isinstance(value, (int, float)) and math.isfinite(value)


# ---------------------------------------------------------------------------
# cli_train
# ---------------------------------------------------------------------------

def phase_cli_train(epochs=2, expect_platform="tpu"):
    """``python -m veles_tpu veles_tpu.samples.mnist`` in-process, bounded
    to ``epochs`` Decision epochs (2 = validate, train one epoch,
    validate): the only thing the smoke adds to the README's command is
    the bound and the weight snapshot it needs for "the weights changed"."""
    import numpy

    from veles_tpu.__main__ import Main
    from veles_tpu.backends import BackendRegistry
    from veles_tpu.ops import resolved_backend

    class BoundedMain(Main):
        initial_weights = None

        def _construct(self):
            super(BoundedMain, self)._construct()
            self.workflow.decision.max_epochs = epochs
            self.launcher.initialize()
            self.initial_weights = [
                numpy.array(fwd.weights.mem)
                for fwd in self.workflow.forwards]

    main = BoundedMain(["veles_tpu.samples.mnist", "--no-logo",
                        "-v", "warning"])
    rc = main.run()
    check(rc == 0, "Main.run() returned %r" % (rc,))
    wf = main.workflow
    device = wf.device
    check(isinstance(device, BackendRegistry.backends[expect_platform]),
          "backend 'auto' resolved to %r, want the %s device"
          % (device, expect_platform))
    check(device.jax_devices[0].platform == expect_platform,
          "workflow device runs on %r" % device.jax_devices[0].platform)
    loss = float(wf.evaluator.loss)
    check(_finite(loss), "last minibatch loss is %r" % loss)
    results = wf.gather_results()
    errors = results["errors_pt"]
    check(_finite(errors["validation"]) and _finite(errors["train"]),
          "error rates are not finite: %r" % (errors,))
    moved = []
    for fwd, before in zip(wf.forwards, main.initial_weights):
        fwd.weights.map_read()
        after = numpy.array(fwd.weights.mem)
        check(numpy.isfinite(after).all(),
              "%s weights went non-finite" % fwd.name)
        moved.append(float(numpy.abs(after - before).max()))
    check(min(moved) > 0.0, "weights did not change: max |dw| %r" % moved)
    loader = wf.loader
    batch = int(loader.max_minibatch_size)
    n_in = int(numpy.prod(loader.minibatch_data.shape[1:]))
    hidden = int(wf.forwards[0].weights.mem.size // n_in)
    return {
        "device": repr(device),
        "epochs": int(results["Total epochs"]),
        "train_minibatches": int(results["Total epochs"])
        * -(-int(loader.class_lengths[2]) // batch),
        "last_loss": round(loss, 6),
        "errors_pt": errors,
        "max_abs_weight_change": moved,
        "kernel_backends": {
            "gemm": resolved_backend("gemm", "float32",
                                     (batch, n_in, hidden)),
            "gd": resolved_backend("gd", "float32",
                                   (batch, n_in, hidden))}}


# ---------------------------------------------------------------------------
# alexnet_train
# ---------------------------------------------------------------------------

def phase_alexnet_train(layers=None, input_shape=None, n_classes=1000,
                        batch=256, train_batches=5, seed=0,
                        expect_platform="tpu"):
    """AlexNet at full width through StandardWorkflow(fused=True): a seeded
    synthetic ImageNet-shaped u8 set resident on the device, one
    validation minibatch + ``train_batches`` train minibatches per epoch,
    run for validate / train / validate."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu import prng
    from veles_tpu.backends import AutoDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.ops import resolved_backend
    from veles_tpu.samples import alexnet
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    layers = layers or alexnet.LAYERS
    shape = tuple(input_shape or alexnet.INPUT_SHAPE)
    n_valid, n_train = batch, batch * train_batches

    class SyntheticImageNetLoader(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.default_rng(seed)
            self.original_data.mem = rng.integers(
                0, 256, (n_valid + n_train,) + shape, dtype=numpy.uint8)
            self.original_labels = [
                int(v) for v in rng.integers(0, n_classes,
                                             n_valid + n_train)]
            self.class_lengths[:] = [0, n_valid, n_train]

    prng.seed_all(1234)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: SyntheticImageNetLoader(
            w, minibatch_size=batch, native_device_dtype=True,
            normalization_type="scale"),
        layers=[{**spec} for spec in layers],
        decision_config={"max_epochs": 2},
        fused=True,
        fused_config={"compute_dtype": jnp.bfloat16})
    wf.launcher = DummyLauncher()
    wf.initialize(device=AutoDevice())
    trainer = wf.fused_trainer
    steps = {"train": 0, "eval": 0, "losses": []}
    inner_run = trainer.run

    def counting_run():
        inner_run()
        if int(wf.loader.minibatch_class) == 2:
            steps["train"] += 1
            steps["losses"].append(float(trainer.loss_value))
        else:
            steps["eval"] += 1

    trainer.run = counting_run
    wf.run()
    check(steps["train"] >= train_batches,
          "took %d train minibatches, want >= %d"
          % (steps["train"], train_batches))
    check(steps["eval"] >= 1, "no evaluation pass ran")
    check(all(_finite(v) for v in steps["losses"]),
          "train losses are not finite: %r" % steps["losses"])
    device = wf.device
    chip = device.jax_devices[0]
    check(chip.platform == expect_platform,
          "workflow device runs on %r" % chip.platform)
    leaves = jax.tree.leaves(trainer._params_)
    check(leaves and all(leaf.devices() == {chip} for leaf in leaves),
          "fused params are not all committed to %s" % chip)
    check(all(bool(jnp.isfinite(leaf).all()) for leaf in leaves),
          "fused params went non-finite")
    compiles = {"step": trainer._step_._cache_size(),
                "eval": trainer._eval_._cache_size()}
    check(compiles == {"step": 1, "eval": 1},
          "want exactly one compile per program, got %r" % compiles)
    fc = [spec for spec in layers if "all2all" in spec["type"]]
    return {
        "input": list(shape), "batch": batch, "classes": n_classes,
        "compute_dtype": "bfloat16", "master_dtype": str(leaves[0].dtype),
        "train_minibatches": steps["train"],
        "eval_minibatches": steps["eval"],
        "losses": [round(v, 4) for v in steps["losses"]],
        "compiles": compiles,
        "params_on": str(chip),
        "kernel_backends": {
            "conv": "xla (lax.conv_general_dilated)",
            "gemm": resolved_backend(
                "gemm", "bfloat16",
                (batch, 4096, fc[0]["->"]["output_sample_shape"]))
            if fc else None}}


# ---------------------------------------------------------------------------
# lm_serve
# ---------------------------------------------------------------------------

#: (prompt tokens, new tokens) of the smoke's requests: mixed lengths,
#: 16..1024 in, 32..128 out
LM_REQUESTS = ((16, 32), (48, 64), (100, 128), (200, 48), (333, 96),
               (512, 32), (777, 64), (1024, 128))


#: loopback only: never through a proxy the environment may name
_HTTP = urllib.request.build_opener(urllib.request.ProxyHandler({}))


def _post_json(url, body, timeout):
    request = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with _HTTP.open(request, timeout=timeout) as reply:
        return reply.status, json.loads(reply.read())


def _serve_session(model, params, prompts, budgets, slots, max_seq,
                   buckets, seed, timeout):
    """Deploy one engine (cache mode from ``root.common.gen``), answer
    every request over loopback HTTP from threads of this process, and
    take the engine down again.  Returns the session's facts."""
    from veles_tpu import prof
    from veles_tpu.gen import GenerativeEngine
    from veles_tpu.serve import ModelRegistry, ServingServer

    engine = GenerativeEngine(model, params=params, max_slots=slots,
                              max_seq=max_seq, prefill_buckets=buckets,
                              seed=seed)
    registry = ModelRegistry()
    server = ServingServer(registry=registry, port=0,
                           request_timeout=timeout)
    try:
        registry.deploy_generative("lm", engine)
        server.start()
        warm_compiles = engine.compile_count
        recompiles0 = prof.ledger.recompiles
        url = "http://127.0.0.1:%d/generate/lm" % server.port
        tic = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            futures = [
                pool.submit(_post_json, url,
                            {"tokens": prompt, "max_new_tokens": budget},
                            timeout)
                for prompt, budget in zip(prompts, budgets)]
            replies = [future.result() for future in futures]
        seconds = time.perf_counter() - tic
        streams = []
        for (status, payload), budget in zip(replies, budgets):
            check(status == 200, "POST /generate/lm -> %s %r"
                  % (status, payload))
            check(len(payload["tokens"]) == budget,
                  "got %d tokens for a budget of %d"
                  % (len(payload["tokens"]), budget))
            streams.append(payload["tokens"])
        check(engine.compile_count == warm_compiles,
              "compile_count moved after warmup: %d -> %d"
              % (warm_compiles, engine.compile_count))
        check(prof.ledger.recompiles == recompiles0,
              "the recompile sentinel flagged a steady-state compile")
        with _HTTP.open("http://127.0.0.1:%d/metrics" % server.port,
                        timeout=timeout) as reply:
            metrics = reply.read().decode()
        gauges = sorted({line.split("{")[0].split(" ")[0]
                         for line in metrics.splitlines()
                         if line.startswith("veles_serve_")})
        check(any(name.startswith("veles_serve_gen") for name in gauges),
              "no veles_serve_gen* gauge on /metrics: %r" % gauges[:8])
        decode_text = engine._decode_executable()[0].as_text()
        return {"kv": engine.kv_mode, "streams": streams,
                "prefill_chunk": engine.prefill_chunk,
                "programs": warm_compiles,
                "decode_has_tpu_custom_call":
                "tpu_custom_call" in decode_text,
                "smoke_seconds": round(seconds, 3),
                "gauges": len(gauges)}
    finally:
        server.stop()
        engine.close()


def _logit_gap(model, params, stream, tok_a, tok_b):
    """|logit[tok_a] - logit[tok_b]| after ``stream`` through the dense
    calibration forward — how flat the logits were where two greedy
    streams parted."""
    logits = model.calibration_logits(params, stream)
    return float(abs(logits[tok_a] - logits[tok_b]))


def phase_lm_serve(cfg=None, requests=LM_REQUESTS, slots=8, max_seq=None,
                   buckets=(32, 256, 1024), chunk=256, block_size=16,
                   seed=0, timeout=600.0):
    """The generative serving path at full width, two sessions: the
    default contiguous cache, then ``root.common.gen.kv="paged"`` with
    a ``prefill_chunk``; their greedy streams are compared."""
    import numpy

    import jax
    import jax.numpy as jnp
    from veles_tpu.config import root
    from veles_tpu.gen import TransformerGenModel
    from veles_tpu.ops import resolved_backend
    from veles_tpu.samples import transformer

    cfg = dict(cfg or transformer.CONFIG)
    max_seq = int(max_seq or cfg["seq_len"])
    model = TransformerGenModel(cfg, compute_dtype=jnp.bfloat16)
    params = jax.tree.map(
        lambda leaf: numpy.asarray(leaf).astype(jnp.bfloat16),
        transformer.init_params(cfg, seed=seed))
    rng = numpy.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab"], n).tolist()
               for n, _new in requests]
    budgets = [new for _n, new in requests]

    sessions = []
    gen_cfg = root.common.gen
    saved = {key: gen_cfg.get(key, None)
             for key in ("kv", "prefill_chunk", "block_size")}
    try:
        sessions.append(_serve_session(
            model, params, prompts, budgets, slots, max_seq, buckets,
            seed, timeout))
        gen_cfg.kv = "paged"
        gen_cfg.prefill_chunk = chunk
        gen_cfg.block_size = block_size
        sessions.append(_serve_session(
            model, params, prompts, budgets, slots, max_seq, buckets,
            seed, timeout))
    finally:
        for key, value in saved.items():
            if value is None:
                gen_cfg.__dict__.pop(key, None)
            else:
                setattr(gen_cfg, key, value)
    check([s["kv"] for s in sessions] == ["contiguous", "paged"],
          "sessions ran in cache modes %r" % [s["kv"] for s in sessions])

    decode_backend = resolved_backend("decode_attention", "bfloat16",
                                      (slots, 1, model.heads,
                                       model.head_dim))
    for session in sessions:
        check(session["decode_has_tpu_custom_call"]
              == (decode_backend == "pallas"),
              "%s decode program: dispatch says %s but tpu_custom_call "
              "present is %s" % (session["kv"], decode_backend,
                                 session["decode_has_tpu_custom_call"]))

    # greedy parity between the two cache modes: the first token of
    # every request must agree; with random weights the logits are flat,
    # so a later argmax may flip with bf16 reduction order — reported,
    # with the logit gap at the first differing step, never hidden
    cont, paged = sessions[0]["streams"], sessions[1]["streams"]
    equal = total = 0
    divergence = None
    for index, (a, b) in enumerate(zip(cont, paged)):
        check(a[0] == b[0],
              "request %d: first generated token differs between the "
              "cache modes (%d vs %d)" % (index, a[0], b[0]))
        total += len(a)
        same = [x == y for x, y in zip(a, b)]
        equal += sum(same)
        if divergence is None and not all(same):
            step = same.index(False)
            divergence = {
                "request": index, "step": step,
                "tokens": [a[step], b[step]],
                "logit_gap": round(_logit_gap(
                    model, params, prompts[index] + a[:step],
                    a[step], b[step]), 6)}
    for session in sessions:
        del session["streams"]
    longest = max(n for n, _new in requests)
    shape = (1, longest, model.heads, model.head_dim)
    return {
        "config": {k: cfg[k] for k in ("dim", "heads", "layers", "vocab",
                                       "seq_len")},
        "weights": "seeded random bfloat16 (seed %d)" % seed,
        "requests": len(requests),
        "prompt_tokens": sum(n for n, _new in requests),
        "new_tokens": total,
        "sessions": sessions,
        "first_tokens_equal": True,
        "equal_token_share": round(equal / total, 6),
        "first_divergence": divergence,
        "kernel_backends": {
            "flash_attention(prefill)": resolved_backend(
                "flash_attention", "bfloat16", shape),
            "chunk_attention": resolved_backend(
                "chunk_attention", "bfloat16",
                (1, chunk, model.heads, model.head_dim)),
            "decode_attention": decode_backend,
            "paged_decode_attention": decode_backend}}


# ---------------------------------------------------------------------------
# --chips N: the pod path and its one-device comparison
# ---------------------------------------------------------------------------

#: max |pod - one device| allowed on the final weights, and relative
#: on the last minibatch's loss: float32 training whose only difference
#: is the order of the gradient sum (4 partial sums all-reduced vs one
#: sum).  Error rates may differ by POD_ERR_PT points (two of the 1000
#: validation samples sitting on an argmax tie).
POD_TOLERANCE = 1e-4
POD_ERR_PT = 0.2


def phase_pod_train(chips=4, epochs=3, batch=1000):
    """The MNIST workflow under PodRuntime on a {"data": chips} mesh vs
    the same seed and steps on one device, in this process."""
    import numpy

    import jax
    from veles_tpu import prng
    from veles_tpu.backends import AutoDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.parallel.mesh import mesh_from_topology
    from veles_tpu.pod import PodRuntime, eval_metrics, train_epochs
    from veles_tpu.samples import mnist

    def make():
        prng.seed_all(1234)
        wf = mnist.create_workflow(
            launcher=DummyLauncher(), max_epochs=epochs,
            minibatch_size=batch, fused=False)
        wf.initialize(device=AutoDevice())
        return wf

    def weights_of(wf):
        out = []
        for fwd in wf.forwards:
            fwd.weights.map_read()
            out.append(numpy.array(fwd.weights.mem))
        return out

    # asking for `chips` devices with fewer attached is an error here,
    # never a quietly smaller mesh
    mesh = mesh_from_topology({"data": chips}, require=("data",))
    check(mesh.devices.size == chips,
          "mesh holds %d devices, want %d" % (mesh.devices.size, chips))
    wf = make()
    pod = PodRuntime(wf, mesh=mesh).install()
    for _ in train_epochs(wf, epochs):
        pass
    shards = wf.loader.minibatch_data.devmem.addressable_shards
    shard_devices = sorted({str(shard.device) for shard in shards})
    check(len(shard_devices) == chips,
          "the batch sits on %d distinct device(s): %r"
          % (len(shard_devices), shard_devices))
    texts = [seg._compiled.as_text() for seg in pod._segments
             if seg._compiled is not None]
    check(texts, "no pod segment program was compiled")
    check(any("all-reduce" in text for text in texts),
          "no all-reduce in any of the %d compiled pod segment "
          "programs" % len(texts))
    programs = {"+".join(seg.names): len(seg._compiled_cache)
                for seg in pod._segments}
    pod_metrics = dict(eval_metrics(wf), loss=float(wf.evaluator.loss))
    pod_weights = weights_of(wf)

    ref = make()
    for _ in train_epochs(ref, epochs):
        pass
    ref_metrics = dict(eval_metrics(ref), loss=float(ref.evaluator.loss))
    ref_weights = weights_of(ref)
    one = ref.loader.minibatch_data.devmem.addressable_shards
    check(len({str(shard.device) for shard in one}) == 1,
          "the comparison run is not on one device")

    diffs = [float(numpy.abs(a - b).max())
             for a, b in zip(pod_weights, ref_weights)]
    check(max(diffs) <= POD_TOLERANCE,
          "final weights differ by %r (> %g) between the %d-chip pod "
          "and one device" % (diffs, POD_TOLERANCE, chips))
    check(set(pod_metrics) == set(ref_metrics),
          "metric keys differ: %r vs %r" % (pod_metrics, ref_metrics))
    for key, a in sorted(pod_metrics.items()):
        b = ref_metrics[key]
        if key == "loss":
            agree = _finite(a) and abs(a - b) <= POD_TOLERANCE * max(
                1.0, abs(b))
        elif key.endswith("_pt"):
            agree = abs(a - b) <= POD_ERR_PT
        else:
            agree = a == b
        check(agree, "metric %s: pod %r vs one device %r" % (key, a, b))
    n_train = int(wf.loader.class_lengths[2])
    return {
        "mesh": dict(mesh.shape), "batch": batch,
        # the first Decision epoch is the initial validation pass
        "train_steps": (epochs - 1) * -(-n_train // batch),
        "batch_shard_devices": shard_devices,
        "programs_per_segment": programs,
        "all_reduce_in_compiled_step": True,
        "max_abs_weight_diff": diffs, "tolerance": POD_TOLERANCE,
        "pod_metrics": pod_metrics, "one_device_metrics": ref_metrics,
        "devices": len(jax.devices())}


# ---------------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(
        description="veles_tpu on-chip smoke (TPU only; no CPU mode)")
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): cli_train, alexnet_train, lm_serve on one "
             "chip; 4: ONLY the PodRuntime {data: 4} path and its "
             "one-device comparison")
    args = parser.parse_args(argv)
    try:
        import jax

        import veles_tpu  # noqa: F401 - the repo must sit next to us
        from veles_tpu import backends
    except ImportError as exc:
        print("chip_smoke.py needs the veles_tpu checkout it lives in "
              "and JAX: %s" % exc, file=sys.stderr)
        return 2
    try:
        device = device_summary()
    except RuntimeError as exc:
        print("chip_smoke.py: JAX found no device: %s"
              % str(exc).strip().splitlines()[0], file=sys.stderr)
        return 2
    if device["platform"] != "tpu":
        print("chip_smoke.py: JAX found %r (%s x%d), not a TPU — this "
              "script has no CPU mode" % (device["platform"],
                                          device["kind"], device["count"]),
              file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print("chip_smoke.py --chips %d: only %d TPU device(s) attached"
              % (args.chips, device["count"]), file=sys.stderr)
        return 2
    cache_dir = backends.enable_compilation_cache(
        platform=device["platform"])
    kind = device["kind"]
    peak = backends.peak_bf16_flops(kind)
    emit({"phase": "setup", "device": device,
          "peak_table_row": {
              "bf16_flops": peak,
              "int8_ops": backends.peak_int8_ops(kind),
              "hbm_bytes": backends.device_hbm_bytes(kind)},
          "compile_cache_dir": cache_dir,
          "jax": jax.__version__,
          "note": "every time below is a smoke value, not a measurement"})
    # the v5e must resolve to the v5e row, never to v5p's (the table
    # matches substrings: "v5p" is listed before "v5" for that reason)
    lowered = kind.lower()
    if "v5" in lowered and ("lite" in lowered or "v5e" in lowered):
        if peak != 197e12:
            print("chip_smoke.py: device kind %r resolved to a peak of "
                  "%r, want the v5e row (197e12)" % (kind, peak),
                  file=sys.stderr)
            return 1
    meter = CompileMeter()
    if args.chips == 1:
        run_phase("cli_train", phase_cli_train, meter)
        run_phase("alexnet_train", phase_alexnet_train, meter)
        run_phase("lm_serve", phase_lm_serve, meter)
    else:
        run_phase("pod_train",
                  lambda: phase_pod_train(chips=args.chips), meter)
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
