"""Step-time breakdown for a sample's fused train step.

Measures, with the honest timing discipline of ``ops/timing.py``
(result-derived host-fetch sync + marginal timing):

- forward only (inference apply)
- forward + backward (value_and_grad, no update)
- the full train step (forward + backward + momentum update)

and prints a markdown table with per-phase seconds, derived phase
costs, images/sec and MFU.  Run on the real chip:

    python -m veles_tpu.scripts.profile_step [--sample alexnet]
        [--batch 256] [--out <report.md>]

(ref: the per-unit timer table ``workflow.py:767-826`` and the
``--sync-run`` kernel-accuracy note ``accelerated_units.py:294-297`` —
this is the fused-step analogue.)
"""

import argparse
import sys


def _peak_flops(device_kind):
    from veles_tpu.backends import peak_bf16_flops
    return peak_bf16_flops(device_kind)


def build(sample, batch):
    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu import prng
    from veles_tpu.znicz.fused_graph import lower_specs

    prng.seed_all(1234)
    if sample == "transformer":
        # the GPT LM (bench stage config).  Keep --batch <= 32: the
        # chunked-CE live memory is O(batch * 128 * vocab) floats.
        # Honors the SAME BENCH_LM_REMAT / BENCH_LM_CE_CHUNK knobs as
        # bench.py's transformer stage, so the report describes the
        # exact program that stage measures.
        import os
        from veles_tpu.samples import transformer as T
        cfg = {"vocab": 32000, "dim": 512, "heads": 8, "layers": 8,
               "mlp_ratio": 4, "seq_len": 1024}
        params0 = T.init_params(cfg, seed=0)
        velocity = jax.tree.map(numpy.zeros_like, params0)
        raw_step = T.make_train_step(
            cfg,
            remat=os.environ.get("BENCH_LM_REMAT", "0") == "1",
            ce_chunk=int(os.environ.get("BENCH_LM_CE_CHUNK", "128")))

        def step(state, x, _labels):
            p, v = state
            p, v, metrics = raw_step(p, v, x)
            return (p, v), metrics

        def apply_fn(state, x):
            return T.apply_fn(state[0], x, cfg)

        train_flops = T.train_step_flops(cfg, batch)
        flops_overrides = {"full_step": train_flops,
                           "forward": train_flops / 3.0}
        x = jax.device_put(T.synthetic_tokens(cfg, batch))
        labels = jax.device_put(
            numpy.zeros((batch,), numpy.int32))
        return ((params0, velocity), step, apply_fn, x, labels,
                flops_overrides)
    if sample == "mnist":
        from __graft_entry__ import MNIST_LAYERS
        from veles_tpu.znicz.fused import (init_mlp_params,
                                           make_train_step, mlp_apply,
                                           _specs_static)
        params = init_mlp_params(784, MNIST_LAYERS)
        step = make_train_step(MNIST_LAYERS)
        static = _specs_static(MNIST_LAYERS)

        def apply_fn(p, x):
            return mlp_apply(p, x, static)
        shape = (784,)
        n_classes = 10
    else:
        mod = __import__("veles_tpu.samples.%s" % sample,
                         fromlist=[sample])
        layers = mod.LAYERS
        shape = getattr(mod, "INPUT_SHAPE", (32, 32, 3))
        n_classes = 1000 if sample == "alexnet" else 10
        params, step, _eval, apply_raw = lower_specs(
            layers, shape, compute_dtype=jnp.bfloat16)

        def apply_fn(p, x):
            return apply_raw(p, x, train=False)
    # recurrent samples: XLA cost analysis counts the T-step sequence
    # scan body ONCE, so FLOPs must come from the analytic closed form
    # (see measure_fused_step's inner-scan caveat)
    flops_overrides = None
    if sample == "mnist_rnn":
        from veles_tpu.znicz.rnn import lstm_fwd_flops, lstm_train_flops
        t, d = shape
        h = int(layers[0]["->"]["hidden_units"])
        flops_overrides = {
            "full_step": lstm_train_flops(batch, t, d, h,
                                          head_classes=n_classes),
            "forward": lstm_fwd_flops(batch, t, d, h,
                                      head_classes=n_classes),
        }
    rng = numpy.random.default_rng(0)
    x = jax.device_put(rng.standard_normal(
        (batch,) + tuple(shape)).astype(numpy.float32))
    labels = jax.device_put(
        rng.integers(0, n_classes, batch).astype(numpy.int32))
    return params, step, apply_fn, x, labels, flops_overrides


def measure_phases(params, step, apply_fn, x, labels, k=10,
                   min_seconds=None, flops_overrides=None):
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.timing import (cost_flops, inprogram_marginal,
                                      measure_fused_step)

    phases = {}
    overrides = flops_overrides or {}

    # full step: in-program two-trip-count marginal (the bench
    # methodology — see ops/timing.py round-3 notes)
    sec, flops = measure_fused_step(
        step, jax.device_put(params), x, labels, k=max(k, 8),
        flops_override=overrides.get("full_step"))
    phases["full_step"] = (sec, flops)

    # forward-only: the same in-program marginal over inference applies,
    # serialized (see _serialized_forward_unit)
    dparams = jax.device_put(params)
    unit = _serialized_forward_unit(lambda p, xx: apply_fn(p, xx),
                                    dparams)

    # flops of one apply: the loop program counts the body ONCE plus
    # the warmup inline iteration — both identical applies, so /2 via a
    # dedicated lowering is unnecessary; use a 1-apply compile instead
    if overrides.get("forward"):
        fwd_flops = overrides["forward"]
    else:
        fwd1 = jax.jit(lambda a, b: apply_fn(a, b)).lower(params, x)
        fwd_flops = cost_flops(fwd1.compile())
    sec_fwd = inprogram_marginal(unit, (x, jnp.float32(0.0)),
                                 k1=2, k2=max(k, 8))
    phases["forward"] = (sec_fwd, fwd_flops)
    return phases


def _serialized_forward_unit(apply2, dparams):
    """The forward-timing loop body shared by measure_phases and
    measure_per_layer: iterations are serialized by feeding a result
    scalar back into one input element (hoist/CSE defeat), and the
    probe abs-sums the WHOLE output — a single-element probe would let
    XLA slice the forward pass down to batch row 0."""
    import jax
    import jax.numpy as jnp

    def unit(carry):
        x_, s = carry
        lead = x_[(slice(0, 1),) * x_.ndim]
        x_ = jax.lax.dynamic_update_slice(
            x_, (lead + (s * 1e-30).astype(x_.dtype)),
            (0,) * x_.ndim)
        o = apply2(dparams, x_)
        return x_, jnp.sum(jnp.abs(o), dtype=jnp.float32)

    return unit


def measure_per_layer(sample, batch, k=8, full_forward=None):
    """Forward seconds per LAYER, by timing each prefix of the layer
    stack (prefix k minus prefix k-1) with the in-program marginal.
    Layer-spec samples only (lower_specs; recurrent samples are
    excluded by the caller — a prefix's cost-analysis FLOPs would
    undercount their inner scan bodies).  Returns
    ``[(label, sec, flops), ...]``; negative differences (two prefixes
    within mutual noise) are clamped to 0.

    ``full_forward``: the already-measured ``(sec, flops)`` of the
    FULL forward (measure_phases), reused for the final prefix so the
    whole stack is not re-timed and re-compiled.
    """
    import jax
    import jax.numpy as jnp
    import numpy

    from veles_tpu import prng
    from veles_tpu.ops.timing import cost_flops, inprogram_marginal
    from veles_tpu.znicz.fused_graph import lower_specs

    mod = __import__("veles_tpu.samples.%s" % sample,
                     fromlist=[sample])
    layers = mod.LAYERS
    shape = getattr(mod, "INPUT_SHAPE", (32, 32, 3))
    rng = numpy.random.default_rng(0)
    x = jax.device_put(rng.standard_normal(
        (batch,) + tuple(shape)).astype(numpy.float32))

    rows, prev_sec, prev_flops = [], 0.0, 0.0
    for n_layers in range(1, len(layers) + 1):
        if full_forward is not None and n_layers == len(layers):
            sec, flops = full_forward
            flops = flops or 0.0
        else:
            prng.seed_all(1234)
            params, _s, _e, apply_raw = lower_specs(
                layers[:n_layers], shape, compute_dtype=jnp.bfloat16)
            dparams = jax.device_put(params)
            unit = _serialized_forward_unit(
                lambda p, xx, _a=apply_raw: _a(p, xx, train=False),
                dparams)
            sec = inprogram_marginal(unit, (x, jnp.float32(0.0)),
                                     k1=2, k2=k)
            flops = cost_flops(jax.jit(
                lambda p, xx, _a=apply_raw: _a(p, xx, train=False)
            ).lower(params, x).compile()) or 0.0
        label = layers[n_layers - 1].get("type", "?")
        rows.append(("%02d %s" % (n_layers, label),
                     max(sec - prev_sec, 0.0),
                     max(flops - prev_flops, 0.0)))
        prev_sec, prev_flops = sec, flops
    return rows


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--sample", default="alexnet",
                        choices=("alexnet", "cifar10", "mnist",
                                 "mnist_rnn", "stl10", "transformer"))
    parser.add_argument("--batch", type=int, default=256)
    parser.add_argument("--k", type=int, default=10)
    parser.add_argument("--out", default=None)
    parser.add_argument("--per-layer", action="store_true",
                        help="append a per-layer forward breakdown "
                             "(prefix-difference timing; layer-spec "
                             "samples only)")
    args = parser.parse_args(argv)

    import jax

    from veles_tpu.backends import enable_compilation_cache
    enable_compilation_cache(platform=jax.devices()[0].platform)
    kind = jax.devices()[0].device_kind
    (params, step, apply_fn, x, labels,
     flops_overrides) = build(args.sample, args.batch)
    phases = measure_phases(params, step, apply_fn, x, labels,
                            k=args.k, flops_overrides=flops_overrides)

    full_sec, full_flops = phases["full_step"]
    fwd_sec, fwd_flops = phases["forward"]
    bwd_sec = full_sec - fwd_sec
    peak = _peak_flops(kind)
    lines = [
        "# %s fused-step profile — %s, batch %d" % (
            args.sample, kind, args.batch),
        "",
        "| Phase | sec/step | share | GFLOP | TFLOP/s |",
        "|---|---|---|---|---|",
    ]
    for name, sec, flops in (
            ("forward", fwd_sec, fwd_flops),
            ("backward+update (derived)", bwd_sec,
             (full_flops - fwd_flops) if full_flops and fwd_flops
             else None),
            ("full step", full_sec, full_flops)):
        tf = (flops / sec / 1e12) if flops and sec > 0 else None
        lines.append("| %s | %.6f | %.0f%% | %s | %s |" % (
            name, sec, 100.0 * sec / full_sec,
            "%.2f" % (flops / 1e9) if flops else "—",
            "%.1f" % tf if tf else "—"))
    ips = args.batch / full_sec
    mfu = (full_flops / full_sec / peak) if (full_flops and peak) \
        else None
    lines += ["",
              "- images/sec: **%.1f**" % ips,
              "- MFU: **%s**" % ("%.4f" % mfu if mfu else "n/a"),
              "- peak bf16 FLOP/s assumed: %s" % (
                  "%.0fe12" % (peak / 1e12) if peak else "unknown")]
    if args.per_layer:
        if args.sample in ("mnist", "transformer", "mnist_rnn"):
            # mnist/transformer are not layer-spec builds; mnist_rnn's
            # inner T-step scan breaks prefix cost analysis (counted
            # once — the same caveat build() fixes analytically)
            lines += ["", "(per-layer breakdown: layer-spec samples "
                          "only — skipped for %s)" % args.sample]
        else:
            rows = measure_per_layer(args.sample, args.batch,
                                     k=max(args.k, 8),
                                     full_forward=phases["forward"])
            lines += ["", "## Per-layer forward (prefix-difference)",
                      "",
                      "(consecutive-prefix differences: rows at or "
                      "below the stopwatch's noise floor print 0 and "
                      "the first row absorbs the carry-update "
                      "overhead — read ms-scale rows, not µs ones)",
                      "",
                      "| layer | sec | share | GFLOP | TFLOP/s |",
                      "|---|---|---|---|---|"]
            for label, sec, flops in rows:
                tf = (flops / sec / 1e12) if flops and sec > 0 \
                    else None
                lines.append("| %s | %.6f | %.0f%% | %s | %s |" % (
                    label, sec,
                    (100.0 * sec / fwd_sec) if fwd_sec else 0.0,
                    "%.2f" % (flops / 1e9) if flops else "—",
                    "%.1f" % tf if tf else "—"))
    report = "\n".join(lines)
    print(report)
    if args.out:
        with open(args.out, "w") as fout:
            fout.write(report + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
