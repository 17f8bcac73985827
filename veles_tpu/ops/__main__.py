"""``python -m veles_tpu.ops`` — the kernel-family CI smoke.

``--smoke`` (wired into ``scripts/lint.sh``) gates the Pallas kernel
families end to end on any host, TPU or not:

1. **parity oracles** — interpret-mode Pallas vs the dense XLA
   reference for every family shipped by ``veles_tpu.ops``: the fused
   backward-GD kernels (dW+optimizer epilogue / db / dX,
   ``ops.gemm.gd_fused_pallas`` vs ``znicz.gd._gd_math``, every
   activation × both storage layouts) and flash-attention fwd+bwd (the
   ``jax.custom_vjp`` pair vs dense attention under ``jax.grad``);
2. **autotune table round-trip** — a real (toy-shape) ``autotune_gd``
   sweep into a temp DB, read back through ``gemm_choice`` and
   ``gd_kernel_choice``, plus the ``scripts.autotune`` stdout-envelope
   unwrap (``DeviceInfo.load_db``);
3. **prof ledger** — a short stitched training run under
   ``root.common.engine.kernels=pallas`` must finish with ZERO
   steady-state recompiles (the fused kernels are resolved at
   stage-build time, so swapping them in must not perturb the
   one-compile-per-shape contract).
"""

import argparse
import sys

import numpy


def _fail(msg):
    print("ops smoke: FAIL — %s" % msg, file=sys.stderr)
    return 1


def _check_gd_parity():
    import jax.numpy as jnp

    from veles_tpu.ops.gemm import _GD_DERIVS, gd_fused_pallas
    from veles_tpu.znicz.gd import _gd_math

    rng = numpy.random.default_rng(7)
    batch, f, n = 24, 70, 50
    x = jnp.asarray(rng.standard_normal((batch, f)), jnp.float32)
    eo = jnp.asarray(rng.standard_normal((batch, n)), jnp.float32)
    hp = (0.05, 0.05, 0.0005, 0.0, 0.9, 0.9)
    worst = 0.0
    for activation in sorted(_GD_DERIVS, key=str):
        for transposed in (False, True):
            w = jnp.asarray(rng.standard_normal(
                (n, f) if transposed else (f, n)), jnp.float32) * 0.1
            vw = jnp.zeros_like(w)
            b = jnp.asarray(rng.standard_normal((n,)), jnp.float32)
            vb = jnp.zeros_like(b)
            y = jnp.asarray(rng.standard_normal((batch, n)),
                            jnp.float32)
            ref = _gd_math(x, y, eo, w, b, vw, vb, *hp,
                           activation=activation,
                           transposed=transposed)
            got = gd_fused_pallas(x, y, eo, w, b, vw, vb, *hp,
                                  activation=activation,
                                  transposed=transposed,
                                  tiles=(32, 32, 8), interpret=True)
            for name, r, g in zip(("w", "b", "vw", "vb", "err_input"),
                                  ref, got):
                err = float(jnp.max(jnp.abs(r - g)))
                worst = max(worst, err)
                if err > 5e-5:
                    return None, (
                        "fused GD %s mismatch (activation=%s, "
                        "transposed=%s): max |Δ| = %.3e"
                        % (name, activation, transposed, err))
    return worst, None


def _check_attention_parity():
    import jax
    import jax.numpy as jnp

    from veles_tpu.config import root
    from veles_tpu.ops.attention import flash_attention

    rng = numpy.random.default_rng(13)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 2, 32)),
                           jnp.float32) for _ in range(3))
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss(fn, use_pallas):
        def inner(q, k, v):
            return jnp.sum(fn(q, k, v, causal=True, block_q=64,
                              block_k=64, use_pallas=use_pallas) * do)
        return inner

    saved = root.common.engine.get("interpret", False)
    root.common.engine.interpret = True       # Pallas arm -> interpret
    try:
        ref = jax.grad(loss(flash_attention, False),
                       argnums=(0, 1, 2))(q, k, v)
        got = jax.grad(loss(flash_attention, True),
                       argnums=(0, 1, 2))(q, k, v)
        fwd_ref = flash_attention(q, k, v, causal=True,
                                  use_pallas=False)
        fwd_got = flash_attention(q, k, v, causal=True, block_q=64,
                                  block_k=64, use_pallas=True)
    finally:
        root.common.engine.interpret = saved
    worst = float(jnp.max(jnp.abs(fwd_ref - fwd_got)))
    for name, r, g in zip("qkv", ref, got):
        worst = max(worst, float(jnp.max(jnp.abs(r - g))))
    if worst > 5e-5:
        return None, ("flash-attention fwd+bwd mismatch: max |Δ| = "
                      "%.3e" % worst)
    return worst, None


def _check_autotune_roundtrip():
    import json
    import os
    import tempfile

    import jax.numpy as jnp

    from veles_tpu.backends import DeviceInfo
    from veles_tpu.ops.benchmark import autotune_gd, gemm_choice

    path = os.path.join(tempfile.mkdtemp(prefix="veles_ops_smoke_"),
                        "device_infos.json")
    try:
        info = autotune_gd(shape=(16, 128, 64), runs=1, db_path=path)
        if "gd_v2" not in info.ratings or "gd" not in info.ratings:
            return "autotune_gd left no gd_v2/gd ratings"
        gemm_choice.cache_clear()
        choice = gemm_choice(jnp.float32, db_path=path, kernel="gd",
                             shape=(16, 128, 64))
        if choice is None or choice[0] not in ("pallas", "xla"):
            return "gemm_choice(kernel='gd') did not round-trip: %r" \
                % (choice,)
        # the scripts.autotune stdout envelope must load as the flat DB
        with open(path) as fin:
            flat = json.load(fin)
        with open(path, "w") as fout:
            json.dump({"devices": flat, "_this_run": {"ts": 0.0}},
                      fout)
        db = DeviceInfo.load_db(path)
        if "_this_run" in db or not any(
                "gd_v2" in i.ratings for i in db.values()):
            return "load_db did not unwrap the autotune envelope"
        gemm_choice.cache_clear()
        choice2 = gemm_choice(jnp.float32, db_path=path, kernel="gd",
                              shape=(16, 128, 64))
        if choice2 != choice:
            return "enveloped DB changed the gd verdict: %r vs %r" \
                % (choice2, choice)
    finally:
        if os.path.exists(path):
            os.unlink(path)
        os.rmdir(os.path.dirname(path))
        gemm_choice.cache_clear()
    return None


def _check_ledger_zero_recompiles():
    """A short stitched training run with kernels=pallas: the fused-GD
    swap happens at stage-build time, so after the warmup compile the
    ledger must stay recompile-free."""
    from veles_tpu import prof, trace
    from veles_tpu.backends import CPUDevice
    from veles_tpu.config import root
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    class SmokeLoader(FullBatchLoader):
        hide_from_registry = True

        def load_data(self):
            rng = numpy.random.default_rng(3)
            self.original_data.mem = rng.standard_normal(
                (64, 8)).astype(numpy.float32)
            self.original_labels = [int(i % 4) for i in range(64)]
            self.class_lengths[:] = [0, 0, 64]

    saved = {k: root.common.engine.get(k, d) for k, d in
             (("kernels", "auto"), ("trace", "off"), ("stitch", "on"))}
    root.common.engine.kernels = "pallas"
    root.common.engine.trace = "on"
    root.common.engine.stitch = "on"
    try:
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: SmokeLoader(w, minibatch_size=16),
            layers=[{"type": "all2all_tanh",
                     "->": {"output_sample_shape": 16},
                     "<-": {"learning_rate": 0.05}},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 4}}],
            decision_config={"max_epochs": 3})
        wf.launcher = DummyLauncher()
        wf.initialize(device=CPUDevice())
        wf.run()
        segments = prof.ledger.entries("segment")
        if not segments:
            return "no stitched segments registered under " \
                "kernels=pallas"
        if prof.ledger.recompiles or prof.flagged:
            return ("%d steady-state recompile(s) under "
                    "kernels=pallas: %r"
                    % (prof.ledger.recompiles, prof.flagged))
    finally:
        for k, val in saved.items():
            setattr(root.common.engine, k, val)
        trace.configure()
    return None


def run_smoke():
    gd_err, msg = _check_gd_parity()
    if msg:
        return _fail(msg)
    attn_err, msg = _check_attention_parity()
    if msg:
        return _fail(msg)
    msg = _check_autotune_roundtrip()
    if msg:
        return _fail(msg)
    msg = _check_ledger_zero_recompiles()
    if msg:
        return _fail(msg)
    print("ops smoke: OK — GD parity max |Δ| = %.3e, attention "
          "fwd+bwd max |Δ| = %.3e, gd autotune table round-trips, "
          "0 recompiles under kernels=pallas" % (gd_err, attn_err))
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="veles_tpu.ops",
        description="Kernel-family utilities; --smoke runs the CI "
                    "parity/autotune/ledger gate.")
    parser.add_argument("--smoke", action="store_true",
                        help="run the kernel CI smoke (interpret-mode "
                             "parity oracles, autotune round-trip, "
                             "zero-recompile ledger gate)")
    args = parser.parse_args(argv)
    if args.smoke:
        return run_smoke()
    parser.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
