"""The ``root.common.engine.kernels`` family acceptance gates
(docs/engine_fast_path.md § Training kernels):

1. interpret-mode PARITY ORACLES — the fused backward-GD Pallas kernel
   (dW + optimizer epilogue / db / dX, every activation × both weight
   storage layouts) against the dense ``znicz.gd._gd_math`` reference;
2. END-TO-END parity — ``kernels=pallas`` must train to the same
   weights as ``kernels=xla`` (documented interpret-mode tolerance)
   with ZERO steady-state recompiles on every training path: the
   stitched-eager per-step program, the folded ``epoch_scan`` window,
   and the 8-device pod (one-pod-one-program pjit, on the conftest's
   virtual CPU mesh);
3. the CPU PERFORMANCE FLOOR (slow) — the fused LM train step of the
   bench ladder must beat its same-run XLA baseline ≥1.2× in the
   long-sequence regime where the materialized [B,H,S,S] attention
   backward is bandwidth-bound (the fused kernels' raison d'être).
"""

import json

import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import prng, prof
from veles_tpu.backends import CPUDevice
from veles_tpu.config import root
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.fullbatch import FullBatchLoader
from veles_tpu.ops.gemm import _GD_DERIVS, gd_fused_pallas
from veles_tpu.znicz.gd import _gd_math
from veles_tpu.znicz.standard_workflow import StandardWorkflow


# ---------------------------------------------------------------------------
# 1. interpret-mode parity oracles
# ---------------------------------------------------------------------------

_HP = (0.05, 0.05, 0.0005, 0.0, 0.9, 0.9)   # lr, lr_b, decay ×2, moment ×2


@pytest.mark.parametrize("activation", sorted(_GD_DERIVS, key=str))
@pytest.mark.parametrize("transposed", [False, True])
def test_gd_fused_matches_dense_math(activation, transposed):
    """One kernel call vs ``_gd_math``: every output (w, b, vw, vb,
    err_input) within the documented interpret tolerance, on
    deliberately tile-unaligned shapes."""
    rng = numpy.random.default_rng(7)
    batch, f, n = 24, 70, 50
    x = jnp.asarray(rng.standard_normal((batch, f)), jnp.float32)
    eo = jnp.asarray(rng.standard_normal((batch, n)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((batch, n)), jnp.float32)
    w = jnp.asarray(rng.standard_normal(
        (n, f) if transposed else (f, n)), jnp.float32) * 0.1
    b = jnp.asarray(rng.standard_normal((n,)), jnp.float32)
    vw, vb = jnp.zeros_like(w), jnp.zeros_like(b)
    ref = _gd_math(x, y, eo, w, b, vw, vb, *_HP,
                   activation=activation, transposed=transposed)
    got = gd_fused_pallas(x, y, eo, w, b, vw, vb, *_HP,
                          activation=activation, transposed=transposed,
                          tiles=(32, 32, 8), interpret=True)
    for name, r, g in zip(("w", "b", "vw", "vb", "err_input"), ref,
                          got):
        numpy.testing.assert_allclose(
            numpy.asarray(g), numpy.asarray(r), atol=5e-5, rtol=0,
            err_msg="%s (activation=%s, transposed=%s)"
                    % (name, activation, transposed))


# ---------------------------------------------------------------------------
# 2. kernels=pallas end-to-end parity, zero steady-state recompiles
# ---------------------------------------------------------------------------

class BlobLoader(FullBatchLoader):
    """Small separable blobs — enough steps per epoch to surface a
    per-step retrace, small enough for interpret-mode Pallas."""

    hide_from_registry = True

    def load_data(self):
        rng = numpy.random.default_rng(42)
        n_train, n_valid, dim = 96, 32, 16
        total = n_train + n_valid
        labels = numpy.tile(numpy.arange(4), total // 4)[:total]
        centers = rng.standard_normal((4, dim)) * 3.0
        self.original_data.mem = (
            centers[labels] + rng.standard_normal((total, dim)) * 0.5
        ).astype(numpy.float32)
        self.original_labels = [int(v) for v in labels]
        self.class_lengths[:] = [0, n_valid, n_train]


def _build(max_epochs=3):
    prng.seed_all(5)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: BlobLoader(w, minibatch_size=16),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 12},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}}],
        decision_config={"max_epochs": max_epochs})
    wf.launcher = DummyLauncher()
    wf.initialize(device=CPUDevice())
    return wf


def _params(wf):
    out = []
    for fwd in wf.forwards:
        for vec in (fwd.weights, fwd.bias):
            vec.map_read()
            out.append(numpy.array(vec.mem))
    return out


@pytest.fixture
def kernels_config():
    saved = {k: root.common.engine.get(k, d) for k, d in (
        ("kernels", "auto"), ("stitch", "on"), ("epoch_scan", "off"))}
    yield root.common.engine
    for key, value in saved.items():
        setattr(root.common.engine, key, value)


def _ab_run(kernels_config, epoch_scan):
    """Train the xla arm then the pallas arm on the identical seeded
    task; return both parameter sets and the pallas arm's recompile
    delta."""
    kernels_config.epoch_scan = epoch_scan
    kernels_config.kernels = "xla"
    wf = _build()
    wf.run()
    ref = _params(wf)

    kernels_config.kernels = "pallas"
    recompiles0 = prof.ledger.recompiles
    wf = _build()
    wf.run()
    return ref, _params(wf), prof.ledger.recompiles - recompiles0


def _assert_parity(ref, got):
    # interpret-mode Pallas accumulates f32 like the dense arm; the
    # residual drift over 3 epochs stays well under 1e-3
    for i, (r, g) in enumerate(zip(ref, got)):
        numpy.testing.assert_allclose(g, r, atol=1e-3, rtol=1e-3,
                                      err_msg="param %d" % i)


@pytest.mark.traced
def test_pallas_matches_xla_stitched_eager(kernels_config):
    ref, got, recompiled = _ab_run(kernels_config, epoch_scan="off")
    _assert_parity(ref, got)
    assert recompiled == 0, \
        "kernels=pallas retraced the stitched per-step program"
    assert prof.ledger.entries("segment"), \
        "the pallas arm did not run stitched"


@pytest.mark.traced
def test_pallas_matches_xla_epoch_scan_window(kernels_config):
    """The fused kernels are closure constants of the stage build, so
    the K-step scan window folds them without retracing."""
    ref, got, recompiled = _ab_run(kernels_config, epoch_scan="auto")
    _assert_parity(ref, got)
    assert recompiled == 0, \
        "kernels=pallas retraced the epoch_scan window"


@pytest.mark.traced
def test_pallas_matches_xla_pod_8dev(kernels_config):
    """One-pod-one-program on the conftest's forced 8-device CPU mesh:
    kernels=pallas must reach the same eval verdicts and weights as
    kernels=xla, with zero steady-state recompiles."""
    from veles_tpu.parallel.mesh import mesh_from_topology
    from veles_tpu.pod import PodRuntime, eval_metrics, train_epochs
    from veles_tpu.pod.__main__ import make_workflow

    def run(kernels):
        kernels_config.kernels = kernels
        wf = make_workflow(max_epochs=2)
        pod = PodRuntime(wf, mesh=mesh_from_topology("auto"))
        pod.install()
        assert pod.shards == 8
        for _ in train_epochs(wf, 2):
            pass
        wf.forwards[0].weights.map_read()
        return (eval_metrics(wf),
                numpy.array(wf.forwards[0].weights.mem))

    ref_metrics, ref_w = run("xla")
    recompiles0 = prof.ledger.recompiles
    got_metrics, got_w = run("pallas")
    assert prof.ledger.recompiles == recompiles0, \
        "kernels=pallas retraced the pod program"
    for key in ("complete", "epochs", "best_n_err_pt"):
        assert got_metrics[key] == ref_metrics[key], \
            (key, got_metrics, ref_metrics)
    numpy.testing.assert_allclose(got_w, ref_w, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# 3. the CPU performance floor (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fused_lm_train_step_beats_xla_baseline_on_cpu():
    """Acceptance floor: the bench ladder's fused LM train step ≥1.2×
    its same-run XLA baseline on CPU.  Off-TPU both arms run the dense
    fast path (interpret-mode Pallas is exempt from throughput
    claims); the A/B isolates the blockwise flash-attention
    custom_vjp backward + chunked CE against AD's materialized
    [B,H,S,S] scores, pinned to S=8192 — deep in the regime where the
    materialization is bandwidth-bound, so the ratio clears the floor
    with margin over host-load noise (observed 1.32-1.56x).

    Runs in a subprocess WITHOUT the conftest's 8-way virtual device
    split — the split divides the host's intra-op threads, which
    starves the compute-leaning blockwise arm and makes the timing
    meaningless as a floor (the ladder itself never runs split)."""
    import os
    import subprocess
    import sys

    import conftest

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = conftest.ORIG_XLA_FLAGS
    env["BENCH_LM_SEQ"] = "8192"
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get(
        "PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import bench; bench.stage_transformer_lm_train()"],
        capture_output=True, text=True, timeout=580, env=env,
        cwd=repo_root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert lines, "the LM train stage emitted no metric line"
    rec = json.loads(lines[-1])
    assert rec["kernels"] == "fused-vs-xla"
    assert rec["recompiles"] == 0, rec
    assert rec["vs_baseline"] >= 1.2, \
        "fused LM train step below the 1.2x CPU floor: %r" % (rec,)


# ---------------------------------------------------------------------------
# 4. the device's work carries the program's names (docs/observability.md)
# ---------------------------------------------------------------------------

def _pallas_names(fn, *args):
    """Names of the ``pallas_call`` equations in ``fn``'s jaxpr, nested
    programs included (tracing only: nothing is lowered or run)."""
    import jax

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for value in eqn.params.values():
                inner = getattr(value, "jaxpr", None)
                if inner is not None:
                    yield from walk(getattr(inner, "jaxpr", inner))

    return sorted(set(walk(jax.make_jaxpr(fn)(*args).jaxpr)))


def _kernel_cases():
    from veles_tpu.ops import attention, gemm, grouped, qgemm
    from veles_tpu.ops import random as ops_random
    f32 = jnp.float32
    q = jnp.zeros((1, 16, 2, 8), f32)           # (b, s, h, d)
    lse = jnp.zeros((1, 2, 16), f32)
    q1 = jnp.zeros((2, 1, 2, 8), f32)           # one decode row a slot
    cache = jnp.zeros((2, 16, 2, 8), f32)
    pool = jnp.zeros((5, 8, 2, 8), f32)         # (blocks, bs, h, d)
    tables = jnp.zeros((2, 2), jnp.int32)
    lengths = jnp.ones(2, jnp.int32)
    a = jnp.zeros((8, 16), f32)
    w = jnp.zeros((16, 8), f32)
    gd = (a, jnp.zeros((8, 8), f32), jnp.zeros((8, 8), f32), w,
          jnp.zeros(8, f32), jnp.zeros_like(w), jnp.zeros(8, f32))

    def gd_fused(*args):
        return gemm.gd_fused_pallas(*args, *_HP, interpret=True)

    return {
        "veles_flash_fwd": (lambda q: attention._flash_fwd(
            q, q, q, causal=True, interpret=True), (q,)),
        "veles_flash_bwd_dq": (lambda q, lse: attention._flash_bwd(
            q, q, q, q, lse, q, causal=True, interpret=True),
            (q, lse)),
        "veles_flash_bwd_dkv": (lambda q, lse: attention._flash_bwd(
            q, q, q, q, lse, q, causal=True, interpret=True),
            (q, lse)),
        "veles_attn_decode": (lambda q, c, n: attention._decode_pallas(
            q, c, c, n, block_k=8, interpret=True),
            (q1, cache, lengths)),
        "veles_attn_paged_decode": (
            lambda q, p, t, n: attention._paged_decode_pallas(
                q, p, p, t, n, interpret=True),
            (q1, pool, tables, lengths)),
        "veles_matmul": (lambda a, w: gemm._matmul_pallas(
            a, w, None, interpret=True), (a, w)),
        "veles_qmatmul": (lambda a, w, s: qgemm._qmatmul_pallas(
            a, w.astype(jnp.int8), s, None, interpret=True),
            (a, w, jnp.ones(8, f32))),
        "veles_gd_err_input": (gd_fused, gd),
        "veles_gd_update_w": (gd_fused, gd),
        "veles_gd_update_b": (gd_fused, gd),
        "veles_uniform": (lambda s: ops_random._uniform_pallas_tpu(
            s, (8, 128)), (jnp.int32(1),)),
        "veles_grouped_matmul": (lambda a, w, sizes: grouped.grouped_matmul(
            a, w[None], sizes, tm=8, use_pallas=True, interpret=True),
            (a, w, jnp.full(1, 8, jnp.int32))),
        # 8 rows through the one of two experts that some row chose
        "veles_expert_mix": (lambda a, w, load: grouped.expert_mix(
            a, jnp.ones((8, 2), f32), [jnp.stack([w.T, w.T])],
            jnp.stack([w, w]), load, use_pallas=True, interpret=True),
            (jnp.zeros((8, 8), f32), w, jnp.asarray([0, 8], jnp.int32))),
        # a chunk of 8 tokens, 2 KV heads of 2 query heads, a ring of 16
        "veles_attn_ring_chunk": (
            lambda q, k, c: attention.ring_chunk_attention(
                q, k, k, c, c, 1, 8, window=16, floor_rows=16,
                interpret=True),
            (jnp.zeros((8, 2, 2, 8), f32), jnp.zeros((8, 16), f32),
             jnp.zeros((2, 16, 16), f32))),
    }


@pytest.mark.parametrize("name", [
    "veles_flash_fwd", "veles_flash_bwd_dq", "veles_flash_bwd_dkv",
    "veles_attn_decode", "veles_attn_paged_decode", "veles_matmul",
    "veles_qmatmul", "veles_gd_err_input", "veles_gd_update_w",
    "veles_gd_update_b", "veles_uniform", "veles_grouped_matmul",
    "veles_expert_mix", "veles_attn_ring_chunk"])
def test_every_pallas_call_carries_its_kernels_name(name):
    """The name a device trace shows for a Pallas kernel is the one
    its ``pallas_call`` was given: one name a kernel."""
    fn, args = _kernel_cases()[name]
    names = _pallas_names(fn, *args)
    assert name in names, names
    assert all(n.startswith("veles_") for n in names), names


def test_no_pallas_call_site_without_a_name():
    import os
    import re

    import veles_tpu.ops as ops
    root_dir = os.path.dirname(ops.__file__)
    sites = 0
    for fname in sorted(os.listdir(root_dir)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(root_dir, fname)) as handle:
            text = handle.read()
        for match in re.finditer(r"pl\.pallas_call\(", text):
            sites += 1
            head = text[match.end():match.end() + 400]
            assert re.search(r'\bname="veles_[a-z_]+"', head), \
                (fname, text[:match.start()].count("\n") + 1)
    assert sites == 17


def _scope_names(lowered):
    """``(every veles. scope, the locations)`` in the lowered text."""
    import re
    locs = set(re.findall(r'loc\("([^"]*)"',
                          lowered.as_text(debug_info=True)))
    scopes = {name for loc in locs
              for name in re.findall(r"veles\.[a-z_.0-9]+", loc)}
    return scopes, locs


def test_fused_step_lowers_with_layer_and_update_scopes():
    import jax

    from veles_tpu.znicz.fused_graph import lower_specs
    solver = {"learning_rate": 0.05, "gradient_moment": 0.9}
    params, step_fn, eval_fn, _apply = lower_specs(
        [{"type": "all2all_tanh", "->": {"output_sample_shape": 8},
          "<-": solver},
         {"type": "softmax", "->": {"output_sample_shape": 10},
          "<-": solver}], (16,), input_norm=(0.5, -1.0))
    x = jnp.zeros((4, 16), jnp.uint8)
    labels = jnp.zeros(4, jnp.int32)
    scopes, locs = _scope_names(jax.jit(step_fn).lower(params, x,
                                                       labels))
    assert {"veles.layer.00.all2all_tanh", "veles.layer.01.softmax",
            "veles.ingest", "veles.loss", "veles.update"} <= scopes
    joined = "\n".join(locs)
    # forward and backward of one layer are told apart by JAX's own
    # wrapping of the scope
    assert "jvp(veles.layer.00." in joined
    assert "transpose(jvp(veles.layer.00." in joined
    assert "transpose(jvp(veles.layer.01." in joined
    assert not any("veles.update" in loc and "jvp(" in loc
                   for loc in locs)
    scopes, _locs = _scope_names(jax.jit(eval_fn).lower(params, x,
                                                        labels))
    assert {"veles.layer.00.all2all_tanh", "veles.layer.01.softmax",
            "veles.ingest"} <= scopes
    assert "veles.update" not in scopes


def test_decode_lowers_with_gpt_scopes_and_none_on_the_scan():
    import jax

    from veles_tpu.gen import TransformerGenModel
    from veles_tpu.samples.transformer import TINY
    model = TransformerGenModel(dict(TINY, seq_len=64))
    lowered = jax.jit(model.decode).lower(
        model.init_params(0), model.init_cache(3, 48),
        jnp.zeros(3, jnp.int32), jnp.ones(3, jnp.int32),
        jnp.ones(3, bool))
    scopes, locs = _scope_names(lowered)
    assert scopes == {"veles.gpt." + part for part in (
        "embed", "qkv", "kv_write", "attn", "proj", "mlp", "readout")}
    # the layer loop and its bookkeeping (the slices of the weights it
    # scans over) stay outside every name; the cache is its carry, so
    # the loop itself writes nothing back: no stacking of ys
    loops = [loc for loc in locs if "while" in loc or "scan" in loc]
    assert any(loc.endswith("/while") for loc in loops), loops
    assert any(loc.endswith("/dynamic_slice") for loc in loops), loops
    assert not any(loc.endswith("/dynamic_update_slice") for loc in loops)
    assert not any("veles." in loc for loc in loops), loops


def test_loader_gathers_lower_with_their_scope():
    from veles_tpu.ops import gather
    data = jnp.zeros((6, 4, 4, 3), jnp.uint8)
    idx = jnp.arange(4, dtype=jnp.int32)
    scopes, _locs = _scope_names(gather._gather_jnp.lower(data, idx))
    assert scopes == {"veles.loader.take_rows"}
    scopes, _locs = _scope_names(gather._gather_norm_jnp.lower(
        data, idx, jnp.float32(0.5), jnp.float32(-1.0)))
    assert scopes == {"veles.loader.take_rows_norm"}
