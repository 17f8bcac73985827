"""Golden tests for the kernel substrate vs numpy reference — mirrors the
reference's ``test_ocl_blas.py`` / ``test_random.py`` strategy: every op
checked against a plain numpy computation, and the Pallas path checked in
interpret mode on CPU (the TPU hardware run is exercised by bench.py)."""

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu.ops import gemm, normalize, reduce as reduce_ops
from veles_tpu.ops.join import join as join_op
from veles_tpu.ops.gather import take_rows
from veles_tpu.ops.random import dropout_mask, normal, uniform


class TestMatmul:
    def _golden(self, m, k, n, activation=None, bias=False, seed=0):
        rng = numpy.random.default_rng(seed)
        a = rng.standard_normal((m, k), dtype=numpy.float32)
        b = rng.standard_normal((k, n), dtype=numpy.float32)
        bv = rng.standard_normal(n, dtype=numpy.float32) if bias else None
        ref = a @ b
        if bias:
            ref = ref + bv
        if activation == "tanh":
            ref = 1.7159 * numpy.tanh(0.6666 * ref)
        elif activation == "strict_relu":
            ref = numpy.maximum(ref, 0)
        return a, b, bv, ref

    def test_jnp_path(self):
        a, b, bv, ref = self._golden(17, 33, 9, bias=True)
        out = gemm.matmul(a, b, bv)
        assert numpy.allclose(out, ref, atol=1e-4)

    def test_pallas_interpret_matches(self):
        a, b, bv, ref = self._golden(16, 128, 128, bias=True)
        from veles_tpu.config import root
        root.common.engine.interpret = True
        try:
            out = gemm.matmul(a, b, bv, use_pallas=True)
        finally:
            root.common.engine.interpret = False
        assert numpy.allclose(out, ref, atol=1e-4)

    def test_pallas_unaligned_shapes(self):
        a, b, _, ref = self._golden(33, 70, 130)
        from veles_tpu.config import root
        root.common.engine.interpret = True
        try:
            out = gemm.matmul(a, b, use_pallas=True)
        finally:
            root.common.engine.interpret = False
        assert numpy.allclose(out, ref, atol=1e-4)

    def test_activation_fused(self):
        a, b, bv, ref = self._golden(8, 16, 4, activation="tanh", bias=True)
        out = gemm.matmul(a, b, bv, "tanh")
        assert numpy.allclose(out, ref, atol=1e-4)

    def test_grad_through_matmul(self):
        """custom VJP: jax.grad through matmul matches numerical grad of
        plain jnp composition."""
        a = numpy.random.default_rng(1).standard_normal(
            (4, 6)).astype(numpy.float32)
        b = numpy.random.default_rng(2).standard_normal(
            (6, 3)).astype(numpy.float32)

        def loss_ours(a_, b_):
            return jnp.sum(gemm.matmul(a_, b_, None, "tanh",
                                       use_pallas=False) ** 2)

        def loss_ref(a_, b_):
            return jnp.sum((1.7159 * jnp.tanh(0.6666 * (a_ @ b_))) ** 2)

        ga, gb = jax.grad(loss_ours, argnums=(0, 1))(a, b)
        ra, rb = jax.grad(loss_ref, argnums=(0, 1))(a, b)
        assert numpy.allclose(ga, ra, atol=1e-3)
        assert numpy.allclose(gb, rb, atol=1e-3)

    def test_grad_strict_relu(self):
        a = numpy.random.default_rng(3).standard_normal(
            (5, 7)).astype(numpy.float32)
        b = numpy.random.default_rng(4).standard_normal(
            (7, 2)).astype(numpy.float32)
        ga = jax.grad(lambda a_: jnp.sum(gemm.matmul(
            a_, b, None, "strict_relu")))(a)
        ra = jax.grad(lambda a_: jnp.sum(
            jnp.maximum(a_ @ b, 0)))(a)
        assert numpy.allclose(ga, ra, atol=1e-4)

    def test_bfloat16_inputs(self):
        a, b, _, ref = self._golden(16, 32, 8)
        out = gemm.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          use_pallas=False)
        assert out.dtype == jnp.bfloat16
        assert numpy.allclose(numpy.asarray(out, numpy.float32), ref,
                              atol=0.5, rtol=0.05)


class TestReduce:
    @pytest.mark.parametrize("axis", [0, 1])
    @pytest.mark.parametrize("op", ["sum", "max", "min"])
    def test_jnp(self, axis, op):
        a = numpy.random.default_rng(0).standard_normal(
            (37, 53)).astype(numpy.float32)
        ref = getattr(numpy, op)(a, axis=axis)
        out = reduce_ops.matrix_reduce(a, axis=axis, op=op)
        assert numpy.allclose(out, ref, atol=1e-4)


class TestGather:
    def test_basic(self):
        data = numpy.arange(40, dtype=numpy.float32).reshape(10, 4)
        idx = numpy.array([3, 1, 7], dtype=numpy.int32)
        out = take_rows(data, idx)
        assert (numpy.asarray(out) == data[idx]).all()

    def test_negative_index_zero_fill(self):
        data = numpy.ones((5, 3), dtype=numpy.float32)
        idx = numpy.array([0, -1, 2], dtype=numpy.int32)
        out = numpy.asarray(take_rows(data, idx))
        assert (out[1] == 0).all() and (out[0] == 1).all()

    def test_3d_data(self):
        data = numpy.random.default_rng(3).standard_normal(
            (6, 4, 5)).astype(numpy.float32)
        idx = numpy.array([2, 4], dtype=numpy.int32)
        out = numpy.asarray(take_rows(data, idx))
        assert out.shape == (2, 4, 5)
        assert numpy.allclose(out, data[idx])


class TestRandomOps:
    def test_uniform_range_and_determinism(self):
        key = jax.random.key(42)
        a = uniform(key, (1000,), low=-2.0, high=3.0)
        b = uniform(key, (1000,), low=-2.0, high=3.0)
        assert (numpy.asarray(a) == numpy.asarray(b)).all()
        assert a.min() >= -2.0 and a.max() < 3.0

    def test_normal_moments(self):
        key = jax.random.key(7)
        x = numpy.asarray(normal(key, (20000,), mean=1.0, stddev=2.0))
        assert abs(x.mean() - 1.0) < 0.1
        assert abs(x.std() - 2.0) < 0.1

    def test_uniform_pallas_refuses_off_tpu(self):
        """The hardware PRNG has no CPU lowering: asking for it off the
        TPU is an error, not threefry bits under its name."""
        from veles_tpu.ops.random import uniform_pallas
        with pytest.raises(NotImplementedError, match="TPU core PRNG"):
            uniform_pallas(3, (256,), low=-1.0, high=1.0)

    def test_dropout_mask(self):
        key = jax.random.key(0)
        mask = numpy.asarray(dropout_mask(key, (10000,), 0.8))
        kept = (mask > 0).mean()
        assert 0.75 < kept < 0.85
        assert numpy.allclose(mask[mask > 0], 1.0 / 0.8)


class TestNormalizeJoin:
    def test_mean_disp(self):
        x = numpy.random.default_rng(0).standard_normal(
            (8, 5)).astype(numpy.float32)
        mean = x.mean(axis=0)
        disp = 1.0 / (x.std(axis=0) + 1e-6)
        out = numpy.asarray(normalize.mean_disp_normalize(
            jnp.asarray(x), jnp.asarray(mean), jnp.asarray(disp)))
        assert numpy.allclose(out, (x - mean) * disp, atol=1e-5)

    def test_join_flattens_and_concats(self):
        a = numpy.ones((4, 2, 3), dtype=numpy.float32)
        b = numpy.zeros((4, 5), dtype=numpy.float32)
        out = numpy.asarray(join_op([jnp.asarray(a), jnp.asarray(b)]))
        assert out.shape == (4, 11)
        assert (out[:, :6] == 1).all() and (out[:, 6:] == 0).all()


class TestHog:
    """HOG features (ref vendored external/hog.py)."""

    def test_shapes_and_norm(self):
        import numpy
        from veles_tpu.ops.hog import hog, hog_batch
        rng = numpy.random.default_rng(0)
        img = rng.random((32, 32)).astype(numpy.float32)
        feat = numpy.asarray(hog(img, orientations=9, cell=8, block=2))
        # 4x4 cells → 3x3 blocks of 2x2x9
        assert feat.shape == (3 * 3 * 2 * 2 * 9,)
        # L2 block norm keeps every block at unit-ish energy
        blocks = feat.reshape(9, 36)
        norms = numpy.linalg.norm(blocks, axis=1)
        assert (norms <= 1.0 + 1e-5).all()
        batch = numpy.asarray(hog_batch(
            rng.random((5, 32, 32, 3)).astype(numpy.float32)))
        assert batch.shape == (5, 324)

    def test_oriented_edges_dominate_expected_bin(self):
        import numpy
        from veles_tpu.ops.hog import hog
        # x-ramp → horizontal gradient → angle 0 bin
        img = numpy.tile(
            numpy.arange(32, dtype=numpy.float32), (32, 1))
        feat = numpy.asarray(hog(img, orientations=9, cell=8,
                                 block=1))
        hist = feat.reshape(-1, 9).sum(axis=0)
        assert hist.argmax() == 0
        # horizontal stripes → vertical gradient → π/2 bin (index 4)
        feat_t = numpy.asarray(hog(img.T, orientations=9, cell=8,
                                   block=1))
        hist_t = feat_t.reshape(-1, 9).sum(axis=0)
        assert hist_t.argmax() == 4

    def test_gradients_flow(self):
        import jax, numpy
        import jax.numpy as jnp
        from veles_tpu.ops.hog import hog
        img = jnp.asarray(numpy.random.default_rng(1).random(
            (16, 16)).astype(numpy.float32))
        g = jax.grad(lambda im: hog(im).sum())(img)
        assert numpy.isfinite(numpy.asarray(g)).all()
        # flat regions (gx=gy=0) must not NaN-poison the gradient
        flat = jnp.zeros((16, 16), jnp.float32).at[4:8, 4:8].set(1.0)
        g2 = jax.grad(lambda im: hog(im).sum())(flat)
        assert numpy.isfinite(numpy.asarray(g2)).all()


def test_timing_multi_step_and_marginal():
    """ops.timing: K-step in-program loop matches K sequential steps,
    probe depends on params+metric, marginal timing returns sane
    positive values (the round-2 stopwatch bug class)."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.timing import (
        host_fetch, make_multi_step, marginal_time, measure_fused_step)

    def step(params, x, labels):
        p = params["w"]
        p = p + 0.25 * jnp.mean(x) + 0.001 * labels.sum()
        return {"w": p}, {"loss": jnp.sum(p)}

    params = {"w": jnp.zeros((4,), jnp.float32)}
    x = jnp.ones((2, 4), jnp.float32)
    labels = jnp.zeros((2,), jnp.int32)
    multi = make_multi_step(step, 5)
    out_params, probe = jax.jit(multi)(params, x, labels)
    # 5 steps of +0.25 each
    numpy.testing.assert_allclose(
        host_fetch(out_params["w"]), numpy.full((4,), 1.25), rtol=1e-6)
    vals = host_fetch(probe)
    assert vals.shape == (2,)
    assert numpy.isfinite(vals).all()

    # measurement needs a step with real work — a trivial step's
    # marginal is pure dispatch jitter and can come out non-positive
    def heavy_step(params, x, labels):
        m = params["m"]
        m = m + 1e-4 * (m @ m)
        return {"m": m}, {"loss": jnp.sum(m)}

    heavy = {"m": jnp.eye(512, dtype=jnp.float32) * 0.01}
    sec_per_step, flops = measure_fused_step(
        heavy_step, heavy, x, labels, k=5, donate=False)
    assert sec_per_step > 0

    calls = []

    def call(sync=False):
        calls.append(sync)

    per = marginal_time(call, min_seconds=0.01)
    assert per > 0


def test_timing_inprogram_marginal_and_dynamic_k():
    """The in-program stopwatch: ONE compiled program timed at two
    runtime trip counts (the per-program dispatch overhead cancels);
    flops come from a loop program's cost = 2 steps, never total/K."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.timing import (
        host_fetch, inprogram_marginal, make_multi_step,
        measure_fused_step)

    def step(params, x, labels):
        p = params["w"]
        p = p + 0.25 * jnp.mean(x) + 0.001 * labels.sum()
        return {"w": p}, {"loss": jnp.sum(p)}

    params = {"w": jnp.zeros((4,), jnp.float32)}
    x = jnp.ones((2, 4), jnp.float32)
    labels = jnp.zeros((2,), jnp.int32)

    # dynamic trip count: the SAME jitted multi runs 3 and 7 steps
    multi = make_multi_step(step)
    jitted = jax.jit(multi)
    for n in (3, 7):
        out_params, _probe = jitted(params, x, labels,
                                    numpy.int32(n))
        numpy.testing.assert_allclose(
            host_fetch(out_params["w"]),
            numpy.full((4,), 0.25 * n), rtol=1e-6)

    # a unit with real work (a no-op unit's marginal is pure dispatch
    # jitter and can come out non-positive on a loaded CI machine)
    w = jnp.eye(128, dtype=jnp.float32) * 0.999

    def unit(c):
        return jnp.tanh(c @ w)

    per = inprogram_marginal(unit, jnp.ones((128, 128), jnp.float32),
                             k1=2, k2=64, target_signal=0.05)
    assert per > 0

    # measure_fused_step returns a positive marginal and flops of ONE
    # step (the loop program's cost analysis counts its body once, so
    # program total = inline first step + body = 2 steps).  The step
    # must do real work: a trivial step's marginal is dispatch jitter.
    def heavy_step(params, x, labels):
        m = params["m"]
        m = m + 1e-4 * (m @ m)
        return {"m": m}, {"loss": jnp.sum(m)}

    heavy = {"m": jnp.eye(512, dtype=jnp.float32) * 0.01}
    sec_per_step, flops = measure_fused_step(heavy_step, heavy, x,
                                             labels, k=8)
    assert sec_per_step > 0
    one_step = jax.jit(heavy_step).lower(heavy, x, labels).compile()
    from veles_tpu.ops.timing import cost_flops
    expect = cost_flops(one_step)
    if expect and flops:
        # probe/loop bookkeeping adds a handful of scalar flops
        assert flops == pytest.approx(expect, rel=0.5)


def test_two_point_marginal_survives_short_point_stall():
    """Round-4 hardening: a transient transport stall in the FIRST
    short-point sample must not skew the marginal — the short point is
    sampled twice up front (min wins) and its spread is recorded as
    provenance.  Before the fix, the single contaminated t1 anchored
    every widen/retry and the marginal converged to the wrong value."""
    from veles_tpu.ops.timing import _two_point_marginal

    true_per_unit = 1e-3
    overhead = 0.05
    calls = {"n": 0}

    def timed(n):
        calls["n"] += 1
        t = overhead + n * true_per_unit
        if calls["n"] == 1:           # stall hits only the first sample
            t += 5.0
        return t

    stats = {}
    m = _two_point_marginal(timed, 4, 32, target_signal=0.01,
                            max_k=10000, stats=stats)
    assert m == pytest.approx(true_per_unit, rel=1e-9)
    assert stats["marginal"] == m
    assert stats["t1_samples"] >= 2
    assert stats["t1_rel_spread"] > 1.0   # the stall left a signature
    assert stats["t1"] == pytest.approx(overhead + 4 * true_per_unit)
    # provenance invariant: the recorded points reproduce the marginal
    assert stats["marginal"] == pytest.approx(
        (stats["t2"] - stats["t1"]) / (stats["k2"] - stats["k1"]))

    # steady-noise convergence: every sample jitters ±20 %, the widen
    # loop still lands within 25 % of truth (deterministic "noise")
    seq = [1.2, 0.95, 1.1, 1.0, 0.9, 1.15, 1.05, 0.85, 1.0, 1.1]
    calls2 = {"n": 0}

    def noisy(n):
        f = seq[calls2["n"] % len(seq)]
        calls2["n"] += 1
        return overhead + n * true_per_unit * f

    m2 = _two_point_marginal(noisy, 4, 32, target_signal=0.05,
                             max_k=10000)
    assert m2 == pytest.approx(true_per_unit, rel=0.25)


def test_timing_pins_operands_on_device():
    """Host-resident numpy params (what lower_specs returns) would be
    re-uploaded on EVERY timed launch — ~0.5 GB/launch for AlexNet,
    whose transfer time swamps the marginal.  The stopwatch must
    device_put its operands once, so no implicit H2D transfer may
    happen during timing — pinned with jax's transfer guard."""
    import jax
    import jax.numpy as jnp

    from veles_tpu.ops.timing import inprogram_marginal, \
        measure_fused_step

    def heavy_step(params, x, labels):
        m = params["m"]
        m = m + 1e-4 * (m @ m)
        return {"m": m}, {"loss": jnp.sum(m)}

    heavy = {"m": numpy.eye(256, dtype=numpy.float32) * 0.01}
    x = numpy.ones((2, 4), numpy.float32)
    labels = numpy.zeros((2,), numpy.int32)
    with jax.transfer_guard("disallow"):
        sec, _flops = measure_fused_step(heavy_step, heavy, x, labels,
                                         k=5)
    assert sec > 0

    def unit(c):
        return c + 1e-4 * (c @ c)

    with jax.transfer_guard("disallow"):
        per = inprogram_marginal(
            unit, numpy.eye(128, dtype=numpy.float32) * 0.01,
            k1=2, k2=8, target_signal=0.0)
    assert per > 0


def test_peak_guard_rejects_faster_than_hardware(monkeypatch):
    """A marginal implying more FLOPs than the chip's peak must be
    re-measured and then refused, never recorded (the round-2 MFU-54
    failure class)."""
    from veles_tpu.ops import benchmark as B

    monkeypatch.setattr("veles_tpu.backends.peak_bf16_flops",
                        lambda kind: 100e12)
    # 1e12 flops in 1e-3 s = 1000 TFLOPs >> 100 peak: reject
    with pytest.raises(RuntimeError, match="exceeds"):
        B._peak_guard(1e-3, 1e12, lambda: 1e-3, "test")
    # 1e12 flops in 0.02 s = 50 TFLOPs < 100 peak: accepted unchanged
    assert B._peak_guard(0.02, 1e12, lambda: 0.02, "test") == 0.02
    # first reading absurd, re-measurement sane: keep the re-measured
    assert B._peak_guard(1e-3, 1e12, lambda: 0.02, "test") == 0.02


def test_autotune_db_drives_dispatch(tmp_path, monkeypatch):
    """The device-infos DB decides matmul dispatch: a committed entry
    flips pallas on (with its tiles) or keeps XLA, per device
    generation and dtype (ref devices/device_infos.json,
    backends.py:623-744)."""
    import json as _json

    import jax
    import jax.numpy as jnp

    from veles_tpu.ops import benchmark, gemm
    from veles_tpu.config import root

    model = jax.devices()[0].device_kind
    db_path = tmp_path / "device_infos.json"
    db_path.write_text(_json.dumps({model: {"gemm": {
        "float32": {"sec_per_flop": 1e-12, "backend": "pallas",
                    "tiles": [256, 256, 256]},
        "bfloat16": {"sec_per_flop": 1e-12, "backend": "xla",
                     "tiles": None},
    }}}))
    monkeypatch.setattr(benchmark, "DEVICE_INFOS_JSON", str(db_path))
    benchmark.gemm_choice.cache_clear()
    try:
        assert benchmark.gemm_choice(jnp.float32) == \
            ("pallas", (256, 256, 256))
        assert benchmark.gemm_choice(jnp.bfloat16) == ("xla", None)
        assert benchmark.gemm_choice(jnp.float64) is None
        assert benchmark.tiles_for_gemm(jnp.float32) == (256, 256, 256)
        on_tpu = jax.devices()[0].platform == "tpu"
        # dispatch honors the DB on TPU; CPU never picks pallas from it
        on, tiles = gemm._dispatch(None, None, jnp.float32)
        assert on == on_tpu
        if on_tpu:
            assert tiles == (256, 256, 256)   # DB tiles flow through
        # explicitly forced pallas still uses the DB's measured tiles
        root.common.engine.pallas_gemm = True
        try:
            on, tiles = gemm._dispatch(None, None, jnp.float32)
            assert on == on_tpu
            assert tiles == (256, 256, 256)
        finally:
            root.common.engine.pallas_gemm = None
        # a caller's explicit tiles beat the DB's
        assert gemm._dispatch(True, (128, 128, 128), jnp.float32) == \
            (True, (128, 128, 128))
        # legacy entries (no "backend" key) must NOT flip dispatch to
        # pallas — their sweep never measured the XLA baseline
        db = _json.loads(db_path.read_text())
        db[model]["gemm"]["float32"].pop("backend")
        db_path.write_text(_json.dumps(db))
        benchmark.gemm_choice.cache_clear()
        assert benchmark.gemm_choice(jnp.float32) == \
            ("xla", (256, 256, 256))
        # flash-attention reads its own kernel entry: blocks AND the
        # backend verdict
        db[model]["flash_attention"] = {"bfloat16": {
            "sec_per_flop": 1e-12, "backend": "xla",
            "tiles": None}}
        db_path.write_text(_json.dumps(db))
        benchmark.gemm_choice.cache_clear()
        from veles_tpu.ops.attention import (
            _resolve_backend, _resolve_blocks)
        assert _resolve_backend(None, jnp.bfloat16) is False
        assert _resolve_backend(True, jnp.bfloat16) is True
        db[model]["flash_attention"]["bfloat16"] = {
            "sec_per_flop": 1e-12, "backend": "pallas",
            "tiles": [256, 512]}
        db_path.write_text(_json.dumps(db))
        benchmark.gemm_choice.cache_clear()
        assert _resolve_blocks(None, None, jnp.bfloat16) == (256, 512)
        assert _resolve_blocks(64, None, jnp.bfloat16) == (64, 512)
        assert _resolve_blocks(None, None, jnp.float32) == (128, 128)
        assert _resolve_backend(None, jnp.bfloat16) == on_tpu
    finally:
        benchmark.gemm_choice.cache_clear()


def test_autotune_gemm_writes_db(tmp_path):
    """The sweep itself (tiny shapes, CPU): produces a DB whose entry
    has backend/tiles/sec_per_flop and that gemm_choice can read
    back — plus per-shape-class, per-precision gemm_v2 entries
    carrying the stopwatch's noise signature (VERDICT r3 items 4/5)."""
    import jax

    from veles_tpu.ops import benchmark

    info = benchmark.autotune_gemm(
        shapes=((64, 64, 64),), dtypes=("float32",),
        candidates=((64, 64, 64),), runs=1,
        db_path=str(tmp_path / "db.json"),
        precision_levels=(0, 1))
    entry = info.ratings["gemm"]["float32"]
    assert entry["backend"] in ("pallas", "xla")
    assert entry["sec_per_flop"] > 0
    choice = benchmark.gemm_choice(
        "float32", db_path=str(tmp_path / "db.json"))
    assert choice is not None
    # v2: one entry per measured precision level, classified by shape,
    # with measurement provenance
    v2 = info.ratings["gemm_v2"]["float32"]
    cls = benchmark.classify_shape(64, 64, 64)
    for lvl in ("p0", "p1"):
        e = v2[lvl][cls]
        assert e["backend"] in ("pallas", "xla")
        assert e["sec_per_flop"] > 0
        assert e["shape"] == [64, 64, 64]
        assert "t1_rel_spread" in e


def test_gemm_choice_respects_precision_and_shape_class(tmp_path,
                                                        monkeypatch):
    """Dispatch routing over the v2 DB: shape classes select their own
    measured entry; a precision level with no measurement falls back
    to XLA — NEVER to tiles raced under another precision's MXU pass
    count (VERDICT r3 item 4)."""
    import json as _json

    import jax
    import jax.numpy as jnp

    from veles_tpu.config import root
    from veles_tpu.ops import benchmark

    def e(backend, tiles, shape):
        return {"sec_per_flop": 1e-12, "backend": backend,
                "tiles": tiles, "shape": shape, "t1_rel_spread": 0.02}

    model = jax.devices()[0].device_kind
    db_path = tmp_path / "device_infos.json"
    db_path.write_text(_json.dumps({model: {
        "gemm": {"float32": {"sec_per_flop": 1e-12,
                             "backend": "pallas",
                             "tiles": [512, 512, 512]}},
        "gemm_v2": {"float32": {
            "p0": {
                "square_large": e("pallas", [256, 256, 256],
                                  [4096, 4096, 4096]),
                "tall_skinny": e("xla", None, [16384, 1024, 1024]),
            },
            "p2": {
                "square_large": e("pallas", [128, 128, 128],
                                  [4096, 4096, 4096]),
            },
        }},
    }}))
    monkeypatch.setattr(benchmark, "DEVICE_INFOS_JSON", str(db_path))
    benchmark.gemm_choice.cache_clear()
    try:
        # p0: shape-class routing picks the class's own winner
        assert benchmark.gemm_choice(
            jnp.float32, shape=(4096, 4096, 4096)) == \
            ("pallas", (256, 256, 256))
        assert benchmark.gemm_choice(
            jnp.float32, shape=(16384, 1024, 1024)) == ("xla", None)
        # no shape info: square_large is the representative entry
        assert benchmark.gemm_choice(jnp.float32) == \
            ("pallas", (256, 256, 256))
        root.common.engine.precision_level = 2
        assert benchmark.gemm_choice(
            jnp.float32, shape=(4096, 4096, 4096)) == \
            ("pallas", (128, 128, 128))
        # p1 was never measured: XLA (None), NOT the p0 tiles
        root.common.engine.precision_level = 1
        assert benchmark.gemm_choice(
            jnp.float32, shape=(4096, 4096, 4096)) is None
        # bfloat16 has neither v2 nor legacy rows at p1: still None
        assert benchmark.gemm_choice(
            jnp.bfloat16, shape=(4096, 4096, 4096)) is None
        root.common.engine.precision_level = 0
        # flash attention routes by sequence regime (flash_v2)
        db = _json.loads(db_path.read_text())
        db[model]["flash_attention_v2"] = {"bfloat16": {
            "seq_2k": e("pallas", [256, 256], [4, 2048, 8, 128]),
            "seq_8k": e("pallas", [512, 256], [1, 8192, 8, 128]),
        }}
        db_path.write_text(_json.dumps(db))
        benchmark.gemm_choice.cache_clear()
        assert benchmark.gemm_choice(
            jnp.bfloat16, kernel="flash_attention",
            shape=(4, 2048, 8, 128)) == ("pallas", (256, 256))
        assert benchmark.gemm_choice(
            jnp.bfloat16, kernel="flash_attention",
            shape=(1, 8192, 8, 128)) == ("pallas", (512, 256))
        # no shape: the canonical seq_2k regime represents the kernel
        assert benchmark.gemm_choice(
            jnp.bfloat16, kernel="flash_attention") == \
            ("pallas", (256, 256))
    finally:
        root.common.engine.precision_level = 0
        benchmark.gemm_choice.cache_clear()
