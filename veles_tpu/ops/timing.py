"""Trustworthy on-device timing.

JAX dispatch is asynchronous: a stopwatch around a call that does not
wait for the result measures the enqueue, and one that waits after
every short call measures mostly the per-program dispatch and fetch
overhead.  Rules enforced here:

1. **Synchronize by fetching real bytes.**  ``host_fetch`` does a
   ``jax.device_get`` of a small array *derived from the result* — the
   D2H copy cannot complete before the producing program does, so the
   sync does not depend on what a runtime reports as "ready".
2. **Amortize the round trip inside the program.**  ``make_multi_step``
   loops K train steps inside ONE jitted program via ``lax.fori_loop``,
   threading the params carry, and returns a probe vector that depends
   on both the final metric and the final params — so the fetched bytes
   prove the whole chain executed.
3. **Cancel fixed overhead exactly.**  ``marginal_time`` times the work
   at two different call counts and reports the *marginal* seconds per
   call; the constant dispatch+fetch overhead subtracts out instead
   of inflating short measurements.

Reference discipline: the in-situ device benchmark
``/root/reference/veles/accelerated_units.py:706-825`` (min-of-N timed
kernel chain) and the ``--sync-run`` timing-accuracy note
(``accelerated_units.py:294-297``).
"""

import time

import jax
import jax.numpy as jnp
import numpy


def host_fetch(x):
    """Force true device synchronization by copying ``x``'s bytes to the
    host: the returned numpy values physically cannot exist before the
    program that produces them has run."""
    return numpy.asarray(jax.device_get(x))


def _first_scalar(tree):
    """A float32 scalar depending on the first array leaf of ``tree``."""
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = jnp.asarray(leaf)
        return arr.astype(jnp.float32).ravel()[0]
    return jnp.float32(0.0)


def probe_of(params, metric):
    """A small vector whose bytes depend on the final params AND the
    final metric — stacked (not summed-with-*0, which an optimizer could
    fold away) so neither dependency can be eliminated."""
    return jnp.stack([_first_scalar(metric), _first_scalar(params)])


def cost_flops(compiled):
    """Total FLOPs of a compiled executable per XLA's own cost
    analysis, or None when unavailable."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca.get("flops", 0.0)) or None
    except Exception:
        return None


def make_multi_step(step_fn, k=None):
    """Wrap ``step_fn(params, x, labels) -> (params, metric)`` into a
    function running several steps inside one XLA program.

    With ``k`` given, the trip count is baked in.  Without it, the
    wrapper takes a fourth *runtime* ``n_steps`` argument, so ONE
    compiled program can be timed at two different step counts — the
    basis of :func:`measure_fused_step`'s in-program marginal timing.

    The first step runs inline (establishing the carry structure, since
    the metric pytree's shapes/dtypes are only known by tracing one
    step); the rest run under ``lax.fori_loop``.  Returns
    ``(params, probe)`` with ``probe`` from :func:`probe_of`.
    """
    if k is not None and k < 1:
        raise ValueError("k must be >= 1, got %d" % k)

    def multi(params, x, labels, *n_steps):
        carry = step_fn(params, x, labels)

        def body(_i, carry):
            p, _m = carry
            return step_fn(p, x, labels)

        hi = (k - 1) if k is not None else n_steps[0] - 1
        params, metric = jax.lax.fori_loop(0, hi, body, carry)
        return params, probe_of(params, metric)

    return multi


def inprogram_marginal(unit_fn, init_carry, k1=8, k2=64, repeats=3,
                       max_retries=2, target_signal=0.25, max_k=100000,
                       stats=None):
    """Marginal seconds per ``unit_fn`` application, measured INSIDE one
    XLA program.

    ``unit_fn(carry) -> carry`` is looped ``n`` times under
    ``lax.fori_loop`` with ``n`` a *runtime* argument, so ONE compiled
    executable is timed at two trip counts and the marginal
    ``(t(k2) - t(k1)) / (k2 - k1)`` cancels the per-program
    dispatch + fetch overhead exactly, which timing across program
    launches does not.

    Sync per measurement is a host fetch of a carry-derived scalar
    (:func:`host_fetch`).

    The trip count is a runtime argument, so after a rough first
    marginal the long point is widened (no recompile) until the timing
    signal ``(k2 - k1) * marginal`` reaches ``target_signal`` seconds —
    tiny units (a 1024³ matmul is ~20 µs) would otherwise drown in the
    host's timing jitter.
    """
    if not k2 > k1 >= 1:
        raise ValueError("need k2 > k1 >= 1, got %r %r" % (k1, k2))

    def prog(carry, n):
        carry = jax.lax.fori_loop(0, n, lambda _i, c: unit_fn(c), carry)
        return _first_scalar(carry)

    # device-resident carry: host numpy leaves would be re-uploaded on
    # every timed launch (see measure_fused_step's identical guard)
    init_carry = jax.device_put(init_carry)
    compiled = jax.jit(prog).lower(
        init_carry, numpy.int32(k1)).compile()
    host_fetch(compiled(init_carry, jax.device_put(numpy.int32(k2))))

    def timed(n):
        best = float("inf")
        arg = jax.device_put(numpy.int32(n))
        for _ in range(repeats):
            tic = time.perf_counter()
            host_fetch(compiled(init_carry, arg))
            best = min(best, time.perf_counter() - tic)
        return best

    return _two_point_marginal(timed, k1, k2, target_signal, max_k,
                               attempts=max_retries + 2,
                               label="inprogram_marginal", stats=stats)


def _two_point_marginal(timed, k1, k2, target_signal, max_k,
                        attempts=4, label="two_point_marginal",
                        stats=None):
    """Shared widen/retry core of the two-trip-count stopwatch.

    ``timed(n)`` = best-of-repeats wall seconds of ONE program doing
    ``n`` work units.  Widens ``k2`` (no recompile — the trip count is
    a runtime arg) until the signal ``(k2 - k1) * marginal`` reaches
    ``target_signal``; doubles it when noise swamps the gap.  A
    ``FloatingPointError`` from a widened run (weights gone non-finite
    at the longer horizon) falls back to the last positive marginal,
    which is still a valid measurement.

    The short point anchors EVERY marginal, so it is sampled twice up
    front, re-timed on every retry, and always taken as the min — one
    transient stall in a single ``t1`` sample would otherwise skew all
    subsequent marginals.

    ``stats``, when a dict, receives the measurement's provenance:
    final ``k1/k2/t1/t2/marginal``, ``t1_samples`` count, and
    ``t1_rel_spread`` = (max−min)/min over the short-point samples — a
    noise signature persisted next to DB ratings so stale/noisy
    entries are detectable."""
    best = None
    best_pt = None          # the exact (t1, t2, k2) that produced best
    t1_samples = [timed(k1), timed(k1)]

    def _record(marginal, pt):
        if stats is not None:
            t1_used, t2_used, k2_used = pt
            lo, hi = min(t1_samples), max(t1_samples)
            stats.update({
                "k1": k1, "k2": k2_used, "t1": t1_used, "t2": t2_used,
                "t1_samples": len(t1_samples),
                "t1_rel_spread": ((hi - lo) / lo) if lo > 0 else None,
                "marginal": marginal})
        return marginal

    for _attempt in range(attempts):
        try:
            if _attempt:
                # paranoid short point: re-time on retry, min wins
                t1_samples.append(timed(k1))
            t1 = min(t1_samples)
            t2 = timed(k2)
        except FloatingPointError:
            # weights gone non-finite at a longer horizon (either
            # point): the last positive marginal is still valid
            if best is not None:
                return _record(best, best_pt)
            raise
        marginal = (t2 - t1) / (k2 - k1)
        if marginal > 0:
            best, best_pt = marginal, (t1, t2, k2)
            if (k2 - k1) * marginal >= target_signal or k2 >= max_k:
                return _record(marginal, best_pt)
            k2 = min(k1 + int(numpy.ceil(target_signal / marginal)),
                     max_k)
        else:
            k2 = min(k2 * 2, max_k)   # noise swamped the gap — widen it
    if best is not None:
        return _record(best, best_pt)
    raise RuntimeError(
        "%s: non-positive marginal (%.6fs at k2=%d) — timing "
        "environment too noisy" % (label, marginal, k2))


def marginal_time(call, min_seconds=2.0, max_calls=10000):
    """Marginal seconds per ``call()``.

    ``call`` must dispatch the work asynchronously; ``call(sync=True)``
    must additionally block until everything dispatched so far has
    truly finished (host fetch).  Times ``n1`` calls and ``n2 > n1``
    calls (scaled so the long run spans ``min_seconds``) and returns
    ``(t2 - t1) / (n2 - n1)`` — the fixed per-measurement overhead
    cancels.
    """
    call(sync=True)                      # warm (compile paths already hot)

    def run(n):
        tic = time.perf_counter()
        for _ in range(n - 1):
            call()
        call(sync=True)
        return time.perf_counter() - tic

    n1 = 1
    for attempt in range(3):
        t1 = run(n1)
        per = max(t1 / n1, 1e-9)
        n2 = int(min(max(n1 * 2, min_seconds / per), max_calls))
        t2 = run(n2)
        marginal = (t2 - t1) / (n2 - n1)
        if marginal > 0:
            return marginal
        # t1 noise exceeded t2 — a failed measurement, never a result
        # (clamping here once published 1.8e21 GFLOPs downstream);
        # lengthen the long run and retry
        min_seconds *= 2.0
    raise RuntimeError(
        "marginal_time: non-positive marginal (%.6fs over %d calls) "
        "after 3 attempts — timing environment too noisy" % (
            marginal, n2 - n1))


def measure_fused_step(step_fn, params, x, labels, k=20,
                       min_seconds=None, donate=False, repeats=3,
                       flops_override=None, stats=None):
    """Measure honest seconds per single ``step_fn`` application.

    ONE program loops the step with a *runtime* trip count
    (:func:`make_multi_step` with ``k=None``); it is timed at trip
    counts ``k1 = max(1, k // 4)`` and ``k2 = k`` and the marginal
    ``(t2 - t1) / (k2 - k1)`` is the per-step time — the per-program
    dispatch/fetch overhead cancels exactly (see
    ``inprogram_marginal``).  Sync is a host fetch of a
    result-derived probe; non-finite probes abort the measurement.

    Returns ``(sec_per_step, flops_per_step)``.  ``flops_per_step`` is
    XLA's cost analysis of the loop program divided by 2: XLA counts a
    while-loop body ONCE regardless of trip count, so the program's
    total is the inline first step + the body = exactly two steps'
    FLOPs (dividing by K, as before round 3, under-reported FLOPs — and
    MFU — by ~K/2×).

    CAVEAT: the same counted-once rule applies to loops INSIDE the
    step.  A step containing an inner ``lax.scan``/``while_loop`` (an
    LSTM's T-step sequence scan, the grad-accum microbatch scan) has
    its inner body counted once, so cost-analysis FLOPs — and the MFU
    derived from them — underreport by roughly the inner trip count.
    For such steps pass ``flops_override`` with an analytic per-step
    count (e.g. :func:`veles_tpu.znicz.rnn.lstm_train_flops`); it is
    returned as ``flops_per_step`` in place of the cost-analysis value.
    ``min_seconds`` is accepted for backward compatibility and ignored:
    the two-trip-count marginal replaces wall-clock budgeting.
    """
    if donate:
        raise ValueError(
            "measure_fused_step: donation is incompatible with the "
            "two-trip-count timing, which re-runs the program from the "
            "same params buffers; pass donate=False")
    k = max(int(k), 2)
    # Pin every operand on device BEFORE timing: host-resident numpy
    # params (lower_specs returns them) would otherwise be re-uploaded
    # on EVERY timed launch — ~0.5 GB/launch for AlexNet, whose
    # transfer time and jitter would swamp the two-point marginal.
    params, x, labels = jax.device_put((params, x, labels))
    multi = make_multi_step(step_fn)          # dynamic trip count
    jitted = jax.jit(multi)
    compiled = jitted.lower(params, x, labels,
                            numpy.int32(k)).compile()
    if flops_override:
        flops = float(flops_override)
    else:
        total = cost_flops(compiled)
        flops = (total / 2.0) if total else None

    k1, k2 = max(1, k // 4), k

    def timed(n):
        best = float("inf")
        arg = jax.device_put(numpy.int32(n))
        for _ in range(repeats):
            tic = time.perf_counter()
            _p, probe = compiled(params, x, labels, arg)
            vals = host_fetch(probe)
            elapsed = time.perf_counter() - tic
            if not numpy.all(numpy.isfinite(vals)):
                raise FloatingPointError(
                    "non-finite probe during timing: %r" % (vals,))
            best = min(best, elapsed)
        return best

    host_fetch(compiled(params, x, labels,
                        jax.device_put(numpy.int32(k1)))[1])     # warm
    # 0.5 s of signal over the host's jitter; widening capped at 20·k
    # steps (more steps = more weight drift on synthetic data = NaN
    # risk, which _two_point_marginal absorbs by falling back)
    marginal = _two_point_marginal(timed, k1, k2, target_signal=0.5,
                                   max_k=max(k2, 20 * k),
                                   label="measure_fused_step",
                                   stats=stats)
    return marginal, flops
