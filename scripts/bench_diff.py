#!/usr/bin/env python
"""bench_diff — the perf-regression watchdog over bench.py records.

A *bank* is one or more JSON files of ``bench.py`` stdout records
(metric / value / unit / mfu / sec_per_step / device_kind / ...), raw
or wrapped in ``{"parsed": {...}}`` envelopes.  This script is the
GATE over one: compare a fresh ``bench.py`` run (or any saved JSONL of
its stdout lines) against the bank per metric and exit non-zero on any
regression beyond the tolerance.  The repo ships no bank of its own —
name one with ``--banked`` — only the small SYNTHETIC envelope
``tests/fixtures/bench_envelope.json`` that ``--selftest`` validates
the comparator against.

Comparison model, per metric name (records are matched by ``metric``
AND ``device_kind`` — a CPU smoke is never judged against a banked TPU
line; ``--ignore-device`` overrides):

* ``value`` — direction inferred from ``unit`` (throughput units are
  higher-better; ``sec``/``ms``/``latency`` units lower-better);
  regression when worse than banked by more than ``--tolerance``
  (relative).
* ``mfu`` — higher-better, same tolerance.
* ``sec_per_step`` — lower-better, same tolerance.
* ``recompiles`` / ``dispatches_per_epoch`` — hard counters: any
  increase over the banked value is a regression (zero tolerance; a
  recompile that "only" costs 5% today is a compile-cache bug either
  way).
* ``steps_per_dispatch`` — lower than banked by more than the
  tolerance is a regression (the one-dispatch-epoch win eroding).
* ``vs_bf16_x`` (higher-better) / ``hbm_per_request_bytes``
  (lower-better) — the ``stage_transformer_gen`` int8 + long-tail
  columns: the quantized-serving throughput win and the per-request
  HBM footprint, gated so the int8 win is a number from round one.

Usage::

    python scripts/bench_diff.py --fresh run.jsonl          # gate a run
    python scripts/bench_diff.py --run                      # run bench.py now
    python scripts/bench_diff.py --selftest                 # CI self-test
    python scripts/bench_diff.py --fresh - < run.jsonl      # stdin

When several banked records share a (metric, device kind), the NEWEST (by
in-band ``ts``, falling back to file order) wins — the envelope is the
latest accepted performance, not the best-ever (hardware sessions
differ; the newest banked line is the one the current code was
accepted against).  The envelope keys by the PAIR, so a newer line
from another device never evicts the matching-device gate.

Exit codes: 0 = no regression, 1 = regression(s) (each printed as
``REGRESSION <metric> <field>: fresh X vs banked Y (limit Z)``),
2 = usage/infrastructure error (no comparable pairs is NOT an error —
it prints a warning and exits 0, so a CPU container passes against a
TPU-only bank without faking numbers).
"""

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: the synthetic envelope ``--selftest`` runs over
SELFTEST_ENVELOPE = os.path.join(REPO_ROOT, "tests", "fixtures",
                                 "bench_envelope.json")

#: unit substrings that mean lower-is-better for ``value`` — checked
#: only after the rate forms ("images/sec", "tokens/s") claim
#: higher-is-better.  The rate check must NOT treat "sec/step" as a
#: rate ("/s" is a substring of "/step"), hence the endswith form.
_LOWER_BETTER_UNITS = ("sec", "ms", "latency", "/step", "bytes")


def _is_rate_unit(unit):
    return "/sec" in unit or unit.endswith("/s") or "per sec" in unit

#: hard counters: any increase over banked is a regression
_COUNTERS = ("recompiles", "dispatches_per_epoch")

#: soft fields beyond ``value`` compared with the relative tolerance
#: (vs_bf16_x: the int8 serving win over the same-run bf16 engine;
#: hbm_per_request_bytes: the paged/int8 capacity win — both from
#: the stage_transformer_gen int8/long-tail records;
#: ttft_p99_ms / handoff_bytes_per_request / autoscaler_actions: the
#: disagg-fleet record — latency under the 500 ms SLO, wire cost per
#: request, and control-loop churn are all regressions when they
#: grow)
#: vs_baseline joins the higher-better set for the same-run A/B
#: stages (transformer_lm_train: fused kernels over the XLA-kernel
#: baseline measured in the SAME process — the ratio eroding means
#: the fused path lost ground even if absolute throughput moved)
#: prefix_hit_rate / spec_accept_rate / vs_nonspec_x: the
#: prefix-cache + speculative-decode record — pages served from the
#: radix tree, drafted tokens the verify accepted, and the
#: tokens/s win over the same-run plain paged line all regress when
#: they fall
_HIGHER_BETTER_FIELDS = ("mfu", "steps_per_dispatch", "vs_bf16_x",
                         "vs_baseline", "prefix_hit_rate",
                         "spec_accept_rate", "vs_nonspec_x")
#: bubble_fraction / all_to_all_bytes_per_step: the pod pp/ep stages —
#: the GPipe ramp/drain idle share and the per-step expert-exchange
#: traffic are both pure cost; either growing means the pipeline
#: schedule or the routing buffers regressed
_LOWER_BETTER_FIELDS = ("sec_per_step", "hbm_per_request_bytes",
                        "ttft_p99_ms", "handoff_bytes_per_request",
                        "autoscaler_actions", "bubble_fraction",
                        "all_to_all_bytes_per_step")


def value_direction(record):
    """+1 = higher better, -1 = lower better, from the unit string."""
    unit = str(record.get("unit", "")).lower()
    if _is_rate_unit(unit):
        return 1
    if any(tag in unit for tag in _LOWER_BETTER_UNITS):
        return -1
    return 1


def iter_records(payload):
    """Yield bench stdout records (dicts with a ``metric`` key) from
    any of the shapes they are stored in: a raw record, a
    ``{"parsed": ...}`` envelope, or a list of either."""
    if isinstance(payload, list):
        for item in payload:
            yield from iter_records(item)
        return
    if not isinstance(payload, dict):
        return
    if "metric" in payload:
        yield payload
        return
    parsed = payload.get("parsed")
    if parsed is not None:
        yield from iter_records(parsed)


def load_banked(paths):
    """``{(metric, device_kind): record}`` — newest banked record per
    (metric, device kind) pair (in-band ``ts`` first, file order as
    the tiebreak).  Keying by the PAIR matters: a newer banked line
    from a different device must not evict the matching-device
    envelope and silently un-gate that metric."""
    envelope = {}
    order = {}
    for rank, path in enumerate(paths):
        try:
            with open(path, "r") as fin:
                payload = json.load(fin)
        except (OSError, ValueError) as exc:
            print("bench_diff: cannot read %s: %s" % (path, exc),
                  file=sys.stderr)
            continue
        for record in iter_records(payload):
            metric = record.get("metric")
            if not metric:
                continue
            key = (metric, record.get("device_kind"))
            stamp = (record.get("ts") or 0, rank)
            if key not in envelope or stamp >= order[key]:
                envelope[key] = record
                order[key] = stamp
    return envelope


def _bank_lookup(banked, metric, device_kind, ignore_device=False):
    """The envelope record a fresh record gates against: the exact
    (metric, device_kind) entry, or — under ``ignore_device`` — the
    newest banked record for the metric across devices."""
    if not ignore_device:
        return banked.get((metric, device_kind))
    best, best_ts = None, None
    for (m, _d), record in banked.items():
        if m != metric:
            continue
        ts = record.get("ts") or 0
        if best is None or ts >= best_ts:
            best, best_ts = record, ts
    return best


def load_fresh(stream):
    """Bench stdout lines (JSONL; non-JSON lines are bench chatter and
    skipped) → list of records."""
    records = []
    for line in stream:
        line = line.strip()
        if not line or not line.startswith("{"):
            continue
        try:
            payload = json.loads(line)
        except ValueError:
            continue
        records.extend(iter_records(payload))
    return records


def _rel_worse(fresh, banked, direction):
    """How much worse (fraction of banked) ``fresh`` is; <= 0 means
    no regression."""
    if banked == 0:
        return 0.0
    return direction * (banked - fresh) / abs(banked)


def compare(fresh_records, banked, tolerance=0.1, ignore_device=False):
    """Return ``(regressions, compared)``: regression message lines
    and the number of (metric, field) pairs actually compared."""
    regressions = []
    compared = 0
    for record in fresh_records:
        metric = record.get("metric")
        bank = _bank_lookup(banked, metric,
                            record.get("device_kind"),
                            ignore_device=ignore_device)
        if bank is None:
            continue

        def _soft(field, direction, fresh_v, bank_v):
            worse = _rel_worse(float(fresh_v), float(bank_v),
                               direction)
            if worse > tolerance:
                regressions.append(
                    "REGRESSION %s %s: fresh %.6g vs banked %.6g "
                    "(%.1f%% worse, tolerance %.1f%%)"
                    % (metric, field, float(fresh_v), float(bank_v),
                       100.0 * worse, 100.0 * tolerance))

        if isinstance(record.get("value"), (int, float)) \
                and isinstance(bank.get("value"), (int, float)):
            compared += 1
            _soft("value", value_direction(bank), record["value"],
                  bank["value"])
        for field in _HIGHER_BETTER_FIELDS:
            if isinstance(record.get(field), (int, float)) \
                    and isinstance(bank.get(field), (int, float)):
                compared += 1
                _soft(field, 1, record[field], bank[field])
        for field in _LOWER_BETTER_FIELDS:
            if isinstance(record.get(field), (int, float)) \
                    and isinstance(bank.get(field), (int, float)):
                compared += 1
                _soft(field, -1, record[field], bank[field])
        for field in _COUNTERS:
            if isinstance(record.get(field), (int, float)) \
                    and isinstance(bank.get(field), (int, float)):
                compared += 1
                if float(record[field]) > float(bank[field]):
                    regressions.append(
                        "REGRESSION %s %s: fresh %g vs banked %g "
                        "(hard counter, zero tolerance)"
                        % (metric, field, float(record[field]),
                           float(bank[field])))
    return regressions, compared


def run_bench(stages=None):
    """Run ``bench.py`` in a child and return its stdout records."""
    import subprocess
    env = dict(os.environ)
    if stages:
        env["BENCH_STAGES"] = stages
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py")],
        capture_output=True, text=True, env=env, cwd=REPO_ROOT)
    sys.stderr.write(proc.stderr[-2000:])
    if proc.returncode:
        print("bench_diff: bench.py exited %d" % proc.returncode,
              file=sys.stderr)
        sys.exit(2)
    return load_fresh(proc.stdout.splitlines())


def selftest(banked_paths, tolerance):
    """The CI self-test over the synthetic fixture envelope:

    1. banked-vs-banked must report ZERO regressions (the gate would
       otherwise fail every honest re-run);
    2. a synthetically degraded copy (throughput halved, MFU halved,
       recompiles bumped) must be caught on every degraded field;
    3. a device_kind mismatch must be skipped, not compared.
    """
    banked = load_banked(banked_paths)
    if not banked:
        print("bench_diff selftest: FAIL — no banked records under %r"
              % (banked_paths,), file=sys.stderr)
        return 1
    records = list(banked.values())
    regressions, compared = compare(records, banked,
                                    tolerance=tolerance)
    if regressions or not compared:
        print("bench_diff selftest: FAIL — banked-vs-banked: %d "
              "compared, regressions %r" % (compared, regressions),
              file=sys.stderr)
        return 1
    degraded = []
    expect = 0
    for record in records:
        bad = dict(record)
        if isinstance(bad.get("value"), (int, float)):
            bad["value"] = bad["value"] * (2.0 if value_direction(
                bad) < 0 else 0.5)
            expect += 1
        if isinstance(bad.get("mfu"), (int, float)):
            bad["mfu"] = bad["mfu"] * 0.5
            expect += 1
        bad["recompiles"] = float(bad.get("recompiles", 0) or 0) + 5
        if isinstance(record.get("recompiles"), (int, float)):
            expect += 1
        degraded.append(bad)
    regressions, _ = compare(degraded, banked, tolerance=tolerance)
    if len(regressions) < expect:
        print("bench_diff selftest: FAIL — degraded run: %d "
              "regression(s) caught, expected >= %d:\n%s"
              % (len(regressions), expect, "\n".join(regressions)),
              file=sys.stderr)
        return 1
    moved = [dict(record, device_kind="somewhere-else")
             for record in records]
    regressions, compared = compare(
        [dict(r, value=0.0) for r in moved], banked,
        tolerance=tolerance)
    if compared or regressions:
        print("bench_diff selftest: FAIL — device mismatch was "
              "compared anyway", file=sys.stderr)
        return 1
    print("bench_diff selftest: OK — %d banked envelope line(s), "
          "degraded copies caught on %d field(s), device mismatch "
          "skipped" % (len(banked), expect))
    return 0


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="bench_diff",
        description="gate a bench.py run against a bank of "
                    "earlier bench.py records")
    parser.add_argument("--banked", nargs="*", default=None,
                        metavar="FILE",
                        help="banked envelope files (required except "
                             "for --selftest, which defaults to "
                             "tests/fixtures/bench_envelope.json)")
    parser.add_argument("--fresh", metavar="FILE",
                        help="a saved bench.py stdout (JSONL); '-' "
                             "reads stdin")
    parser.add_argument("--run", action="store_true",
                        help="run bench.py now and gate its output")
    parser.add_argument("--stages", default=None,
                        help="BENCH_STAGES for --run")
    parser.add_argument("--tolerance", type=float, default=0.1,
                        help="relative tolerance for soft fields "
                             "(default 0.10)")
    parser.add_argument("--ignore-device", action="store_true",
                        help="compare across device kinds (A/B on "
                             "different hardware is lying with "
                             "numbers; you were warned)")
    parser.add_argument("--selftest", action="store_true",
                        help="validate the comparator against the "
                             "synthetic fixture envelope (CI)")
    ns = parser.parse_args(argv)
    if ns.selftest:
        return selftest(ns.banked or [SELFTEST_ENVELOPE], ns.tolerance)
    if not ns.banked:
        parser.error("--banked FILE... is required (the repo ships no "
                     "bank of measurements)")
    banked_paths = ns.banked
    if ns.run:
        fresh = run_bench(ns.stages)
    elif ns.fresh == "-":
        fresh = load_fresh(sys.stdin)
    elif ns.fresh:
        with open(ns.fresh, "r") as fin:
            fresh = load_fresh(fin)
    else:
        parser.error("one of --fresh/--run/--selftest is required")
        return 2
    banked = load_banked(banked_paths)
    regressions, compared = compare(
        fresh, banked, tolerance=ns.tolerance,
        ignore_device=ns.ignore_device)
    if regressions:
        print("\n".join(regressions))
        print("bench_diff: %d regression(s) over %d comparison(s)"
              % (len(regressions), compared))
        return 1
    if not compared:
        print("bench_diff: WARNING — no comparable (metric, "
              "device_kind) pairs between the fresh run (%d record(s))"
              " and the bank (%d envelope line(s)); nothing gated"
              % (len(fresh), len(banked)))
        return 0
    print("bench_diff: OK — %d comparison(s) within tolerance %.1f%%"
          % (compared, 100.0 * ns.tolerance))
    return 0


if __name__ == "__main__":
    sys.exit(main())
