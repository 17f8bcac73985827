#!/usr/bin/env python3
"""Run ONE cell of the benchmark once and print its result.

    python3 benchmarks/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in
a traced run), then ``compared``: every number that decided ``correct``
beside its limit.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics.

There is no CPU mode: without a TPU holding the chips the cell asks for
the run exits non-zero and prints no result.  ``--rehearse`` (the tests
use it) runs the configuration's ``rehearsal`` sizes on whatever JAX
finds and prints NO number under a metric's name: every value is null,
and what the host counted is under ``rehearsal``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def parse(argv):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument(
        "--cell-file", default=None,
        help="a workload file that BENCHMARK.json does not list yet "
             "(rehearsing a cell before it is added)")
    parser.add_argument(
        "--keep-trace", default=None,
        help="write the traced window's extracted intervals and the "
             "trace's listing to this JSON file (how the test fixture "
             "was made)")
    return parser.parse_args(argv)


def device_facts(jax):
    devices = jax.devices()
    peak = 0
    for device in devices:
        stats = device.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def layer_metrics(harness, spec, cell, view):
    """Every per-layer metric the manifest lists for this cell, through
    its own reader; one that finds nothing to read is left out."""
    out = {}
    for metric in spec["per_layer"]:
        if "workloads" in metric and cell not in metric["workloads"]:
            continue
        entry = harness.load_json(HERE, "layer_metrics",
                                  metric["name"] + ".json")
        reader = importlib.import_module(
            "benchmarks.readers." + entry["reader"])
        value = reader.read(dict(view, args=entry.get("args", {})))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import end_to_end, harness, trace_reduce
    entry, params, config = harness.load_cell(
        args.workload, args.rehearse, args.cell_file)
    try:
        jax = harness.start_jax()

        import veles_tpu  # noqa: F401 - the system under test
    except ImportError as exc:
        print("benchmarks/run.py needs the veles_tpu checkout it lives "
              "in, and JAX: %s" % exc, file=sys.stderr)
        return 2
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print("benchmarks/run.py: JAX found no device: %s" % exc,
              file=sys.stderr)
        return 2
    platform = devices[0].platform
    if not args.rehearse and (platform != "tpu"
                              or len(devices) < entry["chips"]):
        print("benchmarks/run.py: cell %s needs %d TPU chip(s); JAX "
              "found %d x %s" % (entry["name"], entry["chips"],
                                 len(devices), platform), file=sys.stderr)
        return 2
    harness.enable_compile_cache(platform)
    peaks = None if platform != "tpu" \
        else harness.peaks_for(devices[0].device_kind)

    ctx = harness.Context(entry, params, config, args.seed, args.seconds,
                          args.trace, args.rehearse)
    driver = harness.load_driver(config).Run(
        ctx, harness.load_reference(config))
    obs = driver.run()
    setup_s = obs["t_open"] - T0
    device = device_facts(jax)

    trace = None
    if args.trace and ctx.tracer.done and platform == "tpu":
        xplane = trace_reduce.find_xplane(ctx.tracer.log_dir)
        extracted = trace_reduce.extract(xplane)
        if args.keep_trace:
            with open(args.keep_trace, "w") as handle:
                json.dump({"listing": trace_reduce.listing(xplane),
                           "extracted": extracted}, handle)
        trace = trace_reduce.reduce(extracted)
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]

    driver.release()
    tic = time.perf_counter()
    checks = driver.verify()
    correct, compared = harness.judge(checks, params["limits"])
    ctx.log("set-up %.3f s, window %.3f s, comparison %.3f s"
            % (setup_s, obs["window_s"], time.perf_counter() - tic))

    spec = harness.manifest()
    if args.trace:
        view = {"obs": obs, "trace": trace, "peaks": peaks,
                "config": config}
        metrics = layer_metrics(harness, spec, entry["name"], view)
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        metrics = {}
        for name in params["reports"]:
            value = setup_s if name == "setup_s" \
                else end_to_end.METRICS[name](obs)
            if value is not None:
                metrics[name] = {"value": value, "unit": units[name]}
    result = {"correct": bool(correct), "attempted": obs["attempted"],
              "failed": obs["failed"]}
    if platform != "tpu":
        # a number from a CPU run is never written under a metric's name
        result["rehearsal"] = {name: m["value"]
                               for name, m in metrics.items()}
        metrics = {name: {"value": None, "unit": m["unit"]}
                   for name, m in metrics.items()}
    result["metrics"] = metrics
    result["device"] = device
    if trace is not None:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["compared"] = compared
    for name, pair in compared.items():
        print("compared %s = %r (limit %r)"
              % (name, pair["value"], pair["limit"]), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
