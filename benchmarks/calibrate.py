#!/usr/bin/env python3
"""Readings for the limits and the rate, made on the chip, many seeds in
ONE process (set-up is most of a run):

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 5 [--controls] [--rates 3,4,5] [--override '{...}']

Per seed one JSON line: the numbers that decide ``correct`` as the
timed path gives them (``program``), with ``--controls`` the same
numbers from the reference in the lower precision and from each planted
fault (``controls``), and the window's end-to-end readings.  With
``--rates`` the cell's arrival rate is replaced by each rate in turn
(the sweep for the knee) and nothing is compared.  The benchmark's own
runs never come through here.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    parser.add_argument("--controls", action="store_true")
    parser.add_argument("--rates", default=None)
    parser.add_argument("--override", default="{}")
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import end_to_end, harness
    jax = harness.start_jax()
    if not args.rehearse and jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache(jax.devices()[0].platform)
    entry, params, config = harness.load_cell(args.workload, args.rehearse)
    override = json.loads(args.override)
    config = harness.merge(config, override.get("config", {}))
    params = harness.merge(params, override.get("params", {}))
    rates = [float(r) for r in args.rates.split(",")] if args.rates \
        else [None]
    for seed in (int(s) for s in args.seeds.split(",")):
        for rate in rates:
            cell = params if rate is None else harness.merge(
                params, {"traffic_spec": {"arrivals": {"rate_rps": rate}}})
            tic = time.perf_counter()
            ctx = harness.Context(entry, cell, config, seed, args.seconds,
                                  0, args.rehearse)
            run = harness.load_driver(config).Run(
                ctx, harness.load_reference(config))
            obs = run.run()
            line = {"seed": seed, "rate_rps": rate,
                    "setup_s": obs["t_open"] - tic,
                    "window_s": obs["window_s"],
                    "attempted": obs["attempted"],
                    "failed": obs["failed"]}
            for name in cell["reports"]:
                if name != "setup_s":
                    line[name] = end_to_end.METRICS[name](obs)
            for key in ("counters", "generator_late_s", "drain_s"):
                if key in obs:
                    line[key] = obs[key]
            if "ttft_s" in obs:
                line["ttft_p50_ms"] = 1e3 * end_to_end.percentile(
                    obs["ttft_s"], 50)
                line["ttft_p95_ms"] = end_to_end.ttft_p95_ms(obs)
            stats = jax.devices()[0].memory_stats() or {}
            line["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
            run.release()
            if rate is None:
                line["program"] = run.verify()
                if hasattr(run, "leaves"):
                    line["program_leaves"] = run.leaves
                if args.controls:
                    line["controls"] = run.controls()
                    if hasattr(run, "control_leaves"):
                        line["control_leaves"] = run.control_leaves
            print(json.dumps(line), flush=True)
            del run, obs
    return 0


if __name__ == "__main__":
    sys.exit(main())
