#!/usr/bin/env bash
# Self-lint veles_tpu/ with the analyze lint pack (pass 3) — the same
# invocation the tier-1 suite gates on (test_analyze.py::
# test_lint_self_clean_tier1); the default path is the whole installed
# package, so the veles_tpu/trace/ observability subsystem self-lints
# here too.  Then run the workflow analyzer (graph doctor + JAX hazard
# pass, V-J06/V-J08 included) over the samples/ demo modules that
# build a real training graph; warnings print, errors fail.
# samples/analyze_demo is deliberately broken (it exercises the rule
# catalog) and is covered by test_analyze.py instead.
# Extra args pass through to the lint invocation, e.g.
#   scripts/lint.sh --json
#   scripts/lint.sh path/to/other/package
set -euo pipefail
cd "$(dirname "$0")/.."
if [ "$#" -gt 0 ]; then
  # passthrough mode (--json, explicit paths): keep the output pure —
  # machine consumers parse it
  exec env JAX_PLATFORMS=cpu python -m veles_tpu.analyze --lint "$@"
fi
env JAX_PLATFORMS=cpu python -m veles_tpu.analyze --lint
# mnist_conv + cifar10 exercise the loader-headed stitch stage (the
# device-resident input pipeline, V-J07) on conv-shaped workflows;
# the analyzer runs with the full rule set, V-J08..V-J11 included
# (V-J11: host-side finiteness probes — the samples must stay silent,
# the in-program health knob being the prescribed remedy)
for sample in veles_tpu.samples.mnist veles_tpu.samples.mnist_ae \
              veles_tpu.samples.mnist_conv veles_tpu.samples.cifar10; do
  echo "== analyze $sample =="
  env JAX_PLATFORMS=cpu python -m veles_tpu.analyze "$sample"
done
# profiler smoke: a short stitched mnist run must leave non-zero
# per-segment flops in the ledger, a parseable perf_report(), every
# compile fingerprinted and ZERO steady-state recompiles
echo "== prof smoke (veles_tpu.samples.mnist) =="
env JAX_PLATFORMS=cpu python -m veles_tpu.prof --smoke veles_tpu.samples.mnist
# epoch-scan smoke: a stitched mnist run under engine.epoch_scan=auto
# must fold K steps per dispatch — host dispatches <= ceil(steps/K) +
# one per class span in trace_report()'s host-gap split — with ZERO
# steady-state recompiles and the V-J10 rule silent over the sample
# workflow (docs/engine_fast_path.md § Epoch mode)
echo "== epoch smoke (one-dispatch-epoch gate) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu \
  python -m veles_tpu.epoch_scan --smoke veles_tpu.samples.mnist
# chaos smoke: a fixed-seed master–slave session over real ZMQ with an
# injected slave death, a dropped job frame and a duplicated update
# frame must COMPLETE — no hang (timeout-wrapped), every job applied
# exactly once, dedup/requeue counters consistent (docs/robustness.md)
echo "== chaos smoke (fault-injection gate) =="
timeout -k 10 120 env JAX_PLATFORMS=cpu python -m veles_tpu.chaos --smoke
# generative serving smoke: warmup must cover every prefill bucket +
# the decode program, then a seeded mixed-length continuous-batching
# session completes with ZERO steady-state compiles (the recompile
# sentinel stays quiet) and every request at exactly its token budget;
# a second PAGED session (block-pool KV, chunked prefill, pool sized
# below the working set) must reproduce the contiguous token streams
# EXACTLY while exercising and recovering >=1 pool-exhaustion
# preemption — the lossless-preemption gate (docs/services.md § Paged
# KV); a third INT8 session (deploy-time per-channel weight
# quantization, the qgemm dequant-epilogue path) must complete the
# same budgets with zero steady-state compiles, a params footprint
# <=0.35x its float twin and the calibration drift gate green
# (docs/services.md § Quantized serving); a fourth PREFIX+SPEC
# session (radix prefix cache + n-gram speculative decode) must
# bitwise-match a plain paged session on a shared-prefix workload
# while actually sharing pages across live slots, evicting only
# cache-only pages and accepting drafted tokens (docs/services.md
# § Prefix cache & speculative decode)
echo "== gen smoke (generative serving + paged KV gate) =="
timeout -k 10 180 env JAX_PLATFORMS=cpu python -m veles_tpu.gen --smoke
# obs smoke: the fleet-observability gate — with tracing off every
# obs hook must be the PR 5 one-attribute-check no-op; then ONE
# traced request must cross server -> scheduler -> engine -> a
# scripted master/slave ZMQ session with its trace id in >=3 role
# lanes of one prof-merged Perfetto timeline (flow arrows included),
# the master scrape endpoint must serve the per-slave round-trip
# histograms, and SLO evaluation over a synthetic breaching series
# must fire exactly the expected multi-window burn alerts
# (docs/observability.md § Request tracing & SLOs)
echo "== obs smoke (request tracing + SLO gate) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m veles_tpu.obs --smoke
# watch smoke: the training-health + live-bus gate — one traced
# stitched session under engine.health=on must publish >=4 distinct
# event kinds (run/epoch/health/perf) consumed by a LIVE bus
# subscriber with finite per-param-group stats; an injected NaN under
# health=strict must raise a typed HealthError naming the poisoned
# param group; and a record/replay ndjson roundtrip must reproduce
# the session exactly (docs/observability.md § Training health &
# live watch)
echo "== watch smoke (training-health telemetry + live bus gate) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m veles_tpu.watch --smoke
# ops smoke: the training-kernel gate — interpret-mode parity oracles
# for every Pallas family (fused backward-GD incl. optimizer epilogue,
# gather+normalize loader head, flash-attention fwd+bwd custom_vjp),
# a toy autotune_gd sweep round-tripped through gemm_choice (stdout
# envelope unwrap included), and a stitched run under
# engine.kernels=pallas finishing with ZERO steady-state recompiles
# (docs/engine_fast_path.md § Training kernels)
echo "== ops smoke (kernel parity + autotune + zero-recompile gate) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m veles_tpu.ops --smoke
# bench_diff self-test: the perf-regression watchdog's comparator
# validated against the synthetic tests/fixtures/bench_envelope.json —
# banked vs banked clean, synthetically degraded copies caught on every
# field, cross-device lines skipped (gate a fresh run against a named
# bank with scripts/bench_diff.py --banked FILE --fresh RUN)
echo "== bench_diff self-test (perf-regression watchdog) =="
python scripts/bench_diff.py --selftest
# pod smoke: an 8-shard CPU session (one pod = one pjit'd stitched
# program) must train the seeded sample to completion with ZERO
# per-step gradient/update frames on the ZMQ wire (chaos wire-site
# counters are the probe), zero steady-state recompiles, eval parity
# with the single-device run, a chip-kill reshard mid-epoch (mesh
# shrink + generation bump) and a byte-identical mesh-sharded
# InferenceEngine — the V-P02 preflight runs inside install().
# Pod-of-pods legs ride the same gate: a pp leg (stacked stages
# pipelined over dp×pp, one dispatch per class pass, bitwise forward
# parity vs the dp twin), an ep leg (all_to_all-routed MoE, token
# parity vs the dense reference at capacity >= n_experts), a
# simulated 2-process multi-host session (the multihost test double)
# asserting the one-update-frame wire gate + lockstep rank weights,
# and a heartbeat device-loss reshard completing with eval parity
echo "== pod smoke (one-pod-one-program + pod-of-pods gate) =="
timeout -k 10 560 env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m veles_tpu.pod --smoke
# fleet smoke: the disaggregated-serving gate — a scripted 2-role
# session (prefill role over the job wire, 2 decode replicas) must
# resolve a seeded request set with EXACT token parity vs a
# single-engine oracle while chaos drops one page-handoff frame
# (exactly-once retry) and one job frame (have-list requeue), a
# chaos-fired replica_drain scales down mid-stream losslessly, a
# synthetic TTFT-p99 burn breach makes the autoscaler shift the
# decode weights, and ZERO steady-state recompiles land on either
# role (docs/services.md § Disaggregated serving)
echo "== fleet smoke (disaggregated prefill/decode gate) =="
timeout -k 10 240 env JAX_PLATFORMS=cpu python -m veles_tpu.fleet --smoke
# plan smoke: the static sharding planner must find a feasible plan
# for both planner paths on a forced 8-device host — the mnist
# workflow path (initialize-but-never-train pricing) and the
# transformer params-pytree path (zero-alloc, Megatron module specs);
# and a topology the batch/axes CANNOT divide must exit non-zero with
# the V-P03 reasons named per candidate (docs/analyze.md § Planner)
echo "== plan smoke (static sharding planner gate) =="
timeout -k 10 120 env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m veles_tpu.analyze --plan veles_tpu.samples.mnist
timeout -k 10 120 env JAX_PLATFORMS=cpu \
  XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -m veles_tpu.analyze --plan veles_tpu.samples.transformer
if out=$(timeout -k 10 120 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m veles_tpu.analyze --plan veles_tpu.samples.mnist \
    --topology 3); then
  echo "plan smoke: expected non-zero exit for --topology 3" >&2
  exit 1
fi
echo "$out"
case "$out" in
  *V-P03*) : ;;
  *) echo "plan smoke: V-P03 not named for the bad topology" >&2
     exit 1 ;;
esac
