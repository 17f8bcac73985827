"""The benchmark's own tests: CPU only, quick, no chip compile.

The manifest and its files; the trace reduction on hand-made intervals
and on the intervals brought back from the first chip run; the required
operations against hand-worked counts; the traffic generator; a
``--rehearse`` run of each driver; the chat_saturated row added as data
only; the control (the reference in the precision below, put in the
program's place) and each fault a cell can have coming out as not
correct through the same ``judge`` that passes the program.
"""

import copy
import json
import os
import re
import subprocess
import sys

import numpy
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmarks import (end_to_end, flops, harness, trace_reduce,  # noqa: E402
                        traffic)

BENCH = os.path.join(REPO_ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def spec():
    return harness.manifest()


# -- the manifest -----------------------------------------------------------

def test_manifest_keys_names_and_units(spec):
    assert sorted(spec) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in spec[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for metric in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
    for metric in spec["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    for cell in spec["workloads"]:
        assert cell["chips"] in (1, 4)
        assert NAME.match(cell["traffic"]) and NAME.match(cell["config"])
        assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert len(json.dumps(spec)) < 64 * 1024


def test_every_named_file_exists_and_cells_report_what_they_must(spec):
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for config in configs.values():
        assert os.path.isfile(os.path.join(REPO_ROOT, config["file"]))
        assert any(config["file"].startswith(p + "/")
                   for p in spec["paths"])
        body = harness.load_json(REPO_ROOT, config["file"])
        for kind in ("drivers", "reference"):
            key = "driver" if kind == "drivers" else "reference"
            assert os.path.isfile(os.path.join(
                BENCH, kind, body[key] + ".py"))
    for cell in spec["workloads"]:
        assert cell["config"] in configs
        params = harness.load_json(BENCH, "workloads",
                                   cell["name"] + ".json")
        assert params["config"] == cell["config"]
        assert params["chips"] == cell["chips"]
        reports = params["reports"]
        assert "setup_s" in reports and len(reports) >= 2
        for name in reports:
            listed = e2e[name].get("workloads")
            assert listed is None or cell["name"] in listed
        for name, metric in e2e.items():
            if cell["name"] in metric.get("workloads", []):
                assert name in reports
        layer = [m for m in spec["per_layer"]
                 if cell["name"] in m.get("workloads", [cell["name"]])]
        assert layer, "cell %s has no per-layer metric" % cell["name"]
        for metric in layer:
            assert metric["moves"] in reports, (cell["name"], metric)
    layers = set()
    for metric in spec["per_layer"]:
        entry = harness.load_json(BENCH, "layer_metrics",
                                  metric["name"] + ".json")
        assert os.path.isfile(os.path.join(
            BENCH, "readers", entry["reader"] + ".py"))
        for key in ("layer", "unit", "moves"):
            assert entry[key] == metric[key]
        assert metric["moves"] in e2e
        layers.add(metric["layer"])
    mfu = [m["name"] for m in spec["per_layer"] if "mfu" in
           m["name"].split(".")]
    assert "train.mfu" in mfu and "serve.mfu.latency" in mfu
    for root, _dirs, files in os.walk(BENCH):
        for name in files:
            if "__pycache__" in root:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_the_configuration_is_the_sample_the_program_ships():
    from benchmarks.drivers.train_fused import program_layers
    from veles_tpu.samples import alexnet
    config = harness.load_json(BENCH, "configs", "alexnet.json")
    assert tuple(config["input_shape"]) == alexnet.INPUT_SHAPE
    ours = program_layers(config)
    assert len(ours) == len(alexnet.LAYERS)
    for mine, theirs in zip(ours, alexnet.LAYERS):
        assert mine["type"] == theirs["type"]
        assert mine.get("<-") == theirs.get("<-")
        for key, value in theirs["->"].items():
            assert mine["->"][key] == value, (key, mine, theirs)
        pad = mine["->"].get("padding", 0)
        assert pad == theirs["->"].get("padding", 0)


# -- trace reduction --------------------------------------------------------

def _handmade():
    ms = 1000000
    ops = [["fusion.1", 0 * ms, 4 * ms], ["fusion.2", 3 * ms, 3 * ms],
           ["copy.3", 10 * ms, 2 * ms], ["fusion.1", 16 * ms, 4 * ms]]
    modules = [["jit_step(123)", 0, 6 * ms], ["jit_step(123)", 16 * ms,
                                              4 * ms],
               ["jit_eval(9)", 10 * ms, 2 * ms]]
    spans = [["bench:window", 0, 20 * ms],
             ["bench:loader", 6 * ms, 4 * ms],
             ["bench:trainer", 12 * ms, 4 * ms]]
    return {"devices": {"/device:TPU:0": {"modules": modules, "ops": ops}},
            "spans": spans}


def test_trace_reduce_on_handmade_intervals():
    summary = trace_reduce.reduce(_handmade())
    assert summary["window_s"] == pytest.approx(0.020)
    # union: [0,6) + [10,12) + [16,20) = 12 ms
    assert summary["busy_s"] == pytest.approx(0.012)
    step = summary["programs"]["jit_step"]
    assert step["count"] == 2
    assert step["total_s"] == pytest.approx(0.010)
    assert step["median_s"] == pytest.approx(0.005)
    assert summary["programs"]["jit_eval"]["count"] == 1
    assert summary["device_ops"][0] == ["fusion.1", pytest.approx(0.008)]
    gaps = dict(summary["idle_gaps"])
    assert gaps == {"loader": pytest.approx(0.004),
                    "trainer": pytest.approx(0.004)}
    assert summary["spans"]["loader"] == {"count": 1, "total_s":
                                          pytest.approx(0.004)}
    assert trace_reduce.merge([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]


def test_trace_reduce_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "spans": []})


@pytest.mark.parametrize("name", ["alexnet_steps", "chat_steps"])
def test_trace_reduce_on_the_intervals_from_the_chip(name):
    path = os.path.join(BENCH, "fixtures", name + ".json")
    fixture = harness.load_json(path)
    summary = trace_reduce.reduce(fixture["extracted"])
    want = fixture["expected"]
    assert summary["window_s"] == pytest.approx(want["window_s"])
    assert summary["busy_s"] == pytest.approx(want["busy_s"])
    assert 0 < summary["busy_s"] <= summary["window_s"]
    for program, stats in want["programs"].items():
        got = summary["programs"][program]
        assert got["count"] == stats["count"]
        assert got["total_s"] == pytest.approx(stats["total_s"])
    assert summary["idle_gaps"] and summary["device_ops"]
    assert len(summary["device_ops"]) <= 10


# -- required operations ----------------------------------------------------

def test_flops_against_hand_worked_counts():
    alexnet = harness.load_json(BENCH, "configs", "alexnet.json")
    macs = dict(flops.convnet_macs(alexnet))
    # conv1: 55 x 55 outputs x 96 kernels x (11 x 11 x 3) = 105,415,200
    assert macs[0] == 55 * 55 * 96 * 363 == 105415200
    # fc6: 6 x 6 x 256 = 9216 inputs x 4096
    assert macs[11] == 9216 * 4096
    forward = 2 * sum(macs.values())
    assert flops.convnet_train_flops_per_image(alexnet) == \
        3 * forward - 2 * macs[0]
    assert 60e6 < flops.convnet_param_count(alexnet) < 63e6
    gpt = harness.load_json(BENCH, "configs", "cerebras_gpt_1p3b.json")
    # one block: 2048 x 6144 + 2048 x 2048 + 2 x 2048 x 8192
    assert flops.gpt_block_matmul_params(gpt) == 12 * 2048 * 2048 \
        == 50331648
    assert 1.30e9 < flops.gpt_param_count(gpt) < 1.33e9
    live = 300
    assert flops.gpt_decode_flops(gpt, live) == (
        2 * 24 * 50331648 + 2 * 50257 * 2048 + 24 * 4 * 2048 * live)
    assert flops.gpt_prefill_flops(gpt, 1) == (
        2 * 24 * 50331648 + 24 * 4 * 2048 + 2 * 50257 * 2048)
    # K and V, 24 layers, 2048 wide, 2 bytes: 196,608 bytes a position
    assert flops.gpt_kv_bytes(gpt, 1) == 196608


# -- traffic ----------------------------------------------------------------

CHAT = os.path.join(BENCH, "workloads", "cerebras_gpt_1p3b.chat.json")


def test_traffic_is_the_seeds_and_the_same_work_for_every_seed():
    spec = harness.load_json(CHAT)["traffic_spec"]
    a = traffic.generate(spec, 50257, 2 ** 31 + 5, 4.0, 20.0)
    b = traffic.generate(spec, 50257, 2 ** 31 + 5, 4.0, 20.0)
    c = traffic.generate(spec, 50257, 7, 4.0, 20.0)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["due"] == y["due"]
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert numpy.array_equal(x["tokens"], y["tokens"])

    def window(requests):
        return [r for r in requests if r["due"] >= 4.0]

    assert len(window(a)) == len(window(c)) == 16      # 0.8 a second
    assert any(not numpy.array_equal(x["tokens"], y["tokens"])
               for x, y in zip(window(a), window(c)))
    # every seed sends the same SET of lengths and gaps, in its own order
    keys = (lambda r: len(r["tokens"]), lambda r: r["max_new_tokens"])
    for key in keys:
        assert sorted(map(key, window(a))) == sorted(map(key, window(c)))
        assert list(map(key, window(a))) != list(map(key, window(c)))
    # the lead-in replays the end of the window's own cycle
    lead = [r for r in a if r["due"] < 4.0]
    assert lead and [r["max_new_tokens"] for r in lead] == \
        [r["max_new_tokens"] for r in window(a)[-len(lead):]]
    dues = [r["due"] for r in a]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 24.0
    full = window(traffic.generate(spec, 50257, 3, 16.0, 400.0))
    lens = [len(r["tokens"]) for r in full]
    assert min(lens) >= 16 and max(lens) <= 1024
    assert 150 < numpy.median(lens) < 240
    # the gaps are the quantiles of the exponential, scaled to the rate
    got = traffic.gaps(spec["arrivals"], 320)
    assert got.sum() == pytest.approx(400.0)
    assert numpy.median(got) == pytest.approx(
        numpy.log(2.0) / 0.8, rel=0.02)


def test_percentile_is_a_request_that_happened():
    assert end_to_end.percentile([1, 2, 3, 4], 95) == 4
    assert end_to_end.percentile(list(range(1, 101)), 95) == 95
    assert end_to_end.percentile([], 95) is None


@pytest.mark.parametrize("n,rank", [
    (1, 1), (49, 48), (50, 49), (51, 50), (100, 97)])
def test_the_97th_percentile_of_the_gaps(n, rank):
    """The rank is the ceiling of 97% of n, at least 1, by integer
    arithmetic; the gap of that rank, whatever the order they came in."""
    assert end_to_end.ceil_pct(n, 97) == rank
    assert end_to_end.ceil_pct(n, 2) == (1 if n <= 50 else 2)
    gaps = [i / 1000.0 for i in range(1, n + 1)]
    gaps = gaps[1::2] + gaps[::2]
    assert end_to_end.gap_p97_ms({"gaps_s": gaps}) == \
        pytest.approx(float(rank), rel=1e-12)
    assert end_to_end.gap_p97_ms({"gaps_s": []}) is None


@pytest.mark.parametrize("slow_pct", [4.8, 5.2])
def test_the_97th_percentile_stays_where_the_95th_jumps(slow_pct):
    """Two populations, 11 ms and 60 ms, over 13,300 gaps: the 95th
    percentile reads the plain step while the slow share is under 5%
    and the stalled one above it, the 97th the stalled one in both."""
    n = 13300
    slow = int(round(n * slow_pct / 100))
    gaps = [0.011] * (n - slow) + [0.060] * slow
    obs = {"gaps_s": gaps}
    assert end_to_end.gap_p95_ms(obs) == pytest.approx(
        11.0 if slow_pct < 5 else 60.0)
    assert end_to_end.gap_p97_ms(obs) == pytest.approx(60.0)
    assert n - end_to_end.ceil_pct(n, 97) + 1 == 400 < slow


def test_every_reported_metric_is_registered_and_listed_exactly(spec):
    """Each cell's ``reports`` names a function of ``METRICS`` (or the
    set-up time, which the run takes itself), and every end-to-end
    metric's ``workloads`` are exactly the cells that report it; a
    metric with no such list is reported by every cell."""
    reporting = {}
    for cell in spec["workloads"]:
        params = harness.load_json(BENCH, "workloads",
                                   cell["name"] + ".json")
        for name in params["reports"]:
            assert name == "setup_s" or name in end_to_end.METRICS, name
            reporting.setdefault(name, []).append(cell["name"])
    cells = [cell["name"] for cell in spec["workloads"]]
    for metric in spec["end_to_end"]:
        assert sorted(reporting.get(metric["name"], [])) == \
            sorted(metric.get("workloads", cells)), metric["name"]
    assert sorted(reporting) == sorted(m["name"] for m in spec["end_to_end"])


# -- a rehearsal run of each driver ----------------------------------------

def _rehearse(cell, extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0",
         "--rehearse"] + list(extra),
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell", ["alexnet.train_b256",
                                  "cerebras_gpt_1p3b.chat"])
def test_rehearsal_prints_the_contracts_last_line(cell):
    result, stderr = _rehearse(cell)
    keys = list(result)
    assert keys[-1] == "compared"
    assert [k for k in keys if k in RESULT_KEYS] == RESULT_KEYS
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    params = harness.load_json(BENCH, "workloads", cell + ".json")
    assert sorted(result["metrics"]) == sorted(params["reports"])
    # no number from a CPU run under a metric's name
    assert all(m["value"] is None for m in result["metrics"].values())
    assert all(v > 0 for v in result["rehearsal"].values())
    for name, pair in result["compared"].items():
        assert "compared %s = " % name in stderr


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "alexnet.train_b256", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_a_new_traffic_mix_is_one_file_of_data(tmp_path):
    """The chat_saturated row of PERF.md's Open questions: the chat
    cell's file with another rate and a shorter list of reported
    metrics, run from a scratch copy with no edit to any file."""
    params = harness.load_json(CHAT)
    params["traffic"] = "chat_saturated"
    params["reports"] = ["out_tokens_per_s", "setup_s"]
    params["rehearsal"]["traffic_spec"]["arrivals"]["rate_rps"] = 60.0
    path = tmp_path / "cerebras_gpt_1p3b.chat_saturated.json"
    path.write_text(json.dumps(params))
    result, _stderr = _rehearse("cerebras_gpt_1p3b.chat_saturated",
                                ["--cell-file", str(path)])
    assert sorted(result["metrics"]) == ["out_tokens_per_s", "setup_s"]
    assert result["rehearsal"]["out_tokens_per_s"] > 0


# -- the control, and the faults a cell can have ----------------------------

def _context(cell, seed, seconds=0.3, tweak=None):
    entry, params, config = harness.load_cell(cell, rehearse=True)
    config, params = copy.deepcopy(config), copy.deepcopy(params)
    if tweak:
        tweak(config, params)
    ctx = harness.Context(entry, params, config, seed, seconds, 0, True)
    return ctx, harness.load_driver(config), harness.load_reference(config)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 50])
def test_training_control_comes_out_not_correct(seed):
    """The program passes the cell's limits; the reference in the
    precision below, and the reference with half the batch left out, put
    in its place through the same ``judge``, do not."""
    from benchmarks.drivers import train_fused
    ctx, driver, reference = _context("alexnet.train_b256", seed)
    limits = ctx.params["limits"]
    run = driver.Run(ctx, reference)
    run.run()
    run.release()
    correct, compared = harness.judge(run.verify(), limits)
    assert correct is True, compared
    assert compared["compiles_in_window"]["value"] == 0
    for name, checks in run.controls().items():
        correct, compared = harness.judge(checks, limits)
        assert correct is False, (name, compared)
    fp8 = run.controls()["fp8"]
    assert fp8["grad_diff"] > limits["grad_diff"]
    # a float32 program agrees with the reference to rounding
    same = train_fused.compare(run.reference_readings(),
                               run.reference_readings())
    assert same["grad_gap"] == same["grad_diff"] == 0


def _readings(scale=None):
    """Hand-made readings of two leaves, one a fiftieth of the other."""
    grad = {"0.b": numpy.full(4, 0.01, numpy.float32),
            "1.w": numpy.full(4, 0.5, numpy.float32)}
    if scale:
        grad = {k: v * scale.get(k, 1.0) for k, v in grad.items()}
    norms = {k: float(numpy.linalg.norm(v)) for k, v in grad.items()}
    return {"losses": [1.0, 0.9], "grad": grad, "grad_norm": norms,
            "delta_norm": dict(norms)}


@pytest.mark.parametrize("leaf,scale,whole", [
    ("0.b", 0.0, 0.02),         # a small leaf left unmoved
    ("0.b", 2.0, 0.02),         # ... or moved double
    ("1.w", 0.0, 0.9998)])
def test_a_frozen_leaf_reads_one_whatever_its_size(leaf, scale, whole):
    """Every leaf's norms are measured against the reference's OWN norm
    of it, so a small leaf cannot hide behind the median leaf's; the
    difference of the whole gradient weighs a leaf by its size."""
    from benchmarks.drivers import train_fused
    checks = train_fused.compare(_readings({leaf: scale}), _readings())
    assert checks["grad_gap"] == pytest.approx(1.0)
    assert checks["delta_gap"] == pytest.approx(1.0)
    assert checks["grad_diff"] == pytest.approx(whole, rel=1e-3)
    limits = harness.load_json(
        BENCH, "workloads", "alexnet.train_b256.json")["limits"]
    assert harness.judge(checks, limits)[0] is False
    same = train_fused.compare(_readings(), _readings())
    assert harness.judge(same, limits)[0] is True


def test_the_reference_step_compiles_once_for_every_seed():
    """The dropout seeds are arguments of the reference's jitted step:
    closed over, every ``--seed`` would compile the float32 step anew
    (34 s a run on the chip)."""
    import jax.numpy as jnp
    from benchmarks.reference import alexnet as reference
    _entry, _params, config = harness.load_cell("alexnet.train_b256",
                                                rehearse=True)
    shape = (config["batch"],) + tuple(config["input_shape"])
    batch = [(jnp.zeros(shape, jnp.uint8),
              jnp.zeros(config["batch"], jnp.int32))]
    for seed in (1, 2 ** 31 + 2):
        reference.train_steps(
            config, reference.init_params(config, seed), batch,
            reference.dropout_seeds(config, seed))
    step = reference._step_program(
        json.dumps(config, sort_keys=True), None, False)
    assert step._cache_size() == 1


class _BrokenStep(object):
    """The compiled step with a fault planted around it."""

    def __init__(self, step, fault):
        self.step, self.fault = step, fault

    def _cache_size(self):
        return self.step._cache_size()

    def __call__(self, params, x, labels):
        import jax
        import jax.numpy as jnp
        if self.fault == "state_unchanged":
            kept = jax.tree.map(jnp.copy, params)
            _new, metrics = self.step(params, x, labels)
            return kept, metrics
        half = x.shape[0] // 2
        return self.step(params, x[:half], labels[:half])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_faults_come_out_not_correct(fault, monkeypatch):
    from veles_tpu.znicz.fused_unit import FusedTrainer
    build = FusedTrainer._build

    def broken_build(self):
        build(self)
        self._step_ = _BrokenStep(self._step_, fault)

    monkeypatch.setattr(FusedTrainer, "_build", broken_build)
    ctx, driver, reference = _context("alexnet.train_b256", 21)
    run = driver.Run(ctx, reference)
    run.run()
    run.release()
    correct, compared = harness.judge(run.verify(), ctx.params["limits"])
    assert correct is False, compared


def _serve(seed, tweak=None, seconds=1.0):
    ctx, driver, reference = _context("cerebras_gpt_1p3b.chat", seed,
                                      seconds, tweak)
    run = driver.Run(ctx, reference)
    return ctx, run


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 50])
def test_serving_control_comes_out_not_correct(seed):
    ctx, run = _serve(seed)
    limits = ctx.params["limits"]
    run.run()
    run.release()
    program = run.verify()
    correct, compared = harness.judge(program, limits)
    assert correct is True, compared
    # float32 rehearsal: every served token is the reference's best
    assert program["logit_gap"] <= 1e-4
    for name, control in run.controls().items():
        checks = dict(program, **control)
        correct, compared = harness.judge(checks, limits)
        assert correct is False, (name, compared)


def test_serving_altered_token_comes_out_not_correct(monkeypatch):
    from veles_tpu.gen.engine import GenerativeEngine
    decode_step = GenerativeEngine.decode_step
    calls = {"n": 0}

    def altered(self):
        result = decode_step(self)
        if result is None:
            return result
        out, active = result
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            out = (out + 1) % self.model.vocab
        return out, active

    monkeypatch.setattr(GenerativeEngine, "decode_step", altered)
    ctx, run = _serve(9)
    run.run()
    run.release()
    correct, compared = harness.judge(run.verify(), ctx.params["limits"])
    assert correct is False, compared
    assert compared["logit_gap"]["value"] > compared["logit_gap"]["limit"]
