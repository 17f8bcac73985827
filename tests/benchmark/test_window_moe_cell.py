"""The ``command_a_plus_05_2026`` configuration's part of the benchmark,
CPU only: the configuration file against the catalog's numbers and the
issue's arithmetic; a ``--rehearse`` run of its cell (through
``--cell-file``, as a cell is rehearsed before it is listed) printing
the contract's last line with every metric null; the controls (the
reference with every linear product in fp8, a served token altered)
coming out not correct through the same ``judge`` that passes the
program; the sample holding long and short prompts; the required work
against hand-worked counts; the readers returning nothing where a
program keeps no such counters."""

import copy
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmarks import harness, wmoe_work  # noqa: E402
from benchmarks.readers import counter, work_share  # noqa: E402

BENCH = os.path.join(REPO_ROOT, "benchmarks")
NAME = "command_a_plus_05_2026"
CELL = NAME + ".mixed_len"
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]

#: the published config.json's numbers (catalog ``architectures.jsonl``,
#: ``command-a-plus-05-2026``), copied here so that an edit of the
#: configuration's file shows
PUBLISHED = {
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_size": 4096,
    "intermediate_size": 4096, "layer_norm_eps": 1e-05, "layer_switch": 4,
    "logit_scale": 1, "max_position_embeddings": 200000,
    "num_attention_heads": 128, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 8, "num_shared_experts": 4,
    "prefix_dense_intermediate_size": 16384,
    "prefix_dense_sliding_window_pattern": 1, "rope_theta": 50000,
    "rotary_pct": 1, "sliding_window": 4096, "vocab_size": 262144,
}
REDUCED = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


@pytest.fixture(scope="module")
def config():
    return harness.load_json(BENCH, "configs", NAME + ".json")


def test_every_published_width_is_unchanged_and_the_cut_is_stated(config):
    spec = harness.manifest()
    entry = [c for c in spec["configs"] if c["name"] == NAME][0]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/" \
        "blob/main/config.json"
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    # reduced lists exactly the keys that differ from the published
    assert sorted(k for k, v in PUBLISHED.items() if config[k] != v) \
        == sorted(REDUCED)
    for key in REDUCED:
        assert config["published"][key] == PUBLISHED[key]
        assert key in config["reduced"]
    kinds = config["layer_types"]
    assert kinds == (["sliding_attention"] * 3 + ["full_attention"]) * 8
    for key, value in (("expert_selection_fn", "sigmoid"),
                       ("hidden_act", "silu"), ("norm_topk_prob", True),
                       ("position_embedding_type", "rope_gptj"),
                       ("shared_expert_combination_strategy", "average"),
                       ("tie_word_embeddings", True),
                       ("use_gated_activation", True),
                       ("use_parallel_block", True),
                       ("use_qk_norm", False), ("rms_norm_eps", None)):
        assert config[key] == value, key
    assert config["router_width"] == 128 and config["held_from"] == 0
    assert "expert parallelism" in config["deployment"]
    assert "8 chips share each layer" in config["deployment"]
    assert any("vision tower" in line for line in config["left_out"])
    assert config["assumed"] and config["dead_keys"]
    assert config["engine"] == {"kv": "contiguous", "max_slots": 24,
                                "max_seq": 32768, "prefill_chunk": 1024}
    rehearsal = harness.merge(config, config["rehearsal"])
    assert rehearsal["layer_types"][:rehearsal["num_hidden_layers"]] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert (rehearsal["sliding_window"],
            rehearsal["engine"]["prefill_chunk"]) == (8, 4)
    assert (rehearsal["num_experts"], rehearsal["router_width"],
            rehearsal["num_experts_per_tok"],
            rehearsal["num_shared_experts"], rehearsal["vocab_size"],
            rehearsal["dtype"]) == (4, 8, 2, 2, 64, "float32")


def test_the_cell_is_the_issues_traffic(config):
    spec = harness.manifest()
    entry = [w for w in spec["workloads"] if w["name"] == CELL][0]
    assert entry == dict(entry, config=NAME, traffic="mixed_len", chips=1)
    assert len(spec["workloads"]) == 4
    params = harness.load_json(BENCH, "workloads", CELL + ".json")
    traffic = params["traffic_spec"]
    assert traffic["prompt_len"] == {
        "dist": "lognormal", "median": 512, "sigma": 1.6, "min": 32,
        "max": 28672}
    assert traffic["output_len"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.7, "min": 16,
        "max": 512}
    assert traffic["arrivals"]["process"] == "poisson"
    assert "shared_prefix" not in traffic
    assert params["lead_in_s"] == 16 and params["verify_sample"] == 6
    assert params["reports"] == ["gap_p97_ms", "out_tokens_per_s",
                                 "setup_s"]
    assert params["limits"]["unanswered"] == 0
    assert params["limits"]["compiles_in_window"] == 0
    # far past the window: the ring has wrapped and chunks have crossed
    # it (three of a window's 84 prompts exceed 8,192)
    assert params["verify_long"] == {"above": 8192, "count": 2}
    # ISSUE 34's rate: 0.6 of the knee of 2.75 requests/s
    assert traffic["arrivals"]["rate_rps"] == 1.65
    assert params["verify_short"] == {"below": 1024, "count": 2}
    # every metric the cell lists has its file, and a reader
    for metric in spec["per_layer"]:
        if CELL in metric.get("workloads", ()):
            entry = harness.load_json(BENCH, "layer_metrics",
                                      metric["name"] + ".json")
            assert os.path.exists(os.path.join(
                BENCH, "readers", entry["reader"] + ".py"))
            assert entry["unit"] == metric["unit"]
            assert entry["moves"] == metric["moves"]
            assert entry["layer"] == metric["layer"]


def test_the_cut_is_the_issues_arithmetic(config):
    from benchmarks.drivers import serve_window_moe
    from veles_tpu.gen import WindowMoEGenModel
    from veles_tpu.samples import window_moe_lm
    pcfg = serve_window_moe.program_config(config)
    assert pcfg["pattern"] == "WWWF"
    m = wmoe_work.dims(config)
    assert (m["n_window"], m["n_full"]) == (3, 1)
    assert wmoe_work.attn_params(m) == 2 * 4096 * (16384 + 1024)
    assert round(wmoe_work.attn_params(m) / 1e6, 1) == 142.6
    assert wmoe_work.expert_params(m) == 3 * 4096 * 4096 == 50331648
    # a layer outside its routed experts: attention, four shared
    # experts, the router
    assert round((wmoe_work.layer_dense_params(m)
                  + m["d"] * m["router"]) / 1e6, 1) == 344.5
    assert window_moe_lm.param_count(pcfg) == \
        4 * (wmoe_work.layer_dense_params(m) + 4096 * 128 + 4096
             + 16 * 50331648) + 32768 * 4096 + 4096
    assert 4.73e9 < window_moe_lm.param_count(pcfg) < 4.74e9
    model = WindowMoEGenModel(pcfg, compute_dtype="bfloat16")
    per_slot = model.cache_nbytes(24, 32768) // 24
    assert per_slot == (3 * 4096 + 32768) * 4096 == 176 << 20
    assert 4.42e9 < model.cache_nbytes(24, 32768) < 4.44e9
    # were all four layers full-length a slot would be 512 MiB
    assert 4 * 32768 * 4096 == 512 << 20


def _rehearse(extra=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 11), "--seconds", "1", "--trace", "0",
         "--rehearse"] + list(extra),
        cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_rehearsal_prints_the_contracts_last_line():
    result, stderr = _rehearse(["--cell-file", os.path.join(
        BENCH, "workloads", CELL + ".json")])
    keys = list(result)
    assert keys[-1] == "compared"
    assert [k for k in keys if k in RESULT_KEYS] == RESULT_KEYS
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    params = harness.load_json(BENCH, "workloads", CELL + ".json")
    assert sorted(result["metrics"]) == sorted(params["reports"])
    assert all(m["value"] is None for m in result["metrics"].values())
    assert all(v > 0 for v in result["rehearsal"].values())
    for name in result["compared"]:
        assert "compared %s = " % name in stderr


def _serve(seed, seconds=1.0):
    entry, params, config = harness.load_cell(CELL, rehearse=True)
    config, params = copy.deepcopy(config), copy.deepcopy(params)
    ctx = harness.Context(entry, params, config, seed, seconds, 0, True)
    run = harness.load_driver(config).Run(
        ctx, harness.load_reference(config))
    return ctx, run


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4])
def test_serving_controls_come_out_not_correct(seed):
    ctx, run = _serve(seed)
    limits = ctx.params["limits"]
    obs = run.run()
    run.release()
    program = run.verify()
    correct, compared = harness.judge(program, limits)
    assert correct is True, compared
    # float32 rehearsal: every served token is the reference's best
    assert program["logit_gap"] <= 1e-4
    controls = run.controls()
    assert sorted(controls) == ["altered_token", "fp8"]
    for name, control in controls.items():
        correct, compared = harness.judge(dict(program, **control), limits)
        assert correct is False, (name, compared)
    # the sample: prompts past the ring's wrap and short ones, both
    sample = run.sample()
    lengths = [len(r["tokens"]) for r in sample]
    assert len(sample) == ctx.params["verify_sample"]
    assert sum(n > 16 for n in lengths) >= 2
    assert sum(n < 8 for n in lengths) >= 2
    assert len({id(r) for r in sample}) == len(sample)
    # the counters of the window, as the readers will find them
    counted = obs["counters"]["hybrid"]
    assert counted["decode_calls"] > 0
    assert abs(counted["decode_calls"]
               - obs["counters"]["decode_steps"]) <= 1
    decode = counted["decode"]
    assert 0 < decode["moe_local_pairs"] <= decode["moe_pairs_total"]
    assert counted["prefill"]["moe_pairs_total"] > 0
    assert counted["tokens_per_held_expert"] == pytest.approx(
        decode["moe_local_pairs"] / (4.0 * 4 * counted["decode_calls"]))
    host = counted["host"]
    assert 0 < host["kv_rows_window"] <= 3 * host["kv_rows_full"]
    assert counted["window_rows_share"] == pytest.approx(
        100.0 * host["kv_rows_window"] / (3.0 * host["kv_rows_full"]))
    assert counted["prefill_calls"] > 0


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


# -- required work ----------------------------------------------------------

def _view(config, events, chunks, counted):
    """``chunks``: ``(time s, start, length)``, as the engine's span
    around each says it in the trace (window 0 .. 10 s)."""
    traced = {"lo": 0.0, "hi": 10.0, "batch_fill_slots": 2.0}
    if counted is not None:
        traced["hybrid"] = counted
    spans = [["veles:gen/prefill_chunk", int(t * 1e9), 1000, 0,
              {"slot": 0, "start": start, "len": length}]
             for t, start, length in chunks]
    spans.append(["veles:gen/step", int(1e9), 1000, 0, {}])
    return {"config": config, "peaks": PEAKS,
            "program_trace": {"window": [0, int(10e9)], "spans": spans,
                              "ops": [], "runs": []},
            "obs": {"token_events": events, "traced": traced}}


COUNTED = {
    "decode_calls": 3, "prefill_calls": 6,
    "prefill": {"moe_local_pairs": 700, "moe_experts_touched": 60,
                "moe_pairs_total": 5000, "moe_expert_load_max": 90},
    "decode": {"moe_local_pairs": 30, "moe_experts_touched": 25,
               "moe_pairs_total": 192, "moe_expert_load_max": 2},
    "host": {"kv_rows_window": 3 * (4096 + 4096 + 100),
             "kv_rows_full": 5001 + 5002 + 100}}


def test_required_work_against_hand_worked_counts(config):
    m = wmoe_work.dims(config)
    dense = 4 * 2 * (2 * 4096 * (16384 + 1024) + 4 * 50331648
                     + 4096 * 128)
    assert wmoe_work.token_flops(m) == dense
    assert wmoe_work.seen(m, 0) == (1, 1)
    assert wmoe_work.seen(m, 4095) == (4096, 4096)
    assert wmoe_work.seen(m, 5000) == (4096, 5001)
    # two queries at positions 4095 and 4096: the window holds the
    # second at 4096 keys, the full layer gives it 4097
    assert wmoe_work.attended_flops(m, 4095, 2) == 4 * 16384 * (
        3 * (4096 + 4096) + (4096 + 4097))
    head = 2 * 4096 * 32768
    # a prompt of 5000 tokens: its last two chunks (of 1024 and 904)
    # ran in the window, an earlier one before it; its first token and
    # two decoded behind it; one token of a prompt of 99
    chunks = [(-1.0, 2048, 1024), (1.0, 3072, 1024), (2.0, 4096, 904)]
    events = [(2.0, 5000, 0), (3.0, 5000, 1), (4.0, 5000, 2),
              (5.0, 99, 1), (11.0, 99, 2)]
    view = _view(config, events, chunks, COUNTED)
    fed = 1928 * dense + wmoe_work.attended_flops(m, 3072, 1928) + head
    assert wmoe_work.wmoe_chunk_flops(view) == \
        700 * 2 * 50331648 + fed
    # scaled to the runs the trace counted
    assert wmoe_work.wmoe_chunk_flops(view, {"count": 4}) == \
        2 * wmoe_work.wmoe_chunk_flops(view)
    # the kernel's share of it: what the real queries see
    assert wmoe_work.attn_chunk_flops(view) == \
        wmoe_work.attended_flops(m, 3072, 1928)
    assert wmoe_work.attn_chunk_flops(view, {"count": 1}) == \
        wmoe_work.attended_flops(m, 3072, 1928) / 2
    decoded = 3 * (dense + head) + 4 * 16384 * (
        3 * (4096 + 4096 + 100) + (5001 + 5002 + 100))
    assert wmoe_work.wmoe_serve_flops(view) == \
        730 * 2 * 50331648 + fed + decoded
    row = 2 * 1024 * 2
    attn = 2 * 4096 * (16384 + 1024) * 2
    assert wmoe_work.attn_window_decode_bytes(view) == \
        3 * 3 * attn + 3 * (4096 + 4096 + 100) * row
    assert wmoe_work.attn_full_decode_bytes(view) == \
        3 * attn + (5001 + 5002 + 100) * row
    assert wmoe_work.experts_decode_bytes(view) == 25 * 50331648 * 2
    fixed = 4 * (4 * 50331648 * 2 + 4096 * 128 * 4) + 4096 * 32768 * 2
    whole = wmoe_work.wmoe_decode_bytes(view, {"count": 6})
    assert whole == pytest.approx(2 * (
        wmoe_work.attn_window_decode_bytes(view)
        + wmoe_work.attn_full_decode_bytes(view)
        + wmoe_work.experts_decode_bytes(view) + 3 * fixed))
    # the issue's sizing: the weights outside the routed experts are
    # 3.0 GB a step, the routed experts 6.4 GB when every one is chosen
    assert 3.0e9 < 4 * attn + fixed < 3.1e9
    assert 6.4e9 < 4 * 16 * 50331648 * 2 < 6.5e9


def test_a_program_without_the_counters_gives_the_readers_nothing(config):
    view = _view(config, [(1.0, 10, 0)], [(1.0, 0, 10)], None)
    for work in wmoe_work.WORK.values():
        assert work(view) is None
    # the expert counters alone (another class's program) do not do
    other = {k: v for k, v in COUNTED.items() if k != "host"}
    for work in wmoe_work.WORK.values():
        assert work(_view(config, [], [], other)) is None
    view["trace"] = {"window_s": 10.0, "chips": 1, "programs": {
        "jit_decode": {"count": 3, "total_s": 0.05},
        "jit_prefill_chunk": {"count": 3, "total_s": 0.15}}}
    spec = harness.manifest()
    names = [metric["name"] for metric in spec["per_layer"]
             if metric.get("workloads") == [CELL]]
    assert len(names) == 14
    for name in names:
        entry = harness.load_json(BENCH, "layer_metrics", name + ".json")
        if entry["reader"] == "work_share":
            assert entry["args"]["module"] == "wmoe_work"
            assert entry["args"]["work"] in wmoe_work.WORK
            if not entry["args"].get("match"):
                assert work_share.read(
                    dict(view, args=entry["args"])) is None
        elif entry["reader"] == "counter":
            assert counter.read(dict(view, args=entry["args"])) is None


def test_the_reader_divides_work_by_peak_and_time(config):
    view = _view(config, [(1.0, 10, 1), (2.0, 10, 2)],
                 [(1.0, 0, 1024)], COUNTED)
    view["trace"] = {"window_s": 10.0, "chips": 1, "programs": {
        "jit_decode": {"count": 3, "total_s": 0.06},
        "jit_prefill_chunk": {"count": 1, "total_s": 0.05}}}

    def read(name):
        entry = harness.load_json(BENCH, "layer_metrics", name + ".json")
        return work_share.read(dict(view, args=entry["args"]))

    got = read("wmoe.decode.hbm_roofline")
    assert got == pytest.approx(
        100.0 * wmoe_work.wmoe_decode_bytes(view, {"count": 3})
        / 819e9 / 0.06)
    assert 0 < got < 100
    got = read("wmoe.chunk.mxu_roofline")
    assert got == pytest.approx(
        100.0 * wmoe_work.wmoe_chunk_flops(view, {"count": 1})
        / 197e12 / 0.05)
    assert 0 < got < 100
    assert read("wmoe.serve.mfu") == pytest.approx(
        100.0 * wmoe_work.wmoe_serve_flops(view) / 10.0 / 197e12)
