"""The ``nemotron3_super_120b_a12b`` configuration's part of the
benchmark, CPU only: the configuration file against the catalog's
numbers; a ``--rehearse`` run of its cell printing the contract's last
line; the controls (the reference with every linear product in fp8, a
served token altered) coming out not correct through the same
``judge`` that passes the program; the required work against
hand-worked counts; the reader returning nothing where a program keeps
no such counters."""

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

from benchmarks import harness, hybrid_work  # noqa: E402
from benchmarks.readers import work_share  # noqa: E402

BENCH = os.path.join(REPO_ROOT, "benchmarks")
CELL = "nemotron3_super_120b_a12b.chat"
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]

#: the published config.json's numbers (catalog ``architectures.jsonl``,
#: ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``), copied here so that an
#: edit of the configuration's file shows
PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 4096, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1,
    "norm_eps": 1e-05, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
    "routed_scaling_factor": 5, "ssm_state_size": 128,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "vocab_size": 131072,
}
REDUCED = {"num_hidden_layers": 11, "n_routed_experts": 128,
           "vocab_size": 32768}


@pytest.fixture(scope="module")
def config():
    return harness.load_json(BENCH, "configs",
                             "nemotron3_super_120b_a12b.json")


def test_every_published_width_is_unchanged_and_the_cut_is_stated(config):
    spec = harness.manifest()
    entry = [c for c in spec["configs"]
             if c["name"] == "nemotron3_super_120b_a12b"][0]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    for key, value in REDUCED.items():
        assert config["published"][key] == PUBLISHED[key]
        assert key in config["reduced"]
    pattern = config["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern.count("M") == 40 \
        and pattern.count("E") == 40 and pattern.count("*") == 8
    assert pattern[:11] == "MEMEMEM*EME"
    assert config["router_width"] == 512 and config["held_from"] == 0
    assert "expert parallelism" in config["deployment"]
    assert config["left_out"] and config["assumed"]
    assert config["engine"] == {"kv": "contiguous", "max_slots": 64,
                                "max_seq": 2048,
                                "prefill_buckets": [32, 256, 1024]}
    rehearsal = harness.merge(config, config["rehearsal"])
    kinds = rehearsal["hybrid_override_pattern"][
        :rehearsal["num_hidden_layers"]]
    assert set(kinds) == {"M", "E", "*"}
    assert rehearsal["n_routed_experts"] < rehearsal["router_width"]


def test_the_cut_is_the_issues_arithmetic(config):
    """The table of ISSUE 28: parameters held here, and the state a
    slot."""
    from benchmarks.drivers import serve_hybrid
    from veles_tpu.gen import HybridGenModel
    from veles_tpu.samples import hybrid_lm
    pcfg = serve_hybrid.program_config(config)
    assert pcfg["pattern"] == "MEMEMEM*EME"
    m = hybrid_work.dims(config)
    assert (m["n_ssm"], m["n_moe"], m["n_attn"]) == (5, 5, 1)
    assert m["conv_dim"] == 10240 and m["d_inner"] == 8192
    assert hybrid_work.ssm_params(m) == 4096 * 18560 + 8192 * 4096
    assert round(hybrid_work.ssm_params(m) / 1e6, 1) == 109.6
    assert round(hybrid_work.attn_params(m) / 1e6, 1) == 35.7
    assert hybrid_work.expert_params(m) == 2 * 1024 * 2688 == 5505024
    # outside the experts: latent 2 x 4.19M, shared 2 x 22.0M, router 2.1M
    assert round((hybrid_work.moe_dense_params(m)
                  + m["d"] * m["router"]) / 1e6, 1) == 54.5
    assert 4.64e9 < hybrid_lm.param_count(pcfg) < 4.67e9
    model = HybridGenModel(pcfg, compute_dtype="bfloat16")
    per_slot = model.cache_nbytes(64, 2048) // 64
    assert model.recurrent_nbytes(64) // 64 == \
        5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert per_slot == model.recurrent_nbytes(64) // 64 \
        + 2 * 2048 * 2 * 128 * 2
    assert 23.3e6 < per_slot < 23.5e6            # 23.4 MB a slot


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
    result, stderr = _rehearse()
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


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 4, 50])
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
    # the counters of the window, as the readers will find them
    counted = obs["counters"]["hybrid"]
    # the engine's and the scheduler's counts are read one after the
    # other while the worker runs: a step may fall between the two
    assert counted["decode_calls"] > 0
    assert abs(counted["decode_calls"]
               - obs["counters"]["decode_steps"]) <= 1
    decode = counted["decode"]
    assert 0 < decode["moe_local_pairs"] <= decode["moe_pairs_total"]
    assert decode["moe_experts_touched"] <= decode["moe_local_pairs"]
    assert counted["tokens_per_held_expert"] == pytest.approx(
        decode["moe_local_pairs"] / (4.0 * 1 * counted["decode_calls"]))


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

def _view(config, events, counted, fill=2.0):
    traced = {"lo": 0.0, "hi": 10.0, "batch_fill_slots": fill}
    if counted is not None:
        traced["hybrid"] = counted
    return {"config": config, "peaks": {"hbm_bytes_per_s": 819e9,
                                        "bf16_flops_per_s": 197e12},
            "obs": {"token_events": events, "traced": traced}}


def test_required_work_against_hand_worked_counts(config):
    m = hybrid_work.dims(config)
    counted = {"decode_calls": 3, "prefill_calls": 1,
               "prefill": {"moe_local_pairs": 50, "moe_experts_touched": 40,
                           "moe_pairs_total": 220,
                           "moe_expert_load_max": 4},
               "decode": {"moe_local_pairs": 30, "moe_experts_touched": 25,
                          "moe_pairs_total": 132,
                          "moe_expert_load_max": 2}}
    # one prompt of 10 tokens prefilled, two tokens decoded behind it
    events = [(1.0, 10, 0), (2.0, 10, 1), (3.0, 10, 2), (11.0, 10, 3)]
    view = _view(config, events, counted)
    dense = 5 * 2 * (4096 * 18560 + 8192 * 4096) \
        + 2 * 2 * 4096 * (4096 + 256) \
        + 5 * 2 * (2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096 * 512)
    assert hybrid_work.token_flops(m, 0) == dense
    head = 2 * 4096 * 32768
    want = (80 * 2 * 5505024
            + 10 * dense + 4 * 4096 * 55 + head
            + 2 * dense + 4 * 4096 * (11 + 12) + 2 * head)
    assert hybrid_work.hybrid_serve_flops(view) == want
    moe_fixed = 5 * ((2 * 4096 * 1024 + 2 * 4096 * 5376) * 2
                     + 4096 * 512 * 4)
    assert hybrid_work.moe_decode_bytes(view) == \
        3 * moe_fixed + 25 * 5505024 * 2
    ssm_fixed = 5 * (4096 * 18560 + 8192 * 4096 + 5 * 10240) * 2
    slot = 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert hybrid_work.ssm_decode_bytes(view) == \
        3 * ssm_fixed + 2 * slot * 6.0
    rest = 3 * (2 * 4096 * (4096 + 256) + 4096 * 32768) * 2 \
        + 2 * 256 * (11 + 12) * 2
    whole = hybrid_work.hybrid_decode_bytes(view, {"count": 6})
    assert whole == pytest.approx(2 * (
        hybrid_work.moe_decode_bytes(view)
        + hybrid_work.ssm_decode_bytes(view) + rest))
    # ISSUE 28's sizing: a full step at 64 live slots with 94% of the
    # held experts touched needs about 11.3 GB
    full = {"decode_calls": 1, "prefill": counted["prefill"],
            "decode": dict(counted["decode"],
                           moe_experts_touched=int(0.94 * 640))}
    step = hybrid_work.hybrid_decode_bytes(
        _view(config, [], full, fill=64.0))
    assert 11.0e9 < step < 11.6e9


def test_a_program_without_the_counters_gives_the_readers_nothing(config):
    view = _view(config, [(1.0, 10, 0)], None)
    for work in hybrid_work.WORK.values():
        assert work(view) is None
    view["trace"] = {"window_s": 10.0, "chips": 1, "programs": {
        "jit_decode": {"count": 3, "total_s": 0.05}}}
    for name in ("hybrid.serve.mfu", "hybrid.decode.hbm_roofline"):
        entry = harness.load_json(BENCH, "layer_metrics", name + ".json")
        assert entry["reader"] == "work_share"
        assert work_share.read(dict(view, args=entry["args"])) is None
    assert work_share.read(dict(view, trace=None, args={})) is None


def test_the_reader_divides_work_by_peak_and_time(config):
    counted = {"decode_calls": 2, "prefill_calls": 0,
               "prefill": {"moe_local_pairs": 0, "moe_experts_touched": 0,
                           "moe_pairs_total": 0, "moe_expert_load_max": 0},
               "decode": {"moe_local_pairs": 10, "moe_experts_touched": 9,
                          "moe_pairs_total": 44, "moe_expert_load_max": 1}}
    view = _view(config, [(1.0, 10, 1), (2.0, 10, 2)], counted, fill=1.0)
    view["trace"] = {"window_s": 10.0, "chips": 1, "programs": {
        "jit_decode": {"count": 2, "total_s": 0.04}}}
    entry = harness.load_json(BENCH, "layer_metrics",
                              "hybrid.decode.hbm_roofline.json")
    got = work_share.read(dict(view, args=entry["args"]))
    want = hybrid_work.hybrid_decode_bytes(view, {"count": 2})
    assert got == pytest.approx(100.0 * want / 819e9 / 0.04)
    assert 0 < got < 100
    entry = harness.load_json(BENCH, "layer_metrics",
                              "hybrid.serve.mfu.json")
    got = work_share.read(dict(view, args=entry["args"]))
    assert got == pytest.approx(
        100.0 * hybrid_work.hybrid_serve_flops(view) / 10.0 / 197e12)
