"""CPU rehearsals of ``chip_smoke.py`` and of the loud-failure rules PR 21
put around the chip: no hidden fallback from the TPU to the CPU, one
compile-cache rule, one process per chip, children that fail the run.

The script itself has no CPU mode — run as a script here it must FAIL.
These tests import it and call its phase functions directly, at tiny
shapes, on the virtual CPU devices ``conftest.py`` forces.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import numpy
import pytest

import chip_smoke
from veles_tpu import backends

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run_script(args=(), cwd=REPO_ROOT, env=None):
    return subprocess.run(
        [sys.executable, SCRIPT if cwd == REPO_ROOT else "chip_smoke.py"]
        + list(args), cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _no_result(proc):
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    lines = proc.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith('{"ok"')


# ---------------------------------------------------------------------------
# the script without a chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_script_fails_without_a_tpu_and_prints_no_result(args):
    proc = _run_script(args)          # inherits JAX_PLATFORMS=cpu
    _no_result(proc)
    assert "not a TPU" in proc.stderr and "no CPU mode" in proc.stderr


def test_script_has_no_cpu_option():
    for flag in ("--cpu", "--platform=cpu", "--device=cpu"):
        proc = _run_script((flag,))
        assert proc.returncode == 2 and "unrecognized" in proc.stderr
    usage = _run_script(("--help",)).stdout
    assert "--chips" in usage and "cpu" not in usage.lower().replace(
        "no cpu mode", "")


def test_script_alone_in_a_directory_fails_and_prints_no_result(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = _run_script(cwd=str(tmp_path), env=env)
    _no_result(proc)
    assert "needs the veles_tpu checkout" in proc.stderr


def test_a_failing_phase_prints_the_reason_and_stops(capsys):
    meter = chip_smoke.CompileMeter()

    def broken():
        chip_smoke.check(False, "what came out is wrong")

    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.run_phase("broken", broken, meter)
    assert exit_info.value.code == 1
    record = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert record == {"phase": "broken", "ok": False,
                      "error": "SmokeFailure: what came out is wrong"}


def test_a_passing_phase_line_labels_its_times_as_smoke_values(capsys):
    meter = chip_smoke.CompileMeter()
    chip_smoke.run_phase(
        "compiles", lambda: {"out": float(jax.jit(lambda x: x * 2 + 1)(
            numpy.float32(3.0)))}, meter)
    record = json.loads(capsys.readouterr().out.strip())
    assert record["phase"] == "compiles" and record["ok"] is True
    assert record["out"] == 7.0
    assert record["smoke_compile_seconds"] > 0.0
    timed = [key for key in record if "seconds" in key]
    assert timed and all(key.startswith("smoke_") for key in timed)


# ---------------------------------------------------------------------------
# the phases, tiny, on the CPU
# ---------------------------------------------------------------------------

def test_cli_train_phase_runs_main_on_the_default_auto_device():
    out = chip_smoke.phase_cli_train(expect_platform="cpu")
    assert out["device"].startswith("<CPUDevice")
    assert out["train_minibatches"] == 60
    assert numpy.isfinite(out["last_loss"])
    assert min(out["max_abs_weight_change"]) > 0
    assert set(out["kernel_backends"]) == {"gemm", "gd"}


def test_cli_train_phase_fails_on_the_wrong_device():
    with pytest.raises(chip_smoke.SmokeFailure, match="want the tpu"):
        chip_smoke.phase_cli_train(expect_platform="tpu")


def test_alexnet_phase_tiny_u8_loader_bf16_step_one_compile_each():
    """Also the regression test of the eval path this phase found
    broken: a u8-resident input is ingested in the bf16 compute dtype,
    and the evaluation forward then met float32 weights."""
    out = chip_smoke.phase_alexnet_train(
        input_shape=(67, 67, 3), batch=8, expect_platform="cpu")
    assert out["train_minibatches"] == 5 and out["eval_minibatches"] == 2
    assert out["compiles"] == {"step": 1, "eval": 1}
    assert out["compute_dtype"] == "bfloat16"
    assert out["master_dtype"] == "float32"
    assert all(numpy.isfinite(out["losses"]))


def test_lm_serve_phase_tiny_both_cache_modes_over_http():
    from veles_tpu.samples.transformer import TINY
    out = chip_smoke.phase_lm_serve(
        cfg=dict(TINY, seq_len=64),
        requests=((3, 4), (9, 6), (17, 5), (30, 8)), slots=2, max_seq=64,
        buckets=(8, 32), chunk=16, block_size=8)
    assert [s["kv"] for s in out["sessions"]] == ["contiguous", "paged"]
    assert out["sessions"][1]["prefill_chunk"] == 16
    assert out["new_tokens"] == 4 + 6 + 5 + 8
    assert out["first_tokens_equal"] is True
    # on the CPU the two cache modes are bitwise equal (the gen smoke's
    # gate); on the chip a later flat-logit argmax may flip
    assert out["equal_token_share"] == 1.0
    assert out["first_divergence"] is None
    assert all(not s["decode_has_tpu_custom_call"]
               for s in out["sessions"])
    assert out["kernel_backends"]["decode_attention"] == "xla"
    from veles_tpu.config import root
    assert root.common.gen.get("kv", "contiguous") == "contiguous"


def test_logit_gap_measures_how_flat_the_logits_were():
    import jax.numpy as jnp
    from veles_tpu.gen import TransformerGenModel
    from veles_tpu.samples.transformer import TINY, init_params
    cfg = dict(TINY, seq_len=32)
    model = TransformerGenModel(cfg, compute_dtype=jnp.bfloat16)
    params = init_params(cfg, seed=3)
    stream = [1, 2, 3, 4, 5]
    logits = numpy.asarray(model.calibration_logits(params, stream))
    order = numpy.argsort(logits)
    top, runner_up = int(order[-1]), int(order[-2])
    gap = chip_smoke._logit_gap(model, params, stream, top, runner_up)
    assert gap == pytest.approx(float(logits[top] - logits[runner_up]))
    assert chip_smoke._logit_gap(model, params, stream, top, top) == 0.0


def test_pod_phase_on_four_virtual_devices():
    out = chip_smoke.phase_pod_train(chips=4, epochs=2, batch=1000)
    assert out["mesh"] == {"data": 4}
    assert len(out["batch_shard_devices"]) == 4
    assert out["all_reduce_in_compiled_step"] is True
    assert max(out["max_abs_weight_diff"]) <= chip_smoke.POD_TOLERANCE
    assert set(out["programs_per_segment"].values()) == {1}
    assert out["pod_metrics"]["best_n_err_pt"] \
        == out["one_device_metrics"]["best_n_err_pt"]


def test_pod_phase_fails_on_one_device_instead_of_shrinking(monkeypatch):
    from veles_tpu.parallel.mesh import MeshTopologyError
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *args: one)
    with pytest.raises(MeshTopologyError, match="asks for 4 devices"):
        chip_smoke.phase_pod_train(chips=4)


# ---------------------------------------------------------------------------
# no hidden fallback to the CPU
# ---------------------------------------------------------------------------

def test_auto_device_raises_when_no_tpu_and_cpu_was_not_asked_for(
        monkeypatch):
    """A process started without JAX_PLATFORMS=cpu that finds no TPU
    stops, naming what it looked for — it does not train on the CPU."""
    monkeypatch.setattr(backends, "_requested_platforms", lambda: "")
    with pytest.raises(RuntimeError) as err:
        backends.AutoDevice()
    message = str(err.value)
    assert "looked for a TPU" in message and "JAX_PLATFORMS=cpu" in message
    # make_device("auto") and the Launcher's default take the same path
    with pytest.raises(RuntimeError, match="looked for a TPU"):
        backends.make_device("auto")


def test_auto_device_never_picks_numpy(monkeypatch):
    monkeypatch.setattr(backends, "_requested_platforms", lambda: "tpu,cpu")
    with pytest.raises(RuntimeError, match="looked for a TPU"):
        backends.AutoDevice()


@pytest.mark.parametrize("name,klass", [("cpu", backends.CPUDevice),
                                        ("numpy", backends.NumpyDevice)])
def test_cpu_and_numpy_are_chosen_when_asked_for_by_name(
        monkeypatch, name, klass):
    monkeypatch.setattr(backends, "_requested_platforms", lambda: "")
    assert isinstance(backends.make_device(name), klass)


def test_hardware_prng_and_unknown_families_are_errors_not_fallbacks():
    from veles_tpu.ops import resolved_backend
    with pytest.raises(ValueError, match="unknown kernel family"):
        resolved_backend("matrix_reduce", "float32", (8, 8))
    # off the TPU every family resolves to XLA, and says so
    assert resolved_backend("gemm", "bfloat16", (256, 4096, 4096)) == "xla"
    assert resolved_backend("decode_attention", "bfloat16",
                            (8, 1, 16, 64)) == "xla"


def test_no_peak_is_assumed_for_an_unknown_device_kind():
    for kind in ("cpu", "TPU v9 imaginary", "", None):
        assert backends.peak_bf16_flops(kind) is None
        assert backends.peak_int8_ops(kind) is None
        assert backends.device_hbm_bytes(kind) is None
    # the v5e's reported kind resolves to the v5e row, never to v5p's
    for kind in ("TPU v5 lite", "TPU v5e"):
        assert backends.peak_bf16_flops(kind) == 197e12
        assert backends.device_hbm_bytes(kind) == 16 << 30
    assert backends.peak_bf16_flops("TPU v5p") == 459e12


# ---------------------------------------------------------------------------
# the one compile-cache rule
# ---------------------------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them (a real
    cache directory must not leak into the rest of the test session)."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.append((key, value)))
    return calls


def _directories_set(config_updates):
    return [(key, value) for key, value in config_updates
            if "dir" in key]


def test_cache_is_keyed_on_the_programs_names(monkeypatch,
                                              config_updates):
    """The names a device trace shows are HLO metadata: the key holds
    them (JAX's default leaves them out, and a cached executable then
    shows the names it was compiled with), and the checkout's own path
    is taken out of the source files so that checkouts share entries."""
    import re
    for env in ("/somewhere", None):
        del config_updates[:]
        if env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                               raising=False)
        backends.enable_compilation_cache(platform="tpu")
        updates = dict(config_updates)
        assert updates[
            "jax_compilation_cache_include_metadata_in_key"] is True
        pattern = updates["jax_hlo_source_file_canonicalization_regex"]
        here = os.path.join(REPO_ROOT, "veles_tpu", "backends.py")
        assert re.sub(pattern, "", here) == os.path.join(
            "veles_tpu", "backends.py")


def test_cache_dir_from_the_environment_means_the_code_sets_nothing(
        monkeypatch, config_updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert backends.enable_compilation_cache(platform="tpu") \
        == str(tmp_path / "env")
    assert _directories_set(config_updates) == []
    assert not (tmp_path / "env").exists()     # JAX makes it, not we


def test_cache_dir_unset_is_one_fixed_path_inside_the_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO_ROOT, ".cache", "xla")
    assert backends.COMPILE_CACHE_DIR == fixed
    for _ in range(2):                         # same answer every time
        assert backends.enable_compilation_cache(platform="tpu") == fixed
    assert _directories_set(config_updates) == [
        ("jax_compilation_cache_dir", fixed)] * 2
    home = os.path.expanduser("~")
    assert not fixed.startswith(home + os.sep) or REPO_ROOT.startswith(
        home + os.sep)
    with open(os.path.join(REPO_ROOT, ".gitignore")) as fin:
        assert ".cache/" in fin.read().split(), \
            ".cache/ must be git-ignored"


def test_cache_stays_off_on_the_cpu(monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
    assert backends.enable_compilation_cache(platform="cpu") is None
    assert backends.enable_compilation_cache() is None   # tests run on cpu
    assert config_updates == []


def _tracked_sources():
    skip = {".git", ".cache", "chiprun_out", "_archive_check",
            "__pycache__", ".pytest_cache"}      # what .gitignore lists
    for base, dirs, names in os.walk(REPO_ROOT):
        dirs[:] = [d for d in dirs if d not in skip]
        for name in names:
            if name.endswith((".py", ".sh")):
                path = os.path.join(base, name)
                with open(path) as fin:
                    yield os.path.relpath(path, REPO_ROOT), fin.read()


def test_no_code_path_sets_a_second_cache_directory():
    """One rule, in backends.enable_compilation_cache: nothing else in
    the tree sets the cache directory in code or exports the variable."""
    import re
    setter = re.compile(
        r"""(setdefault\(|\[)\s*["']JAX_COMPILATION_CACHE_DIR["']"""
        r"""\s*(\]\s*=[^=]|,)|export\s+JAX_COMPILATION_CACHE_DIR""")
    config_key, exporters = [], []
    for rel, text in _tracked_sources():
        if rel == os.path.join("tests", "test_chip_smoke.py"):
            continue
        if "jax_compilation_cache_dir" in text:
            config_key.append(rel)
        if setter.search(text):
            exporters.append(rel)
    assert config_key == [os.path.join("veles_tpu", "backends.py")]
    assert exporters == []


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_parent_has_not_initialised_a_backend_before_it_spawns():
    """import veles_tpu, seeding, the launcher import and Main's own
    set-up (parse, logging, seeds, config) leave JAX's backends alone —
    what lets --optimize / --ensemble-* / the master's -n spawn hand the
    chip to their children."""
    code = """
import sys
import veles_tpu
from veles_tpu import prng, launcher
from veles_tpu.__main__ import Main
from veles_tpu.backends import assert_backend_untouched
prng.seed_all(1234)
main = Main(["veles_tpu.samples.mnist", "--optimize", "2:1"])
main._parse(); main._setup_logging(); main._seed_random()
main._apply_config()
launcher.Launcher(listen="127.0.0.1:0", nodes=["localhost"])
assert_backend_untouched("test")
if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized()
print("untouched")
"""
    env = dict(os.environ, PYTHONPATH=REPO_ROOT)
    env.pop("JAX_PLATFORMS", None)     # the assertion, not the env, decides
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("untouched")


def test_spawn_site_assertion_fires_once_the_parent_holds_the_chip(
        monkeypatch):
    backends.assert_backend_untouched("on the cpu")     # passes: cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the device"):
        backends.assert_backend_untouched("genetics child run")
    from veles_tpu.ensemble import EnsembleModelManager
    manager = EnsembleModelManager(workflow_spec="no.such.workflow",
                                   size=1)
    with pytest.raises(RuntimeError, match="ensemble member run"):
        manager.run()


def test_a_failed_child_fails_the_run_with_a_nonzero_exit():
    from veles_tpu.__main__ import Main
    from veles_tpu.ensemble import EnsembleModelManager
    manager = EnsembleModelManager(workflow_spec="no.such.workflow",
                                   size=1, extra_args=("-d", "numpy"))
    payload = manager.run()
    assert payload["models"][0]["results"] is None
    assert manager.child_failures == 1
    assert Main([])._children_rc(manager) == 1
    manager.child_failures = 0
    assert Main([])._children_rc(manager) == 0


def test_a_failed_genetics_child_fails_the_candidate_and_the_run():
    from veles_tpu.genetics import GeneticsOptimizer
    optimizer = GeneticsOptimizer.__new__(GeneticsOptimizer)
    optimizer.__dict__.update(
        workflow_spec="no.such.workflow", config_file=None,
        extra_args=("-d", "numpy"), fitness_key=None, child_failures=0)
    from veles_tpu.logger import Logger
    Logger.__init__(optimizer)
    assert optimizer._evaluate_subprocess({}) == float("-inf")
    assert optimizer.child_failures == 1


def test_master_fails_when_a_bootstrapped_slave_exits_nonzero():
    from veles_tpu.launcher import Launcher
    launcher = Launcher(listen="127.0.0.1:0")
    launcher._spawned_ = [
        subprocess.Popen([sys.executable, "-c", "raise SystemExit(3)"]),
        subprocess.Popen([sys.executable, "-c", "pass"])]
    assert launcher._reap_spawned(timeout=30.0) == [3]
    assert launcher._spawned_ == []
