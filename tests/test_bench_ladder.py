"""bench.py as ONE process: stage selection, loud failures, chip-or-fail.

``python bench.py`` runs the selected stages in the calling process (a
chip belongs to one process at a time), stamps every record with the
platform / device kind / device count it ran on, refuses a non-TPU
platform unless the caller asked for a CPU rehearsal
(``JAX_PLATFORMS=cpu``), and exits non-zero when any selected stage
raised, was cut at its cap, or never ran.
"""

import io
import json
import contextlib

import pytest

import bench


# ---------------------------------------------------------------------------
# stage selection
# ---------------------------------------------------------------------------

def test_no_selection_means_every_stage_headline_last():
    order = bench._selected_stages(None)
    assert order == bench.STAGE_ORDER
    assert order[-1] == "alexnet"
    assert set(order) == set(bench.STAGES)


def test_only_filters_in_canonical_order():
    assert bench._selected_stages("alexnet, mnist,lstm") == (
        "mnist", "lstm", "alexnet")


def test_unknown_stage_is_an_error_not_a_silent_skip():
    with pytest.raises(ValueError, match="no_such_stage"):
        bench._selected_stages("mnist,no_such_stage")


# ---------------------------------------------------------------------------
# run_stages / main: one process, loud failures
# ---------------------------------------------------------------------------

@pytest.fixture
def fake_stages(monkeypatch):
    for var in ("BENCH_STAGES", "BENCH_TIMEOUT_SCALE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("BENCH_BUDGET_SEC", "600")
    monkeypatch.setattr(bench, "stage_probe", lambda: {})
    calls = []

    def fake(name, fail=None, cap=60):
        def run():
            calls.append(name)
            if fail is not None:
                raise fail
            print(bench._dumps({"metric": name, "value": 1.0,
                                "unit": "images/sec"}))
        return run, cap

    stages = {n: fake(n) for n in bench.STAGES}
    monkeypatch.setattr(bench, "STAGES", stages)
    return stages, calls, fake


def _main_records():
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = bench.main()
    return rc, [json.loads(line)
                for line in buf.getvalue().strip().splitlines() if line]


def test_main_runs_every_stage_in_process_and_exits_zero(fake_stages):
    _stages, calls, _fake = fake_stages
    rc, records = _main_records()
    assert rc == 0
    assert tuple(calls) == bench.STAGE_ORDER
    assert [r["metric"] for r in records] == list(bench.STAGE_ORDER)


def test_main_honors_explicit_stage_selection(fake_stages, monkeypatch):
    _stages, calls, _fake = fake_stages
    monkeypatch.setenv("BENCH_STAGES", "mnist,alexnet")
    rc, _records = _main_records()
    assert rc == 0 and calls == ["mnist", "alexnet"]


def test_stage_error_is_reported_later_stages_run_exit_nonzero(
        fake_stages, capsys):
    stages, calls, fake = fake_stages
    stages["mnist_u8"] = fake("mnist_u8", ValueError("boom"))
    rc, records = _main_records()
    assert rc == 1
    assert calls[-1] == "alexnet"          # kept going to the end
    assert "mnist_u8" not in [r["metric"] for r in records]
    err = capsys.readouterr().err
    assert "bench stage mnist_u8 FAILED: ValueError: boom" in err
    assert "1 of %d selected stage(s) failed" % len(bench.STAGE_ORDER) \
        in err


def test_run_stages_names_each_failure(fake_stages):
    stages, _calls, fake = fake_stages
    stages["mnist"] = fake("mnist", RuntimeError("first line\nsecond"))
    failed = bench.run_stages(("mnist", "mnist_bf16"), budget=600)
    assert list(failed) == ["mnist"]
    assert failed["mnist"].startswith("RuntimeError: first line")


def test_stage_cut_at_its_cap_is_a_failure(fake_stages):
    import time
    stages, calls, _fake = fake_stages

    def hang():
        calls.append("mnist")
        time.sleep(30)

    stages["mnist"] = (hang, 1)
    failed = bench.run_stages(("mnist", "mnist_bf16"), budget=600)
    assert "cut at its" in failed["mnist"]
    assert calls == ["mnist", "mnist_bf16"]


def test_spent_budget_marks_stages_not_run(fake_stages):
    _stages, calls, _fake = fake_stages
    failed = bench.run_stages(("mnist", "alexnet"), budget=10)
    assert not calls
    assert set(failed) == {"mnist", "alexnet"}
    assert all("not run" in reason for reason in failed.values())


def test_main_refuses_a_non_tpu_platform_unless_cpu_was_asked_for(
        fake_stages, monkeypatch, capsys):
    """The tests' JAX runs on the CPU; without JAX_PLATFORMS=cpu in the
    environment that is a missing chip, not a rehearsal."""
    _stages, calls, _fake = fake_stages
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rc, records = _main_records()
    assert rc == 2 and not calls and not records
    assert "measures on a TPU" in capsys.readouterr().err


def test_main_propagates_a_bad_stage_name(fake_stages, monkeypatch):
    monkeypatch.setenv("BENCH_STAGES", "alexnet,typo")
    with pytest.raises(ValueError, match="typo"):
        bench.main()


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------

def test_every_record_names_platform_kind_and_count():
    import jax
    rec = json.loads(bench._dumps({"metric": "m", "value": 1.0}))
    dev = jax.devices()[0]
    assert rec["platform"] == dev.platform == "cpu"
    assert rec["device_kind"] == dev.device_kind
    assert rec["n_devices"] == jax.device_count()
    assert isinstance(rec["ts"], int)


def test_a_stage_that_names_its_own_device_kind_keeps_it():
    rec = json.loads(bench._dumps({
        "metric": "native", "value": 1.0,
        "device_kind": "host-cpu (native engine)"}))
    assert rec["device_kind"] == "host-cpu (native engine)"
    assert rec["platform"] == "cpu"


def test_non_metric_lines_are_not_stamped():
    assert json.loads(bench._dumps({"note": "x"})) == {"note": "x"}


def test_probe_reports_the_device_without_old_lines(capsys):
    probe = bench.stage_probe()
    assert probe["platform"] == "cpu" and probe["n_devices"] >= 1
    assert not [k for k in probe if "banked" in k]
    assert json.loads(capsys.readouterr().out.strip())["platform"] == "cpu"


def test_bench_has_no_child_process_fallback_or_echo_code():
    """One process per chip: the parent/child choreography, the CPU
    ladder and the re-emission of old lines are gone for good."""
    for name in ("_cpu_fallback", "_emit_banked_tail",
                 "_banked_tpu_lines", "_stream_ladder", "_run_stage",
                 "_ladder_cmd", "stage_ladder", "sample_starved",
                 "_cache_dir", "subprocess"):
        assert not hasattr(bench, name), name
