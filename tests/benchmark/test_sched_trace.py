"""The serving scheduler's per-layer metrics: ``readers/step_stall.py``
(what each scheduler step ran before its decode, from the step span's
``prefills`` and ``decoded``) and ``readers/idle_under.py`` (the
device's idle time intersected with the program's own spans), on
hand-made lists with exact answers and on the chat cell's fixture cut
from the chip's trace.  CPU only, quick."""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmarks import harness, program_trace  # noqa: E402
from benchmarks.readers import idle_under, step_stall  # noqa: E402

BENCH = os.path.join(REPO_ROOT, "benchmarks")
MS = 1000000
HALF = MS // 2
EXPERT_CELLS = ["nemotron3_super_120b_a12b.chat",
                "command_a_plus_05_2026.mixed_len"]
STALL = ["sched.stalled_token_share", "sched.multi_stall_token_share",
         "sched.stall_ms"]
IDLE = ["sched.idle_fetch_ms", "sched.idle_launch_ms",
        "sched.idle_host_ms", "sched.idle_empty_share",
        "sched.idle_unspanned_share"]
READERS = {"step_stall": step_stall, "idle_under": idle_under}


def _span(name, start, end, thread=1, **stats):
    return ["veles:gen/" + name, start, end - start, thread, stats]


def _read(metric, extracted):
    entry = harness.load_json(BENCH, "layer_metrics", metric + ".json")
    return READERS[entry["reader"]].read(
        {"program_trace": extracted, "trace": {}, "args": entry["args"]})


def _whole_idle(extracted):
    return idle_under.read({"program_trace": extracted, "trace": {},
                            "args": {"spans": None, "per": None}})


# -- step_stall -----------------------------------------------------------

def _steps():
    """A plain step, a step behind one prefill (admission 20 ms, the
    prefill inside it), a step behind two chunks (40 ms each), and a
    step past the window's edge, which does not count."""
    spans = [
        _span("step", 0, 10 * MS, prefills=0, decoded=4),
        _span("decode", 1 * MS, 9 * MS),
        _span("step", 10 * MS, 45 * MS, prefills=1, decoded=3),
        _span("admit", 11 * MS, 31 * MS),
        _span("prefill", 12 * MS, 30 * MS),
        _span("decode", 32 * MS, 44 * MS),
        _span("step", 45 * MS, 140 * MS, prefills=2, decoded=2),
        _span("prefill_chunk", 46 * MS, 86 * MS),
        _span("prefill_chunk", 87 * MS, 127 * MS),
        # a chunk on another thread is none of this step's
        _span("prefill_chunk", 50 * MS, 60 * MS, thread=2),
        _span("decode", 128 * MS, 139 * MS),
        _span("step", 140 * MS, 260 * MS, prefills=3, decoded=5),
    ]
    return {"window": [0, 200 * MS], "spans": spans, "ops": [],
            "runs": []}


def test_step_stall_on_handmade_steps():
    extracted = _steps()
    assert _read("sched.stalled_token_share", extracted) \
        == pytest.approx(100.0 * 5 / 9)
    assert _read("sched.multi_stall_token_share", extracted) \
        == pytest.approx(100.0 * 2 / 9)
    # (20 of admission + 40 + 40 of chunks) over the two stalled steps
    assert _read("sched.stall_ms", extracted) == pytest.approx(50.0)


def test_a_window_of_plain_steps_reads_zero_and_no_stall():
    extracted = _steps()
    extracted["spans"] = extracted["spans"][:2]
    assert _read("sched.stalled_token_share", extracted) == 0.0
    assert _read("sched.multi_stall_token_share", extracted) == 0.0
    assert _read("sched.stall_ms", extracted) is None


# -- idle_under -----------------------------------------------------------

def _idle_trace():
    """Idle [10, 12], [30, 35], [60, 70], [90, 100] ms of a 100 ms
    window, under two scheduler steps (a plain decode; an admission
    with its prefill, then a decode), a wait with nothing to do between
    them, and stretches under no span."""
    ops = [["jit_decode", "", start * MS, (end - start) * MS]
           for start, end in ((0, 10), (12, 30), (35, 60), (70, 90))]
    spans = [
        _span("step", 5 * MS, 34 * MS),
        _span("decode", 6 * MS, 33 * MS),
        _span("decode_dispatch", 6 * MS, 11 * MS),
        _span("decode_fetch", 11 * MS + HALF, 32 * MS),
        _span("idle", 34 * MS + HALF, 61 * MS),
        _span("step", 62 * MS, 95 * MS),
        _span("admit", 62 * MS + HALF, 80 * MS),
        _span("prefill", 62 * MS + HALF, 80 * MS),
        _span("prefill_dispatch", 63 * MS, 65 * MS),
        _span("prefill_fetch", 65 * MS, 79 * MS),
        _span("decode", 81 * MS, 94 * MS),
        _span("decode_dispatch", 81 * MS, 82 * MS),
        _span("decode_fetch", 82 * MS, 93 * MS + HALF),
    ]
    return {"window": [0, 100 * MS], "spans": spans, "ops": ops,
            "runs": []}


def test_idle_under_on_handmade_intervals():
    extracted = _idle_trace()
    # fetch: [11.5, 12] + [30, 32] + [65, 70] + [90, 93.5] = 11 ms
    assert _read("sched.idle_fetch_ms", extracted) == pytest.approx(11 / 2)
    # launch: [10, 11] + [63, 65] = 3 ms
    assert _read("sched.idle_launch_ms", extracted) == pytest.approx(3 / 2)
    # host, in a step and under neither: [11, 11.5] + [32, 34] +
    # [62, 63] + [93.5, 95] = 5 ms
    assert _read("sched.idle_host_ms", extracted) == pytest.approx(5 / 2)
    # nothing to do: [34.5, 35] + [60, 61] = 1.5 ms of 100
    assert _read("sched.idle_empty_share", extracted) == pytest.approx(1.5)
    # under no span: [34, 34.5] + [61, 62] + [95, 100] = 6.5 ms of 100
    assert _read("sched.idle_unspanned_share", extracted) \
        == pytest.approx(6.5)
    assert _whole_idle(extracted) == pytest.approx(27.0)


def test_idle_under_clips_at_the_window():
    extracted = _idle_trace()
    extracted["window"] = [31 * MS, 100 * MS]
    # the first step straddles the edge: its fetch counts from 31 ms,
    # and its decode span does not count as a step of the window
    assert _read("sched.idle_fetch_ms", extracted) == pytest.approx(9.5)
    assert _whole_idle(extracted) == pytest.approx(100.0 * 24 / 69)


def _classes_ns(extracted, steps):
    lo, hi = program_trace.window_of(extracted)
    per_step = sum(_read(name, extracted) for name in IDLE[:3])
    shares = [_read(name, extracted) for name in IDLE[3:]]
    return per_step * MS * steps + sum(shares) * (hi - lo) / 100.0


def test_the_five_classes_are_the_whole_idle_on_handmade_intervals():
    extracted = _idle_trace()
    assert _classes_ns(extracted, 2) == pytest.approx(27 * MS, abs=1)


# -- on the chat cell's fixture, cut from a traced run on the chip -----------

def _fixture():
    return harness.load_json(
        BENCH, "fixtures",
        "program_trace_cerebras_gpt_1p3b.chat.json")["extracted"]


def test_the_classes_add_up_to_the_fixtures_idle_to_the_nanosecond():
    extracted = _fixture()
    lo, hi = program_trace.window_of(extracted)
    steps = len(program_trace.spans_in_window(extracted,
                                              "veles:gen/decode"))
    assert steps == 4
    whole = sum(seconds for _label, seconds in
                program_trace.idle_gaps(extracted, top=None)) * 1e9
    assert whole == pytest.approx(15430510, abs=1)
    assert _whole_idle(extracted) * (hi - lo) / 100.0 \
        == pytest.approx(whole, abs=1)
    assert _classes_ns(extracted, steps) == pytest.approx(whole, abs=1)
    # the cut holds no wait with nothing to do
    assert _read("sched.idle_empty_share", extracted) == 0.0


def test_fetch_and_launch_a_step_on_the_fixture_by_hand():
    """The six idle gaps of the cut longer than 20 us (the device's
    short gaps between operations add 6,389 ns in all), split by the
    span boundaries printed from the fixture, over its four decode
    steps that lie in the window whole."""
    extracted = _fixture()
    fetch = ((1728523 - 573338) + (75467231 - 73983260)
             + (149266428 - 147887449) + (198180506 - 197032570)
             + (271734552 - 270546928) + (345380280 - 344072632))
    launch = ((3000293 - 1980103) + (76907483 - 75666720)
              + (150849563 - 149486577) + (199366248 - 198325646)
              + (272903026 - 271940043) + (346493685 - 345538020))
    short = 6389 / 4 / MS
    assert _read("sched.idle_fetch_ms", extracted) \
        == pytest.approx(fetch / 4 / MS, abs=short)
    assert _read("sched.idle_launch_ms", extracted) \
        == pytest.approx(launch / 4 / MS, abs=short)


# -- nothing to read --------------------------------------------------------

@pytest.mark.parametrize("metric", STALL + IDLE)
def test_none_when_untraced(metric):
    entry = harness.load_json(BENCH, "layer_metrics", metric + ".json")
    reader = READERS[entry["reader"]]
    assert reader.read({"trace": None, "args": entry["args"]}) is None
    bare = {"window": [0, 10], "spans": [], "runs": [["jit_step", 0, 5]],
            "ops": [["jit_step", "", 0, 5]]}
    assert reader.read({"program_trace": bare, "trace": {},
                        "args": entry["args"]}) is None


@pytest.mark.parametrize("metric", STALL)
def test_step_stall_is_none_on_steps_without_the_stats(metric):
    """Step spans of a scheduler that does not count its prefills
    carry ``emitted`` and nothing this reader counts; the idle readers
    still read the spans such a program has."""
    extracted = _fixture()
    assert all("decoded" not in span[4] for span in extracted["spans"])
    assert _read(metric, extracted) is None
    assert _read("sched.idle_fetch_ms", extracted) > 0


@pytest.mark.parametrize("metric", STALL + IDLE)
def test_each_metric_is_listed_for_the_expert_cells(metric):
    spec = harness.manifest()
    (listed,) = [m for m in spec["per_layer"] if m["name"] == metric]
    entry = harness.load_json(BENCH, "layer_metrics", metric + ".json")
    assert listed["workloads"] == EXPERT_CELLS
    assert listed["layer"] == entry["layer"] == "serving scheduler"
    assert entry["reader"] in READERS
    assert listed["source"] == ("program_span" if metric in STALL
                                else "device_trace")
    assert listed["moves"] == ("gap_p97_ms" if metric in STALL
                               else "out_tokens_per_s")
