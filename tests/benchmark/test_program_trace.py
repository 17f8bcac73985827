"""The program's own names in a profiler trace: the arithmetic of
``benchmarks/program_trace.py`` on hand-made lists, scope extraction on
name stacks copied from the chip's trace, the wire-format walk on a
hand-built ``.xplane.pb``, and each new reader on a fixture cut from
one traced run of each cell.  CPU only, quick.
"""

import io
import json
import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

from benchmarks import harness, program_trace, trace_reduce  # noqa: E402
from benchmarks.readers import (scope_device_ms, scope_roofline,  # noqa: E402
                                span_ms, span_stat)

BENCH = os.path.join(REPO_ROOT, "benchmarks")
MS = 1000000


# -- scope extraction, on name stacks from the chip's trace (PR 26) ---------

@pytest.mark.parametrize("stack,scope,bare", [
    ("jit(decode)/while/body/closed_call/veles.gpt.attn/"
     "veles_attn_decode/pallas_call",
     "veles.gpt.attn/veles_attn_decode", "veles_attn_decode"),
    ("jit(decode)/while/body/closed_call/veles.gpt.kv_write/gather",
     "veles.gpt.kv_write", "veles.gpt.kv_write"),
    ("jit(decode)/while/body/closed_call/veles.gpt.qkv/"
     "bsd,dchx->bschx/dot_general", "veles.gpt.qkv", "veles.gpt.qkv"),
    ("jit(decode)/veles.gpt.embed/gather", "veles.gpt.embed",
     "veles.gpt.embed"),
    ("jit(step_fn)/transpose(jvp(veles.layer.00.conv_str))/"
     "conv_general_dilated",
     "transpose(jvp(veles.layer.00.conv_str))",
     "veles.layer.00.conv_str"),
    ("jit(step_fn)/jvp(veles.layer.03.conv)/conv_general_dilated",
     "jvp(veles.layer.03.conv)", "veles.layer.03.conv"),
    ("jit(step_fn)/veles.update/mul", "veles.update", "veles.update"),
    ("jit(_gather_jnp)/veles.loader.take_rows/jit(_where)/select_n",
     "veles.loader.take_rows", "veles.loader.take_rows"),
    ("jit(_gather_jnp)/jit(_where)/select_n", "", ""),
    ("data", "", ""),
    ("", "", ""),
])
def test_scope_of_a_name_stack(stack, scope, bare):
    assert program_trace.scope_of(stack) == scope
    assert program_trace.bare_scope(scope) == bare
    # ``tf_op`` is the name stack, a colon, and a type that may be empty
    assert program_trace.name_stack(stack + ":") == stack
    assert program_trace.name_stack(stack + ":Conv2D") == stack


# -- arithmetic on hand-made lists -------------------------------------------

def _handmade():
    """Two runs of a step program (the second cut by the window's
    edge), one whole run of a loader program, spans on two threads."""
    runs = [["jit_step", 0, 10 * MS], ["jit_take", 10 * MS, 4 * MS],
            ["jit_step", 16 * MS, 10 * MS]]
    ops = [
        ["jit_step", "jvp(veles.layer.00.conv)", 0, 3 * MS],
        ["jit_step", "transpose(jvp(veles.layer.00.conv))", 3 * MS,
         4 * MS],
        ["jit_step", "veles.update", 7 * MS, 1 * MS],
        ["jit_step", "", 8 * MS, 2 * MS],
        ["jit_take", "", 10 * MS, 3 * MS],
        ["jit_take", "veles.loader.take_rows", 13 * MS, 1 * MS],
        ["jit_step", "jvp(veles.layer.00.conv)", 16 * MS, 3 * MS],
        ["jit_step", "", 19 * MS, 1 * MS]]
    spans = [
        ["veles:unit/trainer", 0, 15 * MS, 0, {}],
        ["veles:fused/dispatch", 1 * MS, 1 * MS, 0, {"train": 1}],
        ["veles:fused/wait", 2 * MS, 8 * MS, 0, {"train": 1}],
        ["veles:fused/dispatch", 10 * MS, 1 * MS, 0, {"train": 0}],
        ["veles:fused/wait", 11 * MS, 3 * MS, 0, {"train": 0}],
        # another thread's span lies inside the unit's by time alone
        ["veles:gen/admit", 5 * MS, 2 * MS, 1, {"queue_wait_us": 1500}],
        ["veles:gen/admit", 12 * MS, 1 * MS, 1, {"queue_wait_us": 500}],
        # cut by the window's edge: counted nowhere
        ["veles:fused/wait", 17 * MS, 9 * MS, 0, {"train": 1}]]
    return {"window": [0, 20 * MS], "spans": spans, "ops": ops,
            "runs": runs}


def test_scopes_of_a_program_over_its_whole_runs():
    extracted = _handmade()
    totals, runs = program_trace.program_scopes(extracted, "jit_step")
    assert runs == 1            # the second run is cut by the window
    assert totals == {
        "jvp(veles.layer.00.conv)": pytest.approx(0.003),
        "transpose(jvp(veles.layer.00.conv))": pytest.approx(0.004),
        "veles.update": pytest.approx(0.001),
        "unscoped": pytest.approx(0.002)}
    assert sum(totals.values()) == pytest.approx(0.010)
    assert program_trace.matching(totals, r"veles\.layer",
                                  r"transpose\(") == pytest.approx(0.003)
    assert program_trace.matching(totals, r"transpose\(.*veles\.layer") \
        == pytest.approx(0.004)
    assert program_trace.matching(totals, "unscoped") \
        == pytest.approx(0.002)
    assert program_trace.matching(totals, r"veles\.gpt") is None
    assert program_trace.program_scopes(extracted, "jit_none") == ({}, 0)


def test_the_one_name_rule_for_unscoped_operations():
    """In a program all of whose scoped operations carry ONE name, an
    operation the compiler made (the loader's layout copy) counts
    under that name; in any other program it is ``unscoped``."""
    totals, runs = program_trace.program_scopes(_handmade(), "jit_take")
    assert runs == 1
    assert totals == {"veles.loader.take_rows": pytest.approx(0.004)}
    assert program_trace.programs_carrying(
        _handmade(), r"veles\.loader\.") == ["jit_take"]


def test_self_time_is_less_the_children_on_the_same_thread():
    extracted = _handmade()
    spans = extracted["spans"]
    unit = spans[0]
    # dispatch 1 + wait 8 + dispatch 1 + wait 3 nest in it; the other
    # thread's admits do not, whatever their times
    assert program_trace.covered(unit, spans) == 13 * MS
    assert program_trace.self_ns(unit, spans) == 2 * MS
    waits = [s for s in spans if s[0] == "veles:fused/wait"]
    assert program_trace.covered(unit, waits) == 11 * MS
    assert program_trace.self_ns(spans[1], spans) == 1 * MS


def test_spans_are_clipped_at_the_window():
    extracted = _handmade()
    waits = program_trace.spans_in_window(extracted, "veles:fused/wait")
    assert [span[2] for span in waits] == [8 * MS, 3 * MS]
    train = program_trace.spans_in_window(
        extracted, "veles:fused/wait", {"train": 1})
    assert [span[2] for span in train] == [8 * MS]
    extracted["window"] = None      # first to last device event
    assert program_trace.window_of(extracted) == (0, 26 * MS)
    assert program_trace.window_of(
        {"window": None, "ops": [], "runs": [], "spans": []}) is None


def test_idle_gaps_by_the_programs_own_spans():
    gaps = dict(program_trace.idle_gaps(_handmade()))
    # busy [0,14) and [16,20): the one gap [14,16) lies half under the
    # unit's span, which is the only one that reaches it
    assert gaps == {"unit/trainer": pytest.approx(0.002)}
    out = io.StringIO()
    program_trace.report(_handmade(), out)
    text = out.getvalue()
    assert "idle under unit/trainer" in text
    assert "jit_step: 1 runs, 10.000 ms a run" in text
    assert "transpose(jvp(veles.layer.00.conv))" in text


def test_operations_get_the_program_that_holds_them_in_time():
    runs = [["jit_a", 0, 10], ["jit_b", 20, 10]]
    metadata = {
        "%fusion.1 = f32[8] fusion(...)": [
            (11, "jit(a)/veles.update/mul:"),
            (22, "jit(b)/jvp(veles.layer.00.conv)/mul:")],
        "%copy = u8[4] copy(...)": [(22, "data:")],
        "%while.3 = (s32[]) while(...)": [(11, "jit(a)/while:")]}
    raw = [["%fusion.1 = f32[8] fusion(...)", 2, 3],
           ["%fusion.1 = f32[8] fusion(...)", 21, 3],
           ["%copy = u8[4] copy(...)", 25, 2],
           ["%while.3 = (s32[]) while(...)", 0, 10],    # a container
           ["%unknown = f32[] add(...)", 15, 1]]        # between runs
    named = program_trace.name_operations(
        raw, runs, metadata, {11: "jit_a", 22: "jit_b"})
    assert named == [["jit_a", "veles.update", 2, 3],
                     ["jit_b", "jvp(veles.layer.00.conv)", 21, 3],
                     ["jit_b", "", 25, 2],
                     ["", "", 15, 1]]


# -- the wire-format walk, on a hand-built file ------------------------------

def _varint(value):
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def test_operation_metadata_from_a_hand_built_xplane(tmp_path):
    stat_names = {1: "tf_op", 2: "program_id", 3: "hlo_category"}
    stat_metadata = b"".join(
        _field(5, _field(1, ident) + _field(
            2, _field(1, ident) + _field(2, label)))
        for ident, label in stat_names.items())

    def event_metadata(ident, text, stats):
        body = _field(1, ident) + _field(2, text) + b"".join(
            _field(5, stat) for stat in stats)
        return _field(4, _field(1, ident) + _field(2, body))

    big = 16714605214249085287            # a fingerprint over 2**63
    device = (_field(1, 7) + _field(2, "/device:TPU:0")
              + _field(3, b"\x00" * 64)   # a line: skipped, not read
              + event_metadata(5, "%fusion.9 = f32[8] fusion(...)", [
                  _field(1, 3) + _field(5, "fusion"),
                  _field(1, 2) + _field(3, big),
                  _field(1, 1) + _field(
                      5, "jit(decode)/veles.gpt.mlp/add:")])
              + event_metadata(6, "%copy.84 = bf16[2] copy(...)", [
                  _field(1, 2) + _field(3, big)])
              + stat_metadata)
    host = _field(2, "/host:CPU") + event_metadata(1, "veles:x/y", [])
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, device))
    assert program_trace.operation_metadata(str(path)) == {
        "%fusion.9 = f32[8] fusion(...)": [
            (big, "jit(decode)/veles.gpt.mlp/add:")],
        "%copy.84 = bf16[2] copy(...)": [(big, "")]}
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(_field(1, host))
    assert program_trace.operation_metadata(str(empty)) == {}


# -- the readers ---------------------------------------------------------------

def _view(extracted, args, **more):
    return dict({"program_trace": extracted, "trace": {}, "args": args},
                **more)


def test_readers_on_handmade_lists():
    extracted = _handmade()
    assert scope_device_ms.read(_view(extracted, {
        "program": "jit_step", "match": r"veles\.layer",
        "exclude": r"transpose\("})) == pytest.approx(3.0)
    assert scope_device_ms.read(_view(extracted, {
        "program": "jit_step", "match": "unscoped"})) \
        == pytest.approx(2.0)
    # no program named: the one that carries the scope, its compiler-
    # made copy included
    assert scope_device_ms.read(_view(extracted, {
        "match": r"veles\.loader\."})) == pytest.approx(4.0)
    assert span_ms.read(_view(extracted, {
        "span": "veles:fused/wait", "where": {"train": 1}})) \
        == pytest.approx(8.0)
    # the window (20) less every wait in it (8 + 3), over train steps
    assert span_ms.read(_view(extracted, {
        "span": "veles:fused/wait", "rest_of_window_per": {
            "span": "veles:fused/dispatch", "where": {"train": 1}}})) \
        == pytest.approx(9.0)
    assert span_ms.read(_view(extracted, {
        "span": "veles:unit/trainer",
        "minus": ["veles:fused/wait"]})) == pytest.approx(4.0)
    assert span_stat.read(_view(extracted, {
        "span": "veles:gen/admit", "stat": "queue_wait_us",
        "scale": 0.001})) == pytest.approx(1.0)


@pytest.mark.parametrize("reader,args", [
    (scope_device_ms, {"program": "jit_step", "match": r"veles\.gpt"}),
    (scope_device_ms, {"match": r"veles\.gpt"}),
    (scope_roofline, {"program": "jit_decode", "match": "veles_attn",
                      "work": "gpt_kv_read_bytes",
                      "peak": "hbm_bytes_per_s"}),
    (span_ms, {"span": "veles:gen/step"}),
    (span_ms, {"span": "veles:fused/wait", "rest_of_window_per": {
        "span": "veles:gen/step"}}),
    (span_stat, {"span": "veles:gen/step", "stat": "emitted"}),
    (span_stat, {"span": "veles:gen/admit", "stat": "no_such"}),
])
def test_a_reader_that_finds_nothing_returns_none(reader, args):
    """... and does not raise: not traced, a trace of a program with
    none of the names (the parent's), a name that is not there."""
    assert reader.read({"trace": None, "args": args}) is None
    bare = {"window": [0, 10], "spans": [], "runs": [["jit_step", 0, 5]],
            "ops": [["jit_step", "", 0, 5]]}
    assert reader.read(_view(bare, args)) is None
    assert reader.read(_view(_handmade(), args)) is None


FIXTURES = {"alexnet.train_b256": "program_trace_alexnet.train_b256",
            "cerebras_gpt_1p3b.chat":
                "program_trace_cerebras_gpt_1p3b.chat"}


@pytest.mark.parametrize("cell", sorted(FIXTURES))
def test_new_readers_on_the_fixture_cut_from_the_chips_trace(cell):
    """A few steps of one traced run of each cell (my chip run, PR
    26), already reduced to ``[program, scope, start, duration]`` and
    spans with stats: every new per-layer metric of the cell reads the
    value recorded with the fixture, through the manifest's own files."""
    import importlib
    fixture = harness.load_json(BENCH, "fixtures",
                                FIXTURES[cell] + ".json")
    spec = harness.manifest()
    new = [m for m in spec["per_layer"][11:] if cell in m["workloads"]]
    assert new and sorted(m["name"] for m in new) \
        == sorted(fixture["expected"])
    _entry, _params, config = harness.load_cell(cell)
    view = {"program_trace": fixture["extracted"], "trace": {},
            "obs": fixture["obs"], "config": config,
            "peaks": harness.peaks_for("TPU v5 lite")}
    for metric in new:
        entry = harness.load_json(BENCH, "layer_metrics",
                                  metric["name"] + ".json")
        assert entry["reader"] in ("scope_device_ms", "scope_roofline",
                                   "span_ms", "span_stat")
        reader = importlib.import_module(
            "benchmarks.readers." + entry["reader"])
        value = reader.read(dict(view, args=entry["args"]))
        assert value == pytest.approx(fixture["expected"][metric["name"]],
                                      rel=1e-9), metric["name"]
        assert value > 0
        if metric["unit"] == "%":
            assert value < 100
    # the sums the acceptance asks for hold on the cut as well
    if cell == "alexnet.train_b256":
        want = fixture["expected"]
        whole = fixture["program_mean_ms"]["jit_step_fn"]
        parts = sum(want["fused.%s_ms_per_step" % part] for part in (
            "forward", "backward", "update", "unscoped"))
        assert parts == pytest.approx(whole, rel=0.02)
    assert json.dumps(fixture)      # plain data


# -- the one idle-gap labeller, against the rule it replaced -----------------
#
# The oracles below are the quadratic loops that ``trace_reduce.reduce``,
# ``program_trace.idle_gaps`` and ``program_trace.span_table`` ran before
# they shared ``trace_reduce.label_gaps`` and ``program_trace.nested_ns``:
# every gap against every span, every span against every span of its
# thread.  The sweeps have to give the same numbers, letter for letter.

def _old_label(gaps, spans, prefix):
    labelled = {}
    for start, end in gaps:
        best, best_key = "unattributed", None
        for span in spans:
            name, s_start, s_dur = span[:3]
            cover = min(end, s_start + s_dur) - max(start, s_start)
            if cover <= 0:
                continue
            key = (2 * cover >= end - start, -s_dur, cover)
            if best_key is None or key > best_key:
                best, best_key = name[len(prefix):], key
        labelled[best] = labelled.get(best, 0) + (end - start)
    return labelled


def _old_gaps(busy, lo, hi):
    gaps, cursor = [], lo
    for start, end in busy + [[hi, hi]]:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    return gaps


def _ranked(labelled, top=10):
    return [[name, ns / 1e9] for name, ns in sorted(
        labelled.items(), key=lambda kv: -kv[1])[:top]]


def _old_idle_gaps(extracted):
    lo, hi = program_trace.window_of(extracted)
    busy = trace_reduce.merge(
        (max(start, lo), min(start + duration, hi))
        for _p, _s, start, duration in extracted["ops"]
        if start + duration > lo and start < hi)
    return _ranked(_old_label(_old_gaps(busy, lo, hi), extracted["spans"],
                              program_trace.SPAN_PREFIX))


def _old_covered(span, others):
    start, end = span[1], span[1] + span[2]
    inside = [(o[1], o[1] + o[2]) for o in others
              if o is not span and o[3] == span[3]
              and o[1] >= start and o[1] + o[2] <= end]
    return sum(e - s for s, e in trace_reduce.merge(inside))


def _old_span_table(extracted):
    spans = program_trace.spans_in_window(extracted)
    by_thread = {}
    for span in spans:
        by_thread.setdefault(span[3], []).append(span)
    table = {}
    for span in spans:
        count, total, own = table.get(span[0], (0, 0, 0))
        table[span[0]] = (count + 1, total + span[2], own + span[2]
                          - _old_covered(span, by_thread[span[3]]))
    return table


def _old_reduce_idle_gaps(extracted):
    spans = extracted["spans"]
    window = [s for s in spans if s[0] == trace_reduce.WINDOW_SPAN][0]
    lo, hi = window[1], window[1] + window[2]
    first = extracted["devices"][sorted(extracted["devices"])[0]]
    busy = trace_reduce.merge(
        (max(start, lo), min(start + duration, hi))
        for _n, start, duration in first["ops"] or first["modules"]
        if start + duration > lo and start < hi)
    others = [s for s in spans if s[0] != trace_reduce.WINDOW_SPAN]
    return _ranked(_old_label(_old_gaps(busy, lo, hi), others,
                              trace_reduce.SPAN_PREFIX))


NAMES = ["gen/step", "gen/decode_fetch", "gen/prefill_fetch", "gen/admit",
         "gen/emit", "gen/idle", "unit/trainer", "fused/wait"]


def _random_spans(rng, lo, hi, threads=3):
    """Spans on a few threads: trees of nested spans (children often
    starting or ending with their parent), copies of a span under
    another name (equal length, equal cover), spans of no length, and
    spans that straddle gaps and the window's edges.  Times lie on a
    coarse grid, so that keys tie often."""
    spans = []

    def tree(thread, start, end, depth):
        cursor = start
        while cursor < end and len(spans) < 400:
            begin = cursor + 10 * rng.randint(0, 3)
            length = 10 * rng.randint(0, max((end - begin) // 20, 1))
            if begin + length > end:
                break
            spans.append([rng.choice(NAMES), begin, length, thread, {}])
            if rng.random() < 0.2:
                spans.append([rng.choice(NAMES), begin, length, thread, {}])
            if depth < 3 and length >= 40 and rng.random() < 0.7:
                tree(thread, begin, begin + length, depth + 1)
            cursor = begin + length

    for thread in range(threads):
        tree(thread, lo - 200, hi + 200, 0)
    for _ in range(rng.randint(0, 20)):
        begin = 10 * rng.randint((lo - 500) // 10, (hi + 100) // 10)
        spans.append([rng.choice(NAMES), begin, 10 * rng.randint(0, 60),
                      rng.randrange(threads), {}])
    rng.shuffle(spans)
    return [["veles:" + name, start, length, thread, stats]
            for name, start, length, thread, stats in spans]


def _random_ops(rng, lo, hi):
    ops, cursor = [], lo - 10 * rng.randint(0, 30)
    while cursor < hi + 300:
        length = 10 * rng.randint(1, 25)
        ops.append(["jit_p", "", cursor, length])
        cursor += length + 10 * rng.choice([0, 0, rng.randint(1, 40),
                                            -rng.randint(0, 5)])
    return ops


def _random_trace(seed):
    import random
    rng = random.Random(seed)
    lo, hi = 1000, 1000 + 10 * rng.randint(200, 600)
    return {"window": [lo, hi], "spans": _random_spans(rng, lo, hi),
            "ops": _random_ops(rng, lo, hi), "runs": [["jit_p", lo, 10]]}


def _as_driver_trace(extracted):
    """The same intervals in ``trace_reduce.extract``'s form: two
    chips (the first one's gaps are labelled) and ``bench:`` spans."""
    lo, hi = extracted["window"]
    ops = [["fusion", start, length]
           for _p, _s, start, length in extracted["ops"]]
    spans = [["bench:" + name[len("veles:"):], start, length]
             for name, start, length, _t, _s in extracted["spans"]]
    return {"devices": {"/device:TPU:0": {"modules": [], "ops": ops},
                        "/device:TPU:1": {"modules": [],
                                          "ops": ops[::2]}},
            "spans": spans + [[trace_reduce.WINDOW_SPAN, lo, hi - lo]]}


def _report(extracted):
    out = io.StringIO()
    program_trace.report(extracted, out)
    return out.getvalue()


def _assert_same_as_the_old_rule(extracted, monkeypatch):
    assert program_trace.idle_gaps(extracted) == _old_idle_gaps(extracted)
    assert program_trace.span_table(extracted) == \
        _old_span_table(extracted)
    spans = extracted["spans"]
    waits = [s for s in spans if s[0].endswith("fetch")]
    assert program_trace.nested_ns(spans, waits) == \
        [_old_covered(span, waits) for span in spans]
    text = _report(extracted)
    with monkeypatch.context() as patch:
        patch.setattr(program_trace, "idle_gaps", _old_idle_gaps)
        patch.setattr(program_trace, "span_table", _old_span_table)
        assert _report(extracted) == text


@pytest.mark.parametrize("seed", range(50))
def test_the_sweeps_are_the_old_rule_on_random_traces(seed, monkeypatch):
    extracted = _random_trace(seed)
    assert len(extracted["spans"]) > 20 and program_trace.idle_gaps(
        extracted)
    _assert_same_as_the_old_rule(extracted, monkeypatch)
    driver = _as_driver_trace(extracted)
    assert trace_reduce.reduce(driver)["idle_gaps"] == \
        _old_reduce_idle_gaps(driver)


@pytest.mark.parametrize("name", sorted(FIXTURES.values()) + [
    "handmade"])
def test_the_sweeps_are_the_old_rule_on_the_fixtures(name, monkeypatch):
    extracted = _handmade() if name == "handmade" else harness.load_json(
        BENCH, "fixtures", name + ".json")["extracted"]
    _assert_same_as_the_old_rule(extracted, monkeypatch)


@pytest.mark.parametrize("name", ["alexnet_steps", "chat_steps"])
def test_the_driver_spans_label_as_before_on_the_fixtures(name):
    extracted = harness.load_json(BENCH, "fixtures",
                                  name + ".json")["extracted"]
    got = trace_reduce.reduce(extracted)["idle_gaps"]
    assert got and got == _old_reduce_idle_gaps(extracted)


def test_a_tie_goes_to_the_first_span_listed():
    gaps = [(10, 20)]
    spans = [["veles:b", 0, 30], ["veles:a", 0, 30], ["veles:c", 16, 8]]
    assert trace_reduce.label_gaps(gaps, spans, "veles:") == {"b": 10}
    assert trace_reduce.label_gaps(gaps, spans[1:], "veles:") == {"a": 10}
    # a short span under half of the gap loses to a long one over half,
    # and wins where it is alone; over half, the shorter span wins
    assert trace_reduce.label_gaps(gaps, spans[2:], "veles:") == {"c": 10}
    assert trace_reduce.label_gaps(
        gaps, spans + [["veles:d", 14, 8]], "veles:") == {"d": 10}
    assert trace_reduce.label_gaps([(40, 50)], spans, "veles:") == \
        {"unattributed": 10}
