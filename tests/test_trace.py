"""veles_tpu.trace — the unified tracing/observability subsystem.

Recorder mechanics (ring wraparound keeps the newest spans, per-thread
nesting, the disabled path's no-work contract), Chrome trace-event
export schema, report totals matching the exported file, the
summarizer CLI, the ``engine.trace`` knob — and the CI canary: a
``traced``-marked stitched sample run asserting that ALL FIVE
instrumented categories (segment, loader, h2d, serve, jobs) actually
emit events, so a refactor can never silently detach the
instrumentation."""

import json
import os
import sys
import threading
import time

import numpy
import pytest

from veles_tpu import trace
from veles_tpu.config import root
from veles_tpu.trace.core import TraceRecorder


@pytest.fixture
def live_trace():
    """Enable the GLOBAL recorder directly (workflow-free tests that
    must not depend on the config knob); restores the stock disabled
    state."""
    rec = trace.recorder
    saved = (rec.enabled, rec.path, rec.role)
    rec.clear()
    rec.enabled = True
    yield trace
    rec.enabled, rec.path, rec.role = saved
    rec.clear()


# -- recorder mechanics ----------------------------------------------------

def test_ring_wraparound_keeps_newest_spans():
    rec = TraceRecorder(capacity=8)
    rec.enabled = True
    for i in range(20):
        rec.record("X", "cat", "s%d" % i, i * 1000, 10)
    events = rec.events()
    assert len(events) == 8
    assert [ev[2] for ev in events] == ["s%d" % i for i in range(12, 20)]
    assert rec.dropped == 12
    assert rec.recorded == 20
    # the aggregate counters survive wraparound (bench reads these)
    assert rec.count("cat") == 20
    assert rec.count("cat", "s3") == 1          # wrapped out, still counted
    assert rec.category_counts() == {"cat": 20}


def test_thread_interleaved_spans_nest_per_thread(live_trace):
    barrier = threading.Barrier(2)

    def work(name):
        barrier.wait()
        with trace.span("test", "outer-" + name):
            time.sleep(0.002)
            with trace.span("test", "inner-" + name):
                time.sleep(0.002)
            time.sleep(0.002)

    threads = [threading.Thread(target=work, args=(n,))
               for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    by_tid = {}
    for ph, cat, name, ts, dur, tid, _args, _role in \
            trace.recorder.events():
        if cat == "test":
            by_tid.setdefault(tid, {})[name.split("-")[0]] = (ts,
                                                             ts + dur)
    assert len(by_tid) == 2
    names = set()
    for spans in by_tid.values():
        assert set(spans) == {"outer", "inner"}
        # context-manager spans nest strictly per thread: the inner
        # interval lies inside the SAME thread's outer interval even
        # though both threads interleave in the shared ring
        assert spans["outer"][0] < spans["inner"][0]
        assert spans["inner"][1] < spans["outer"][1]
        names.update(spans)
    assert names == {"outer", "inner"}


def test_disabled_path_is_one_inert_annotation_no_recording():
    """Ring off, a span is the bare profiler annotation: nothing is
    recorded, no timestamp is taken and no lock is held in Python;
    ``instant`` / ``counter`` / ``complete`` cost one check each."""
    from jax.profiler import TraceAnnotation
    rec = trace.recorder
    assert not rec.enabled, "tests must start with tracing off"
    before = rec.recorded
    # whatever the arguments, the ring-off span is the annotation
    # itself (inert without a profiler session), not a recording span
    for span in (trace.span("a", "b"), trace.span("c", "d", {"k": 1})):
        assert type(span) is TraceAnnotation
    # callable-count: span(), the lookup of the annotation class and
    # the three ring-only hooks are the ONLY python frames — the
    # annotation's own enter/exit are C, and nothing touches the
    # clock, the lock or the ring
    calls = []

    def prof(frame, event, arg):
        if event == "call":
            calls.append(frame.f_code.co_name)

    sys.setprofile(prof)
    try:
        with trace.span("cat", "name") as span:
            span.set_metadata(emitted=3)    # same API ring on or off
        trace.instant("cat", "name")
        trace.counter("cat", "name", 1)
        trace.complete("cat", "name", 0, 1)
    finally:
        sys.setprofile(None)
    assert calls.count("span") == 1
    assert sorted(c for c in calls if c in (
        "span", "_annotation", "instant", "counter", "complete")) == \
        ["_annotation", "complete", "counter", "instant", "span"]
    assert not {"record", "__enter__", "__exit__"} & set(calls), calls
    assert len(calls) <= 8, calls     # nothing else ran underneath
    assert rec.recorded == before     # and nothing was recorded


def test_ring_off_span_costs_under_five_microseconds():
    """No profiler session, ring off: one inert annotation a span
    (0.4 us measured alone; the limit is wide because six test
    workers share the host)."""
    rec = trace.recorder
    assert not rec.enabled
    before = rec.recorded
    best = float("inf")
    for _ in range(3):
        tic = time.perf_counter()
        for _i in range(100000):
            with trace.span("gen", "decode_fetch"):
                pass
        best = min(best, (time.perf_counter() - tic) / 100000)
    assert best < 5e-6, best
    assert rec.recorded == before


def test_live_span_feeds_both_sinks(live_trace):
    """Ring on: the span records its X event, late metadata included,
    and still carries the annotation's interface."""
    with trace.span("gen", "step", role="server") as span:
        span.set_metadata(emitted=2)
    (event,) = [ev for ev in trace.recorder.events()
                if ev[1] == "gen"]
    assert event[2] == "step" and event[6] == {"emitted": 2}
    assert event[7] == "server"


# -- export / report -------------------------------------------------------

def _record_sample_timeline():
    with trace.span("segment", "dispatch", {"segment": "fwd+gd"}):
        time.sleep(0.001)
    with trace.span("segment", "dispatch", {"segment": "fwd+gd"}):
        time.sleep(0.001)
    with trace.span("loader", "serve_minibatch"):
        pass
    trace.instant("jobs", "heartbeat", {"gap_ms": 2.0}, role="master")
    trace.counter("h2d", "h2d_bytes", 4096)
    trace.complete("serve", "request", time.perf_counter_ns() - 10000,
                   10000, {"rows": 3}, role="server")


def test_chrome_export_is_schema_valid_trace_event_json(live_trace,
                                                       tmp_path):
    _record_sample_timeline()
    path = trace.save(str(tmp_path / "t.json"))
    with open(path) as fin:
        payload = json.load(fin)
    assert payload["displayTimeUnit"] == "ms"
    events = payload["traceEvents"]
    assert isinstance(events, list) and events
    phases = set()
    pids = set()
    for ev in events:
        # the trace-event schema: every record has a phase, a pid and
        # a tid; named events have names; complete events have ts+dur
        assert ev["ph"] in ("M", "X", "i", "C")
        assert isinstance(ev["pid"], int)
        assert "tid" in ev
        phases.add(ev["ph"])
        pids.add(ev["pid"])
        if ev["ph"] == "M":
            assert ev["name"] == "process_name"
            assert ev["args"]["name"]
            continue
        assert ev["name"]
        assert isinstance(ev["ts"], (int, float))
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
        if ev["ph"] == "C":
            assert "value" in ev["args"]
    assert phases == {"M", "X", "i", "C"}
    # one pid per role: trainer + master + server were all recorded
    roles = {ev["args"]["name"] for ev in events if ev["ph"] == "M"}
    assert roles == {"trainer", "master", "server"}
    assert len(pids) == 3


def test_report_totals_match_the_exported_file(live_trace, tmp_path):
    _record_sample_timeline()
    live_summary = trace.summary()
    live_report = trace.report_text()
    path = trace.save(str(tmp_path / "t.json"))
    file_events = trace.load(path)
    assert trace.summary(file_events) == live_summary
    assert trace.report_text(file_events) == live_report
    # and the numbers are the recorded truth
    assert live_summary["categories"]["segment"]["spans"] == 2
    assert live_summary["segment"]["dispatches"] == 2
    assert live_summary["segment"]["host_gap_ms"] >= 0
    assert live_summary["counters"]["h2d_bytes"] == 4096


def test_load_accepts_bare_array_trace_files(live_trace, tmp_path):
    """Chrome traces come in two standard shapes: the object form this
    module writes and a bare JSON array — load() takes both."""
    _record_sample_timeline()
    path = trace.save(str(tmp_path / "obj.json"))
    with open(path) as fin:
        events = json.load(fin)["traceEvents"]
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(events))
    assert trace.load(str(bare)) == trace.load(path)


def test_summarizer_cli(live_trace, tmp_path, capsys):
    import veles_tpu.trace.__main__ as cli
    _record_sample_timeline()
    path = trace.save(str(tmp_path / "t.json"))
    assert cli.main([path]) == 0
    out = capsys.readouterr().out
    assert "per-category totals" in out
    assert "segment" in out and "dispatch" in out
    assert cli.main([path, "--json"]) == 0
    digest = json.loads(capsys.readouterr().out)
    assert digest["categories"]["segment"]["spans"] == 2
    assert cli.main([str(tmp_path / "missing.json")]) == 2


def test_category_busy_is_interval_union_not_a_nested_sum(live_trace):
    """Nested same-category spans (a serve request enclosing its
    batched device call) must count ONCE in the category's busy_ms —
    summing would report >100% utilization."""
    with trace.span("serve", "request"):
        with trace.span("serve", "batch_infer"):
            time.sleep(0.003)
    digest = trace.summary()
    by_name = {item["name"]: item["total_ms"]
               for item in digest["top_spans"]}
    busy = digest["categories"]["serve"]["busy_ms"]
    # union == the outer span alone, NOT outer + inner
    assert busy < by_name["request"] + by_name["batch_infer"]
    assert abs(busy - by_name["request"]) < 0.5


def test_metrics_text_lines(live_trace):
    _record_sample_timeline()
    text = trace.metrics_text()
    assert "veles_trace_recorded_total %d" % trace.recorder.recorded \
        in text
    assert 'veles_trace_events_total{cat="segment"} 2' in text
    # the events_total family is labeled-only (no unlabeled sample
    # that would double sum() under aggregation) and contiguous
    samples = [l for l in text.splitlines()
               if l.startswith("veles_trace_events_total")]
    assert samples and all("{cat=" in l for l in samples)


# -- the knob --------------------------------------------------------------

def test_configure_knob_off_on_path(tmp_path):
    rec = trace.recorder
    saved = (rec.enabled, rec.path, root.common.engine.get("trace"))
    try:
        root.common.engine.trace = "off"
        assert trace.configure() is False and rec.path is None
        root.common.engine.trace = "on"
        assert trace.configure() is True and rec.path is None
        target = str(tmp_path / "run.json")
        root.common.engine.trace = target
        assert trace.configure() is True
        assert rec.path == target
    finally:
        rec.enabled, rec.path = saved[0], saved[1]
        root.common.engine.trace = saved[2]


def test_workflow_initialize_honors_trace_knob():
    from veles_tpu.workflow import Workflow
    rec = trace.recorder
    saved = (rec.enabled, rec.path, root.common.engine.get("trace"))
    try:
        root.common.engine.trace = "on"
        Workflow(None).initialize()
        assert trace.enabled()
        root.common.engine.trace = "off"
        Workflow(None).initialize()
        assert not trace.enabled()
    finally:
        rec.enabled, rec.path = saved[0], saved[1]
        root.common.engine.trace = saved[2]


def test_device_trace_is_noop_on_cpu():
    with trace.device_trace() as running:
        assert not running      # CPU backend: the bridge stays off


class _FakeTpu(object):
    platform = "tpu"


@pytest.mark.parametrize("failing", ["start_trace", "stop_trace"])
def test_device_trace_raises_what_the_profiler_raises(
        failing, monkeypatch, tmp_path):
    """No hidden fallback: on an accelerator the profiler's own error
    reaches the operator (and the options are the harness's)."""
    import jax
    seen = {}

    def start(logdir, profiler_options=None, **_kw):
        seen["logdir"] = logdir
        seen["levels"] = (profiler_options.python_tracer_level,
                          profiler_options.host_tracer_level)
        if failing == "start_trace":
            raise RuntimeError("profiler says no")

    def stop():
        if failing == "stop_trace":
            raise RuntimeError("profiler says no")

    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTpu()])
    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", stop)
    with pytest.raises(RuntimeError, match="profiler says no"):
        with trace.device_trace(str(tmp_path)) as running:
            assert running
    assert seen == {"logdir": str(tmp_path), "levels": (0, 1)}


# -- the CI canary: five categories over a real stitched run ---------------

def _build_stitched_workflow(minibatch_size=32, **workflow_kwargs):
    from veles_tpu import prng
    from veles_tpu.backends import CPUDevice
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow

    class BlobLoader(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.default_rng(42)
            n = 200
            labels = numpy.tile(numpy.arange(10), n // 10)
            centers = rng.standard_normal((10, 16)) * 3.0
            self.original_data.mem = (
                centers[labels]
                + rng.standard_normal((n, 16)) * 0.7
            ).astype(numpy.float32)
            self.original_labels = [int(x) for x in labels]
            self.class_lengths[:] = [0, 50, 150]

    prng.seed_all(5)
    wf = StandardWorkflow(
        None,
        loader_factory=lambda w: BlobLoader(
            w, minibatch_size=minibatch_size),
        layers=[{"type": "all2all_tanh",
                 "->": {"output_sample_shape": 8},
                 "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 10},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}}],
        decision_config={"max_epochs": 2, "fail_iterations": 10 ** 6},
        **workflow_kwargs)
    wf.launcher = DummyLauncher()
    wf.initialize(device=CPUDevice())
    return wf


class _ScriptedMaster(object):
    def __init__(self, n_jobs=3):
        self.n_jobs = n_jobs
        self.served = 0
        self.updates = []

    def checksum(self):
        return "traced-v1"

    def generate_data_for_slave(self, slave):
        if self.served >= self.n_jobs:
            return None
        self.served += 1
        return {"job_number": self.served}

    def apply_data_from_slave(self, data, slave):
        self.updates.append(data)

    def drop_slave(self, slave):
        pass


class _ScriptedSlave(object):
    def checksum(self):
        return "traced-v1"

    def do_job(self, data, callback):
        callback({"result": data["job_number"]})


@pytest.mark.traced
def test_all_five_instrumented_categories_emit(tmp_path):
    """The instrumentation canary (and the acceptance run): one traced
    session covering the stitched trainer, the serving engine and the
    master–slave job layer must emit events in EVERY category —
    segment (stitched dispatches), loader (minibatch serving), h2d
    (transfer counters), serve (request lifecycle) and jobs (job
    lifecycle) — and the exported JSON must be a Perfetto-loadable
    trace-event file whose report matches the live one.  A refactor
    that detaches any hook fails here, not in production."""
    from veles_tpu.parallel.jobs import JobClient, JobServer
    from veles_tpu.serve.batcher import DynamicBatcher
    from veles_tpu.serve.engine import InferenceEngine

    assert trace.enabled(), "the traced marker must arm the recorder"

    # trainer: stitched eager run → segment + loader + h2d
    wf = _build_stitched_workflow()
    assert trace.enabled(), \
        "initialize() re-read the knob and must keep recording on"
    wf.run()
    assert wf.stitch_report()["dispatches"] > 0

    # serving: engine + dynamic batcher → serve
    engine = InferenceEngine.from_forwards(
        wf.forwards, sample_shape=(16,), max_batch_size=8).warmup()
    batcher = DynamicBatcher(engine, max_wait_ms=1.0)
    try:
        out = batcher.infer(numpy.zeros((3, 16), numpy.float32))
        assert out.shape == (3, 10)
    finally:
        batcher.stop()

    # job layer: scripted master–slave session over real ZMQ → jobs
    master = _ScriptedMaster(n_jobs=3)
    server = JobServer(master).start()
    try:
        client = JobClient(_ScriptedSlave(), server.endpoint)
        client.handshake()
        assert client.run()
        client.close()
    finally:
        server.stop()
    assert len(master.updates) == 3

    counts = trace.recorder.category_counts()
    for category in ("segment", "loader", "h2d", "serve", "jobs"):
        assert counts.get(category, 0) > 0, \
            "category %r emitted nothing: %r" % (category, counts)

    # the export is Perfetto-loadable and agrees with the live report
    live_summary = trace.summary()
    path = trace.save(str(tmp_path / "session.json"))
    file_events = trace.load(path)
    assert trace.summary(file_events) == live_summary
    span_cats = {ev["cat"] for ev in file_events if ev["ph"] == "X"}
    assert {"segment", "loader", "serve", "jobs"} <= span_cats
    counter_cats = {ev["cat"] for ev in file_events
                    if ev["ph"] == "C"}
    assert "h2d" in counter_cats
    # per-role pids separated trainer, server, master and the slave
    with open(path) as fin:
        raw = json.load(fin)["traceEvents"]
    roles = {ev["args"]["name"] for ev in raw if ev["ph"] == "M"}
    assert {"trainer", "server", "master"} <= roles
    assert any(role.startswith("slave-") for role in roles)
    # the text report names every category
    report = wf.trace_report()
    for category in ("segment", "loader", "h2d", "serve", "jobs"):
        assert category in report


@pytest.mark.traced
def test_traced_run_reports_d2h_accounting():
    """The symmetric D2H satellite: a stitched run that fetches its
    deferred metrics pays accounted device→host traffic, visible both
    in Watcher.d2h_bytes and as the d2h_bytes counter track."""
    from veles_tpu.memory import Watcher

    before_bytes = Watcher.d2h_bytes
    before_events = trace.recorder.count("h2d", "d2h_bytes")
    wf = _build_stitched_workflow()
    wf.run()
    assert Watcher.d2h_bytes > before_bytes
    assert trace.recorder.count("h2d", "d2h_bytes") > before_events


# -- the bridge: the program's spans in a profiler session ------------------

def _host_events(log_dir):
    """``[(name, start_ns, end_ns, thread, stats)]`` of every
    ``veles:`` event on the host plane of the newest trace."""
    import glob

    import jax
    (path,) = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for thread, line in enumerate(plane.lines):
            for event in line.events:
                if event.name.startswith(trace.ANNOTATION_PREFIX):
                    out.append((event.name, event.start_ns,
                                event.start_ns + event.duration_ns,
                                thread, dict(event.stats)))
    return out


@pytest.fixture(scope="module")
def program_session(tmp_path_factory):
    """ONE profiler session on the CPU (a process holds one): a
    scheduler worker over the tiny LM serves two requests, then a tiny
    fused workflow trains a few steps.  The ring stays off."""
    import jax

    from veles_tpu.gen import (GenerativeEngine, GenerativeScheduler,
                               TransformerGenModel)
    from veles_tpu.samples.transformer import TINY
    assert not trace.enabled()
    engine = GenerativeEngine(
        TransformerGenModel(dict(TINY, seq_len=64)), max_slots=3,
        max_seq=48, prefill_buckets=(8, 16), seed=0).warmup()
    scheduler = GenerativeScheduler(engine).start()
    wf = _build_stitched_workflow(fused=True)
    log_dir = str(tmp_path_factory.mktemp("xplane"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        futures = [scheduler.submit([1, 2, 3, 4, 5], 4),
                   scheduler.submit(list(range(1, 12)), 3)]
        served = [future.result(60) for future in futures]
        time.sleep(0.12)        # the worker, with nothing to do
        wf.run()
    finally:
        jax.profiler.stop_trace()
        scheduler.stop()
        engine.close()
    assert [len(tokens) for tokens in served] == [4, 3]
    return _host_events(log_dir)


def _named(events, name):
    return [ev for ev in events if ev[0] == "veles:" + name]


def _inside(child, parent):
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[2] <= parent[2])


def test_session_holds_the_fused_steps_phases(program_session):
    events = program_session
    units = {ev[0] for ev in events if ev[0].startswith("veles:unit/")}
    assert any("Loader" in name for name in units), units
    assert any("FusedTrainer" in name for name in units), units
    assert any("Decision" in name for name in units), units
    (trainer,) = {ev[0] for ev in events if "FusedTrainer" in ev[0]}
    runs = [ev for ev in events if ev[0] == trainer]
    dispatches = _named(events, "fused/dispatch")
    waits = _named(events, "fused/wait")
    labels = _named(events, "fused/labels")
    assert len(runs) == len(dispatches) == len(waits) == len(labels)
    train = [ev for ev in dispatches if ev[4]["train"] == 1]
    assert len(train) >= 3          # 150 train samples at 32 a batch
    assert {ev[4]["train"] for ev in dispatches} == {0, 1}
    assert {ev[4]["train"] for ev in waits} == {0, 1}
    for run in runs:
        inner = [ev for ev in labels + dispatches + waits
                 if _inside(ev, run)]
        assert [ev[0].split("/")[1] for ev in sorted(
            inner, key=lambda ev: ev[1])] == ["labels", "dispatch",
                                              "wait"]
    syncs = _named(events, "fused/sync_weights")
    assert syncs and all(any(_inside(sync, run) for run in runs)
                         for sync in syncs)
    assert len({ev[3] for ev in runs + syncs}) == 1     # one thread


def test_session_holds_the_schedulers_steps_on_its_own_thread(
        program_session):
    events = program_session
    steps = _named(events, "gen/step")
    (worker,) = {ev[3] for ev in steps}
    fused = {ev[3] for ev in _named(events, "fused/dispatch")}
    assert worker not in fused
    for name in ("idle", "admit", "prefill", "prefill_dispatch",
                 "prefill_fetch", "decode", "decode_prepare",
                 "decode_dispatch", "decode_fetch", "emit"):
        found = _named(events, "gen/" + name)
        assert found, name
        assert {ev[3] for ev in found} == {worker}, name
    # nesting, by time, on the one thread
    for child, parent in (("admit", "step"), ("prefill", "admit"),
                          ("prefill_dispatch", "prefill"),
                          ("prefill_fetch", "prefill"),
                          ("decode", "step"), ("emit", "step"),
                          ("decode_prepare", "decode"),
                          ("decode_dispatch", "decode"),
                          ("decode_fetch", "decode")):
        parents = _named(events, "gen/" + parent)
        for ev in _named(events, "gen/" + child):
            assert any(_inside(ev, p) for p in parents), (child, parent)
    assert not any(_inside(idle, step) for step in steps
                   for idle in _named(events, "gen/idle"))
    for decode in _named(events, "gen/decode"):
        inner = sorted((ev for ev in events if ev is not decode
                        and _inside(ev, decode)), key=lambda ev: ev[1])
        assert [ev[0].rsplit("_", 1)[1] for ev in inner] == [
            "prepare", "dispatch", "fetch"]


def test_session_spans_carry_their_keyword_arguments(program_session):
    events = program_session
    admits = sorted(_named(events, "gen/admit"), key=lambda ev: ev[1])
    assert [ev[4]["prompt"] for ev in admits] == [5, 11]
    first, second = (ev[4]["req"] for ev in admits)
    assert second == first + 1          # one counter a scheduler
    assert all(0 <= ev[4]["queue_wait_us"] < 60e6 for ev in admits)
    prefills = sorted(_named(events, "gen/prefill"),
                      key=lambda ev: ev[1])
    assert [(ev[4]["bucket"], ev[4]["len"]) for ev in prefills] == [
        (8, 5), (16, 11)]
    decodes = _named(events, "gen/decode")
    assert {ev[4]["active"] for ev in decodes} <= {1, 2}
    emits = _named(events, "gen/emit")
    assert len(emits) == len(decodes)
    assert sorted(ev[4]["n"] for ev in emits) == sorted(
        ev[4]["active"] for ev in decodes)
    steps = _named(events, "gen/step")
    # 7 tokens in all: two come from the prefills, five from decodes
    assert sum(ev[4]["prefills"] for ev in steps) == 2
    assert sum(ev[4]["decoded"] for ev in steps) == 5
    assert not any("emitted" in ev[4] for ev in steps)
    assert sum(ev[4]["n"] for ev in emits) == 5
