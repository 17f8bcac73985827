"""What each scheduler step ran before its decode: the ``prefills`` and
``decoded`` stats of ``veles:gen/step`` and the counters
``decode_tokens_stalled`` / ``decode_tokens_multi_stalled`` (with their
``/metrics`` gauges and ``watch_snapshot`` keys), stepped by hand over
the tiny LM engine in each admission mode: whole-prompt prefills, chunks,
and speculative decode."""

import pytest

from tests.test_gen import build_engine, spec_workload
from veles_tpu import trace
from veles_tpu.gen import GenerativeScheduler

STALLED = 'gen_decode_tokens_stalled_total{model="lm"}'
MULTI = 'gen_decode_tokens_multi_stalled_total{model="lm"}'


def _steps():
    """``(prefills, decoded)`` of every recorded scheduler step."""
    out = []
    for event in trace.recorder.events():
        if event[1] == "gen" and event[2] == "step":
            assert "emitted" not in event[6]
            out.append((event[6]["prefills"], event[6]["decoded"]))
    return out


def _scheduler(engine):
    from veles_tpu.serve import ServingMetrics
    metrics = ServingMetrics()
    return GenerativeScheduler(engine, metrics=metrics, name="lm"), metrics


def _assert_counters(scheduler, metrics, stalled, multi):
    assert scheduler.decode_tokens_stalled == stalled
    assert scheduler.decode_tokens_multi_stalled == multi
    watched = scheduler.watch_snapshot()
    assert watched["decode_tokens_stalled"] == stalled
    assert watched["decode_tokens_multi_stalled"] == multi
    snap = metrics.snapshot()
    assert snap[STALLED] == stalled and snap[MULTI] == multi
    text = metrics.render_text()
    assert "veles_serve_" + STALLED in text
    assert "veles_serve_" + MULTI in text


@pytest.mark.traced
def test_whole_prompt_steps_count_the_prefills_before_their_decode():
    engine = build_engine()
    scheduler, metrics = _scheduler(engine)
    first = scheduler.submit(list(range(1, 6)), 8)
    scheduler.step()         # one prefill, then a decode of its slot
    scheduler.step()         # plain
    later = [scheduler.submit(list(range(2, 5)), 6),
             scheduler.submit(list(range(3, 12)), 6)]
    scheduler.step()         # two prefills, then a decode of three
    scheduler.step()
    assert _steps() == [(1, 1), (0, 1), (2, 3), (0, 3)]
    _assert_counters(scheduler, metrics, stalled=4, multi=3)
    scheduler.run_until_idle()
    assert all(f.done() for f in [first] + later)
    steps = _steps()
    # every token a decode emitted is counted once; the first tokens
    # come from the three prefills
    assert sum(d for _p, d in steps) + 3 == scheduler.tokens_total
    assert sum(p for p, _d in steps) == engine.prefill_calls == 3
    scheduler.stop(drain=False)
    assert STALLED not in metrics.snapshot()
    assert MULTI not in metrics.snapshot()
    engine.close()


@pytest.mark.traced
def test_chunked_steps_count_every_chunk_before_their_decode():
    engine = build_engine(prefill_chunk=8)
    scheduler, metrics = _scheduler(engine)
    short = scheduler.submit(list(range(1, 6)), 10)
    scheduler.step()         # its one chunk, then its first decode
    longs = [scheduler.submit(list(range(1, 21)), 4),
             scheduler.submit(list(range(2, 22)), 4)]
    scheduler.step()         # a chunk of each of the two, one decode
    scheduler.step()
    scheduler.step()         # their last chunks: three decode
    scheduler.step()         # plain
    assert _steps() == [(1, 1), (2, 1), (2, 1), (2, 3), (0, 3)]
    _assert_counters(scheduler, metrics, stalled=6, multi=5)
    scheduler.run_until_idle()
    assert all(f.done() for f in [short] + longs)
    scheduler.stop(drain=False)
    engine.close()


@pytest.mark.traced
def test_speculative_steps_count_every_accepted_token():
    engine = build_engine(buckets=(8, 16, 32), speculative="ngram",
                          draft_k=4)
    scheduler, metrics = _scheduler(engine)
    (tokens, max_new), = spec_workload(n=1)
    future = scheduler.submit(tokens, max_new)
    scheduler.run_until_idle()
    assert len(future.result(0)) == max_new
    steps = _steps()
    assert steps[0][0] == 1 and all(p == 0 for p, _d in steps[1:])
    # one slot: a step that decoded more than one token accepted a draft
    assert max(d for _p, d in steps) > 1
    assert sum(d for _p, d in steps) == max_new - 1
    _assert_counters(scheduler, metrics, stalled=steps[0][1], multi=0)
    scheduler.stop(drain=False)
    engine.close()


def test_counters_without_the_ring():
    """Ring off: the counters still count (an operator reads them from
    ``/metrics`` with no profiler), and nothing is recorded."""
    assert not trace.enabled()
    before = trace.recorder.recorded
    engine = build_engine()
    scheduler, metrics = _scheduler(engine)
    scheduler.submit(list(range(1, 6)), 4)
    scheduler.submit(list(range(2, 9)), 4)
    scheduler.step()
    _assert_counters(scheduler, metrics, stalled=2, multi=2)
    scheduler.run_until_idle()
    _assert_counters(scheduler, metrics, stalled=2, multi=2)
    assert trace.recorder.recorded == before
    scheduler.stop(drain=False)
    engine.close()
