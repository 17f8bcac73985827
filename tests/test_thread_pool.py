"""Host-side executor tests: wants_thread units run off-thread with
control-graph ordering preserved, background work overlaps the main
loop, and loader prefetch overlaps (simulated) IO with a slow consumer.

Mirrors the reference's threaded-execution contract
(``veles/thread_pool.py:71``, ``veles/units.py:496-505``) under the
TPU re-design's FIFO scheduler.
"""

import threading
import time

import numpy

from veles_tpu.dummy import DummyUnit, DummyWorkflow
from veles_tpu.loader.base import Loader
from veles_tpu.mutable import Bool
from veles_tpu.plumbing import Repeater
from veles_tpu.units import Unit


class ThreadRecorder(DummyUnit):
    def __init__(self, workflow, **kwargs):
        super(ThreadRecorder, self).__init__(workflow, **kwargs)
        self.thread_ids = []
        self.run_times = []

    def run(self):
        super(ThreadRecorder, self).run()
        self.thread_ids.append(threading.get_ident())
        self.run_times.append(time.monotonic())


class SleepUnit(ThreadRecorder):
    def __init__(self, workflow, sleep=0.05, **kwargs):
        super(SleepUnit, self).__init__(workflow, **kwargs)
        self.sleep = sleep

    def run(self):
        super(SleepUnit, self).run()
        time.sleep(self.sleep)


def test_wants_thread_runs_off_main_thread():
    wf = DummyWorkflow()
    bg = ThreadRecorder(wf, name="bg")
    bg.wants_thread = True
    fg = ThreadRecorder(wf, name="fg")
    bg.link_from(wf.start_point)
    fg.link_from(wf.start_point)
    wf.end_point.link_from(bg, fg)
    wf.initialize()
    wf.run()
    assert fg.thread_ids == [threading.get_ident()]
    assert bg.thread_ids[0] != threading.get_ident()


def test_background_unit_ordering_preserved():
    """A unit control-downstream of a wants_thread unit only runs after
    it completes."""
    wf = DummyWorkflow()
    order = []

    class Tracker(DummyUnit):
        def run(self):
            super(Tracker, self).run()
            if self.name == "slow_bg":
                time.sleep(0.1)
            order.append(self.name)

    bg = Tracker(wf, name="slow_bg")
    bg.wants_thread = True
    down = Tracker(wf, name="down")
    bg.link_from(wf.start_point)
    down.link_from(bg)
    wf.end_point.link_from(down)
    wf.initialize()
    wf.run()
    assert order == ["slow_bg", "down"]


def test_background_unit_overlaps_loop():
    """A slow wants_thread side-branch (a plotter, say) must NOT
    serialize with the main repeater loop."""
    n_iters = 5
    side_sleep = 0.1
    wf = DummyWorkflow()
    rep = Repeater(wf)
    trainer = SleepUnit(wf, sleep=0.01, name="trainer")
    side = SleepUnit(wf, sleep=side_sleep, name="side")
    side.wants_thread = True
    stop = Bool(False)
    count = {"n": 0}

    class Decision(DummyUnit):
        def run(self):
            nonlocal stop
            super(Decision, self).run()
            count["n"] += 1
            if count["n"] >= n_iters:
                stop <<= True

    dec = Decision(wf, name="decision")
    rep.link_from(wf.start_point)
    trainer.link_from(rep)
    dec.link_from(trainer)
    side.link_from(dec)          # side branch off the loop
    rep.link_from(dec)           # back-edge
    rep.gate_block = stop
    wf.end_point.link_from(dec)
    wf.end_point.gate_block = ~stop
    wf.initialize()
    tic = time.monotonic()
    wf.run()
    elapsed = time.monotonic() - tic
    assert count["n"] == n_iters
    # concurrent duplicate triggers are DISCARDED (ref units.py:793-801),
    # so the slow side branch runs fewer times than the loop iterates —
    # that's the decoupling working
    assert 1 <= side.run_count <= n_iters
    # serialized would be ≥ n_iters * side_sleep = 0.5 s; overlap keeps
    # the critical path ≈ loop time + one trailing side run
    assert elapsed < n_iters * side_sleep * 0.8, \
        "background side branch serialized the loop (%.3fs)" % elapsed


class SlowIOLoader(Loader):
    """Synthetic loader whose per-sample 'IO' sleeps, with the pure
    prefetch fill contract."""

    supports_prefetch = True

    def __init__(self, workflow, io_delay=0.05, **kwargs):
        super(SlowIOLoader, self).__init__(workflow, **kwargs)
        self.io_delay = io_delay
        self.fill_threads = []
        #: (start, end) of every fill's IO, by time.monotonic()
        self.fill_spans = []

    def load_data(self):
        self._has_labels = True
        self.class_lengths[:] = [0, 0, 64]

    def create_minibatch_data(self):
        self.minibatch_data.reset(numpy.zeros(
            (self.max_minibatch_size, 4), dtype=numpy.float32))

    def _fill(self, indices, data_out, raw_labels_out):
        tic = time.monotonic()
        time.sleep(self.io_delay)
        self.fill_spans.append((tic, time.monotonic()))
        for i, idx in enumerate(indices):
            data_out[i] = float(idx)
            raw_labels_out[i] = int(idx) % 8

    def fill_minibatch(self):
        self.fill_threads.append(threading.get_ident())
        n = self.minibatch_size
        self.minibatch_data.map_write()
        self._fill(self.minibatch_indices.mem[:n],
                   self.minibatch_data.mem[:n],
                   self.raw_minibatch_labels)

    def fill_minibatch_into(self, indices, data_out, raw_labels_out):
        self.fill_threads.append(threading.get_ident())
        self._fill(indices, data_out, raw_labels_out)


def _run_loader_loop(prefetch, io_delay=0.04, train_delay=0.04,
                     epochs=2):
    from veles_tpu import prng
    prng.seed_all(4321)        # identical shuffles across compared runs
    wf = DummyWorkflow()
    loader = SlowIOLoader(wf, io_delay=io_delay, minibatch_size=16,
                          prefetch=prefetch)
    rep = Repeater(wf)
    stop = Bool(False)
    seen = []
    #: (start, end) of every consumer step, on the fills' clock
    loader.step_spans = []

    class Trainer(DummyUnit):
        def run(self):
            nonlocal stop
            super(Trainer, self).run()
            tic = time.monotonic()
            time.sleep(train_delay)
            loader.step_spans.append((tic, time.monotonic()))
            seen.append(numpy.array(loader.minibatch_data.mem))
            if loader.epoch_ended and loader.epoch_number >= epochs:
                stop <<= True

    trainer = Trainer(wf, name="trainer")
    rep.link_from(wf.start_point)
    loader.link_from(rep)
    trainer.link_from(loader)
    rep.link_from(trainer)
    rep.gate_block = stop
    wf.end_point.link_from(trainer)
    wf.end_point.gate_block = ~stop
    wf.initialize()
    tic = time.monotonic()
    wf.run()
    elapsed = time.monotonic() - tic
    return elapsed, seen, loader


def _steps_with_io_under_them(loader):
    """Consumer steps that some fill's IO ran beside: the overlap, read
    from the order of events and not from a ratio of two stopwatches
    (which a CPU shared by six test workers does not keep)."""
    return sum(any(start < toc and end > tic
                   for start, end in loader.fill_spans)
               for tic, toc in loader.step_spans)


def test_loader_prefetch_overlaps_io():
    _, seen_off, loader_off = _run_loader_loop(prefetch=False)
    _, seen_on, loader = _run_loader_loop(prefetch=True)
    assert len(seen_on) == len(seen_off)
    for a, b in zip(seen_on, seen_off):
        numpy.testing.assert_array_equal(a, b)
    # prefetched fills must have happened off the scheduler thread
    assert any(t != threading.get_ident() for t in loader.fill_threads)
    # without prefetch a fill and a step take turns on one thread; with
    # it, fill k+1 has STARTED before step k ends, for every step whose
    # successor the loader can predict: all but the last of each epoch
    # of 4 steps (the wrap reshuffles), 9 of 12.  Half is asked for: a
    # worker thread that wakes a whole step late is the machine's
    steps = len(loader.step_spans)
    assert steps == len(loader_off.step_spans) >= 8
    assert _steps_with_io_under_them(loader_off) == 0
    assert _steps_with_io_under_them(loader) >= steps // 2, \
        (loader.fill_spans, loader.step_spans)


def test_loader_prefetch_epoch_wrap_correctness():
    """Across epoch wraps the prediction goes stale (reshuffle); the
    loader must detect it and serve identical data to the no-prefetch
    run even WITH shuffling enabled."""
    t_off, seen_off, _ = _run_loader_loop(
        prefetch=False, io_delay=0.0, train_delay=0.0, epochs=3)
    t_on, seen_on, _ = _run_loader_loop(
        prefetch=True, io_delay=0.0, train_delay=0.0, epochs=3)
    assert len(seen_on) == len(seen_off)
    for a, b in zip(seen_on, seen_off):
        numpy.testing.assert_array_equal(a, b)


def test_prefetch_exception_propagates_and_recovers():
    """A fill_minibatch_into that throws in the worker must not lose
    the batch OR the exception: the failure surfaces at consume time
    (logged) and the serve falls back to a synchronous fill — the
    served stream stays identical to the no-prefetch run."""
    fail_on = {3}

    class FlakyLoader(SlowIOLoader):
        def __init__(self, workflow, **kwargs):
            super(FlakyLoader, self).__init__(workflow, **kwargs)
            self.bg_calls = 0
            self.failures = 0

        def fill_minibatch_into(self, indices, data_out,
                                raw_labels_out):
            self.bg_calls += 1
            if self.bg_calls in fail_on:
                self.failures += 1
                raise RuntimeError("synthetic IO failure")
            super(FlakyLoader, self).fill_minibatch_into(
                indices, data_out, raw_labels_out)

    def run(prefetch, loader_cls):
        from veles_tpu import prng
        prng.seed_all(4321)
        wf = DummyWorkflow()
        loader = loader_cls(wf, io_delay=0.0, minibatch_size=16,
                            prefetch=prefetch)
        rep = Repeater(wf)
        stop = Bool(False)
        seen = []

        class Trainer(DummyUnit):
            def run(self):
                nonlocal stop
                super(Trainer, self).run()
                time.sleep(0.01)    # let the flaky future resolve
                seen.append(numpy.array(loader.minibatch_data.mem))
                if loader.epoch_ended and loader.epoch_number >= 2:
                    stop <<= True

        trainer = Trainer(wf, name="trainer")
        rep.link_from(wf.start_point)
        loader.link_from(rep)
        trainer.link_from(loader)
        rep.link_from(trainer)
        rep.gate_block = stop
        wf.end_point.link_from(trainer)
        wf.end_point.gate_block = ~stop
        wf.initialize()
        wf.run()
        return seen, loader

    seen_ref, _ = run(False, SlowIOLoader)
    seen_flaky, loader = run(True, FlakyLoader)
    assert loader.failures >= 1, "the failure injection never fired"
    assert len(seen_flaky) == len(seen_ref)
    for a, b in zip(seen_flaky, seen_ref):
        numpy.testing.assert_array_equal(a, b)


def test_no_stale_prefetch_after_reinitialize():
    """initialize() reshuffles the index space — a background fill
    buffered before the re-initialize must NOT be served afterwards
    even when its (offset, size) key matches (the stale-buffer-reuse
    hazard; initialize drops all in-flight fills)."""
    from veles_tpu import prng

    def serve_after_reinit(prefetch):
        prng.seed_all(777)
        wf = DummyWorkflow()
        loader = SlowIOLoader(wf, io_delay=0.0, minibatch_size=16,
                              prefetch=prefetch)
        loader.link_from(wf.start_point)
        wf.end_point.link_from(loader)
        wf.initialize()
        for _ in range(3):
            loader.run()    # leaves a prefetched batch 4 in flight
        assert not prefetch or loader._prefetch_futures_
        loader.initialize()                # reshuffle: new epoch order
        assert not loader._prefetch_futures_
        loader.run()
        return numpy.array(loader.minibatch_data.mem)

    a = serve_after_reinit(prefetch=True)
    b = serve_after_reinit(prefetch=False)
    numpy.testing.assert_array_equal(a, b)


def test_prefetch_ring_reuses_buffers_and_publishes_device():
    """The staging ring allocates its slots ONCE (no per-fill
    zeros_like churn) and, with a jit device attached, the worker's
    upload lands as the published device copy — both Vector sides
    fresh on a hit, nothing left for the consumer to transfer."""
    from veles_tpu import prng
    from veles_tpu.backends import CPUDevice

    prng.seed_all(4321)
    wf = DummyWorkflow()
    wf.device = CPUDevice()
    loader = SlowIOLoader(wf, io_delay=0.0, minibatch_size=16,
                          prefetch=True)
    loader.link_from(wf.start_point)
    wf.end_point.link_from(loader)
    wf.initialize(device=wf.device)
    slot_ids = set()
    orig_acquire = type(loader._staging()).acquire

    def spy_acquire(self):
        slot = orig_acquire(self)
        slot_ids.add(id(slot))
        return slot

    type(loader._staging()).acquire = spy_acquire
    try:
        hits = 0
        for _ in range(8):
            loader.run()
            time.sleep(0.02)        # let the background fill land
            if loader.minibatch_data._dev_fresh_ \
                    and loader.minibatch_data._host_fresh_:
                hits += 1
        assert hits >= 3, "prefetch hits never published device copies"
        assert len(slot_ids) <= loader._staging().depth
    finally:
        type(loader._staging()).acquire = orig_acquire


def test_drain_waits_for_background_not_gating_end_point():
    """run() returning means quiescent: an in-flight background unit
    that the end_point does NOT wait on is still joined before run()
    returns (a unit not yet started when the workflow stops may
    legitimately skip — the contract covers *running* units)."""
    wf = DummyWorkflow()
    bg = SleepUnit(wf, sleep=0.5, name="bg")
    bg.wants_thread = True
    # fg sleeps long enough that bg is definitely mid-run when the end
    # point fires and sets stopped
    fg = SleepUnit(wf, sleep=0.15, name="fg")
    bg.link_from(wf.start_point)
    fg.link_from(wf.start_point)
    wf.end_point.link_from(fg)          # end point ignores bg entirely
    wf.initialize()
    tic = time.monotonic()
    wf.run()
    assert len(bg.run_times) == 1, "bg never started; race in test"
    assert time.monotonic() - tic >= 0.45, \
        "run() returned before the in-flight background unit finished"


def test_drain_raises_on_wedged_background_unit():
    """A running background unit outliving QUIESCENCE_TIMEOUT fails
    run() loudly instead of silently violating the quiescence
    contract."""
    import pytest

    wf = DummyWorkflow()
    bg = SleepUnit(wf, sleep=1.2, name="bg")
    bg.wants_thread = True
    fg = SleepUnit(wf, sleep=0.1, name="fg")
    bg.link_from(wf.start_point)
    fg.link_from(wf.start_point)
    wf.end_point.link_from(fg)
    wf.initialize()
    wf.QUIESCENCE_TIMEOUT = 0.2        # instance override for the test
    try:
        with pytest.raises(RuntimeError, match="not quiescent"):
            wf.run()
        assert len(bg.run_times) == 1
    finally:
        time.sleep(1.3)                # let the straggler drain out of
        # the shared pool before other tests run


def _run_dist_slave(loader_prefetch, n_jobs=8, io_delay=0.12,
                    train_delay=0.12):
    """Distributed mirror of _run_loader_loop: a master serves index
    jobs, the slave fills minibatches (slow IO) and 'trains' (sleep)."""
    from veles_tpu import prng
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.parallel.jobs import JobClient, JobServer

    class CountingLoader(SlowIOLoader):
        def init_unpickled(self):
            super(CountingLoader, self).init_unpickled()
            self.sync_fills = 0
            self.bg_fills = 0

        def fill_minibatch(self):
            self.sync_fills += 1
            super(CountingLoader, self).fill_minibatch()

        def fill_minibatch_into(self, indices, data_out, raw_labels_out):
            self.bg_fills += 1
            super(CountingLoader, self).fill_minibatch_into(
                indices, data_out, raw_labels_out)

    seen = []

    def build(is_master, is_slave):
        prng.seed_all(4321)
        wf = DummyWorkflow()
        loader = CountingLoader(
            wf, io_delay=0.0 if is_master else io_delay,
            minibatch_size=16, prefetch=loader_prefetch)

        class Trainer(DummyUnit):
            def run(self):
                super(Trainer, self).run()
                if is_slave:
                    time.sleep(train_delay)
                    seen.append(numpy.array(loader.minibatch_data.mem))

        trainer = Trainer(wf, name="trainer")
        loader.link_from(wf.start_point)
        trainer.link_from(loader)
        wf.end_point.link_from(trainer)
        wf.launcher = DummyLauncher(is_master=is_master,
                                    is_slave=is_slave)
        wf.initialize()
        return wf, loader

    master_wf, _master_loader = build(True, False)
    slave_wf, slave_loader = build(False, True)
    server = JobServer(master_wf).start()
    try:
        client = JobClient(slave_wf, server.endpoint)
        client.handshake()
        tic = time.monotonic()
        assert client.run_prefetch(max_jobs=n_jobs)
        elapsed = time.monotonic() - tic
        client.close()
    finally:
        server.stop()
    return elapsed, seen, slave_loader


def test_slave_mode_minibatch_prefetch_overlaps_io():
    """The loader's IO overlap must exist in DISTRIBUTED runs too: the
    next job's payload (already double-buffered by the job client)
    feeds prefetch_job_data, so the fill runs during the current job's
    compute instead of serializing in front of it."""
    io_delay = 0.12    # large vs comms noise — ratio asserts flake
    t_off, seen_off, loader_off = _run_dist_slave(
        loader_prefetch=False, io_delay=io_delay)
    t_on, seen_on, loader_on = _run_dist_slave(
        loader_prefetch=True, io_delay=io_delay)
    # identical data served either way
    assert len(seen_on) == len(seen_off) > 0
    for a, b in zip(seen_on, seen_off):
        numpy.testing.assert_array_equal(a, b)
    # the prefetched path was genuinely taken: only the first job (no
    # payload buffered yet) plus at most two race losers may fill
    # synchronously; analyze_dataset's fills are shared by both runs
    analyze_fills = loader_off.sync_fills - 8      # 8 jobs
    assert loader_on.bg_fills >= 5
    assert loader_on.sync_fills <= analyze_fills + 3
    # and it bought real wall-clock overlap: each consumed prefetch
    # hides one io_delay; require at least 3 fills' worth of savings
    # (absolute bound — ratio asserts flake under CI load)
    assert t_on < t_off - 3 * io_delay, \
        "slave prefetch gave no overlap (on=%.3fs off=%.3fs)" % (
            t_on, t_off)


def test_atexit_registered_once_across_recreations(monkeypatch):
    """Recreating the pool after shutdown() must not stack another
    atexit handler each time (thread_pool.py registers once per
    process)."""
    from veles_tpu import thread_pool
    calls = []
    monkeypatch.setattr(thread_pool, "_atexit_registered", False)
    monkeypatch.setattr(thread_pool.atexit, "register",
                        lambda fn, *a, **kw: calls.append(fn))
    thread_pool.shutdown()
    for _ in range(3):
        assert thread_pool.get_pool() is not None
        thread_pool.shutdown()
    assert calls == [thread_pool.shutdown]
