"""veles_tpu.pod — one-pod-one-program training: the parity, wire,
elastic-membership and observability acceptance gates, plus the
plumbing it rides (mesh_from_topology, Vector shardings, the V-P02
preflight, the mesh-sharded InferenceEngine port).

The suite runs on the conftest's 8-device virtual CPU mesh, so every
sharded path here exercises real multi-device GSPMD programs."""

import threading
import time

import numpy
import pytest

from veles_tpu import chaos, prof
from veles_tpu.backends import NumpyDevice
from veles_tpu.parallel.jobs import JobServer
from veles_tpu.parallel.mesh import (MeshTopologyError,
                                     mesh_from_topology)
from veles_tpu.pod import (PodError, PodMaster, PodRuntime, PodWorker,
                           eval_metrics, train_epochs)
from veles_tpu.pod.__main__ import SMOKE_EPOCHS, make_workflow

EPOCHS = SMOKE_EPOCHS


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    chaos.controller.disarm()


@pytest.fixture
def live_trace():
    """Knob-based trace enabling (workflow initialize() re-reads the
    knob — mirrors tests/test_chaos.py)."""
    from veles_tpu import trace
    from veles_tpu.config import root
    saved = root.common.engine.get("trace", "off")
    root.common.engine.trace = "on"
    trace.recorder.clear()
    trace.configure()
    yield trace
    root.common.engine.trace = saved
    trace.configure()
    trace.recorder.clear()


def final_weights(wf):
    wf.forwards[0].weights.map_read()
    return numpy.array(wf.forwards[0].weights.mem)


def run_reference(epochs=EPOCHS):
    """Single-device stitched oracle, driven by the SAME per-epoch
    stepper the pod worker uses."""
    wf = make_workflow(max_epochs=epochs)
    for _ in train_epochs(wf, epochs):
        pass
    return wf


# -- mesh_from_topology ------------------------------------------------------

def test_mesh_from_topology_spellings():
    mesh = mesh_from_topology("auto")
    assert mesh.shape["data"] == 8
    assert mesh_from_topology(4).shape == {"data": 4}
    mesh = mesh_from_topology("4x2")
    assert mesh.shape == {"data": 4, "model": 2}
    mesh = mesh_from_topology({"data": -1, "model": 2})
    assert mesh.shape == {"data": 4, "model": 2}
    mesh = mesh_from_topology(None, require=("data", "model"))
    assert mesh.shape == {"data": 8, "model": 1}


def test_mesh_from_topology_typed_errors():
    with pytest.raises(MeshTopologyError):
        mesh_from_topology({"data": 3})          # 3 does not match 8
    with pytest.raises(MeshTopologyError):
        mesh_from_topology({"data": 3, "model": -1})   # 8 % 3
    with pytest.raises(MeshTopologyError):
        mesh_from_topology({"data": -1, "model": -1})  # two wildcards
    with pytest.raises(MeshTopologyError):
        mesh_from_topology({"data": 0})
    with pytest.raises(MeshTopologyError):
        mesh_from_topology("2x2x2")
    with pytest.raises(MeshTopologyError):
        mesh_from_topology("banana")


@pytest.mark.parametrize("topology", [None, "auto", 1, {"data": -1}])
def test_mesh_from_topology_one_device_runs_auto_and_one(topology):
    import jax
    mesh = mesh_from_topology(topology, devices=jax.devices()[:1],
                              require=("data", "model"))
    assert mesh.shape == {"data": 1, "model": 1}


@pytest.mark.parametrize("topology", [{"data": 8}, 4, "2x2",
                                      {"data": -1, "model": 2}])
def test_mesh_from_topology_one_device_refuses_more(topology):
    """Asking for N devices with one attached is an error, never a
    silent 1-device mesh on the first chip."""
    import jax
    with pytest.raises(MeshTopologyError, match="1 is attached"):
        mesh_from_topology(topology, devices=jax.devices()[:1])


# -- Vector shardings --------------------------------------------------------

def test_vector_set_sharding_preserves_and_places():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from veles_tpu.backends import AutoDevice
    from veles_tpu.memory import Vector
    mesh = mesh_from_topology("auto")
    vec = Vector(numpy.arange(64, dtype=numpy.float32))
    vec.initialize(AutoDevice())
    before = numpy.array(vec.devmem)            # single-device upload
    vec.set_sharding(NamedSharding(mesh, P("data")))
    dev = vec.devmem
    assert dev.sharding.is_equivalent_to(
        NamedSharding(mesh, P("data")), 1)
    numpy.testing.assert_array_equal(numpy.asarray(dev), before)
    # values survive a reshard back to replicated, and clearing
    # restores plain device puts
    vec.set_sharding(NamedSharding(mesh, P()))
    numpy.testing.assert_array_equal(numpy.asarray(vec.devmem), before)
    vec.set_sharding(None)
    assert vec.sharding is None
    numpy.testing.assert_array_equal(numpy.asarray(vec.devmem), before)


# -- install preconditions ---------------------------------------------------

def test_pod_requires_stitched_workflow():
    wf = make_workflow(device=NumpyDevice())    # interpret: no segments
    with pytest.raises(PodError):
        PodRuntime(wf).install()


def test_pod_requires_divisible_batch():
    wf = make_workflow(batch=60)                # 60 % 8 != 0
    with pytest.raises(PodError):
        PodRuntime(wf).install()


# -- THE parity gate ---------------------------------------------------------

def test_pod_parity_gate():
    """Acceptance: on the 8-device mesh, pod training produces eval
    metrics equal to the single-device stitched run AND the ZMQ
    master–slave run it replaces, with final weights within numerical
    tolerance (the psum reorders float reductions — bitwise equality
    is not the contract)."""
    from veles_tpu.parallel.jobs import JobClient

    reference_wf = run_reference()
    reference = eval_metrics(reference_wf)
    assert reference["complete"]

    # the pod run (standalone runtime — membership adds control
    # frames, not numerics)
    pod_wf = make_workflow()
    pod = PodRuntime(pod_wf, mesh=mesh_from_topology("auto"))
    pod.install()
    assert pod.shards == 8
    for _ in train_epochs(pod_wf, EPOCHS):
        pass
    pod_metrics = eval_metrics(pod_wf)

    # the ZMQ per-minibatch master–slave run this path replaces
    zmq_master = make_workflow(device=NumpyDevice(), is_master=True)
    zmq_slave = make_workflow(is_slave=True)
    server = JobServer(zmq_master).start()
    try:
        client = JobClient(zmq_slave, server.endpoint,
                           rpc_timeout_ms=2000)
        client.handshake()
        assert client.run() is True
        client.close()
    finally:
        server.stop()
    zmq_metrics = eval_metrics(zmq_master)

    for key in ("complete", "epochs", "best_n_err_pt"):
        assert pod_metrics[key] == reference[key], \
            (key, pod_metrics, reference)
        assert pod_metrics[key] == zmq_metrics[key], \
            (key, pod_metrics, zmq_metrics)
    numpy.testing.assert_allclose(
        final_weights(pod_wf), final_weights(reference_wf),
        rtol=0, atol=5e-5)


# -- THE wire gate -----------------------------------------------------------

def test_pod_wire_gate_zero_per_step_frames():
    """Acceptance: steady-state pod training exchanges ZERO per-step
    gradient/update frames over ZMQ — chaos wire-site counters are
    the probe — and control traffic is O(heartbeats + epochs)."""
    chaos.controller.arm([], seed=1)            # counters only
    recompiles_before = prof.ledger.recompiles
    master_wf = make_workflow(device=NumpyDevice())
    master = PodMaster(master_wf, pods=1, epochs=EPOCHS)
    server = JobServer(master, heartbeat_interval=0.3).start()
    worker = PodWorker(make_workflow(), server.endpoint,
                       rpc_timeout_ms=4000)
    try:
        assert worker.run() is True
    finally:
        worker.close()
        server.stop()
    minibatches = EPOCHS * (512 // 64)
    update_frames = chaos.controller.frames("master_recv", "update")
    epoch_frames = chaos.controller.frames("master_recv", "pod_epoch")
    assert update_frames == 1, \
        "exactly ONE update frame (the final lease result) may ride " \
        "the wire; saw %d for %d minibatches trained" % (
            update_frames, minibatches)
    assert 1 <= epoch_frames <= EPOCHS, \
        "control plane must be O(epochs): %d" % epoch_frames
    assert chaos.controller.frames("master_send", "job") < minibatches
    # the final update installed the pod-trained weights on the master
    assert master.done, "lease never completed"
    assert prof.ledger.recompiles == recompiles_before, \
        "pod steady state must not retrace"
    # per-shard ledger dimension: the segment entries carry the axis
    pod_entries = [e for e in prof.ledger.entries("segment")
                   if e.shards == 8]
    assert pod_entries, "no segment entry carries the shard dimension"
    assert any(e.psum_bytes > 0 for e in pod_entries), \
        "gradient psum traffic never accounted"
    report = prof.report_text()
    assert "pod:" in report and "psum" in report


# -- elastic membership (THE chaos satellite pack) ---------------------------

def test_pod_elastic_chip_kill_parity(live_trace, tmp_path):
    """A seeded chaos schedule kills one simulated chip mid-epoch: the
    pod must reshard (8 -> 4 under the halving policy), bump its
    generation, report it upstream on the next epoch sync, and STILL
    converge to eval parity with the fault-free run — with the
    reshard and its provoking injection visible in the merged
    Perfetto timeline as one pod pid with per-shard lanes."""
    reference = eval_metrics(run_reference())

    chaos.controller.arm([
        {"site": "pod_chip", "action": "chip_kill", "nth": 5},
        {"site": "slave_send", "action": "dup", "op": "update",
         "nth": 1},
    ], seed=7)
    master_wf = make_workflow(device=NumpyDevice())
    master = PodMaster(master_wf, pods=1, epochs=EPOCHS)
    server = JobServer(master, heartbeat_interval=0.3).start()
    worker = PodWorker(make_workflow(), server.endpoint,
                       rpc_timeout_ms=4000)
    try:
        assert worker.run() is True
    finally:
        worker.close()
        bundle_path = str(tmp_path / "pod_session.json")
        server.save_session_profile(bundle_path)
        server.stop()
    injected = chaos.controller.snapshot()["injected"]
    assert injected.get("chip_kill") == 1, injected
    assert worker.runtime.reshards == 1
    assert worker.runtime.shards == 4, \
        "halving policy: 8 devices minus one -> 4-shard data axis"
    assert worker.runtime.generation == 2, \
        "an elastic reshard must bump the generation"
    # ...and the control plane saw the bump
    progress = master.progress.get("pod-0")
    assert progress and progress["generation"] == 2, progress
    # duplicated final update deduplicated by the PR 7 machinery
    assert server.dedup_dropped >= 1
    # eval parity with the fault-free run
    metrics = (master.done.get("pod-0") or {}).get("metrics") or {}
    assert metrics.get("complete") is True
    assert abs(metrics["best_n_err_pt"]
               - reference["best_n_err_pt"]) <= 2.0, \
        (metrics, reference)

    # observability: reshard + injection + per-shard lanes, merged
    assert live_trace.recorder.count("pod", "reshard") == 1
    assert live_trace.recorder.count("chaos") >= 2
    merged = prof.merge.merged_events(prof.merge.load(bundle_path))
    pod_events = [ev for ev in merged if ev.get("role") == "pod"]
    names = {(ev.get("cat"), ev.get("name")) for ev in merged}
    assert ("pod", "reshard") in names
    assert ("chaos", "chip_kill") in names
    lanes = {ev["tid"] for ev in pod_events
             if ev.get("name") == "shard_dispatch"}
    assert {0, 1, 2, 3} <= lanes, \
        "one pod pid must carry a dispatch lane per shard: %r" % lanes


def test_pod_master_kill_and_resume(live_trace):
    """Master crash-recovery on the pod path: kill the master
    mid-lease, restart a fresh one on the same port — the worker
    reconnects, the requeued lease is re-granted, and the worker
    RESUMES from its local epoch counter (its training state never
    left its HBM), completing with eval parity.  The pre-restart
    final update is stale-rejected, the re-granted lease's answer
    applies (PR 7 exactly-once)."""
    reference = eval_metrics(run_reference(epochs=EPOCHS))

    master1 = PodMaster(make_workflow(device=NumpyDevice()),
                        pods=1, epochs=EPOCHS)
    server1 = JobServer(master1, heartbeat_interval=0.3,
                        slave_timeout=8.0).start()
    port = server1.port
    worker = PodWorker(make_workflow(), server1.endpoint,
                       rpc_timeout_ms=1200, reconnect_max_wait=20.0)
    done = []
    runner = threading.Thread(target=lambda: done.append(worker.run()))
    runner.start()
    # wait for at least one epoch sync, then "crash" the master
    deadline = time.time() + 60
    while time.time() < deadline and not master1.progress:
        time.sleep(0.02)
    assert master1.progress, "no epoch sync before the kill"
    server1.kill()

    import zmq
    master2 = PodMaster(make_workflow(device=NumpyDevice()),
                        pods=1, epochs=EPOCHS)
    # the killed server's ROUTER releases the endpoint asynchronously
    # (stop() joins the loop thread with a bound) — retry the rebind
    # like a restarted process's supervisor would
    for _ in range(80):
        try:
            server2 = JobServer(master2, port=port,
                                heartbeat_interval=0.3,
                                slave_timeout=8.0)
            break
        except zmq.error.ZMQError:
            time.sleep(0.25)
    else:
        pytest.fail("killed master's endpoint never released")
    server2.start()
    try:
        runner.join(120)
        assert not runner.is_alive(), "pod session hung after restart"
        assert done == [True]
    finally:
        worker.close()
        server2.stop()
    assert master2.done.get("pod-0"), \
        "the re-granted lease must deliver its final update"
    assert worker._progress.get("pod-0") == EPOCHS
    metrics = master2.done["pod-0"]["metrics"]
    assert metrics.get("complete") is True
    assert abs(metrics["best_n_err_pt"]
               - reference["best_n_err_pt"]) <= 2.0


def test_pod_lease_requeued_on_drop():
    """Elastic membership at the lease level: a dropped worker's
    unfinished lease goes back on the queue and the next worker
    finishes it."""
    from veles_tpu.parallel.jobs import SlaveDescription
    master = PodMaster(make_workflow(device=NumpyDevice()),
                       pods=1, epochs=1)
    slave = SlaveDescription("w1")
    lease = master.generate_data_for_slave(slave)
    assert lease["pod_lease"]["lease"] == "pod-0"
    master.drop_slave(slave)
    other = SlaveDescription("w2")
    again = master.generate_data_for_slave(other)
    assert again["pod_lease"]["lease"] == "pod-0", \
        "the dropped worker's lease must be re-granted"
    from veles_tpu.workflow import NoJobYet
    with pytest.raises(NoJobYet):
        master.generate_data_for_slave(slave)


# -- V-P02 -------------------------------------------------------------------

def test_check_pod_batch_and_budget_and_segments():
    from veles_tpu.analyze import check_pod, rule_catalog
    assert "V-P02" in rule_catalog()
    wf = make_workflow()
    mesh = mesh_from_topology("auto")
    clean = check_pod(wf, mesh)
    assert not clean.has_errors, clean.render_text()
    # batch divisibility
    report = check_pod(wf, mesh, batch_size=60)
    assert any(f.rule == "V-P02" and "divide" in f.message
               for f in report.errors())
    # per-shard residency vs a toy HBM budget
    report = check_pod(wf, mesh, hbm_bytes=1024)
    assert any(f.rule == "V-P02" and "residency" in f.message
               for f in report.errors())
    # param_rules move the check: leaves the rules shard count at
    # 1/shards, so the documented remedy (fsdp_rules/tp_rules) can
    # actually turn a failing residency plan into a passing one —
    # there must exist a budget the replicated plan busts and the
    # sharded plan fits
    from jax.sharding import PartitionSpec as P

    def residency_error(budget, rules=None):
        rep = check_pod(wf, mesh, hbm_bytes=budget, param_rules=rules)
        return any("residency" in f.message for f in rep.errors())

    shard_all = lambda leaf: P("data")     # noqa: E731
    boundary = [b for b in range(1024, 65536, 512)
                if residency_error(b) and not residency_error(
                    b, rules=shard_all)]
    assert boundary, \
        "sharding every param leaf must lower per-shard residency"
    # no data axis at all
    report = check_pod(wf, mesh, data_axis="nope")
    assert report.has_errors
    # an unstitched workflow is named, not crashed on
    loose = make_workflow(device=NumpyDevice())
    report = check_pod(loose, mesh)
    assert any("no stitched segments" in f.message for f in report)


def test_pod_preflight_fail_mode():
    wf = make_workflow(batch=64)
    pod = PodRuntime(wf, preflight="fail")
    pod.install()       # clean plan passes in fail mode
    pod.uninstall()


# -- the serve-engine mesh port ----------------------------------------------

def test_inference_engine_mesh_parity_and_fallback():
    """The gen engine's declarative mesh-sharded forward, ported: the
    same trained workflow served through a pjit'd engine answers
    byte-identically to the single-device engine; a None/1-device
    mesh IS the single-device path."""
    from veles_tpu.serve.engine import InferenceEngine
    wf = run_reference(epochs=1)
    batch = numpy.random.default_rng(3).standard_normal(
        (8, 16)).astype(numpy.float32)
    plain = InferenceEngine.from_workflow(wf, max_batch_size=8)
    plain.warmup()
    sharded = InferenceEngine.from_workflow(
        wf, max_batch_size=8, mesh=mesh_from_topology("auto"))
    assert sharded.mesh is not None
    sharded.warmup()
    numpy.testing.assert_array_equal(plain.infer(batch),
                                     sharded.infer(batch))
    # TP-style param rule: column-shard the hidden layer, still exact
    from jax.sharding import PartitionSpec as P

    def rule(leaf):
        shape = numpy.shape(leaf)
        if len(shape) == 2 and shape[-1] % 8 == 0:
            return P(None, "data")
        return None

    tp = InferenceEngine.from_workflow(
        wf, max_batch_size=8, mesh=mesh_from_topology("auto"),
        param_specs=rule)
    tp.warmup()
    numpy.testing.assert_allclose(tp.infer(batch), plain.infer(batch),
                                  rtol=0, atol=1e-5)
    # single-device fallback: no pjit wrapper at all
    import jax
    one_mesh = mesh_from_topology("auto", devices=jax.devices()[:1])
    fallback = InferenceEngine.from_workflow(
        wf, max_batch_size=8, mesh=one_mesh)
    assert fallback.mesh is None
    numpy.testing.assert_array_equal(plain.infer(batch),
                                     fallback.infer(batch))


# -- pod-of-pods (multi-host pods, pp/ep rules, device loss) -----------------

def test_multihost_pod_transparent_delegation():
    """A single-process MultiHostPod IS its PodRuntime: same install/
    uninstall lifecycle, describe() decorated with process topology,
    host_range covering the whole dataset."""
    from veles_tpu.pod import MultiHostPod
    wf = make_workflow(max_epochs=1)
    pod = MultiHostPod(wf)
    assert pod.process_count == 1
    assert pod.process_index == 0
    assert pod.is_coordinator
    assert pod.host_range(64) == (0, 64)
    pod.install()
    try:
        assert pod.runtime.installed
        desc = pod.describe()
        assert desc["processes"] == 1
        assert desc["process_index"] == 0
        assert desc["coordinator"] is True
        assert desc["shards"] == pod.runtime.shards
        # assemble: identity placement on one process
        local = numpy.zeros((16, 4), numpy.float32)
        out = pod.assemble(local)
        assert out.shape == (16, 4)
    finally:
        pod.uninstall()
    assert not pod.runtime.installed


def test_device_loss_detector_heartbeat_reshard(live_trace):
    """A silent host is declared lost after ``timeout``: one
    ``jobs:heartbeat_stall`` instant per host, ONE reshard dropping
    its devices_per_host chips, no re-loss on the next poll."""
    from veles_tpu.pod import DeviceLossDetector
    wf = make_workflow(max_epochs=1)
    runtime = PodRuntime(wf, mesh=mesh_from_topology(
        {"data": -1}, require=("data",)))
    runtime.install()
    try:
        clock = {"now": 100.0}
        det = DeviceLossDetector(runtime, timeout=5.0,
                                 devices_per_host=4,
                                 clock=lambda: clock["now"])
        det.beat("host-0")
        det.beat("host-1")
        assert det.hosts() == ["host-0", "host-1"]
        assert det.poll() == []                 # everyone fresh
        clock["now"] += 10.0
        det.beat("host-0")                      # host-0 stays alive
        gen = runtime.generation
        shards = runtime.shards
        stalls = live_trace.recorder.count("jobs", "heartbeat_stall")
        assert det.poll() == ["host-1"]
        assert det.stalls == 1
        assert runtime.generation == gen + 1
        assert runtime.shards == shards - 4
        assert live_trace.recorder.count(
            "jobs", "heartbeat_stall") == stalls + 1
        # the lost host left the table: no repeated reshard
        assert det.poll() == []
        assert det.hosts() == ["host-0"]
        assert runtime.generation == gen + 1
    finally:
        runtime.uninstall()


def test_device_loss_detector_dispatch_failure():
    """Typed classification: an UNAVAILABLE-style runtime error
    reshards and returns True (retry); anything else returns False
    (re-raise) and never touches the mesh."""
    from veles_tpu.pod import DeviceLossDetector, is_device_loss
    assert is_device_loss(RuntimeError("UNAVAILABLE: socket closed"))
    assert is_device_loss(RuntimeError(
        "device lost: slice health check failed"))
    assert is_device_loss(RuntimeError("DEADLINE EXCEEDED waiting"))
    assert not is_device_loss(RuntimeError("Invalid argument: dim 3"))
    assert not is_device_loss(ValueError("unavailable"))
    assert not is_device_loss(None)
    wf = make_workflow(max_epochs=1)
    runtime = PodRuntime(wf, mesh=mesh_from_topology(
        {"data": -1}, require=("data",)))
    runtime.install()
    try:
        det = DeviceLossDetector(runtime, devices_per_host=4)
        gen = runtime.generation
        assert not det.dispatch_failure(ValueError("shape mismatch"))
        assert runtime.generation == gen
        assert det.dispatch_failure(
            RuntimeError("UNAVAILABLE: connection reset by peer"))
        assert det.dispatch_losses == 1
        assert runtime.generation == gen + 1
    finally:
        runtime.uninstall()


def test_pp_ep_rules_shard_leading_dim():
    """pp_rules/ep_rules: stage/expert-stacked leaves shard their
    leading dim over the pipe/expert axis, everything else (scalars,
    small leaves, indivisible leading dims) replicates."""
    import pytest
    from jax.sharding import PartitionSpec as P

    from veles_tpu.parallel.dp import ep_rules, pp_rules
    from veles_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"data": 2, "pipe": 4})
    rules = pp_rules(mesh, min_elements=64)
    assert rules(numpy.zeros((4, 32, 32))) == P("pipe", None, None)
    assert rules(numpy.zeros((8, 64))) == P("pipe", None)
    assert rules(numpy.zeros((3, 64, 64))) is None   # 3 % 4 != 0
    assert rules(numpy.zeros((4, 2))) is None        # too small
    assert rules(numpy.float32(0.5)) is None         # scalar
    with pytest.raises(ValueError):
        pp_rules(make_mesh({"data": -1}))            # no pipe axis
    emesh = make_mesh({"data": 2, "expert": 4})
    erules = ep_rules(emesh, min_elements=64)
    assert erules(numpy.zeros((4, 16, 32))) == P("expert", None, None)
    assert erules(numpy.zeros((4, 8))) is None       # below min_elements
