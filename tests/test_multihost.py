"""Multi-host SPMD (parallel/multihost.py): two REAL processes join one
JAX runtime over the distributed coordinator, build a single global
mesh, feed host-local loader shards, and run the fused DP train step —
the DCN-scale analogue of the reference's ~100-node master–slave
(``manualrst_veles_distributed_training.rst:4``), with the gradient
all-reduce crossing process boundaries inside XLA instead of riding
pickled ZMQ payloads."""

import json
import os
import socket
import subprocess
import sys

import numpy

WORKER = r"""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")

from veles_tpu import prng
from veles_tpu.parallel import data_parallel, make_mesh, multihost
from veles_tpu.parallel.mesh import shard_batch
from veles_tpu.znicz.fused import init_mlp_params, make_train_step

multihost.initialize()          # VELES_* env vars from the parent
pid = multihost.process_index()

mesh = make_mesh({"data": -1})  # global: 2 procs x 4 devices = 8
prng.seed_all(1234)
layers = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
     "<-": {"learning_rate": 0.01, "gradient_moment": 0.9}},
    {"type": "softmax", "->": {"output_sample_shape": 8},
     "<-": {"learning_rate": 0.01}},
]
params = init_mlp_params(32, layers)
step = data_parallel(make_train_step(layers), mesh, params)

# the GLOBAL batch: every process materializes the full array for the
# expectation check, then feeds ONLY its host_shard_range rows
rng_all = __import__("numpy").random.default_rng(0)
numpy_ = __import__("numpy")
gx = rng_all.standard_normal((32, 32)).astype(numpy_.float32)
glabels = (numpy_.arange(32) % 8).astype(numpy_.int32)
start, stop = multihost.host_shard_range(32)
x = multihost.from_host_local(gx[start:stop], shard_batch(mesh))
labels = multihost.from_host_local(
    glabels[start:stop], shard_batch(mesh, ndim=1))

params, metrics = step(params, x, labels)
jax.block_until_ready(params)
result = json.dumps({
    "pid": pid,
    "n_global_devices": len(jax.devices()),
    "n_local_devices": len(jax.local_devices()),
    "process_count": multihost.process_count(),
    "is_coordinator": multihost.is_coordinator(),
    "shard": [start, stop],
    "loss": float(metrics["loss"]),
    "n_err": int(metrics["n_err"]),
})
out_dir = os.environ.get("VELES_OUT_DIR")
if out_dir:
    # ranks launched by spmd_launch share one stdout pipe where
    # concurrent lines can interleave; files are per-rank
    with open(os.path.join(out_dir, "rank%d.json" % pid), "w") as f:
        f.write(result + "\n")
print(result)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_fused_dp_step(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        env.update({
            "VELES_COORDINATOR": "127.0.0.1:%d" % port,
            "VELES_NUM_PROCS": "2",
            "VELES_PROC_ID": str(pid),
            "PYTHONPATH": repo_root + os.pathsep
            + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
        outs.append(json.loads(out.strip().splitlines()[-1]))

    by_pid = {o["pid"]: o for o in outs}
    assert set(by_pid) == {0, 1}
    for o in outs:
        assert o["n_global_devices"] == 8       # one mesh spans hosts
        assert o["n_local_devices"] == 4
        assert o["process_count"] == 2
    assert by_pid[0]["is_coordinator"] and not by_pid[1]["is_coordinator"]
    # contiguous non-overlapping host shards covering the global batch
    assert by_pid[0]["shard"] == [0, 16] and by_pid[1]["shard"] == [16, 32]
    # the all-reduced loss/metrics are REPLICATED: every process sees
    # the same global number (the step consumed rows from both hosts)
    assert by_pid[0]["loss"] == by_pid[1]["loss"]
    assert by_pid[0]["n_err"] == by_pid[1]["n_err"]
    assert 0 <= by_pid[0]["n_err"] <= 32
    assert numpy.isfinite(by_pid[0]["loss"])


def test_spmd_launch_boots_local_fleet(tmp_path):
    """scripts/spmd_launch runs the same command on every node with
    rank env vars set (``sh -c`` stands in for ssh, as in the slave
    bootstrap tests) and the booted processes form one runtime."""
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH",
                                                         "")
    env["VELES_OUT_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "veles_tpu.scripts.spmd_launch",
         "-n", "localhost x2",
         "--coordinator", "127.0.0.1:%d" % port,
         "--launch-transform", "sh -c",
         "--", sys.executable, str(script)],
        env=env, cwd=repo_root, capture_output=True, text=True,
        timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    outs = [json.loads((tmp_path / ("rank%d.json" % pid)).read_text())
            for pid in range(2)]
    assert len(outs) == 2
    by_pid = {o["pid"]: o for o in outs}
    assert set(by_pid) == {0, 1}
    assert all(o["n_global_devices"] == 8 for o in outs)
    assert by_pid[0]["loss"] == by_pid[1]["loss"]
    # both ranks were announced on stderr with their target host
    assert "rank 0 on localhost" in proc.stderr
    assert "rank 1 on localhost" in proc.stderr


# -- in-process edge cases (the process_double test double) ------------------

def test_host_shard_range_even_and_uneven():
    from veles_tpu.parallel import multihost
    from veles_tpu.parallel.multihost import MultiHostShardError
    with multihost.process_double(3) as dbl:
        # even split: typed refusal when the batch does not divide
        import pytest
        with pytest.raises(MultiHostShardError):
            multihost.host_shard_range(10)
        # uneven: the remainder lands on the LAST rank only
        spans = []
        for rank in range(3):
            with dbl.rank(rank):
                spans.append(multihost.host_shard_range(
                    10, allow_uneven=True))
        assert spans == [(0, 3), (3, 6), (6, 10)]
        # spans tile [0, 10) with no overlap
        assert spans[0][1] == spans[1][0] and spans[1][1] == spans[2][0]


def test_from_host_local_single_process_identity():
    """No coordinator, one process: from_host_local must be a plain
    identity placement — the transparent single-host fallback the
    pod-of-pods delegation contract rides on."""
    import jax

    from veles_tpu.parallel import multihost
    from veles_tpu.parallel.mesh import make_mesh, shard_batch
    assert not multihost.configured()
    mesh = make_mesh({"data": -1})
    local = numpy.arange(32, dtype=numpy.float32).reshape(16, 2)
    out = multihost.from_host_local(local, shard_batch(mesh))
    assert isinstance(out, jax.Array)
    assert out.shape == (16, 2)
    numpy.testing.assert_array_equal(numpy.asarray(out), local)


def test_from_host_local_typed_error_on_indivisible_axis():
    """The sharding's data axis must split evenly across processes —
    a typed MultiHostShardError (a ValueError subclass, so legacy
    handlers keep working)."""
    import pytest

    from veles_tpu.parallel import multihost
    from veles_tpu.parallel.multihost import MultiHostShardError
    from veles_tpu.parallel.mesh import make_mesh, shard_batch
    mesh = make_mesh({"data": -1})          # 8 shards; 8 % 3 != 0
    local = numpy.zeros((4, 2), numpy.float32)
    with multihost.process_double(3):
        with pytest.raises(MultiHostShardError) as err:
            multihost.from_host_local(local, shard_batch(mesh),
                                      global_shape=(12, 2))
        assert issubclass(err.type, ValueError)


def test_process_double_banks_shards_incrementally():
    """Sequential rank simulation: each rank's from_host_local banks
    its shard, the LAST rank's call returns the fully assembled
    global — the invariant the pod smoke's 2-process leg drives."""
    from veles_tpu.parallel import multihost
    from veles_tpu.parallel.mesh import make_mesh, shard_batch
    mesh = make_mesh({"data": -1})
    full = numpy.arange(64, dtype=numpy.float32).reshape(16, 4)
    with multihost.process_double(2) as dbl:
        assert multihost.configured()
        assert multihost.process_count() == 2
        with dbl.rank(0):
            assert multihost.is_coordinator()
            partial = multihost.from_host_local(
                full[:8], shard_batch(mesh), global_shape=(16, 4))
            # rank 1 has not contributed yet: its rows are zero-padded
            got = numpy.asarray(partial)
            numpy.testing.assert_array_equal(got[:8], full[:8])
            assert not got[8:].any()
        with dbl.rank(1):
            assert not multihost.is_coordinator()
            out = multihost.from_host_local(
                full[8:], shard_batch(mesh), global_shape=(16, 4))
            numpy.testing.assert_array_equal(numpy.asarray(out), full)
    assert not multihost.configured()


def test_process_double_does_not_nest_and_checks_rank():
    import pytest

    from veles_tpu.parallel import multihost
    with multihost.process_double(2) as dbl:
        with pytest.raises(RuntimeError):
            with multihost.process_double(2):
                pass
        with pytest.raises(ValueError):
            with dbl.rank(2):
                pass
    with pytest.raises(ValueError):
        multihost.process_double(0)
