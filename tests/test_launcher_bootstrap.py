"""Remote slave bootstrap: the master launcher spawns its own slaves
(ref ``launch_remote_progs`` ``launcher.py:617-660`` + YARN discovery
``:887``), exercised fully locally via the ``sh -c`` launch transform —
the spawned command rides as one argument exactly as ssh would pass it
to the remote shell.
"""

import json
import sys
import textwrap
import threading

import pytest

from veles_tpu.launcher import (
    Launcher, discover_nodes_from_yarn, parse_nodes)

# one module defines the workflow for BOTH sides so the checksum
# handshake passes (the checksum covers the defining source file)
BOOT_MODULE = textwrap.dedent("""
    import numpy
    import sys

    from veles_tpu import prng
    from veles_tpu.loader.fullbatch import FullBatchLoader
    from veles_tpu.znicz.standard_workflow import StandardWorkflow


    class BootLoader(FullBatchLoader):
        def load_data(self):
            rng = numpy.random.default_rng(5)
            n = 120
            labels = (numpy.arange(n) % 4).astype(int)
            centers = rng.standard_normal((4, 8)) * 3
            self.original_data.mem = (
                centers[labels] + rng.standard_normal((n, 8)) * 0.5
            ).astype(numpy.float32)
            self.original_labels = [int(v) for v in labels]
            self.class_lengths[:] = [0, 40, 80]


    LAYERS = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 6},
         "<-": {"learning_rate": 0.05, "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 4},
         "<-": {"learning_rate": 0.05}},
    ]


    def make(launcher):
        prng.seed_all(21)
        wf = StandardWorkflow(
            None,
            loader_factory=lambda w: BootLoader(w, minibatch_size=20),
            layers=[{**s} for s in LAYERS],
            decision_config={"max_epochs": 2})
        wf.launcher = launcher
        return wf


    if __name__ == "__main__":
        # re-import under the canonical module name so unit classes hash
        # identically on both sides (the real CLI loads workflow files
        # by module name too)
        import importlib.util
        import os
        spec = importlib.util.spec_from_file_location(
            "boot_wf", os.path.abspath(__file__))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["boot_wf"] = mod
        spec.loader.exec_module(mod)
        from veles_tpu.launcher import Launcher
        launcher = Launcher(master_address=sys.argv[1], device="numpy")
        wf = mod.make(launcher)
        launcher.initialize()
        launcher.run()
""")


def test_parse_nodes():
    assert parse_nodes(["hostA", "b:2222", "c x3", "d:22x2",
                        "e.example.com", "linux01", "f*4"]) == [
        ("hostA", 22, 1), ("b", 2222, 1), ("c", 22, 3), ("d", 22, 2),
        ("e.example.com", 22, 1),
        # glued xN after a bare host is a HOSTNAME, not a count
        ("linux01", 22, 1), ("f", 22, 4)]
    with pytest.raises(ValueError):
        parse_nodes(["bad spec::"])
    with pytest.raises(ValueError):
        parse_nodes(["host:abc"])


def test_yarn_discovery():
    import functools
    import http.server

    payload = {"nodes": {"node": [
        {"nodeHostName": "w1", "state": "RUNNING"},
        {"nodeHostName": "w2", "state": "LOST"},
        {"nodeHostName": "w3", "state": "RUNNING"},
    ]}}

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            assert self.path == "/ws/v1/cluster/nodes"
            body = json.dumps(payload).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        nodes = discover_nodes_from_yarn(
            "http://127.0.0.1:%d" % httpd.server_port)
        assert nodes == ["w1", "w3"]
    finally:
        httpd.shutdown()


def test_master_bootstraps_slaves_locally(tmp_path):
    """End-to-end: master spawns 2 slaves through the launch transform,
    they connect, do jobs, master's weights move, spawned procs exit."""
    import importlib.util
    import os
    import numpy

    import veles_tpu
    repo_root = os.path.dirname(os.path.dirname(veles_tpu.__file__))
    script = tmp_path / "boot_wf.py"
    script.write_text(BOOT_MODULE)
    spec = importlib.util.spec_from_file_location("boot_wf", str(script))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["boot_wf"] = mod
    try:
        spec.loader.exec_module(mod)
        launcher = Launcher(
            listen="127.0.0.1:0", device="numpy",
            nodes=["localhost x2"],
            slave_launch_transform="sh -c",
            # spawned processes don't get pytest's conftest env or
            # sys.path — pin the virtual CPU platform and the repo
            # root explicitly, like conftest does for this process
            slave_command="env JAX_PLATFORMS=cpu PYTHONPATH=%s %s %s "
                          "%%(master)s"
                          % (repo_root, sys.executable, script),
            advertise_host="127.0.0.1")
        wf = mod.make(launcher)
        launcher.initialize()
        w_before = numpy.array(wf.forwards[0].weights.mem)
        launcher.run()
        assert launcher._server.endpoint
        assert not launcher._spawned_          # reaped
        # the slaves have said goodbye by now (the master reaps them
        # BEFORE its server goes away, so a late starter hears "no more
        # jobs" and exits 0); what they did is in the server's count
        assert launcher._server._updates_applied > 0, \
            "no spawned slave completed a job"
        w_after = numpy.array(wf.forwards[0].weights.mem)
        assert not numpy.allclose(w_before, w_after)
    finally:
        sys.modules.pop("boot_wf", None)
