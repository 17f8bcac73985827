"""Launcher: mode selection and workflow lifecycle (ref
``veles/launcher.py:100-906``).

The reference's Launcher owns the Twisted reactor, picks
standalone/master/slave from ``-l``/``-m`` flags (``launcher.py:333-356``),
boots graphics + web status, selects the device, initializes the workflow
and runs it.  The TPU re-design needs no reactor: ``Workflow.run`` is a
synchronous drain loop, the distributed layer is the threaded ZeroMQ job
server/client (:mod:`veles_tpu.parallel.jobs`), and on-pod data
parallelism lives *inside* the jitted step — so the Launcher here is the
thin conductor the units consult (``is_master``/``is_slave``/
``is_standalone``/``device``/``stop``), not an event loop.
"""

import json
import os
import re
import shlex
import socket
import subprocess
import sys
import threading
import time

from veles_tpu.cmdline import CommandLineArgumentsRegistry
from veles_tpu.config import root
from veles_tpu.logger import Logger


def parse_nodes(specs):
    """``host[:ssh_port][xN]`` specs → [(host, ssh_port, count)]
    (ref node-spec parsing in ``launcher.py:194-268``).

    The count may be glued to the port (``host:22x3``) or follow the
    host as ``host *3`` / ``host x3`` — but never glued directly to a
    bare hostname, where it would be ambiguous (``linux01`` is a host,
    not ``linu`` × 1)."""
    out = []
    for spec in specs:
        s = str(spec).strip()
        count = 1
        m = re.search(r"(?:\*|\s+x)\s*(\d+)$", s)
        if m:
            count = int(m.group(1))
            s = s[:m.start()].rstrip()
        host, sep, port_part = s.partition(":")
        ssh_port = 22
        if sep:
            pm = re.match(r"^(\d+)(?:x(\d+))?$", port_part)
            if not pm:
                raise ValueError("bad node spec %r "
                                 "(want host[:port][xN])" % (spec,))
            ssh_port = int(pm.group(1))
            if pm.group(2):
                count = int(pm.group(2))
        if not re.match(r"^[\w.\-]+$", host):
            raise ValueError("bad node spec %r "
                             "(want host[:port][xN])" % (spec,))
        out.append((host, ssh_port, count))
    return out


def discover_nodes_from_yarn(rm_url):
    """Node list from a YARN ResourceManager REST endpoint
    (ref ``_discover_nodes_from_yarn`` ``launcher.py:887``): GET
    ``<rm>/ws/v1/cluster/nodes``, keep RUNNING nodes' hostnames."""
    import urllib.request
    url = rm_url.rstrip("/") + "/ws/v1/cluster/nodes"
    with urllib.request.urlopen(url, timeout=30) as resp:
        data = json.loads(resp.read())
    nodes = (data.get("nodes") or {}).get("node") or []
    return [n["nodeHostName"] for n in nodes
            if n.get("state", "RUNNING") == "RUNNING"]


class Launcher(Logger, metaclass=CommandLineArgumentsRegistry):
    """Conducts one workflow run in one of three modes
    (ref ``manualrst_veles_modes.rst:4-23``):

    - **standalone** (default): initialize device + workflow, run to
      completion in this process.
    - **master** (``listen`` address given): never executes the graph
      body; serves jobs to slaves via :class:`JobServer`
      (ref ``workflow.py:350-354``).
    - **slave** (``master_address`` given): connects a
      :class:`JobClient` and executes jobs until the master says
      ``no_more_jobs``.
    """

    def __init__(self, workflow=None, **kwargs):
        super(Launcher, self).__init__()
        self.listen = kwargs.get("listen", "")
        self.master_address = kwargs.get("master_address", "")
        if self.listen and self.master_address:
            raise ValueError("cannot be both master (listen) and slave "
                             "(master_address)")
        # None → make_device falls back to root.common.engine.backend
        self.device_spec = kwargs.get("device")
        self.testing = kwargs.get("testing", False)
        self.web_status_enabled = kwargs.get("web_status", False)
        self.graphics_enabled = kwargs.get("graphics", False)
        #: remote bootstrap (ref ``launch_remote_progs``
        #: ``launcher.py:617-660``): node specs the master ssh-spawns
        #: slaves onto; ``yarn`` URL adds discovered nodes
        self.nodes = list(kwargs.get("nodes") or [])
        if kwargs.get("yarn"):
            self.nodes.extend(discover_nodes_from_yarn(kwargs["yarn"]))
        #: template producing the remote-launch prefix; ``%(host)s`` /
        #: ``%(port)d`` substituted per node (ref
        #: ``--slave-launch-transform``).  The slave command is appended
        #: as ONE argument (ssh semantics) — so ``sh -c`` exercises the
        #: same path fully locally.
        self.slave_launch_transform = kwargs.get(
            "slave_launch_transform",
            "ssh -o BatchMode=yes -p %(port)d %(host)s")
        #: explicit slave command with ``%(master)s`` placeholder;
        #: default: this process's argv with -l/--nodes swapped for -m
        self.slave_command = kwargs.get("slave_command")
        #: hostname remotes dial back to (default: this host's fqdn —
        #: the bind address may be 0.0.0.0)
        self.advertise_host = kwargs.get("advertise_host")
        #: master crash-recovery: checkpoint dir + cadence (fall back
        #: to root.common.engine.checkpoint.*) and the --resume flag
        self.checkpoint_dir = kwargs.get("checkpoint_dir")
        self.checkpoint_every = kwargs.get("checkpoint_every")
        self.resume = kwargs.get("resume", False)
        self.stopped = False
        self.device = None
        self.workflow = None
        self._server = None
        self._client = None
        self._spawned_ = []
        self._web_status = None
        self._graphics = None
        self._start_time = None
        if workflow is not None:
            workflow.launcher = self

    @staticmethod
    def init_parser(parser):
        group = parser.add_argument_group("launcher")
        group.add_argument(
            "-l", "--listen", default="", metavar="HOST:PORT",
            help="run as MASTER, listening for slaves here "
                 "(ref launcher.py:194-268)")
        group.add_argument(
            "-m", "--master-address", default="", metavar="HOST:PORT",
            help="run as SLAVE of this master")
        group.add_argument(
            "-d", "--device", default=None,
            help="backend: auto | tpu | cpu | numpy; default: "
                 "root.common.engine.backend (ref backends.py:352)")
        group.add_argument(
            "-n", "--nodes", nargs="*", default=[],
            metavar="HOST[:PORT][xN]",
            help="ssh-spawn N slaves per host from the master "
                 "(ref launcher.py:617-660)")
        group.add_argument(
            "--yarn", default=None, metavar="RM_URL",
            help="discover slave nodes from a YARN ResourceManager "
                 "(ref launcher.py:887)")
        group.add_argument(
            "--slave-launch-transform",
            default="ssh -o BatchMode=yes -p %(port)d %(host)s",
            help="remote-launch prefix template")
        group.add_argument(
            "--checkpoint-dir", default=None, metavar="DIR",
            help="master mode: checkpoint the train state here "
                 "(async, every --checkpoint-every jobs and at epoch "
                 "boundaries; default root.common.engine.checkpoint)")
        group.add_argument(
            "--checkpoint-every", type=int, default=None,
            metavar="K", help="checkpoint every K applied updates")
        group.add_argument(
            "--resume", action="store_true",
            help="master mode: restore the latest checkpoint from "
                 "--checkpoint-dir before serving jobs (crash "
                 "recovery; see docs/robustness.md)")
        group.add_argument(
            "--analyze", action="store_true",
            help="dry run: construct the workflow (no initialize, no "
                 "device buffers), run the static pre-flight (graph "
                 "doctor + JAX hazard analyzer) and exit non-zero on "
                 "errors (see docs/analyze.md)")
        group.add_argument(
            "-p", "--graphics", action="store_true",
            help="launch the detached plotting client")
        group.add_argument(
            "--web-status", action="store_true",
            help="start the web status server (ref web_status.py:113)")

    # -- mode flags (consulted by Workflow/units) ---------------------------
    @property
    def is_master(self):
        return bool(self.listen)

    @property
    def is_slave(self):
        return bool(self.master_address)

    @property
    def is_standalone(self):
        return not (self.is_master or self.is_slave)

    @property
    def mode(self):
        return ("master" if self.is_master else
                "slave" if self.is_slave else "standalone")

    # -- workflow registration (Workflow.launcher setter calls these) -------
    def add_ref(self, workflow):
        self.workflow = workflow

    def del_ref(self, workflow):
        if self.workflow is workflow:
            self.workflow = None

    # -- lifecycle ----------------------------------------------------------
    def initialize(self, **kwargs):
        """Pick the device, boot services, initialize the workflow in
        dependency order (ref ``launcher.py:431-524``).  The master holds
        canonical state but never runs kernels, so it gets the cheap
        numpy device (ref: master never calls ``run()``,
        ``workflow.py:350-354``)."""
        if self.workflow is None:
            raise RuntimeError("no workflow attached to this launcher")
        # arm/disarm fault injection from root.common.chaos.* — the
        # launcher is the knob-driven entry; tests and the chaos smoke
        # arm the controller programmatically instead
        from veles_tpu import chaos
        chaos.configure()
        # arm the observability plane's knobs the same way (currently
        # the root.common.obs.blackbox_dir flight recorder)
        from veles_tpu import obs
        obs.configure()
        from veles_tpu.backends import make_device
        spec = "numpy" if self.is_master else self.device_spec
        self.device = kwargs.pop("device", None) or make_device(spec)
        self.info("%s mode; device=%s", self.mode, self.device)
        if self.graphics_enabled and not self.is_master:
            from veles_tpu.config import root
            from veles_tpu.graphics_server import GraphicsServer
            # root.common.graphics.port pins the endpoint across runs
            # (viewers keep their subscription); .multicast adds the
            # reference's lab-wide epgm broadcast
            self._graphics = GraphicsServer.launch(
                port=int(root.common.graphics.get("port", 0) or 0))
        if self.web_status_enabled:
            from veles_tpu.web_status import WebStatus
            self._web_status = WebStatus(
                host=root.common.web.host, port=root.common.web.port)
            self._web_status.start()
        self.workflow.initialize(device=self.device, **kwargs)
        return self

    def run(self):
        """Run to completion in the selected mode and return the
        workflow (ref ``launcher.py:550-616``)."""
        self._start_time = time.time()
        try:
            if self.is_master:
                self._run_master()
            elif self.is_slave:
                self._run_slave()
            else:
                self.workflow.run()
        finally:
            self.stopped = True
            self._teardown()
        return self.workflow

    def _run_master(self):
        from veles_tpu.parallel.jobs import JobServer
        host, port = _split_endpoint(self.listen)
        self._server = JobServer(self.workflow, port=port, host=host,
                                 checkpoint_dir=self.checkpoint_dir,
                                 checkpoint_every=self.checkpoint_every)
        if self.resume:
            self._server.resume_from_checkpoint()
        finished = threading.Event()
        self._server.on_finished = finished.set
        self._server.start()
        self.info("master serving jobs on %s", self._server.endpoint)
        try:
            if self.nodes:
                self._spawn_remote_slaves()
            while not finished.is_set() and not self.stopped:
                finished.wait(0.2)
                if finished.is_set() or self.stopped:
                    break
                if (self._spawned_
                        and all(p.poll() is not None
                                for p in self._spawned_)
                        and not self._server.slaves):
                    # bootstrap-only cluster: every slave we spawned is
                    # dead and nothing is connected — nobody is coming;
                    # fail loudly instead of waiting forever
                    raise RuntimeError(
                        "all %d bootstrapped slaves exited (rc=%r) "
                        "with none connected; run cannot finish" % (
                            len(self._spawned_),
                            [p.returncode for p in self._spawned_]))
        finally:
            self._server.print_stats()
            # reap BEFORE the server goes away: a bootstrapped slave
            # that is still starting up must hear "no more jobs" and
            # exit 0, not time out against a vanished master
            failed = self._reap_spawned()
            self._server.stop()
        if failed:
            raise RuntimeError(
                "%d bootstrapped slave(s) exited non-zero (rc=%r)"
                % (len(failed), failed))

    # -- remote bootstrap (ref launch_remote_progs launcher.py:617-660) -----
    def _master_endpoint(self):
        """The endpoint remotes dial: the server's bound port on this
        host's fqdn (the bind host may be 0.0.0.0/127.0.0.1)."""
        _bhost, bport = _split_endpoint(self._server.endpoint
                                        if self._server else self.listen)
        return "%s:%d" % (self.advertise_host or socket.getfqdn(), bport)

    def _build_slave_command(self):
        if self.slave_command:
            return self.slave_command % {
                "master": self._master_endpoint()}
        # default: re-run this process's command line as a slave.
        # `python -m veles_tpu` runs show argv[0] as .../__main__.py —
        # re-running that path directly would put the package dir (not
        # the repo root) on sys.path and break `import veles_tpu` on
        # non-installed checkouts; rebuild the -m form instead.
        argv0 = list(sys.argv[:1])
        if argv0 and os.path.basename(argv0[0]) == "__main__.py" and \
                os.path.basename(os.path.dirname(
                    os.path.abspath(argv0[0]))) == "veles_tpu":
            argv0 = ["-m", "veles_tpu"]
        argv = [sys.executable] + argv0 + list(sys.argv[1:])
        out, skip_one, skip_multi = [], False, False
        for arg in argv:
            if skip_one:
                skip_one = False
                continue
            if skip_multi:
                # --nodes is nargs='*': swallow values until the next
                # option flag, exactly as argparse consumed them
                if not arg.startswith("-"):
                    continue
                skip_multi = False
            if arg in ("-l", "--listen", "--yarn"):
                skip_one = True
                continue
            if arg in ("-n", "--nodes"):
                skip_multi = True
                continue
            if arg.startswith(("--listen=", "--nodes=", "--yarn=")):
                continue
            out.append(arg)
        out += ["-m", self._master_endpoint()]
        return shlex.join(out)

    def _spawn_remote_slaves(self):
        from veles_tpu.backends import assert_backend_untouched
        assert_backend_untouched("master's slave bootstrap")
        cmd = self._build_slave_command()
        for nhost, nport, count in parse_nodes(self.nodes):
            prefix = shlex.split(self.slave_launch_transform
                                 % {"host": nhost, "port": nport})
            for i in range(count):
                self.info("spawning slave %d/%d on %s: %s",
                          i + 1, count, nhost, cmd)
                # the command rides as ONE argument, exactly as ssh
                # would pass it to the remote shell
                self._spawned_.append(subprocess.Popen(prefix + [cmd]))

    def _reap_spawned(self, timeout=30.0):
        """Wait for (then terminate, then kill) every spawned slave;
        returns the exit codes of those that FAILED on their own — a
        slave this cleanup had to terminate after the run is not a
        failed child."""
        deadline = time.time() + timeout
        spawned, self._spawned_ = self._spawned_, []
        for proc in spawned:
            try:
                proc.wait(max(0.1, deadline - time.time()))
                continue
            except subprocess.TimeoutExpired:
                self.warning("spawned slave pid %d did not exit; "
                             "terminating", proc.pid)
                proc.terminate()
            try:
                proc.wait(2.0)
            except subprocess.TimeoutExpired:
                self.warning("spawned slave pid %d ignored SIGTERM; "
                             "killing", proc.pid)
                proc.kill()
                proc.wait(2.0)
        return [proc.returncode for proc in spawned
                if proc.returncode and proc.returncode > 0]

    def _run_slave(self):
        from veles_tpu.parallel.jobs import JobClient
        host, port = _split_endpoint(self.master_address)
        self._client = JobClient(
            self.workflow, "tcp://%s:%d" % (host, port))
        self._client.handshake()
        self._client.run()
        self._client.close()

    def stop(self):
        self.stopped = True
        if self.workflow is not None:
            self.workflow.stop()
        if self._server is not None:
            self._server.stop()

    def on_workflow_finished(self):
        self.stopped = True

    def _teardown(self):
        if self._web_status is not None:
            self._web_status.stop()
        if self._graphics is not None:
            self._graphics.shutdown()
        if self.workflow is not None and self._start_time is not None:
            self.info("workflow finished in %.1f s (%s mode)",
                      time.time() - self._start_time, self.mode)
            stats = self.workflow.get_unit_run_time_stats()
            if stats:
                self.workflow.print_stats()

    # -- status payload (ref launcher.py:852-886) ---------------------------
    def status(self):
        wf = self.workflow
        return {
            "mode": self.mode,
            "stopped": self.stopped,
            "device": str(self.device),
            "workflow": type(wf).__name__ if wf is not None else None,
            "slaves": ([s.__dict__.copy()
                        for s in self._server.slaves.values()]
                       if self._server is not None else []),
            "uptime": (time.time() - self._start_time
                       if self._start_time else 0.0),
            "pid": os.getpid(),
        }

    def status_json(self):
        return json.dumps(self.status(), default=str)


def _split_endpoint(spec):
    """'host:port' | ':port' | 'port' → (host, int(port))."""
    host, sep, port = str(spec).rpartition(":")
    if not sep:
        host = ""
    return host or "127.0.0.1", int(port)
