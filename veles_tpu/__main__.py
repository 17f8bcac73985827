"""``python -m veles_tpu <workflow> [<config>] [key=value ...]`` — the
framework entry point (ref ``veles/__main__.py:136-859``).

Call sequence mirrors SURVEY §3.1: parse args → seed named PRNGs →
load workflow module (file, dotted module, or snapshot) → exec config
file against ``root.*`` → apply ``key=value`` overrides → construct
Launcher + workflow → initialize → run.

Workflow module conventions supported:

- ``run(load, main)`` — the reference convention
  (``__main__.py:716-799``): the module calls ``load(WorkflowClass,
  **kwargs)`` to construct and ``main(**kwargs)`` to initialize+run.
- ``create_workflow(device=..., **kwargs) -> workflow`` — the native
  convention used by :mod:`veles_tpu.samples`.
"""

import importlib
import importlib.util
import logging
import os
import runpy
import sys

from veles_tpu import prng
from veles_tpu.cmdline import make_parser
from veles_tpu.config import (
    apply_site_config, root, update_from_arguments)
from veles_tpu.launcher import Launcher
from veles_tpu.logger import Logger


_peak_printer_registered = False


class Main(Logger):
    """One CLI invocation (ref ``Main`` ``__main__.py:136``)."""

    def __init__(self, argv=None):
        super(Main, self).__init__()
        self.argv = list(sys.argv[1:] if argv is None else argv)
        self.args = None
        self.launcher = None
        self.workflow = None
        self.module = None

    # -- setup --------------------------------------------------------------
    def _parse(self):
        parser = make_parser()
        args, extra = parser.parse_known_args(self.argv)
        # argparse puts stray key=value positionals into `extra` or
        # `config`; sort them out (ref __main__.py:474-482).
        overrides = list(args.overrides)
        for item in extra:
            if "=" in item and not item.startswith("-"):
                overrides.append(item)
            else:
                parser.error("unrecognized argument: %s" % item)
        if args.config and "=" in args.config and \
                not os.path.exists(args.config):
            overrides.insert(0, args.config)
            args.config = None
        args.overrides = overrides
        self.args = args
        return args

    def _setup_logging(self):
        level = getattr(logging, self.args.verbosity.upper())
        logging.basicConfig(level=level)
        logging.getLogger().setLevel(level)
        for name in filter(None, self.args.debug.split(",")):
            logging.getLogger(name).setLevel(logging.DEBUG)
        if getattr(self.args, "log_db", ""):
            from veles_tpu.logger import duplicate_logs_to_db
            self.log_db_handler = duplicate_logs_to_db(self.args.log_db)

    def _seed_random(self):
        """Seed every named stream (ref ``__main__.py:483-538``)."""
        spec = self.args.random_seed
        if spec is None:
            prng.seed_all(1234)
            return
        try:
            prng.seed_all(int(spec))
            return
        except ValueError:
            pass
        # path[:dtype[:count]] — read seed bytes from a file
        # (ref random_generator.py:106: /dev/urandom support).
        parts = spec.split(":")
        path, dtype, count = (
            parts[0],
            parts[1] if len(parts) > 1 else "uint32",
            int(parts[2]) if len(parts) > 2 else 16)
        import numpy
        with open(path, "rb") as fin:
            raw = numpy.frombuffer(
                fin.read(numpy.dtype(dtype).itemsize * count),
                dtype=dtype, count=count)
        prng.seed_all(int(numpy.sum(raw.astype(numpy.uint64)) %
                          (2 ** 31)))

    def _apply_config(self):
        """Exec the config file then CLI overrides against ``root``
        (ref ``__main__.py:426-482``)."""
        apply_site_config()
        if self.args.config:
            with open(self.args.config, "r") as fin:
                code = compile(fin.read(), self.args.config, "exec")
            exec(code, {"root": root})
        if self.args.overrides:
            update_from_arguments(self.args.overrides)

    # -- model loading ------------------------------------------------------
    def _load_module(self, spec):
        """Import a workflow module from a file path or dotted name
        (ref ``_load_model`` ``__main__.py:396-425``)."""
        if os.path.exists(spec):
            name = os.path.splitext(os.path.basename(spec))[0]
            modspec = importlib.util.spec_from_file_location(name, spec)
            module = importlib.util.module_from_spec(modspec)
            sys.modules[name] = module
            modspec.loader.exec_module(module)
            return module
        return importlib.import_module(spec)

    def _construct(self):
        """Build launcher + workflow from the module or a snapshot."""
        launcher_kwargs = {
            "listen": self.args.listen,
            "master_address": self.args.master_address,
            "device": self.args.device,
            "testing": self.args.test,
            "graphics": self.args.graphics,
            "web_status": self.args.web_status,
            "checkpoint_dir": getattr(self.args, "checkpoint_dir",
                                      None),
            "checkpoint_every": getattr(self.args, "checkpoint_every",
                                        None),
            "resume": getattr(self.args, "resume", False),
        }
        if self.args.snapshot:
            from veles_tpu.snapshotter import load_snapshot
            self.workflow = load_snapshot(self.args.snapshot)
            self.launcher = Launcher(self.workflow, **launcher_kwargs)
            self.info("resumed workflow from %s", self.args.snapshot)
            return
        if not self.args.workflow:
            raise SystemExit("no workflow given (and no --snapshot)")
        self.module = self._load_module(self.args.workflow)
        if hasattr(self.module, "run"):
            self._construct_via_run(launcher_kwargs)
        elif hasattr(self.module, "create_workflow"):
            self.launcher = Launcher(**launcher_kwargs)
            extra = {"fused": True} if self.args.fused else {}
            self.workflow = self.module.create_workflow(
                launcher=self.launcher, **extra)
            if self.workflow.launcher is not self.launcher:
                self.workflow.launcher = self.launcher
        else:
            raise SystemExit(
                "workflow module %r defines neither run(load, main) nor "
                "create_workflow(...)" % self.args.workflow)

    def _construct_via_run(self, launcher_kwargs):
        """The reference convention: module.run(load, main)
        (``__main__.py:591-715``)."""
        main_self = self

        def load(workflow_class, **kwargs):
            main_self.launcher = Launcher(**launcher_kwargs)
            if main_self.args.fused:
                # explicit opt-in only: non-StandardWorkflow classes
                # need not accept the kwarg
                kwargs.setdefault("fused", True)
            main_self.workflow = workflow_class(
                main_self.launcher, **kwargs)
            return main_self.workflow, None

        def main(**kwargs):
            if main_self.args.analyze:
                return    # pre-flight wants the constructed graph only
            main_self.launcher.initialize(**kwargs)
            if not main_self.args.dry_run:
                main_self.launcher.run()

        self.module.run(load, main)

    @staticmethod
    def print_peak_memory():
        """Peak RSS line, registered atexit (ref startup step 7:
        'Peak memory usage printer is registered on program exit')."""
        import resource
        peak_kib = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        print("Peak resident memory: %.1f MiB" % (peak_kib / 1024.0),
              file=sys.stderr)

    @staticmethod
    def _version_line():
        import jax

        import veles_tpu
        return "veles_tpu %s (jax %s, %s)" % (
            veles_tpu.__version__, jax.__version__,
            "python %d.%d" % sys.version_info[:2])

    def _dump_unit_attributes(self, mode):
        """Print every unit's __dict__ after initialization
        (ref ``--dump-unit-attributes``); ``pretty`` elides arrays."""
        import numpy

        for unit in self.workflow:
            attrs = {}
            for key, value in sorted(vars(unit).items()):
                if key.endswith("_"):
                    continue
                if mode == "pretty" and isinstance(
                        value, numpy.ndarray) and value.size > 16:
                    value = "<%s array %s>" % (value.dtype,
                                               "x".join(map(
                                                   str, value.shape)))
                attrs[key] = value
            print("%s: %s" % (unit.name or type(unit).__name__, attrs))

    def _daemonize(self):
        """Double-fork into the background (ref ``-b``)."""
        if os.fork() > 0:
            os._exit(0)
        os.setsid()
        if os.fork() > 0:
            os._exit(0)
        devnull = os.open(os.devnull, os.O_RDWR)
        for fd in (0, 1):
            os.dup2(devnull, fd)
        # keep stderr: logging still reaches the launch terminal's
        # redirect target if any; daemons should pair this with
        # --log-db for durable records
        self.info("daemonized (pid %d)", os.getpid())

    # -- run ----------------------------------------------------------------
    def run(self):
        args = self._parse()
        if args.version:
            print(self._version_line())
            return 0
        if args.html_help:
            import tempfile

            from veles_tpu.scripts.generate_frontend import generate
            fd, path = tempfile.mkstemp(suffix=".html",
                                        prefix="veles_tpu_help_")
            with os.fdopen(fd, "w") as fout:
                fout.write(generate())
            print("argument reference written to %s" % path)
            return 0
        if not args.no_logo:
            print(self._version_line(), file=sys.stderr)
        global _peak_printer_registered
        if not _peak_printer_registered:
            _peak_printer_registered = True
            import atexit
            atexit.register(self.print_peak_memory)
        if args.background:
            self._daemonize()
        if args.visualize and not args.dry_run:
            # "initialize but do not run" must hold for BOTH workflow
            # conventions: run(load, main) modules consult dry_run
            # inside main(), so set it rather than special-casing
            args.dry_run = "init"
        if args.device in ("numpy", "cpu"):
            # a CPU-only run must not touch (or wait for) the TPU: pin
            # the platform before any backend is initialized
            import jax
            if jax.config.jax_platforms != "cpu":
                jax.config.update("jax_platforms", "cpu")
        self._setup_logging()
        if args.manhole:
            from veles_tpu import manhole
            manhole.install(namespace={"main": self})
            self.info("manhole armed: SIGUSR1 dumps stacks, SIGUSR2 "
                      "serves a REPL (pid %d)", os.getpid())
        if args.debug_nans:
            import jax
            jax.config.update("jax_debug_nans", True)
            self.info("NaN checking enabled (jax_debug_nans)")
        if args.debug_pickle:
            from veles_tpu import snapshotter
            snapshotter.DEBUG_PICKLE = True
            self.info("pickle diagnostics enabled")
        self._seed_random()
        self._apply_config()
        # config may carry a seed (e.g. ensemble members get distinct
        # streams via common.engine.seed); CLI --random-seed wins
        cfg_seed = root.common.engine.get("seed", None)
        if cfg_seed is not None and args.random_seed is None:
            prng.seed_all(int(cfg_seed))
        if args.dump_config:
            root.print_()
        if args.dry_run == "load":
            self.info("dry run (load) complete")
            return 0
        if args.frontend:
            from veles_tpu.scripts.generate_frontend import generate
            with open(args.frontend, "w") as fout:
                fout.write(generate())
            self.info("wrote frontend form to %s", args.frontend)
            return 0
        if args.optimize:
            return self._run_optimization()
        if args.ensemble_train or args.ensemble_test:
            return self._run_ensemble()
        if args.profile:
            # device-level tracing around the whole run (the per-unit
            # wall-time table remains in Workflow.print_stats)
            import jax.profiler
            jax.profiler.start_trace(args.profile)
            self.info("jax.profiler trace → %s", args.profile)
        try:
            return self._run_constructed(args)
        finally:
            if args.profile:
                import jax.profiler
                jax.profiler.stop_trace()
                self.info("profiler trace written to %s", args.profile)

    def _run_constructed(self, args):
        self._construct()
        if args.analyze:
            if self.workflow is None:
                raise SystemExit("--analyze: no workflow constructed")
            from veles_tpu.analyze import analyze_workflow
            report = analyze_workflow(self.workflow)
            print(report.render_text())
            return 1 if report.has_errors else 0
        if args.result_file:
            self.workflow.result_file = args.result_file
        if self.workflow is not None and \
                not getattr(self.workflow, "_is_initialized", False) \
                and self.launcher is not None:
            self.launcher.initialize()
        if args.workflow_graph and self.workflow is not None:
            with open(args.workflow_graph, "w") as fout:
                fout.write(self.workflow.generate_graph())
            self.info("wrote workflow graph to %s", args.workflow_graph)
        if args.dump_unit_attributes != "no" and \
                self.workflow is not None:
            self._dump_unit_attributes(args.dump_unit_attributes)
        if args.visualize and self.workflow is not None:
            # initialize-only + graph written into the snapshots dir
            # (the documented location); plotting endpoints only live
            # as long as a process, so the advice is a fixed port —
            # not a reattach promise that would dangle
            out_dir = root.common.dirs.get("snapshots", ".")
            os.makedirs(out_dir, exist_ok=True)
            path = os.path.join(out_dir, "workflow_graph.dot")
            with open(path, "w") as fout:
                fout.write(self.workflow.generate_graph())
            self.info(
                "visualize: graph at %s — not running.  For live "
                "plots, run WITHOUT --visualize and attach "
                "graphics_client to the GraphicsServer endpoint "
                "printed at startup (pin it with "
                "root.common.graphics.port)", path)
            return 0
        if args.dry_run:
            self.info("dry run (%s) complete", args.dry_run)
            return 0
        if self.module is None or not hasattr(self.module, "run"):
            # run() convention already ran inside _construct_via_run
            self.launcher.run()
        if args.result_file and self.workflow is not None:
            self.workflow.write_results(args.result_file)
        return 0

    def _run_optimization(self):
        """--optimize SIZE[:GENERATIONS] (ref ``__main__.py:334``)."""
        try:
            from veles_tpu.genetics import GeneticsOptimizer
        except ImportError:
            raise SystemExit(
                "--optimize requires veles_tpu.genetics")
        # tuneable Range/Choice markers may live at module level in the
        # workflow itself (the reference's GeneticExample pattern):
        # import it so the scan sees them — harmless when the markers
        # come from the config file instead
        try:
            self._load_module(self.args.workflow)
        except Exception:
            self.warning("could not pre-import %r for the tuneable "
                         "scan; relying on the config file",
                         self.args.workflow)
        size, _, generations = self.args.optimize.partition(":")
        optimizer = GeneticsOptimizer(
            workflow_spec=self.args.workflow,
            config_file=self.args.config,
            population_size=int(size),
            generations=int(generations) if generations else None,
            result_file=self.args.result_file or None,
            extra_args=self._child_args())
        best = optimizer.run()
        self.info("best config: %s fitness=%s", best.config_overrides,
                  best.fitness)
        return self._children_rc(optimizer)

    def _children_rc(self, spawner):
        """Exit code of a run that spawned child runs: non-zero when
        any child exited non-zero (each was logged as it failed)."""
        if spawner.child_failures:
            self.error("%d child run(s) exited non-zero",
                       spawner.child_failures)
            return 1
        return 0

    def _child_args(self):
        """CLI args every spawned child run (GA member, ensemble
        member) must inherit: the device, --fused, and the parent's
        key=value overrides — a child evaluating a config the user
        never asked for would silently skew the search."""
        extra = []
        if getattr(self.args, "device", None):
            extra += ["-d", self.args.device]
        if self.args.fused:
            extra.append("--fused")
        extra += list(self.args.overrides)
        return extra

    def _run_ensemble(self):
        try:
            from veles_tpu.ensemble import (
                EnsembleModelManager, EnsembleTestManager)
        except ImportError:
            raise SystemExit(
                "--ensemble-* requires veles_tpu.ensemble")
        if self.args.ensemble_train:
            n, _, ratio = self.args.ensemble_train.partition(":")
            manager = EnsembleModelManager(
                workflow_spec=self.args.workflow,
                config_file=self.args.config,
                size=int(n), train_ratio=float(ratio or 1.0),
                result_file=self.args.result_file or None,
                extra_args=self._child_args())
        else:
            manager = EnsembleTestManager(
                workflow_spec=self.args.workflow,
                config_file=self.args.config,
                input_file=self.args.ensemble_test,
                result_file=self.args.result_file or None,
                extra_args=self._child_args())
        manager.run()
        return self._children_rc(manager)


def __run__():
    sys.exit(Main().run())


if __name__ == "__main__":
    __run__()
