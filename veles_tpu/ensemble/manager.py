"""Ensemble train/test managers.

Contract with workflows (mirrors the reference's config seam): each
member run receives config overrides
``root.common.ensemble.{index,size,train_ratio}`` plus a distinct PRNG
seed, and reports metrics through ``--result-file``.  Loaders honor
``root.common.ensemble.train_ratio`` automatically
(:mod:`veles_tpu.loader.base`), so any StandardWorkflow model is
ensemble-able unmodified.

Like the genetics optimizer, members can also be farmed to slaves as
jobs through :class:`veles_tpu.parallel.jobs.JobServer` — each job is a
whole training run (task parallelism, SURVEY §2.4).
"""

import json
import os
import subprocess
import sys
import tempfile

from veles_tpu.logger import Logger


class _EnsembleBase(Logger):
    def __init__(self, workflow_spec=None, config_file=None,
                 result_file=None, evaluate=None, extra_args=()):
        super(_EnsembleBase, self).__init__()
        self.workflow_spec = workflow_spec
        self.config_file = config_file
        self.result_file = result_file
        self.evaluate = evaluate   # in-process hook (tests/embedding)
        #: CLI args every member inherits (-d, --fused, overrides)
        self.extra_args = tuple(extra_args)
        #: member runs that exited non-zero
        self.child_failures = 0

    def _spawn(self, overrides, extra_args=()):
        """One child training/testing run; returns its results dict
        (ref ``base_workflow.py:135-150``)."""
        fd, result_path = tempfile.mkstemp(suffix=".json",
                                           prefix="veles_ens_")
        os.close(fd)
        try:
            cmd = [sys.executable, "-m", "veles_tpu", self.workflow_spec]
            if self.config_file:
                cmd.append(self.config_file)
            cmd.append("--result-file=%s" % result_path)
            cmd += list(self.extra_args)
            cmd += list(extra_args)
            cmd += ["%s=%s" % (path, json.dumps(value))
                    for path, value in overrides.items()]
            from veles_tpu.backends import assert_backend_untouched
            assert_backend_untouched("ensemble member run")
            self.info("spawning: %s", " ".join(cmd))
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                # the member is lost AND the run's exit code says so
                # at the end (Main._run_ensemble)
                self.child_failures += 1
                self.error("member failed (rc=%d): %s",
                           proc.returncode, proc.stderr[-2000:])
                return None
            with open(result_path, "r") as fin:
                return json.load(fin)
        finally:
            os.unlink(result_path)

    def _write(self, payload):
        if self.result_file:
            with open(self.result_file, "w") as fout:
                json.dump(payload, fout, indent=2)


class EnsembleModelManager(_EnsembleBase):
    """Trains ``size`` members, each on a ``train_ratio`` random subset
    (ref ``model_workflow.py:50``)."""

    def __init__(self, size=5, train_ratio=1.0, seed_base=1234,
                 **kwargs):
        super(EnsembleModelManager, self).__init__(**kwargs)
        if size < 1:
            raise ValueError("ensemble size must be >= 1")
        if not 0.0 < train_ratio <= 1.0:
            raise ValueError("train_ratio must be in (0, 1]")
        self.size = size
        self.train_ratio = train_ratio
        self.seed_base = seed_base
        self.results = []
        self._pending = list(range(size))   # job-layer work set
        self._inflight = {}

    def overrides_for(self, index):
        return {
            "common.ensemble.index": index,
            "common.ensemble.size": self.size,
            "common.ensemble.train_ratio": self.train_ratio,
            "common.engine.seed": self.seed_base + index,
        }

    def run(self):
        self.results = []
        for index in range(self.size):
            overrides = self.overrides_for(index)
            if self.evaluate is not None:
                member = self.evaluate(overrides)
            else:
                member = self._spawn(overrides)
            self.results.append({"index": index,
                                 "overrides": overrides,
                                 "results": member})
        trained = [r for r in self.results if r["results"] is not None]
        self.info("ensemble: %d/%d members trained", len(trained),
                  self.size)
        payload = {"size": self.size, "train_ratio": self.train_ratio,
                   "models": self.results}
        self._write(payload)
        return payload

    # -- job-layer mode (one member per slave job) -------------------------
    def checksum(self):
        return "ensemble-train:%d:%s" % (self.size, self.workflow_spec)

    def generate_data_for_slave(self, slave):
        if not self._pending:
            if self._inflight:
                from veles_tpu.workflow import NoJobYet
                raise NoJobYet()   # a member may be requeued on drop
            return None
        index = self._pending.pop(0)
        self._inflight[slave.id] = index
        return {"index": index, "overrides": self.overrides_for(index)}

    def apply_data_from_slave(self, data, slave):
        self._inflight.pop(slave.id, None)
        self.results.append(data)

    def drop_slave(self, slave):
        index = self._inflight.pop(slave.id, None)
        if index is not None:   # requeue (ref base_workflow.py:124-128)
            self._pending.insert(0, index)


class EnsembleTestManager(_EnsembleBase):
    """Runs every trained member on the test set and aggregates
    (ref ``test_workflow.py:50``)."""

    def __init__(self, input_file=None, input_data=None, **kwargs):
        super(EnsembleTestManager, self).__init__(**kwargs)
        if input_data is not None:
            self.listing = input_data
        elif input_file:
            with open(input_file, "r") as fin:
                self.listing = json.load(fin)
        else:
            raise ValueError("input_file or input_data required")

    def run(self):
        outputs = []
        for member in self.listing["models"]:
            overrides = dict(member["overrides"])
            if self.evaluate is not None:
                result = self.evaluate(overrides)
            else:
                # resume the member's trained snapshot (recorded by the
                # Snapshotter's result metric); testing a fresh workflow
                # would score random weights
                snapshot = (member.get("results") or {}).get("snapshot")
                extra = ("--test",)
                if snapshot:
                    extra += ("-w", snapshot)
                else:
                    self.warning(
                        "member %s has no snapshot in its results — "
                        "testing an untrained model (add a Snapshotter "
                        "to the training workflow)", member["index"])
                result = self._spawn(overrides, extra_args=extra)
            outputs.append({"index": member["index"], "results": result})
        payload = {"size": self.listing["size"], "tests": outputs,
                   "aggregate": self.aggregate(outputs)}
        self._write(payload)
        return payload

    @staticmethod
    def aggregate(outputs):
        """Averages every shared numeric metric across members."""
        acc = {}
        counts = {}
        for entry in outputs:
            results = entry.get("results") or {}
            for key, value in results.items():
                try:
                    v = float(value)
                except (TypeError, ValueError):
                    continue
                acc[key] = acc.get(key, 0.0) + v
                counts[key] = counts.get(key, 0) + 1
        return {key: acc[key] / counts[key] for key in acc}
