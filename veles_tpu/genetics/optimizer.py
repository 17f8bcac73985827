"""GeneticsOptimizer: hyperparameter search over config Tuneables.

Parity target: reference ``veles/genetics/optimization_workflow.py`` —
``GeneticsOptimizer`` (``:70``) / ``OptimizationWorkflow`` (``:298``):
``--optimize SIZE[:GENERATIONS]`` evolves a population of config
chromosomes, each evaluated by (a) an in-process callable, (b) a child
``veles_tpu`` process reading back ``--result-file`` JSON
(ref ``_exec`` ``:268``), or (c) slave jobs through the cross-slice job
layer (``generate_data_for_slave`` ``:186``) — the TPU build's
task-parallel mode where each job is a whole training run on a slice.
"""

import json
import os
import subprocess
import sys
import tempfile

from veles_tpu.config import root
from veles_tpu.genetics import tune
from veles_tpu.genetics.core import Population
from veles_tpu.logger import Logger


def fitness_from_results(results, fitness_key=None):
    """Extracts a maximizable fitness from a result-file dict.

    Priority: explicit key → ``fitness`` → negated first ``*err*``
    metric → first numeric value.
    """
    def numeric(v):
        try:
            f = float(v)
        except (TypeError, ValueError):
            return None
        return f

    if fitness_key is not None:
        value = numeric(results.get(fitness_key))
        if value is None:
            raise ValueError("result file lacks numeric %r" % fitness_key)
        return value
    if "fitness" in results:
        value = numeric(results["fitness"])
        if value is not None:
            return value
    for key in sorted(results):
        if "err" in key or "loss" in key:
            value = numeric(results[key])
            if value is not None:
                return -value
    for key in sorted(results):
        value = numeric(results[key])
        if value is not None:
            return value
    raise ValueError("no numeric metric in results %r" % (results,))


class GeneticsOptimizer(Logger):
    """Evolves config Tuneables to maximize a fitness.

    Modes (pick one):
      * ``evaluate=callable(overrides_dict) -> fitness`` — in-process.
      * ``workflow_spec=path`` — child ``python -m veles_tpu`` per
        chromosome, fitness from ``--result-file`` JSON.
      * attach to a :class:`veles_tpu.parallel.jobs.JobServer` — call
        :meth:`generate_data_for_slave` / :meth:`apply_data_from_slave`
        (task-parallel jobs; SURVEY §2.4 row 2).
    """

    def __init__(self, population_size=20, generations=None,
                 config=None, evaluate=None, workflow_spec=None,
                 config_file=None, result_file=None, fitness_key=None,
                 max_evaluations=None, extra_args=(),
                 **population_kwargs):
        super(GeneticsOptimizer, self).__init__()
        self.config = config if config is not None else root
        self.tuneables = tune.scan_tuneables(self.config)
        if not self.tuneables:
            raise ValueError(
                "config has no Tuneable (Range/Choice) values to optimize")
        self.evaluate = evaluate
        self.workflow_spec = workflow_spec
        self.config_file = config_file
        self.result_file = result_file
        self.fitness_key = fitness_key
        self.extra_args = tuple(extra_args)
        if generations is None and max_evaluations is None:
            # `--optimize SIZE` without :GENERATIONS must terminate
            generations = 10
        self.generations = generations
        self.max_evaluations = max_evaluations
        self.evaluations = 0
        self.population = Population(
            tune.specs_of(self.tuneables), size=population_size,
            **population_kwargs)
        # chromosome 0 starts at the defaults (the reference seeds the
        # population with the hand-written config)
        self.population.chromosomes[0].genes[:] = \
            tune.default_genome(self.tuneables)
        self._inflight = {}   # slave_id → chromosome (distributed mode)
        #: child runs that exited non-zero (standalone subprocess mode)
        self.child_failures = 0

    # -- shared -------------------------------------------------------------
    def overrides_for(self, chromo):
        return tune.decode_genome(self.tuneables, chromo.genes)

    @property
    def best(self):
        return self.population.best

    # -- standalone ---------------------------------------------------------
    def _evaluate_one(self, chromo):
        overrides = self.overrides_for(chromo)
        if self.evaluate is not None:
            fitness = float(self.evaluate(overrides))
        elif self.workflow_spec is not None:
            fitness = self._evaluate_subprocess(overrides)
        else:
            raise RuntimeError("no evaluate callable or workflow_spec")
        chromo.fitness = fitness
        self.evaluations += 1
        self.debug("evaluated %s → %.6g", overrides, fitness)

    def _evaluate_subprocess(self, overrides):
        """Child `python -m veles_tpu` run (ref ``_exec`` ``:268``)."""
        fd, result_path = tempfile.mkstemp(suffix=".json",
                                           prefix="veles_ga_")
        os.close(fd)
        try:
            cmd = [sys.executable, "-m", "veles_tpu",
                   self.workflow_spec]
            if self.config_file:
                cmd.append(self.config_file)
            cmd.append("--result-file=%s" % result_path)
            cmd += list(self.extra_args)
            cmd += ["%s=%s" % (path, json.dumps(value))
                    for path, value in overrides.items()]
            from veles_tpu.backends import assert_backend_untouched
            assert_backend_untouched("genetics child run")
            self.info("spawning: %s", " ".join(cmd))
            proc = subprocess.run(cmd, capture_output=True, text=True)
            if proc.returncode != 0:
                # the candidate fails (-inf) AND the run's exit code
                # says so at the end (Main._run_optimization)
                self.child_failures += 1
                self.error("child failed (rc=%d): %s", proc.returncode,
                           proc.stderr[-2000:])
                return float("-inf")
            with open(result_path, "r") as fin:
                results = json.load(fin)
            return fitness_from_results(results, self.fitness_key)
        finally:
            os.unlink(result_path)

    def run(self):
        """Standalone evolution loop; returns the best chromosome with
        ``.config_overrides`` attached."""
        generation = 0
        while True:
            for chromo in self.population.pending:
                self._evaluate_one(chromo)
                if self.max_evaluations is not None and \
                        self.evaluations >= self.max_evaluations:
                    break
            best = self.population.best
            generation += 1
            self.info("generation %d done: best fitness %.6g",
                      generation, best.fitness)
            if self.generations is not None and \
                    generation >= self.generations:
                break
            if self.max_evaluations is not None and \
                    self.evaluations >= self.max_evaluations:
                break
            if self.population.pending:   # stopped mid-generation
                break
            self.population.evolve()
        best = self.population.best
        best.config_overrides = self.overrides_for(best)
        if self.result_file:
            with open(self.result_file, "w") as fout:
                json.dump({"fitness": best.fitness,
                           "overrides": best.config_overrides,
                           "evaluations": self.evaluations}, fout,
                          indent=2)
        return best

    # -- distributed (job-layer) mode --------------------------------------
    def checksum(self):
        return "genetics:%d:%s" % (
            len(self.tuneables),
            ",".join(path for path, _ in self.tuneables))

    def generate_data_for_slave(self, slave):
        """One pending chromosome per job; evolves the population when a
        generation completes (ref ``optimization_workflow.py:186``)."""
        from veles_tpu.workflow import NoJobYet
        pending = [c for c in self.population.pending
                   if id(c) not in {id(v) for v in
                                    self._inflight.values()}]
        if not pending:
            if self._inflight:
                # generation boundary: results still in flight — slaves
                # must wait, not quit (protocol "wait" op)
                raise NoJobYet()
            if self.generations is None or \
                    self.population.generation + 1 < self.generations:
                self.population.evolve()
                return self.generate_data_for_slave(slave)
            return None   # generation cap reached
        chromo = pending[0]
        self._inflight[slave.id] = chromo
        return {"genes": chromo.genes.tolist(),
                "overrides": self.overrides_for(chromo)}

    def apply_data_from_slave(self, data, slave):
        chromo = self._inflight.pop(slave.id, None)
        if chromo is None:
            return
        chromo.fitness = float(data["fitness"])
        self.evaluations += 1

    def drop_slave(self, slave):
        """Requeue the dead slave's chromosome (ref ``:218-222``)."""
        self._inflight.pop(slave.id, None)
