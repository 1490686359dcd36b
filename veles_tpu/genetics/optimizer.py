"""GeneticsOptimizer: drives subprocess evaluations of chromosomes.

Reference ``genetics/optimization_workflow.py:70-283``: each chromosome's
fitness comes from a FULL training run in a subprocess (pickled config +
result-file read-back). Kept here: the subprocess-per-evaluation contract
(CLI override strings instead of pickled configs — same layering),
generation loop with no-improvement early stop. Local evaluations run
ONE child at a time — each needs the chip (``core/children.py``);
parallel evaluation is the fleet's job (fleet distribution hands the
same subprocess commands to slaves, each with its own device).

Fitness: the result JSON's ``EvaluationFitness`` if present, else
``-best_validation_errors`` (maximized either way).
"""

import json
import os
import sys
import tempfile

from veles_tpu.core.children import run_device_child
from veles_tpu.core.logger import Logger
from veles_tpu.genetics.core import Population


class GeneticsOptimizer(Logger):
    """Population-parallel hyperparameter search (reference
    ``GeneticsOptimizer``)."""

    def __init__(self, workflow_file, config_file=None, genes=(),
                 population_size=12, generations=5,
                 no_improvement_limit=3, extra_args=(), seed=None,
                 fleet=None, representation="numeric"):
        super().__init__(logger_name="GeneticsOptimizer")
        self.workflow_file = workflow_file
        self.config_file = config_file
        self.population = Population(list(genes), size=population_size,
                                     representation=representation)
        self.generations = generations
        self.no_improvement_limit = no_improvement_limit
        self.extra_args = list(extra_args)
        self.seed = seed
        self.best_fitness_history = []
        # fleet mode (reference optimization_workflow.py:179-279):
        # chromosome evaluations are jobs served to fleet slaves
        self._farm = self._farm_server = None
        if fleet is not None:
            from veles_tpu.fleet.farm import TaskFarmMaster
            from veles_tpu.fleet.server import Server
            self._farm = TaskFarmMaster("genetics")
            self._farm_server = Server(fleet, self._farm).start()
            self._farm.on_new_tasks = self._farm_server.kick

    # -- one evaluation --------------------------------------------------------
    def _command(self, chromosome, result_file=None):
        cmd = [sys.executable, "-m", "veles_tpu", self.workflow_file,
               self.config_file or "-"]
        cmd += chromosome.config_overrides()
        if result_file is not None:
            cmd += ["--result-file", result_file]
        if self.seed is not None:
            cmd += ["--seed", str(self.seed)]
        cmd += self.extra_args
        return cmd

    @staticmethod
    def fitness_from_results(results):
        if "EvaluationFitness" in results:
            return float(results["EvaluationFitness"])
        if results.get("best_validation_errors") is not None:
            return -float(results["best_validation_errors"])
        raise ValueError("result file carries neither EvaluationFitness "
                         "nor best_validation_errors")

    def _evaluate_fleet(self):
        """Submit the generation's evaluations to the task farm; fleet
        slaves run them (reference slaves evaluated chromosomes the same
        way, optimization_workflow.py:216-279)."""
        pending = [m for m in self.population.members
                   if m.fitness is None]
        tags = {}
        for i, member in enumerate(pending):
            task_id = "gen%d-%d" % (self.population.generation, i)
            tags[task_id] = member
            self._farm.submit(task_id, self._command(member))
        results = self._farm.wait_batch()
        self._farm.take_results()
        for task_id, member in tags.items():
            update = results.get(task_id, {})
            if update.get("rc") or "results" not in update:
                self.warning("fleet evaluation failed: %s", update)
                member.fitness = -1e30
            else:
                member.fitness = self.fitness_from_results(
                    update["results"])
                self.info("evaluated %s -> %.4f", member.values,
                          member.fitness)

    def evaluate_generation(self):
        """Run all unevaluated members, one child at a time."""
        if self._farm is not None:
            return self._evaluate_fleet()
        for index, member in enumerate(self.population.members):
            if member.fitness is not None:
                continue
            fd, result_file = tempfile.mkstemp(suffix=".json",
                                               prefix="genetics_")
            os.close(fd)
            returncode, stderr_path = run_device_child(
                self._command(member, result_file),
                "genetics-g%d-%d" % (self.population.generation, index))
            if returncode != 0:
                self.warning("evaluation failed (rc=%d): %s; its stderr "
                             "is in %s", returncode, member, stderr_path)
                member.fitness = -1e30
            else:
                with open(result_file) as fin:
                    member.fitness = self.fitness_from_results(
                        json.load(fin))
                self.info("evaluated %s -> %.4f", member.values,
                          member.fitness)
            os.unlink(result_file)

    # -- the optimization loop -------------------------------------------------
    def run(self):
        best_ever = None
        stale = 0
        try:
            for generation in range(self.generations):
                self.evaluate_generation()
                best = self.population.best
                self.best_fitness_history.append(best.fitness)
                self.info("generation %d best: %s fitness=%.4f",
                          generation, best.values, best.fitness)
                if best_ever is None or best.fitness > best_ever.fitness:
                    best_ever = best
                    stale = 0
                else:
                    stale += 1
                    if stale >= self.no_improvement_limit:
                        self.info("stopping: no improvement for %d "
                                  "generations", stale)
                        break
                if generation + 1 < self.generations:
                    self.population.evolve()
        finally:
            if self._farm is not None:
                self._farm.close()
                self._farm_server.kick()  # let idle slaves drain + exit
                self._farm_server.drain()  # 'no more jobs' must flush
                self._farm_server.stop()
        return best_ever
