"""Ensemble train/test runners (reference ``ensemble/model_workflow.py`` /
``test_workflow.py``): subprocess per instance, metrics+snapshot paths
gathered into an ensemble JSON."""

import json
import os
import sys
import tempfile

from veles_tpu.core import prng
from veles_tpu.core.children import run_device_child
from veles_tpu.core.logger import Logger


class EnsembleTrainer(Logger):
    """Train N instances (reference ``--ensemble-train N:r``), one
    child at a time — each needs the chip (``core/children.py``)."""

    def __init__(self, workflow_file, config_file=None, instances=4,
                 train_ratio=0.8, output="ensemble.json", extra_args=()):
        super().__init__(logger_name="EnsembleTrainer")
        self.workflow_file = workflow_file
        self.config_file = config_file
        self.instances = instances
        self.train_ratio = train_ratio
        self.output = output
        self.extra_args = list(extra_args)

    def run(self):
        rng = prng.get("ensemble")
        results = []
        for index in range(self.instances):
            fd, result_file = tempfile.mkstemp(suffix=".json",
                                               prefix="ensemble_")
            os.close(fd)
            seed = int(rng.randint(1, 2 ** 31))
            cmd = [sys.executable, "-m", "veles_tpu", self.workflow_file,
                   self.config_file or "-",
                   "--result-file", result_file,
                   "--seed", str(seed),
                   "--train-ratio", str(self.train_ratio)]
            cmd += self.extra_args
            self.info("training instance %d (seed=%d)", index, seed)
            returncode, stderr_path = run_device_child(
                cmd, "ensemble-%d" % index)
            entry = {"index": index, "seed": seed,
                     "returncode": returncode}
            if returncode == 0:
                with open(result_file) as fin:
                    entry["results"] = json.load(fin)
            else:
                self.warning("instance %d failed (rc=%d); its stderr is "
                             "in %s", index, returncode, stderr_path)
            os.unlink(result_file)
            results.append(entry)

        payload = {"workflow": self.workflow_file,
                   "train_ratio": self.train_ratio,
                   "instances": results}
        with open(self.output, "w") as fout:
            json.dump(payload, fout, indent=1, default=str)
        self.info("ensemble summary written to %s", self.output)
        return payload


class EnsembleTester(Logger):
    """Re-evaluate stored ensemble snapshots (reference
    ``--ensemble-test``)."""

    def __init__(self, ensemble_file, workflow_file=None, config_file=None,
                 extra_args=()):
        super().__init__(logger_name="EnsembleTester")
        self.ensemble_file = ensemble_file
        self.workflow_file = workflow_file
        self.config_file = config_file
        self.extra_args = list(extra_args)

    def run(self):
        with open(self.ensemble_file) as fin:
            ensemble = json.load(fin)
        workflow_file = self.workflow_file or ensemble["workflow"]
        outputs = []
        for entry in ensemble["instances"]:
            snapshot = (entry.get("results") or {}).get("Snapshot")
            if not snapshot or not os.path.exists(str(snapshot)):
                self.warning("instance %d has no snapshot; skipping",
                             entry["index"])
                continue
            fd, result_file = tempfile.mkstemp(suffix=".json",
                                               prefix="enstest_")
            os.close(fd)
            cmd = [sys.executable, "-m", "veles_tpu", workflow_file,
                   self.config_file or "-", "-w", str(snapshot),
                   "--result-file", result_file] + self.extra_args
            returncode, stderr_path = run_device_child(
                cmd, "enstest-%d" % entry["index"])
            entry_out = {"index": entry["index"],
                         "returncode": returncode}
            if returncode == 0:
                with open(result_file) as fin:
                    entry_out["results"] = json.load(fin)
            else:
                self.warning("instance %d test failed (rc=%d); its "
                             "stderr is in %s", entry["index"],
                             returncode, stderr_path)
            os.unlink(result_file)
            outputs.append(entry_out)
        return {"ensemble": self.ensemble_file, "tests": outputs}
