"""Tests for the genetics gray tier, fleet task farm, and ensemble
combiner."""

import numpy
import pytest

from veles_tpu.dummy import DummyLauncher, DummyWorkflow
from veles_tpu.genetics.config import Range
from veles_tpu.genetics.core import (GrayCodec, Population, gray_decode,
                                     gray_encode)


def genes():
    return [("root.lr", Range(0.5, 0.0, 1.0)),
            ("root.units", Range(8, 2, 30))]


class TestGrayCodec:
    def test_gray_identities(self):
        for n in range(64):
            assert gray_decode(gray_encode(n)) == n
        # adjacent integers differ by exactly one bit
        for n in range(63):
            diff = gray_encode(n) ^ gray_encode(n + 1)
            assert bin(diff).count("1") == 1

    def test_roundtrip_within_accuracy(self):
        codec = GrayCodec(genes(), accuracy=1000)
        values = [0.333, 17]
        decoded = codec.decode(codec.encode(values))
        assert abs(decoded[0] - 0.333) <= 1e-3
        assert abs(decoded[1] - 17) <= 1e-3

    def test_decode_clips_to_range(self):
        codec = GrayCodec(genes(), accuracy=10)
        bits = [1] * codec.total_bits  # max codes, possibly out of range
        decoded = codec.decode(bits)
        assert 0.0 <= decoded[0] <= 1.0
        assert 2 <= decoded[1] <= 30


class TestGrayPopulation:
    def test_evolution_stays_in_range(self):
        pop = Population(genes(), size=8, representation="gray",
                         crossover="two_point")
        assert pop.mutation_type == "binary_point"
        for _ in range(3):
            for m in pop.members:
                # fitness: prefer lr near 0.7
                m.fitness = -abs(m.values[0] - 0.7)
            pop.evolve()
            for m in pop.members:
                assert 0.0 <= m.values[0] <= 1.0
                assert 2 <= m.values[1] <= 30

    def test_gray_with_arithmetic_crossover_falls_back_to_numeric(self):
        # value-space crossovers stay usable under the gray representation
        pop = Population(genes(), size=4, representation="gray",
                         crossover="arithmetic")
        a, b = pop.members[:2]
        child = pop.cross(a, b)
        for (lo_hi, v) in zip(((0.0, 1.0), (2, 30)), child.values):
            assert lo_hi[0] <= v <= lo_hi[1]


class TestTaskFarm:
    def test_loopback_over_fleet_protocol(self, tmp_path):
        """Submit shell tasks through the REAL fleet server/client pair
        and collect results (reference optimization_workflow.py:179-279
        distribution semantics)."""
        import sys
        from veles_tpu.fleet.farm import (TaskFarmMaster, TaskFarmSlave,
                                          farm_worker)
        from veles_tpu.fleet.server import Server
        import threading

        farm = TaskFarmMaster("test")
        server = Server("127.0.0.1:0", farm).start()
        farm.on_new_tasks = server.kick
        worker = threading.Thread(
            target=farm_worker,
            args=("127.0.0.1:%d" % server.port, "test"), daemon=True)
        worker.start()
        # each task: python writes {"value": N} into its --result-file
        code = ("import json,sys;"
                "argv=sys.argv;"
                "path=argv[argv.index('--result-file')+1];"
                "json.dump({'value': int(argv[1])}, open(path,'w'))")
        for i in range(3):
            farm.submit("t%d" % i, [sys.executable, "-c", code, str(i)])
        results = farm.wait_batch(timeout=60)
        assert {k: v["results"]["value"] for k, v in results.items()} == \
            {"t0": 0, "t1": 1, "t2": 2}
        # second batch after a quiet period (the between-generations case)
        farm.take_results()
        farm.submit("t3", [sys.executable, "-c", code, "7"])
        results = farm.wait_batch(timeout=60)
        assert results["t3"]["results"]["value"] == 7
        farm.close()
        server.kick()
        worker.join(timeout=10)
        assert not worker.is_alive()
        server.stop()

    def test_drop_slave_requeues(self):
        from veles_tpu.fleet.farm import TaskFarmMaster

        class Slave:
            id = "s1"

        farm = TaskFarmMaster("x")
        farm.submit("a", ["cmd"])
        job = farm.generate_data_for_slave(Slave())
        assert job["task_id"] == "a"
        assert farm.generate_data_for_slave(Slave()) is False  # parked
        farm.drop_slave(Slave())
        job2 = farm.generate_data_for_slave(Slave())
        assert job2["task_id"] == "a"  # requeued


class TestEnsembleCombiner:
    def test_output_dumper_and_loader_roundtrip(self, tmp_path):
        from veles_tpu.ensemble import (EnsembleLoader, OutputDumper,
                                        build_combiner_file)
        from veles_tpu.loader.base import TRAIN

        rng = numpy.random.RandomState(0)
        n, dim = 30, 3
        winners = rng.randint(0, dim, n)
        entries = []
        for mid in range(2):
            wf = DummyWorkflow()
            dumper = OutputDumper(wf, model_id="m%d" % mid, klass=TRAIN)
            # simulate two epoch sweeps of minibatches
            outputs = rng.rand(n, dim).astype(numpy.float32)
            # model outputs correlate with winners: boost the true class
            outputs[numpy.arange(n), winners] += 2.0
            for start in range(0, n, 10):
                dumper.output = outputs[start:start + 10]
                dumper.minibatch_indices = numpy.arange(start, start + 10)
                dumper.minibatch_valid_size = 10
                dumper.minibatch_class = TRAIN
                dumper.run()
            entries.append(dumper.entry(labels=["a", "b", "c"]))
        path = build_combiner_file(
            entries, [["a", "b", "c"][w] for w in winners],
            str(tmp_path / "models.json"))

        loader = EnsembleLoader(DummyWorkflow(), file=path,
                                minibatch_size=10)
        loader.initialize()
        assert loader.class_lengths == [0, 0, n]
        assert loader.original_data.shape == (n, 2, dim)
        labels = numpy.asarray(loader.original_labels.mem)
        numpy.testing.assert_array_equal(labels, winners)

    def test_output_dumper_wired_into_workflow(self):
        """Regression: a leaf-linked dumper races the repeater loop and
        records rows from the WRONG class; wire() puts it in the control
        chain so every recorded row belongs to its class."""
        from veles_tpu.ensemble import OutputDumper
        from veles_tpu.loader.base import VALID
        from veles_tpu.models.mlp import MLPWorkflow

        rng = numpy.random.RandomState(0)
        X = rng.rand(300, 8).astype(numpy.float32)
        y = (X[:, 0] > 0.5).astype(numpy.int32)
        wf = MLPWorkflow(
            DummyLauncher(), layers=(8, 2),
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 100, 200],
                               minibatch_size=50),
            learning_rate=0.2, max_epochs=3, fused=False, name="dump-wf")
        dumper = OutputDumper(wf, model_id="m", klass=VALID).wire(wf)
        wf.initialize()
        wf.run()
        assert sorted(dumper.rows) == list(range(100))
        entry = dumper.entry()
        assert len(entry["Output"]) == 100

    def test_combiner_model_trains_on_stack(self, tmp_path):
        """Member outputs -> EnsembleLoader -> combiner MLP learns the
        vote (the full reference combiner pipeline)."""
        from veles_tpu.ensemble import build_combiner_file
        from veles_tpu.ensemble.combiner import EnsembleLoader
        from veles_tpu.models.standard import StandardWorkflow

        rng = numpy.random.RandomState(1)
        n, dim = 120, 4
        winners = rng.randint(0, dim, n)
        entries = []
        for mid in range(3):
            outputs = rng.rand(n, dim).astype(numpy.float32) * 0.3
            good = rng.rand(n) < 0.8  # each member is 80% accurate
            outputs[numpy.arange(n)[good], winners[good]] += 1.0
            entries.append({"id": "m%d" % mid,
                            "Output": outputs.tolist(), "Labels": []})
        path = build_combiner_file(entries, winners.tolist(),
                                   str(tmp_path / "models.json"))
        wf = StandardWorkflow(
            DummyLauncher(),
            loader_cls=EnsembleLoader,
            loader_kwargs=dict(file=path, minibatch_size=20,
                               validation_ratio=0.25),
            layers=[{"type": "all2all_tanh", "output_sample_shape": 16},
                    {"type": "softmax", "output_sample_shape": dim}],
            learning_rate=0.1,
            decision_kwargs=dict(max_epochs=8), name="combiner")
        wf.initialize()
        wf.run()
        best = wf.decision.best_n_err[1]
        assert best is not None and best <= 10, \
            "combiner at %s/30 validation errors" % best
