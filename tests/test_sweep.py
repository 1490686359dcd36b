"""Sweep-tier fusion: class-sweep scanning of arbitrary JitUnit chains.

The sweep tier: workflows the full fused engine declines (custom
host units, custom layer types) must reach sweep-granular dispatch, not
per-tick dispatch, while matching graph mode numerically — metrics
exactly, weights to fp-reassociation tolerance. Every tier applies the
stopping epoch's final train update (graph mode holds the EndPoint's
AND-gate behind the gd chain for it — StandardWorkflow wiring).
"""

import numpy
import pytest

import jax.numpy as jnp

from veles_tpu.core import prng
from veles_tpu.core.distributable import TriviallyDistributable
from veles_tpu.core.units import Unit
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import TRAIN, VALID
from veles_tpu.models.mlp import MLPWorkflow
from veles_tpu.parallel.segments import FusedSegment
from veles_tpu.parallel.sweep import FusedSweep


class Observer(Unit, TriviallyDistributable):
    """A transparent host unit: counts ticks, touches no slots."""

    sweep_transparent = True

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.ticks = 0

    def run(self):
        self.ticks += 1


class OpaqueObserver(Observer):
    """Same unit without the transparency declaration."""

    sweep_transparent = False


def _dataset(n=1200, features=64, classes=10):
    rng = numpy.random.RandomState(7)
    data = rng.rand(n, features).astype(numpy.float32)
    labels = rng.randint(0, classes, n).astype(numpy.int32)
    return data, labels


def _build(data, labels, observer_cls=None, max_epochs=3, **kwargs):
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    wf = MLPWorkflow(
        DummyLauncher(), layers=(24, 10),
        loader_kwargs=dict(data=data, labels=labels,
                           class_lengths=[0, 300, 900],
                           minibatch_size=100,
                           normalization_type="linear"),
        learning_rate=0.1, max_epochs=max_epochs, name="sweep-test",
        **kwargs)
    if observer_cls is not None:
        obs = observer_cls(wf, name="observer")
        fwd1 = wf.forwards[1]
        fwd1.unlink_from(wf.forwards[0])
        obs.link_from(wf.forwards[0])
        fwd1.link_from(obs)
        wf.observer = obs
    return wf


def _train(wf):
    wf.initialize()
    wf.run()
    return wf


def _assert_parity(a, b, atol=1e-3):
    assert a.decision.best_n_err[VALID] == b.decision.best_n_err[VALID]
    assert a.decision._epochs_done == b.decision._epochs_done
    assert a.decision.last_epoch_n_err == b.decision.last_epoch_n_err
    for fa, fb in zip(a.forwards, b.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fa.weights.data), numpy.asarray(fb.weights.data),
            atol=atol)


def test_sweep_engages_and_matches_graph_mode():
    """A transparent host unit mid-chain: the full engine declines, the
    sweep tier takes over, and the results match per-unit graph mode."""
    data, labels = _dataset()
    graph = _train(_build(data, labels, Observer, fused=False))
    swept = _train(_build(data, labels, Observer, fused="auto"))
    assert swept.fused_tick is None, "full engine must decline"
    sweep_unit = getattr(swept, "sweep_unit", None)
    assert isinstance(sweep_unit, FusedSweep), "sweep tier did not engage"
    assert sweep_unit.ticks > 0
    _assert_parity(graph, swept)


def test_sweep_host_unit_fires_per_tick():
    data, labels = _dataset()
    swept = _train(_build(data, labels, Observer, fused="auto",
                          max_epochs=2))
    assert isinstance(getattr(swept, "sweep_unit", None), FusedSweep)
    # 3 VALID + 9 TRAIN minibatches per epoch, 2 epochs — graph mode
    # would have fired the observer once per tick
    graph = _train(_build(data, labels, Observer, fused=False,
                          max_epochs=2))
    assert swept.observer.ticks == graph.observer.ticks


def test_opaque_host_unit_falls_back_to_segments():
    """No transparency declaration => per-tick segment tier (the unit
    may read per-minibatch slot state)."""
    data, labels = _dataset()
    wf = _train(_build(data, labels, OpaqueObserver, fused="auto",
                       max_epochs=1))
    assert getattr(wf, "sweep_unit", None) is None
    assert any(isinstance(u, FusedSegment) for u in wf.units)


def test_sweep_custom_jit_layer():
    """A layer type the full engine has never heard of (custom JitUnit
    subclass) still reaches sweep dispatch — the generality claim."""
    from veles_tpu.nn.all2all import All2AllTanh

    class ScaledTanh(All2AllTanh):
        """Custom forward: standard tanh layer with a 1.1 output scale
        (enough to be unrecognizable to extract_model_spec by class)."""

        def compute(self, *tensors):
            return super().compute(*tensors) * 1.1

    from veles_tpu.nn.gd import GDTanh

    class GDScaledTanh(GDTanh):
        def compute(self, err_output, x, y, weights, bias, vel_w, vel_b,
                    *rest):
            # d(1.1*t)/dt: fold the scale into the incoming error and
            # undo it on the saved output the derivative reads
            return super().compute(err_output * 1.1, x, y / 1.1, weights,
                                   bias, vel_w, vel_b, *rest)

    from veles_tpu.models import standard as std
    std.FORWARD_TYPES["scaled_tanh"] = (ScaledTanh, GDScaledTanh)
    try:
        from veles_tpu.models.standard import StandardWorkflow
        data, labels = _dataset()

        def build(fused):
            prng.get("default").seed(11)
            prng.get("loader").seed(22)
            return StandardWorkflow(
                DummyLauncher(),
                layers=[{"type": "scaled_tanh",
                         "output_sample_shape": (24,)},
                        {"type": "softmax", "output_sample_shape": (10,)}],
                loader_kwargs=dict(data=data, labels=labels,
                                   class_lengths=[0, 300, 900],
                                   minibatch_size=100,
                                   normalization_type="linear"),
                learning_rate=0.05, fused=fused,
                decision_kwargs=dict(max_epochs=2), name="custom-layer")

        graph = _train(build(False))
        swept = _train(build("auto"))
        assert swept.fused_tick is None
        assert isinstance(getattr(swept, "sweep_unit", None), FusedSweep)
        _assert_parity(graph, swept)
    finally:
        del std.FORWARD_TYPES["scaled_tanh"]


def test_sweep_adam_solver_state_carries():
    """Adam's second moments + step counter ride the scan carry: a
    2-epoch graph run and a 2-epoch sweep run both end after the same
    18 updates (every tier applies the stopping epoch's final update)
    and land on the same weights and step count."""
    data, labels = _dataset()
    graph = _train(_build(data, labels, Observer, fused=False,
                          solver="adam", max_epochs=2))
    swept = _train(_build(data, labels, Observer, fused="auto",
                          solver="adam", max_epochs=2))
    assert isinstance(getattr(swept, "sweep_unit", None), FusedSweep)
    assert float(swept.gds[0]._step.data) == 18.0
    assert float(graph.gds[0]._step.data) == 18.0
    for fg, fs in zip(graph.forwards, swept.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data),
            numpy.asarray(fs.weights.data), atol=1e-3)


def test_sweep_mse_chain():
    """Regression chains (EvaluatorMSE/DecisionMSE) sweep too — the
    full engine supports them only with FullBatchLoaderMSE; here the
    sweep tier proves the generic path."""
    from veles_tpu.models.standard import StandardWorkflow

    rng = numpy.random.RandomState(3)
    data = rng.rand(800, 32).astype(numpy.float32)
    targets = rng.rand(800, 4).astype(numpy.float32)

    def build(fused):
        prng.get("default").seed(5)
        prng.get("loader").seed(6)
        wf = StandardWorkflow(
            DummyLauncher(), evaluator="mse",
            layers=[{"type": "all2all_tanh", "output_sample_shape": (16,)},
                    {"type": "all2all", "output_sample_shape": (4,)}],
            loader_kwargs=dict(data=data, targets=targets,
                               class_lengths=[0, 200, 600],
                               minibatch_size=100,
                               normalization_type="none"),
            learning_rate=0.05, fused=fused,
            decision_kwargs=dict(max_epochs=2), name="mse-sweep")
        obs = Observer(wf, name="observer")
        fwd1 = wf.forwards[1]
        fwd1.unlink_from(wf.forwards[0])
        obs.link_from(wf.forwards[0])
        fwd1.link_from(obs)
        return wf

    graph = _train(build(False))
    swept = _train(build("auto"))
    assert isinstance(getattr(swept, "sweep_unit", None), FusedSweep)
    assert swept.decision._epochs_done == graph.decision._epochs_done
    numpy.testing.assert_allclose(
        swept.decision.last_epoch_loss, graph.decision.last_epoch_loss,
        rtol=1e-4)
    for fg, fs in zip(graph.forwards, swept.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(fs.weights.data),
            atol=1e-3)


def test_sweep_gate_mutation_slow_path():
    """A birth gate .set() after the splice: the safety net executes
    per-unit and honors the gate, exactly like graph mode."""
    data, labels = _dataset()
    swept = _build(data, labels, Observer, fused="auto", max_epochs=2)
    swept.initialize()
    sweep_unit = getattr(swept, "sweep_unit", None)
    assert isinstance(sweep_unit, FusedSweep)
    # block the observer mid-run via its (birth) gate
    swept.observer.gate_skip.set()
    swept.run()
    assert swept.decision._epochs_done == 2
    assert swept.observer.ticks == 0  # the gate was honored
    assert getattr(sweep_unit, "_warned_slow_", False)


def test_sweep_pipelined_identical_on_max_epochs_stop():
    """Pipelined sweeps (metrics one epoch late, prefetched) must
    produce exactly the plain sweep run's outputs on a max_epochs
    stop."""
    data, labels = _dataset()
    plain = _train(_build(data, labels, Observer, fused="auto",
                          max_epochs=4, fused_pipeline=False))
    piped = _train(_build(data, labels, Observer, fused="auto",
                          max_epochs=4, fused_pipeline=True))
    assert piped.sweep_unit is not None and piped.sweep_unit.pipelined
    assert not plain.sweep_unit.pipelined
    assert piped.decision._epochs_done == plain.decision._epochs_done
    assert piped.decision.best_n_err[VALID] == plain.decision.best_n_err[
        VALID]
    assert piped.decision.best_epoch == plain.decision.best_epoch
    for fp, fs in zip(plain.forwards, piped.forwards):
        numpy.testing.assert_array_equal(
            numpy.asarray(fp.weights.data), numpy.asarray(fs.weights.data))


def test_sweep_pipelined_identical_on_no_improvement_stop():
    """The lagged no-improvement stop drops the speculative epoch and
    rolls the state back — outputs identical to the unpipelined run."""
    data, labels = _dataset()
    kwargs = dict(fused="auto", max_epochs=50, fail_iterations=2)
    plain = _train(_build(data, labels, Observer, fused_pipeline=False,
                          **kwargs))
    piped = _train(_build(data, labels, Observer, fused_pipeline=True,
                          **kwargs))
    assert piped.sweep_unit is not None and piped.sweep_unit.pipelined
    assert piped.decision._epochs_done == plain.decision._epochs_done
    assert piped.decision.best_n_err[VALID] == plain.decision.best_n_err[
        VALID]
    assert piped.decision.best_epoch == plain.decision.best_epoch
    for fp, fs in zip(plain.forwards, piped.forwards):
        numpy.testing.assert_array_equal(
            numpy.asarray(fp.weights.data), numpy.asarray(fs.weights.data))


def test_sweep_snapshot_resume(tmp_path):
    """A swept workflow pickles and resumes: the FusedSweep rides the
    snapshot (EPHEMERAL = excluded from checksum, not from pickle), its
    volatile plan/state rebuild, and training continues."""
    import os
    import glob

    from veles_tpu.snapshotter import SnapshotterToFile

    data, labels = _dataset()
    wf = _build(data, labels, Observer, fused="auto", max_epochs=2)
    snap = SnapshotterToFile(wf, directory=str(tmp_path), prefix="swp",
                             interval=1, time_interval=0)
    snap.link_from(wf.decision)
    snap.gate_skip = ~wf.decision.improved
    wf.end_point.unlink_from(wf.decision)
    wf.end_point.link_from(snap)
    wf.initialize()
    assert isinstance(wf.sweep_unit, FusedSweep)
    wf.run()
    assert glob.glob(os.path.join(str(tmp_path), "swp_*.pickle*"))

    restored = SnapshotterToFile.import_(snap.destination)
    assert restored.restored_from_snapshot
    restored.workflow = __import__(
        "veles_tpu.dummy", fromlist=["DummyLauncher"]).DummyLauncher()
    # the splice survived the pickle: the sweep unit is still the
    # loader's consumer and keeps its member list
    assert isinstance(restored.sweep_unit, FusedSweep)
    assert restored.sweep_unit in restored.loader.links_to
    restored.decision.max_epochs = 4
    restored.decision.complete.unset()
    restored.decision.train_ended.unset()
    restored.initialize()
    # _enable_segments must NOT have spliced a second engine
    assert sum(1 for u in restored.units
               if isinstance(u, FusedSweep)) == 1
    restored.run()
    assert restored.decision._epochs_done >= 2
    assert restored.sweep_unit.ticks > 0


def test_sweep_dispatch_count():
    """The speed claim in structural form: host dispatches per epoch are
    sweep-granular (chunked), not minibatch-granular."""
    data, labels = _dataset()
    swept = _build(data, labels, Observer, fused="auto", max_epochs=3)
    swept.initialize()
    unit = swept.sweep_unit
    assert isinstance(unit, FusedSweep)
    swept.run()
    # 2 sweeps/epoch x 3 epochs = 6 sweep ticks (12 minibatches each
    # epoch served in 2 class sweeps)
    assert unit.ticks == 6
