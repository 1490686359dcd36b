"""End-to-end MSE (regression) workflows: the Znicz EvaluatorMSE +
DecisionMSE model family, and their ride on the partial-fusion tier
(the full fused engine recognizes softmax chains only — MSE used to be
one of the graph-mode-cliff casualties)."""

import numpy

from veles_tpu.core import prng
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import VALID
from veles_tpu.models.standard import StandardWorkflow
from veles_tpu.parallel.segments import FusedSegment


def _dataset(n=1200, din=16, dout=4):
    rng = numpy.random.RandomState(3)
    X = rng.rand(n, din).astype(numpy.float32)
    W = rng.randn(din, dout).astype(numpy.float32) * 0.4
    Y = numpy.tanh(X @ W) + 0.01 * rng.randn(n, dout).astype(
        numpy.float32)
    return X, Y.astype(numpy.float32)


def _build(fused="auto", max_epochs=6):
    prng.get("default").seed(1111)
    prng.get("loader").seed(2222)
    X, Y = _dataset()
    return StandardWorkflow(
        DummyLauncher(),
        layers=[{"type": "all2all_tanh", "output_sample_shape": (24,)},
                {"type": "all2all", "output_sample_shape": (4,)}],
        evaluator="mse",
        loader_kwargs=dict(data=X, targets=Y,
                           class_lengths=[0, 200, 1000],
                           minibatch_size=100,
                           normalization_type="linear",
                           target_normalization_type="none"),
        learning_rate=0.1, gradient_moment=0.9,
        decision_kwargs=dict(max_epochs=max_epochs),
        fused=fused, name="mse-wf")


def test_mse_workflow_learns_graph_mode():
    wf = _build(fused=False, max_epochs=15)
    wf.initialize()
    wf.run()
    best = wf.decision.best_n_err[VALID]
    # target variance is ~0.4 — well below it proves the regression
    # actually fits, not just centers
    assert best is not None and best < 0.08, \
        "validation mse %s did not drop" % best
    assert wf.decision._epochs_done == 15


def test_mse_workflow_rides_fused_engine():
    """The FULL fused engine (sweep dispatch) now handles regression:
    targets gathered in-jit, grads of masked MSE — numerically matching
    the graph-mode GD chain."""
    graph = _build(fused=False)
    graph.initialize()
    graph.run()

    fused = _build(fused="auto")
    fused.initialize()
    assert fused.fused_tick is not None, \
        "fused engine declined the MSE chain"
    assert fused.fused_tick._loss_kind_ == "mse"
    fused.run()

    assert abs(fused.decision.best_n_err[VALID]
               - graph.decision.best_n_err[VALID]) < 1e-4
    assert fused.decision._epochs_done == graph.decision._epochs_done
    # float reassociation between the fused autodiff graph and the
    # per-unit chain compounds over 15 momentum epochs (same bound family
    # as tests/test_fused.py)
    for fg, ff in zip(graph.forwards, fused.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(ff.weights.data),
            atol=1e-2)


def test_mse_with_host_unit_rides_partial_fusion():
    """An MSE chain with a custom host unit: the full engine declines
    (unrecognized unit in the chain) and partial fusion takes over."""
    from veles_tpu.core.distributable import TriviallyDistributable
    from veles_tpu.core.units import Unit

    class Spy(Unit, TriviallyDistributable):
        ticks = 0

        def run(self):
            type(self).ticks += 1

    def splice(wf):
        spy = Spy(wf, name="spy")
        fwd1 = wf.forwards[1]
        fwd1.unlink_from(wf.forwards[0])
        spy.link_from(wf.forwards[0])
        fwd1.link_from(spy)
        return spy

    graph = _build(fused=False)
    splice(graph)
    graph.initialize()
    graph.run()

    seg = _build(fused="auto")
    splice(seg)
    seg.initialize()
    assert seg.fused_tick is None, \
        "full engine must decline a chain with a host unit"
    segments = [u for u in seg.units if isinstance(u, FusedSegment)]
    assert len(segments) == 2
    seg.run()
    assert abs(seg.decision.best_n_err[VALID]
               - graph.decision.best_n_err[VALID]) < 1e-6
    for fg, fs in zip(graph.forwards, seg.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(fs.weights.data),
            atol=1e-5)


def test_mse_snapshot_suffix_and_metrics():
    wf = _build(fused=False, max_epochs=2)
    wf.initialize()
    wf.run()
    assert wf.decision.snapshot_suffix.startswith("validation_mse_")
    assert wf.decision.get_metric_names()[0] == "best_validation_mse"
    assert wf.decision.best_mse[VALID] == wf.decision.best_n_err[VALID]
