"""Partial fusion (parallel/segments.py): numerical identity + wiring.

The "graph-mode cliff" fix, tier 1: any chain of JitUnits —
including workflows the full fused engine declines — collapses into
per-tick composite dispatches with graph-mode numerics.
"""

import numpy

from veles_tpu.core import prng
from veles_tpu.core.distributable import TriviallyDistributable
from veles_tpu.core.units import Unit
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import VALID
from veles_tpu.models.mlp import MLPWorkflow
from veles_tpu.parallel import segments


def _digits():
    from dataset_fixtures import digits_dataset
    return digits_dataset()


def _build(max_epochs=3):
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    X, y = _digits()
    return MLPWorkflow(
        DummyLauncher(), layers=(32, 10),
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 297, 1500],
                           minibatch_size=100,
                           normalization_type="linear"),
        learning_rate=0.1, max_epochs=max_epochs, fused=False,
        name="segments-test")


class HostSpy(Unit, TriviallyDistributable):
    """A custom pure-host unit spliced into the chain — the partial
    fusion engine must keep it host-side between two segments."""

    def __init__(self, workflow, **kwargs):
        super().__init__(workflow, **kwargs)
        self.ticks = 0
        self.seen_shapes = set()
        self.watched = None  # linked Array to observe

    def run(self):
        self.ticks += 1
        if self.watched is not None and self.watched.data is not None:
            self.seen_shapes.add(tuple(self.watched.data.shape))


def test_chain_extraction_and_partition():
    wf = _build()
    chain = segments.chain_of(wf)
    names = [type(u).__name__ for u in chain]
    assert names == ["All2AllTanh", "All2AllSoftmax", "EvaluatorSoftmax",
                     "DecisionGD", "GDSoftmax", "GDTanh"]
    parts = segments.partition(chain)
    kinds = [(kind, len(p) if kind == "segment" else type(p).__name__)
             for kind, p in parts]
    assert kinds == [("segment", 3), ("host", "DecisionGD"),
                     ("segment", 2)]


def test_segments_match_graph_mode():
    graph = _build()
    graph.initialize()
    graph.run()

    seg = _build()
    created = segments.enable(seg)
    assert len(created) == 2
    seg.initialize()
    seg.run()

    assert seg.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]
    assert seg.decision._epochs_done == graph.decision._epochs_done
    for fg, fs in zip(graph.forwards, seg.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(fs.weights.data),
            atol=1e-5)
        numpy.testing.assert_allclose(
            numpy.asarray(fg.bias.data), numpy.asarray(fs.bias.data),
            atol=1e-5)


def _splice_spy(wf):
    """Insert a HostSpy between fwd0 and fwd1 (control only — data links
    stay as they are)."""
    spy = HostSpy(wf, name="spy")
    spy.watched = wf.forwards[0].output
    fwd1 = wf.forwards[1]
    fwd1.unlink_from(wf.forwards[0])
    spy.link_from(wf.forwards[0])
    fwd1.link_from(spy)
    return spy


def test_custom_host_unit_splits_segments():
    graph = _build()
    graph_spy = _splice_spy(graph)
    graph.initialize()
    graph.run()

    seg = _build()
    seg_spy = _splice_spy(seg)
    created = segments.enable(seg)
    # fwd0 alone is a 1-unit run (stays per-unit); [fwd1, evaluator] and
    # [gds] fuse
    assert len(created) == 2
    seg.initialize()
    seg.run()

    assert seg_spy.ticks == graph_spy.ticks > 0
    assert seg_spy.seen_shapes == graph_spy.seen_shapes
    assert seg.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]
    for fg, fs in zip(graph.forwards, seg.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(fs.weights.data),
            atol=1e-5)


def test_segments_learn():
    seg = _build(max_epochs=8)
    segments.enable(seg)
    seg.initialize()
    seg.run()
    best = seg.decision.best_n_err[VALID]
    assert best is not None and best < 45


def test_mid_segment_monitor_still_fires():
    """A side unit hanging off a MID-segment member (a monitor linked
    from fwd0) must keep firing after fusion — its provider link is
    rewired to the segment."""
    graph = _build()
    gmon = HostSpy(graph, name="mon")
    gmon.watched = graph.forwards[0].output
    gmon.link_from(graph.forwards[0])
    graph.initialize()
    graph.run()

    seg = _build()
    smon = HostSpy(seg, name="mon")
    smon.watched = seg.forwards[0].output
    smon.link_from(seg.forwards[0])
    created = segments.enable(seg)
    assert len(created) == 2
    seg.initialize()
    seg.run()

    assert smon.ticks == gmon.ticks > 0
    assert seg.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]


def test_segments_with_adam_solver():
    """Partial fusion x Adam: the segment planner builds its dataflow
    plan from the GD units' EXTENDED slot tuples (second moments + step
    are instance-level INPUTS/OUTPUTS), and training still learns."""
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    X, y = _digits()
    seg = MLPWorkflow(
        DummyLauncher(), layers=(32, 10),
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 297, 1500],
                           minibatch_size=100,
                           normalization_type="linear"),
        learning_rate=0.01, solver="adam", max_epochs=6, fused=False,
        name="segments-adam")
    _splice_spy(seg)
    created = segments.enable(seg)
    assert created, "partial fusion did not engage"
    seg.initialize()
    seg.run()
    best = seg.decision.best_n_err[VALID]
    assert best is not None and best < 45, best
    import numpy
    assert float(numpy.asarray(seg.gds[0]._step.data)) > 0
