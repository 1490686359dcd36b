"""Fused tick mode: numerical identity with graph mode + wiring checks.

The headline design promise (SURVEY §7.1): one workflow tick = one fused
XLA computation, numerically identical to the per-unit graph dispatch.
These tests train the same topology both ways from identical seeds and
compare weights and metrics.
"""

import numpy
import pytest

import jax.numpy as jnp

from veles_tpu.core import prng
from veles_tpu.dummy import DummyLauncher
from veles_tpu.loader.base import VALID
from veles_tpu.models.mlp import MLPWorkflow
from veles_tpu.models.standard import StandardWorkflow


def _digits_dataset():
    from sklearn.datasets import load_digits
    digits = load_digits()
    X = digits.data.astype(numpy.float32)
    y = digits.target.astype(numpy.int32)
    perm = numpy.random.RandomState(0).permutation(len(X))
    return X[perm], y[perm]


def _build_mlp(fused, mesh=None, max_epochs=3, sweep=True,
               pipeline=False, fail_iterations=50):
    # pipeline=False by default HERE: the identity tests compare the
    # plain engine against graph mode / explicit pipelined builds
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    X, y = _digits_dataset()
    return MLPWorkflow(
        DummyLauncher(), layers=(32, 10),
        loader_kwargs=dict(data=X, labels=y,
                           class_lengths=[0, 297, 1500],
                           minibatch_size=100,
                           normalization_type="linear"),
        learning_rate=0.1, max_epochs=max_epochs, fused=fused, mesh=mesh,
        fused_sweep=sweep, fused_pipeline=pipeline,
        fail_iterations=fail_iterations, name="fused-identity")


def _train(wf):
    wf.initialize()
    wf.run()
    return wf


@pytest.mark.parametrize("sweep", [False, True])
def test_fused_mode_matches_graph_mode(sweep):
    """Same seeds, same data: fused (per-tick AND scanned-sweep engines)
    and graph mode must produce the same weights and per-epoch metrics."""
    graph = _train(_build_mlp(fused=False))
    fused = _train(_build_mlp(fused=True, sweep=sweep))
    assert fused.fused_tick is not None, "fused mode did not engage"
    assert fused.fused_tick.ticks > 0
    # identical epoch accounting
    assert fused.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]
    assert fused.decision._epochs_done == graph.decision._epochs_done
    # near-identical weights: each train tick agrees to fp reassociation
    # between the fused autodiff graph and the per-unit chain,
    # compounding over 45 ticks to ~1e-4 measured — metrics stay exact.
    # (atol was 2e-2 before round 4's gate fix: graph mode used to DROP
    # the stopping epoch's final update, and the slack masked it.)
    for fg, ff in zip(graph.forwards, fused.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(ff.weights.data),
            atol=1e-3)
        numpy.testing.assert_allclose(
            numpy.asarray(fg.bias.data), numpy.asarray(ff.bias.data),
            atol=1e-3)


def test_fused_mode_learns():
    wf = _train(_build_mlp(fused=True, max_epochs=8))
    assert wf.fused_tick is not None
    best = wf.decision.best_n_err[VALID]
    assert best is not None and best < 45, \
        "validation errors %s/297 — did not learn" % best


def test_fused_data_parallel_matches_single_device():
    """Pod mode: the shard_mapped fused tick over a 4-device data axis
    must match the single-device fused run exactly (psum-merged grads ==
    full-batch grads)."""
    import jax
    from veles_tpu.parallel.mesh import build_mesh
    single = _train(_build_mlp(fused=True))
    mesh = build_mesh(devices=jax.devices()[:4], data=4)
    dp = _train(_build_mlp(fused=True, mesh=mesh))
    assert dp.fused_tick is not None and dp.fused_tick.mesh is mesh
    assert dp.decision.best_n_err[VALID] == single.decision.best_n_err[
        VALID]
    for fs, fd in zip(single.forwards, dp.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fs.weights.data), numpy.asarray(fd.weights.data),
            atol=1e-3)


def test_pipelined_data_parallel_matches_single_device():
    """The product default (pipelined) composed with a data-parallel
    mesh must still match the plain single-device fused run exactly."""
    import jax
    from veles_tpu.parallel.mesh import build_mesh

    single = _train(_build_mlp(fused=True))
    mesh = build_mesh(devices=jax.devices()[:4], data=4)
    dp = _train(_build_mlp(fused=True, mesh=mesh, pipeline=True))
    assert dp.fused_tick is not None and dp.fused_tick.pipelined
    assert dp.decision.best_n_err[VALID] == single.decision.best_n_err[
        VALID]
    assert dp.decision._epochs_done == single.decision._epochs_done
    for fs, fd in zip(single.forwards, dp.forwards):
        numpy.testing.assert_allclose(
            numpy.asarray(fs.weights.data), numpy.asarray(fd.weights.data),
            atol=1e-3)


def test_fused_convnet_matches_graph_mode():
    """Conv + pooling topologies fuse too."""
    from sklearn.datasets import load_digits
    d = load_digits()
    X = d.images.astype(numpy.float32)[..., None]  # (N, 8, 8, 1) NHWC
    y = d.target.astype(numpy.int32)
    perm = numpy.random.RandomState(0).permutation(len(X))
    X, y = X[perm][:600], y[perm][:600]
    layers = [
        {"type": "conv_tanh", "n_kernels": 8, "kx": 3, "ky": 3},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "softmax", "output_sample_shape": (10,)},
    ]

    def build(fused):
        prng.get("default").seed(99)
        prng.get("loader").seed(77)
        return StandardWorkflow(
            DummyLauncher(), layers=layers,
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 100, 500],
                               minibatch_size=100,
                               normalization_type="linear"),
            learning_rate=0.05, fused=fused,
            decision_kwargs=dict(max_epochs=2), name="fused-conv")

    graph = _train(build(False))
    fused = _train(build(True))
    assert fused.fused_tick is not None
    assert fused.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]
    for fg, ff in zip(graph.forwards, fused.forwards):
        if getattr(fg, "weights", None) is None:
            continue
        numpy.testing.assert_allclose(
            numpy.asarray(fg.weights.data), numpy.asarray(ff.weights.data),
            atol=2e-3)


def test_fused_annealing_applies():
    """set_learning_rate() must keep working in fused mode (hypers are
    traced inputs, not baked-in constants)."""
    wf = _build_mlp(fused=True, max_epochs=1)
    wf.initialize()
    assert wf.fused_tick is not None
    for gd in wf.gds:
        gd.set_learning_rate(0.0)
    w0 = numpy.asarray(wf.forwards[0].weights.data).copy()
    wf.run()
    numpy.testing.assert_array_equal(
        w0, numpy.asarray(wf.fused_tick._params_[0]["p"]["w"]),
        "lr=0 must freeze the weights — annealing ignored by fused tick")


def test_fused_disabled_on_host_fallback(monkeypatch):
    """The loader's HBM-OOM host fallback must revert to graph mode."""
    from veles_tpu.memory import Array

    def boom(self, *a, **kw):
        raise MemoryError("synthetic HBM OOM")

    monkeypatch.setattr(Array, "to_device", boom)
    wf = _build_mlp(fused="auto", max_epochs=1)
    wf.initialize()
    assert wf.fused_tick is None, "fused mode must disengage"
    assert wf.loader.fill_data is True
    wf.run()
    assert wf.decision._epochs_done == 1  # graph mode trained fine


def test_fused_snapshot_weights_current():
    """Weights written back at epoch boundaries are the fused params (the
    Snapshotter path sees current state, not the init values)."""
    wf = _build_mlp(fused=True, max_epochs=1)
    wf.initialize()
    init_w = numpy.asarray(wf.forwards[0].weights.data).copy()
    wf.run()
    final_w = numpy.asarray(wf.forwards[0].weights.data)
    assert not numpy.allclose(init_w, final_w), \
        "epoch-boundary write-back did not happen"
    tick_w = numpy.asarray(wf.fused_tick._params_[0]["p"]["w"])
    numpy.testing.assert_array_equal(final_w, tick_w)


def test_fused_transformer_matches_graph_mode():
    """layer_norm + self_attention + softmax head fuses, with per-leaf
    update policies matching the graph-mode GD units (qkv/out decay,
    norm-shift no decay)."""
    rng = numpy.random.RandomState(0)
    n, t, e = 300, 8, 16
    X = rng.randn(n, t, e).astype(numpy.float32) * 0.1
    y = rng.randint(0, 2, n).astype(numpy.int32)
    for i in range(n):
        X[i, : t // 2 if y[i] == 0 else t, 0] += 1.0
    layers = [
        {"type": "layer_norm"},
        {"type": "self_attention", "heads": 4},
        {"type": "softmax", "output_sample_shape": (2,)},
    ]

    def build(fused):
        prng.get("default").seed(11)
        prng.get("loader").seed(12)
        return StandardWorkflow(
            DummyLauncher(), layers=layers,
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 50, 250],
                               minibatch_size=50),
            learning_rate=0.05, weights_decay=1e-4, fused=fused,
            decision_kwargs=dict(max_epochs=1), name="fused-attn")

    graph = _train(build(False))
    fused = _train(build(True))
    assert fused.fused_tick is not None
    # metrics must agree EXACTLY; weights follow the fp-reassociation
    # contract of the dense identity test (bf16 softmax/rsqrt
    # reassociation; momentum is off here so the drift does not
    # compound)
    assert fused.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]
    for fg, ff in zip(graph.forwards, fused.forwards):
        for attr in ("weights", "bias", "out_weights", "out_bias"):
            ag, af = getattr(fg, attr, None), getattr(ff, attr, None)
            if ag is None or ag.data is None:
                continue
            numpy.testing.assert_allclose(
                numpy.asarray(ag.data), numpy.asarray(af.data),
                atol=2e-3)


def test_fused_transformer_block_matches_graph_mode():
    """The COMPLETE pre-LN transformer block — layer_norm → residual
    self_attention → layer_norm → residual ffn → softmax head — fuses
    and matches graph mode (metrics exactly, weights to fp tolerance)."""
    rng = numpy.random.RandomState(1)
    n, t, e = 300, 8, 16
    X = rng.randn(n, t, e).astype(numpy.float32) * 0.1
    y = rng.randint(0, 2, n).astype(numpy.int32)
    for i in range(n):
        X[i, : t // 2 if y[i] == 0 else t, 0] += 1.0
    layers = [
        {"type": "layer_norm"},
        {"type": "self_attention", "heads": 4, "residual": True},
        {"type": "layer_norm"},
        {"type": "ffn", "ratio": 2},
        {"type": "softmax", "output_sample_shape": (2,)},
    ]

    def build(fused):
        prng.get("default").seed(21)
        prng.get("loader").seed(22)
        return StandardWorkflow(
            DummyLauncher(), layers=layers,
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 50, 250],
                               minibatch_size=50),
            learning_rate=0.05, weights_decay=1e-4, fused=fused,
            decision_kwargs=dict(max_epochs=1), name="fused-block")

    graph = _train(build(False))
    fused = _train(build(True))
    assert fused.fused_tick is not None
    assert fused.decision.best_n_err[VALID] == graph.decision.best_n_err[
        VALID]
    for fg, ff in zip(graph.forwards, fused.forwards):
        for attr in ("weights", "bias", "out_weights", "out_bias"):
            ag, af = getattr(fg, attr, None), getattr(ff, attr, None)
            if ag is None or ag.data is None:
                continue
            numpy.testing.assert_allclose(
                numpy.asarray(ag.data), numpy.asarray(af.data),
                atol=2e-3)


def test_pipelined_is_the_default_product_path():
    """StandardWorkflow defaults to the pipelined fused engine in
    standalone sweep mode (the path `python -m veles_tpu` executes)."""
    prng.get("default").seed(1)
    prng.get("loader").seed(1)
    X, y = _digits_dataset()
    wf = MLPWorkflow(
        DummyLauncher(), layers=(16, 10),
        loader_kwargs=dict(data=X, labels=y,
                           class_lengths=[0, 297, 1500],
                           minibatch_size=100),
        learning_rate=0.1, max_epochs=1, name="default-pipeline")
    wf.initialize()
    assert wf.fused_tick is not None and wf.fused_tick.pipelined
    assert wf.decision.pipeline_depth == 1
    wf.run()
    assert wf.decision._epochs_done == 1


def test_pipelined_identical_on_max_epochs_stop():
    """Pipelined epoch mode (metrics one epoch late, sync overlapped)
    must produce EXACTLY the plain sweep mode's outputs when max_epochs
    stops the run — same epochs, same best error, same final weights."""
    plain = _train(_build_mlp(fused=True, max_epochs=4))
    piped = _train(_build_mlp(fused=True, max_epochs=4, pipeline=True))
    assert piped.fused_tick is not None and piped.fused_tick.pipelined
    assert piped.decision._epochs_done == plain.decision._epochs_done
    assert piped.decision.best_n_err[VALID] == plain.decision.best_n_err[
        VALID]
    assert piped.decision.best_epoch == plain.decision.best_epoch
    for fp, fq in zip(plain.forwards, piped.forwards):
        numpy.testing.assert_array_equal(
            numpy.asarray(fp.weights.data), numpy.asarray(fq.weights.data))


def test_pipelined_identical_on_no_improvement_stop():
    """A fail_iterations stop is discovered one epoch LATE in pipelined
    mode; the speculative epoch must be dropped and the params rolled
    back so outputs match the plain run exactly. lr=0 freezes learning:
    epoch 1 cannot improve on epoch 0, forcing the stop path."""
    def build(pipeline):
        wf = _build_mlp(fused=True, max_epochs=50, pipeline=pipeline,
                        fail_iterations=1)
        wf.initialize()
        for gd in wf.gds:
            gd.set_learning_rate(0.0)
        wf.run()
        return wf

    plain = build(False)
    piped = build(True)
    assert piped.fused_tick.pipelined
    assert plain.decision._epochs_done < 50, "stop path not exercised"
    assert piped.decision._epochs_done == plain.decision._epochs_done
    assert piped.decision.best_n_err[VALID] == plain.decision.best_n_err[
        VALID]
    for fp, fq in zip(plain.forwards, piped.forwards):
        numpy.testing.assert_array_equal(
            numpy.asarray(fp.weights.data), numpy.asarray(fq.weights.data))


def test_pipelined_rollback_restores_pre_speculation_weights():
    """With real learning and a tight improvement budget, the rolled-back
    weights must equal the plain run's final weights (the speculative
    epoch's training must leave no trace)."""
    plain = _train(_build_mlp(fused=True, max_epochs=50,
                              fail_iterations=2))
    piped = _train(_build_mlp(fused=True, max_epochs=50, pipeline=True,
                              fail_iterations=2))
    assert plain.decision._epochs_done < 50, "stop path not exercised"
    assert piped.decision._epochs_done == plain.decision._epochs_done
    for fp, fq in zip(plain.forwards, piped.forwards):
        numpy.testing.assert_array_equal(
            numpy.asarray(fp.weights.data), numpy.asarray(fq.weights.data))


def _attach_snapshotter(wf, directory, **kwargs):
    """Snapshot-on-improved wiring: gate_SKIP (skip still propagates the
    tick) and serialized BEFORE the end point — a parallel end point
    could race a same-tick final snapshot (see tests/test_snapshotter.py
    for the full rationale)."""
    from veles_tpu.snapshotter import Snapshotter

    snap = Snapshotter(wf, directory=str(directory), time_interval=0,
                       **kwargs)
    snap.link_from(wf.decision)
    snap.gate_skip = ~wf.decision.improved
    wf.end_point.unlink_from(wf.decision)
    wf.end_point.link_from(snap)
    return snap


@pytest.mark.parametrize("pipeline", [False, True])
def test_fused_snapshot_on_improved_holds_evaluated_weights(tmp_path,
                                                            pipeline):
    """The deferred sweep materialization fires ``improved`` on the
    epoch-end tick — the unit Arrays must still hold the weights the
    validation metric was MEASURED on (eval-tick write-back), so the
    snapshot re-evaluates to exactly the recorded best error. The
    pipelined case exercises the final max_epochs drain, where TWO
    epochs materialize on one tick (digits improves monotonically, so
    the final epoch takes 'improved' there)."""
    from veles_tpu.snapshotter import SnapshotterToFile

    wf = _build_mlp(fused=True, max_epochs=5, pipeline=pipeline)
    snap = _attach_snapshotter(wf, tmp_path, prefix="sem")
    wf.initialize()
    wf.run()
    best = wf.decision.best_n_err[VALID]
    restored = SnapshotterToFile.import_(snap.destination)
    X, y = _digits_dataset()
    w0, b0 = restored.forwards[0].weights.data, restored.forwards[0].bias.data
    w1, b1 = restored.forwards[1].weights.data, restored.forwards[1].bias.data
    Xv = jnp.asarray(X[:297])
    dmin = Xv.min(axis=1, keepdims=True)
    dmax = Xv.max(axis=1, keepdims=True)
    Xn = (Xv - dmin) * (2.0 / (dmax - dmin)) - 1.0  # linear normalizer
    h = 1.7159 * jnp.tanh(0.6666 * (Xn @ w0 + b0))  # Znicz scaled tanh
    n_err = int((jnp.argmax(h @ w1 + b1, 1) != jnp.asarray(y[:297])).sum())
    assert n_err == best, \
        "snapshot re-evaluates to %d but recorded best is %d" % (n_err, best)


def test_fused_eval_publishes_confusion():
    """Fused eval passes emit the confusion increment; the Decision
    accumulates the whole VALID sweep (MatrixPlotter feed parity with
    graph mode)."""
    wf = _train(_build_mlp(fused=True, max_epochs=2))
    assert wf.fused_tick is not None
    cm = wf.decision.last_epoch_confusion
    assert cm is not None and cm.shape == (10, 10)
    assert int(cm.sum()) == 297  # every VALID row accounted
    graph = _train(_build_mlp(fused=False, max_epochs=2))
    graph_cm = numpy.asarray(graph.decision.last_epoch_confusion)
    # the modes' weights drift ~1e-5/tick (fp reassociation), flipping a
    # few borderline argmaxes: totals must match, cells near-match
    assert int(graph_cm.sum()) == 297
    delta = numpy.abs(numpy.asarray(cm) - graph_cm).sum()
    assert delta <= 8, "confusion matrices differ by %d entries" % delta


def test_fused_confusion_per_tick_and_dp():
    """The per-tick eval path AND the shard_mapped DP path publish the
    psum-merged confusion (the sweep test above covers only the scan
    path)."""
    import jax
    from veles_tpu.parallel.mesh import build_mesh

    # per-tick engine (sweep off)
    wf = _train(_build_mlp(fused=True, max_epochs=1, sweep=False))
    cm = wf.decision.last_epoch_confusion
    assert cm is not None and int(cm.sum()) == 297

    # data-parallel engine: cm must be the psum over shards
    mesh = build_mesh(devices=jax.devices()[:4], data=4)
    dp = _train(_build_mlp(fused=True, max_epochs=1, mesh=mesh))
    cm_dp = dp.decision.last_epoch_confusion
    assert cm_dp is not None and int(cm_dp.sum()) == 297


def test_fused_confusion_disabled_flag(monkeypatch):
    """compute_confusion=False skips the fused cm publish (parity with
    the graph evaluator's opt-out)."""
    wf = _build_mlp(fused=True, max_epochs=1)
    wf.evaluator.compute_confusion = False
    wf.initialize()
    assert wf.fused_tick is not None
    wf.run()
    assert wf.decision.last_epoch_confusion is None


def test_pipelined_snapshot_resume_continues(tmp_path):
    """A snapshot taken by the PIPELINED engine (improved fires on the
    epoch-end tick) must resume and continue training: the lagged-epoch
    queue and the tick's params history are session state, rebuilt
    empty on unpickle."""
    from veles_tpu.snapshotter import SnapshotterToFile

    wf = _build_mlp(fused=True, max_epochs=3, pipeline=True)
    snap = _attach_snapshotter(wf, tmp_path, prefix="pr")
    wf.initialize()
    assert wf.fused_tick.pipelined
    wf.run()
    best_before = wf.decision.best_n_err[VALID]

    restored = SnapshotterToFile.import_(snap.destination)
    restored.workflow = DummyLauncher()
    restored.decision.max_epochs = 6
    restored.decision.complete.unset()
    restored.decision.train_ended.unset()
    restored.initialize()
    assert restored.fused_tick is not None and restored.fused_tick.pipelined
    restored.run()
    assert restored.decision._epochs_done == 6
    # STRICT improvement: the pickled best alone would satisfy <=; three
    # more epochs on digits reliably lower the error, so a broken resume
    # (e.g. garbage params after restore) fails here
    assert restored.decision.best_n_err[VALID] < best_before


class TestAdamSolver:
    """solver="adam" (additive beyond the reference's momentum-only GD):
    graph and fused modes share gd.make_updater, so they must agree."""

    def _build(self, fused, solver="adam", max_epochs=3, sweep=True):
        prng.get("default").seed(4321)
        prng.get("loader").seed(8765)
        X, y = _digits_dataset()
        return MLPWorkflow(
            DummyLauncher(), layers=(32, 10),
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 297, 1500],
                               minibatch_size=100,
                               normalization_type="linear"),
            learning_rate=0.01, solver=solver, max_epochs=max_epochs,
            fused=fused, fused_sweep=sweep, fused_pipeline=False,
            fail_iterations=50, name="adam-identity")

    def test_adam_learns_graph_mode(self):
        wf = _train(self._build(fused=False))
        assert wf.decision.best_n_err[VALID] is not None
        assert wf.decision.best_n_err[VALID] < 40  # < ~13.5% on digits
        # adam state exists and evolved (graph mode really ran)
        gd = wf.gds[0]
        assert wf.fused_tick is None
        assert gd._second_w.data is not None
        assert float(gd._step.data) > 0

    @pytest.mark.parametrize("sweep", [False, True])
    def test_adam_fused_matches_graph(self, sweep):
        graph = _train(self._build(fused=False))
        fused = _train(self._build(fused=True, sweep=sweep))
        assert fused.fused_tick is not None, "fused mode did not engage"
        assert (fused.decision.best_n_err[VALID]
                == graph.decision.best_n_err[VALID])
        # weights: LOOSE tolerance by design — adam's first-step update
        # is lr*sign(g) (bias-corrected m/sqrt(s) with tiny s), which
        # amplifies fp-reassociation differences between the fused and
        # per-unit autodiff graphs on near-zero gradients into +-2*lr
        # jumps. Metric-level equality above is the parity contract;
        # this bound only catches gross update bugs (wrong lr/sign/
        # moment wiring would blow past it)
        for fg, ff in zip(graph.forwards, fused.forwards):
            numpy.testing.assert_allclose(
                numpy.asarray(fg.weights.data),
                numpy.asarray(ff.weights.data), atol=0.05)
        # step counts advance one per TRAIN tick. Known, pre-existing
        # one-tick offset: on the stopping tick graph mode's gds sit
        # BELOW the decision in the cycle and get gate-blocked by
        # `complete`, while the fused sweep trains its whole last class
        # sweep before the decision sees the metrics
        g_step = float(graph.gds[0]._step.data)
        f_step = float(fused.gds[0]._step.data)
        assert g_step > 0 and abs(g_step - f_step) <= 1

    def test_adam_adapts_fast(self):
        """Sanity: the adaptive update is live — two epochs at lr=0.01
        already put digits validation under 20% error."""
        wf = _train(self._build(fused=True, max_epochs=2))
        assert wf.decision.best_n_err[VALID] < 60

    def test_adam_snapshot_roundtrip(self, tmp_path):
        """Second moments + step survive a snapshot: resumed training
        continues from the same optimizer state."""
        import pickle

        wf = _train(self._build(fused=True, max_epochs=2))
        step_before = float(wf.gds[0]._step.data)
        blob = pickle.dumps(wf)
        wf2 = pickle.loads(blob)
        gd2 = wf2.gds[0]
        assert float(gd2._step.data) == step_before
        numpy.testing.assert_array_equal(
            numpy.asarray(gd2._second_w.data),
            numpy.asarray(wf.gds[0]._second_w.data))

    @pytest.mark.parametrize("fused", [False, True])
    def test_adagrad_learns(self, fused):
        """solver="adagrad": same stateful-slot machinery as adam (no
        first moment, no bias correction), both execution modes."""
        wf = _train(self._build(fused=fused, solver="adagrad",
                                max_epochs=4))
        assert (wf.fused_tick is not None) == fused
        assert wf.decision.best_n_err[VALID] is not None
        assert wf.decision.best_n_err[VALID] < 45
        gd = wf.gds[0]
        assert float(numpy.asarray(gd._step.data)) > 0
        assert numpy.asarray(gd._second_w.data).sum() > 0


def test_lr_decay_on_plateau():
    """decision_kwargs lr_decay/lr_decay_patience anneal every GD unit
    when validation stops improving — in fused mode (traced hypers make
    set_learning_rate effective without retrace)."""
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    X, y = _digits_dataset()
    wf = MLPWorkflow(
        DummyLauncher(), layers=(32, 10),
        loader_kwargs=dict(data=X, labels=y,
                           class_lengths=[0, 297, 1500],
                           minibatch_size=100,
                           normalization_type="linear"),
        # lr=0: NOTHING ever improves after epoch 1, so the plateau
        # counter climbs deterministically
        learning_rate=0.0, max_epochs=7, fused=True,
        fused_pipeline=False,
        decision_kwargs=dict(max_epochs=7, lr_decay=0.5,
                             lr_decay_patience=2),
        name="lr-decay")
    wf.initialize()
    wf.run()
    # epochs 2..7 -> >=5 no-improvement epochs -> >=2 decays (at 2, 4, 6)
    lr = wf.gds[0].learning_rate
    assert lr == 0.0  # 0 * factor stays 0 — decay applied cleanly
    assert wf.decision._epochs_without_improvement >= 4
    # a REAL decay: start from a positive lr and force a plateau
    prng.get("default").seed(4321)
    prng.get("loader").seed(8765)
    wf2 = MLPWorkflow(
        DummyLauncher(), layers=(32, 10),
        loader_kwargs=dict(data=X, labels=y,
                           class_lengths=[0, 297, 1500],
                           minibatch_size=100,
                           normalization_type="linear"),
        learning_rate=1e-7, max_epochs=6, fused=True,
        fused_pipeline=False,
        decision_kwargs=dict(max_epochs=6, lr_decay=0.5,
                             lr_decay_patience=2),
        name="lr-decay2")
    wf2.initialize()
    wf2.run()
    assert wf2.gds[0].learning_rate < 1e-7  # decayed at least once
