"""Pipeline (pp) and expert (ep) parallelism tests on the 8-device
virtual CPU mesh (conftest.py)."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu.parallel.mesh import build_mesh


class TestPipeline:
    def _stage_fn(self):
        def stage(w, x):
            return jnp.tanh(x @ w["w"] + w["b"])
        return stage

    def _weights(self, n_stages, d, rng):
        return {
            "w": jnp.asarray(rng.randn(n_stages, d, d).astype(
                numpy.float32) * 0.3),
            "b": jnp.asarray(rng.randn(n_stages, d).astype(
                numpy.float32) * 0.1),
        }

    @pytest.mark.parametrize("n_stages,n_micro", [(4, 8), (8, 8), (2, 4)])
    def test_matches_sequential(self, n_stages, n_micro):
        from veles_tpu.parallel.pipeline import (make_pipeline,
                                                 shard_stage_weights)

        mesh = build_mesh(devices=jax.devices()[:n_stages],
                          data=1, pipe=n_stages)
        rng = numpy.random.RandomState(0)
        d = 8
        batch = jnp.asarray(rng.randn(n_micro * 4, d).astype(
            numpy.float32))
        weights = self._weights(n_stages, d, rng)
        stage = self._stage_fn()

        # sequential reference: stages applied in order
        expected = batch
        for s in range(n_stages):
            expected = stage(
                jax.tree.map(lambda a, s=s: a[s], weights), expected)

        pipeline = make_pipeline(mesh, stage, n_micro)
        got = pipeline(shard_stage_weights(weights, mesh), batch)
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(expected),
                                      rtol=2e-5, atol=2e-5)

    def test_single_jit_computation(self):
        """The whole pipeline (fill + steady + drain) is ONE compiled
        computation — count traces."""
        from veles_tpu.parallel.pipeline import (make_pipeline,
                                                 shard_stage_weights)

        mesh = build_mesh(devices=jax.devices()[:4], data=1, pipe=4)
        rng = numpy.random.RandomState(1)
        weights = shard_stage_weights(self._weights(4, 8, rng), mesh)
        pipeline = jax.jit(make_pipeline(mesh, self._stage_fn(), 4))
        batch = jnp.asarray(rng.randn(8, 8).astype(numpy.float32))
        pipeline(weights, batch)
        assert pipeline._cache_size() == 1


class TestExpertParallel:
    @pytest.mark.parametrize("n_experts,ep", [(8, 8), (8, 4), (16, 8)])
    def test_matches_dense_reference(self, n_experts, ep):
        """e_local > 1 configs exercise the (ep, e_local) flattening in
        both all_to_all directions — the trickiest index algebra."""
        from veles_tpu.parallel.expert import (init_moe_params,
                                               make_moe_ffn,
                                               reference_moe,
                                               shard_moe_params)

        d_model, d_hidden = 16, 32
        mesh = build_mesh(devices=jax.devices()[:ep], data=1,
                          expert=ep)
        rng = numpy.random.RandomState(0)
        params = init_moe_params(rng, n_experts, d_model, d_hidden)
        tokens = jnp.asarray(rng.randn(64, d_model).astype(numpy.float32))
        # generous capacity: zero drops -> exact parity with the dense
        # single-device routing
        moe = make_moe_ffn(mesh, n_experts, capacity_factor=float(
            n_experts))
        y, drop_frac = moe(shard_moe_params(params, mesh), tokens)
        expected = reference_moe(
            jax.tree.map(jnp.asarray, params), tokens)
        assert float(drop_frac) == 0.0
        numpy.testing.assert_allclose(numpy.asarray(y),
                                      numpy.asarray(expected),
                                      rtol=2e-4, atol=2e-4)

    def test_capacity_drops_reported(self):
        from veles_tpu.parallel.expert import (init_moe_params,
                                               make_moe_ffn,
                                               shard_moe_params)

        mesh = build_mesh(devices=jax.devices()[:8], data=1, expert=8)
        rng = numpy.random.RandomState(0)
        params = init_moe_params(rng, 8, 16, 32)
        # adversarial: identical tokens all route to ONE expert; a tight
        # capacity must drop most of them and say so
        tokens = jnp.ones((64, 16), jnp.float32)
        moe = make_moe_ffn(mesh, 8, capacity_factor=1.0)
        y, drop_frac = moe(shard_moe_params(params, mesh), tokens)
        assert float(drop_frac) > 0.5
        # dropped tokens produce zero output rows (GShard semantics)
        zero_rows = (numpy.abs(numpy.asarray(y)).sum(axis=1) < 1e-7).sum()
        assert zero_rows >= 32

class TestSequenceParallelTraining:
    """The dp x sp transformer train step (parallel/transformer_step.py):
    sequence-parallel TRAINING, not just the attention op."""

    def _data(self, b=4, t=32, e=16, vocab=11, seed=0):
        rng = numpy.random.RandomState(seed)
        x = jnp.asarray(rng.randn(b, t, e).astype(numpy.float32) * 0.3)
        labels = jnp.asarray(rng.randint(0, vocab, (b, t)))
        return rng, x, labels

    def test_dp_sp_matches_single_device(self):
        from veles_tpu.parallel.mesh import build_mesh
        from veles_tpu.parallel.transformer_step import (
            build_transformer_train_step, init_transformer_params,
            shard_tokens)

        rng, x, labels = self._data()
        params = init_transformer_params(rng, n_blocks=2, embed=16,
                                         heads=4, vocab=11)
        single = build_transformer_train_step(heads=4)
        p1, (loss1, err1) = single(params, x, labels)

        mesh = build_mesh(data=2, seq=4)
        sharded = build_transformer_train_step(heads=4, mesh=mesh)
        xs, ls = shard_tokens([x, labels], mesh)
        p2, (loss2, err2) = sharded(params, xs, ls)
        assert float(loss1) == pytest.approx(float(loss2), rel=1e-5)
        assert int(err1) == int(err2)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            numpy.testing.assert_allclose(numpy.asarray(a),
                                          numpy.asarray(b),
                                          rtol=1e-4, atol=1e-5)

    def test_ring_strategy_matches_ulysses(self):
        """Ring attention is scan-based and differentiable: a ring-SP
        train step must match the Ulysses one on identical inputs."""
        from veles_tpu.parallel.mesh import build_mesh
        from veles_tpu.parallel.transformer_step import (
            build_transformer_train_step, init_transformer_params,
            shard_tokens)

        rng, x, labels = self._data(seed=5)
        params = init_transformer_params(rng, n_blocks=1, embed=16,
                                         heads=4, vocab=11)
        mesh = build_mesh(data=2, seq=4)
        xs, ls = shard_tokens([x, labels], mesh)
        outs = {}
        for strategy in ("ulysses", "ring"):
            step = build_transformer_train_step(heads=4, mesh=mesh,
                                                sp_strategy=strategy)
            outs[strategy] = step(params, xs, ls)
        pu, (lu, eu) = outs["ulysses"]
        pr, (lr, er) = outs["ring"]
        assert float(lu) == pytest.approx(float(lr), rel=1e-4)
        assert int(eu) == int(er)
        for a, b in zip(jax.tree.leaves(pu), jax.tree.leaves(pr)):
            numpy.testing.assert_allclose(
                numpy.asarray(a), numpy.asarray(b), rtol=1e-3,
                atol=1e-4)

    def test_training_reduces_loss(self):
        from veles_tpu.parallel.mesh import build_mesh
        from veles_tpu.parallel.transformer_step import (
            build_transformer_train_step, init_transformer_params,
            shard_tokens)

        rng, x, labels = self._data(seed=2)
        params = init_transformer_params(rng, n_blocks=1, embed=16,
                                         heads=4, vocab=11)
        mesh = build_mesh(data=2, seq=4)
        step = build_transformer_train_step(heads=4, mesh=mesh,
                                            learning_rate=0.5)
        xs, ls = shard_tokens([x, labels], mesh)
        first = None
        for i in range(12):
            params, (loss, _) = step(params, xs, ls)
            if first is None:
                first = float(loss)
        assert float(loss) < first * 0.7, \
            "loss %.4f -> %.4f: sp training not learning" % (first,
                                                             float(loss))


class TestPipelineTraining:
    """Differentiable pipeline: the train step's grads
    must match the sequential single-device reference, and training
    must actually reduce the loss."""

    def _setup(self, n_stages=4, n_micro=8, d=8, dp=1):
        from veles_tpu.parallel.pipeline import shard_stage_weights
        mesh = build_mesh(devices=jax.devices()[:n_stages * dp],
                          data=dp, pipe=n_stages)
        rng = numpy.random.RandomState(0)
        weights = {
            "w": jnp.asarray(rng.randn(n_stages, d, d).astype(
                numpy.float32) * 0.3),
            "b": jnp.asarray(rng.randn(n_stages, d).astype(
                numpy.float32) * 0.1)}
        batch = jnp.asarray(rng.randn(n_micro * 4 * dp, d).astype(
            numpy.float32))
        targets = jnp.asarray(rng.randn(batch.shape[0], d).astype(
            numpy.float32))

        def stage(w, x):
            return jnp.tanh(x @ w["w"] + w["b"])

        return mesh, stage, weights, batch, targets

    @staticmethod
    def _mse(outputs, targets):
        return jnp.mean((outputs - targets) ** 2)

    def _sequential_step(self, stage, weights, batch, targets, lr):
        from veles_tpu.parallel.pipeline import sequential_reference

        def loss_fn(w):
            return self._mse(sequential_reference(stage, w, batch),
                             targets)

        loss, grads = jax.value_and_grad(loss_fn)(weights)
        new = jax.tree.map(lambda w, g: w - lr * g, weights, grads)
        return new, loss

    @pytest.mark.parametrize("n_stages,n_micro", [(4, 8), (8, 4)])
    def test_train_step_matches_sequential(self, n_stages, n_micro):
        from veles_tpu.parallel.pipeline import (
            make_pipeline_train_step, shard_stage_weights)

        mesh, stage, weights, batch, targets = self._setup(
            n_stages, n_micro)
        step = make_pipeline_train_step(mesh, stage, n_micro, self._mse,
                                        learning_rate=0.1)
        got_w, got_loss = step(shard_stage_weights(weights, mesh),
                               batch, targets)
        want_w, want_loss = self._sequential_step(stage, weights, batch,
                                                  targets, 0.1)
        numpy.testing.assert_allclose(float(got_loss), float(want_loss),
                                      rtol=1e-5)
        for key in ("w", "b"):
            numpy.testing.assert_allclose(
                numpy.asarray(got_w[key]), numpy.asarray(want_w[key]),
                rtol=2e-4, atol=2e-5)

    def test_pp_dp_composition_matches(self):
        """pp4 x dp2: sharded batch + psum-merged grads must equal the
        single-device sequential step on the SAME global batch."""
        from veles_tpu.parallel.pipeline import (
            make_pipeline_train_step, shard_stage_weights)

        mesh, stage, weights, batch, targets = self._setup(
            n_stages=4, n_micro=4, dp=2)
        step = make_pipeline_train_step(mesh, stage, 4, self._mse,
                                        learning_rate=0.1)
        got_w, got_loss = step(shard_stage_weights(weights, mesh),
                               batch, targets)
        want_w, want_loss = self._sequential_step(stage, weights, batch,
                                                  targets, 0.1)
        numpy.testing.assert_allclose(float(got_loss), float(want_loss),
                                      rtol=1e-5)
        for key in ("w", "b"):
            numpy.testing.assert_allclose(
                numpy.asarray(got_w[key]), numpy.asarray(want_w[key]),
                rtol=2e-4, atol=2e-5)

    def test_training_reduces_loss(self):
        from veles_tpu.parallel.pipeline import (
            make_pipeline_train_step, shard_stage_weights)

        mesh, stage, weights, batch, targets = self._setup()
        # a learnable objective: match the output of a "teacher" with
        # different weights
        rng = numpy.random.RandomState(7)
        targets = jnp.tanh(batch @ jnp.asarray(
            rng.randn(8, 8).astype(numpy.float32) * 0.3))
        step = make_pipeline_train_step(mesh, stage, 8, self._mse,
                                        learning_rate=0.2)
        w = shard_stage_weights(weights, mesh)
        losses = []
        for _ in range(30):
            w, loss = step(w, batch, targets)
            losses.append(float(loss))
        # grads are proven exact against the sequential reference above;
        # this asserts the optimization loop actually descends
        assert losses[-1] < losses[0] * 0.6, losses
        assert all(b <= a + 1e-4 for a, b in zip(losses, losses[1:])), \
            losses


class TestExpertTraining:
    """Differentiable MoE: grads through dispatch,
    all_to_all and the gate-probability combine."""

    def _setup(self, n_experts=8, ep=8, tokens=64, d=16, h=32):
        from veles_tpu.parallel.expert import (init_moe_params,
                                               shard_moe_params)
        mesh = build_mesh(devices=jax.devices()[:ep], data=1, expert=ep)
        rng = numpy.random.RandomState(0)
        params = init_moe_params(rng, n_experts, d, h)
        x = jnp.asarray(rng.randn(tokens, d).astype(numpy.float32))
        targets = jnp.asarray(rng.randn(tokens, d).astype(
            numpy.float32) * 0.1)
        return mesh, params, shard_moe_params(params, mesh), x, targets

    def test_train_step_matches_dense_reference(self):
        """With capacity ample enough that nothing drops, one sharded
        train step must equal the dense single-device reference step."""
        from veles_tpu.parallel.expert import (make_moe_train_step,
                                               reference_moe)

        mesh, params, sharded, x, targets = self._setup()
        step = make_moe_train_step(mesh, 8, capacity_factor=8.0,
                                   learning_rate=0.05)
        got_p, got_loss = step(sharded, x, targets)

        def dense_loss(p):
            return jnp.mean((reference_moe(p, x) - targets) ** 2)

        want_loss, grads = jax.value_and_grad(dense_loss)(
            jax.tree.map(jnp.asarray, params))
        want_p = jax.tree.map(lambda w, g: w - 0.05 * g,
                              jax.tree.map(jnp.asarray, params), grads)
        numpy.testing.assert_allclose(float(got_loss), float(want_loss),
                                      rtol=1e-5)
        for key in ("gate", "w1", "b1", "w2", "b2"):
            numpy.testing.assert_allclose(
                numpy.asarray(got_p[key]), numpy.asarray(want_p[key]),
                rtol=2e-4, atol=2e-5)

    def test_training_reduces_loss(self):
        from veles_tpu.parallel.expert import make_moe_train_step

        mesh, params, sharded, x, targets = self._setup()
        step = make_moe_train_step(mesh, 8, capacity_factor=4.0,
                                   learning_rate=0.1)
        p = sharded
        losses = []
        for _ in range(20):
            p, loss = step(p, x, targets)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.7, losses
