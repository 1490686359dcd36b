"""Tests for the veles_tpu.ops library (the Znicz-kernel equivalents)."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu import ops
from veles_tpu.ops import activations, losses
from veles_tpu.ops.gemm import matmul, pallas_matmul


class TestGemm:
    def test_matmul_matches_numpy(self):
        rng = numpy.random.RandomState(0)
        a = rng.rand(17, 33).astype(numpy.float32)
        b = rng.rand(33, 9).astype(numpy.float32)
        out = matmul(jnp.asarray(a), jnp.asarray(b), precision_level=2)
        numpy.testing.assert_allclose(out, a @ b, rtol=1e-5)

    def test_precision_levels_all_close(self):
        rng = numpy.random.RandomState(1)
        a = rng.rand(32, 64).astype(numpy.float32)
        b = rng.rand(64, 16).astype(numpy.float32)
        ref = a @ b
        for level in (0, 1, 2):
            out = matmul(jnp.asarray(a), jnp.asarray(b),
                         precision_level=level)
            # level 0 is bf16 passes; level 1 ~ bf16x3 ("Kahan" tier)
            tol = {0: 2e-2, 1: 1e-3, 2: 1e-5}[level]
            numpy.testing.assert_allclose(out, ref, rtol=tol)

    def test_pallas_matmul_interpret(self):
        """Blocked Pallas kernel vs numpy, incl. ragged shapes (padding)."""
        rng = numpy.random.RandomState(2)
        for m, k, n in ((128, 128, 128), (130, 70, 50)):
            a = rng.rand(m, k).astype(numpy.float32)
            b = rng.rand(k, n).astype(numpy.float32)
            out = pallas_matmul(jnp.asarray(a), jnp.asarray(b),
                                out_dtype=jnp.float32,
                                bm=64, bn=64, bk=64, interpret=True)
            numpy.testing.assert_allclose(out, a @ b, rtol=1e-4)


class TestAutotuneCacheHygiene:
    """ISSUE 5 satellite: the autotune cache must
    reject physically impossible entries — the two-length slope
    estimator can go negative under timing jitter, and a persisted
    negative timing gated a product matmul on a measurement that never
    happened."""

    @pytest.fixture
    def cache_file(self, tmp_path, monkeypatch):
        from veles_tpu.core.config import root
        from veles_tpu.ops import gemm

        path = str(tmp_path / "pallas_tuning.json")
        monkeypatch.setattr(root.common.engine, "pallas_autotune_cache",
                            path, raising=False)
        monkeypatch.setattr(gemm, "_tuning_cache", None, raising=False)
        monkeypatch.setattr(gemm, "_insane_warned", False,
                            raising=False)
        return path

    def test_poisoned_rows_dropped_at_load_and_file_cleaned(
            self, cache_file, caplog):
        import json
        import logging

        from veles_tpu.ops import gemm

        # the literal r5 artifact shape: a negative xla_seconds beside
        # healthy rows
        poisoned = {
            "bfloat16:10": {"blocks": [256, 256, 512],
                            "seconds": 9.4e-05,
                            "xla_seconds": -0.000107,
                            "beats_xla": True},
            "bfloat16:11": {"blocks": [512, 512, 512],
                            "seconds": 2e-4, "xla_seconds": 3e-4,
                            "beats_xla": True},
            "int8:1024x4096": {"use_pallas": True, "block_n": 512,
                               "seconds": 0.0},
        }
        with open(cache_file, "w") as fout:
            json.dump(poisoned, fout)
        with caplog.at_level(logging.WARNING, logger="gemm.autotune"):
            cache = gemm._load_cache()
        assert set(cache) == {"bfloat16:11"}
        # the artifact on disk is cleaned too — it stops advertising
        # the impossible measurement
        assert set(json.load(open(cache_file))) == {"bfloat16:11"}
        warnings = [r for r in caplog.records
                    if "physically impossible" in r.getMessage()]
        assert len(warnings) == 1  # warn-once

    def test_dropped_bucket_retunes_as_default(self, cache_file):
        import json

        from veles_tpu.ops import gemm

        with open(cache_file, "w") as fout:
            json.dump({"bfloat16:10": {"blocks": [128, 128, 512],
                                       "seconds": -1.0,
                                       "beats_xla": True}}, fout)
        # the poisoned verdict must not engage the kernel...
        a = jnp.ones((1024, 1024), jnp.bfloat16)
        assert gemm._tuned_beats_xla(a, a) is False
        # ...and the block lookup falls back to the defaults
        assert gemm._tuned_blocks(1024, 1024, 1024, "bfloat16") \
            == gemm._DEFAULT_BLOCKS

    def test_persist_rejects_insane_rows(self, cache_file):
        import json

        from veles_tpu.ops import gemm

        gemm._persist_cache({
            "good": {"blocks": [1, 1, 1], "seconds": 1e-4,
                     "xla_seconds": 2e-4, "beats_xla": True},
            "negative": {"blocks": [1, 1, 1], "seconds": -1e-4},
            "zero": {"blocks": [1, 1, 1], "seconds": 0.0},
            "nan": {"blocks": [1, 1, 1], "seconds": float("nan")},
            "inf": {"blocks": [1, 1, 1], "xla_seconds": float("inf")},
            "not-a-dict": 7,
        })
        assert set(json.load(open(cache_file))) == {"good"}

    def test_sane_entry_predicate(self):
        from veles_tpu.ops import gemm

        assert gemm._sane_entry({"seconds": 1e-5, "xla_seconds": 2e-5})
        assert gemm._sane_entry({"blocks": [1, 2, 3]})  # no timings
        assert not gemm._sane_entry({"seconds": -1e-5})
        assert not gemm._sane_entry({"xla_seconds": 0})
        assert not gemm._sane_entry({"seconds": True})
        assert not gemm._sane_entry([1, 2])


class TestActivations:
    @pytest.mark.parametrize("name", list(activations.ACTIVATIONS))
    def test_deriv_matches_autodiff(self, name):
        fwd, deriv = activations.ACTIVATIONS[name]
        x = jnp.linspace(-2.0, 2.0, 41)
        if name == "strict_relu":
            x = x + 0.013  # avoid the kink
        y = fwd(x)
        expected = jax.vmap(jax.grad(lambda v: fwd(v)))(x)
        numpy.testing.assert_allclose(deriv(y), expected,
                                      rtol=1e-3, atol=1e-4)


class TestLosses:
    def test_softmax_xent_err_matches_autodiff(self):
        rng = numpy.random.RandomState(3)
        logits = jnp.asarray(rng.randn(8, 5).astype(numpy.float32))
        labels = jnp.asarray(rng.randint(0, 5, 8))
        err, loss, n_err, max_conf = losses.softmax_cross_entropy(
            logits, labels)
        grad = jax.grad(
            lambda lg: losses.softmax_cross_entropy(lg, labels)[1])(logits)
        numpy.testing.assert_allclose(err, grad, rtol=1e-4, atol=1e-6)
        assert 0 <= int(n_err) <= 8
        assert 0.0 < float(max_conf) <= 1.0

    def test_confusion_matrix(self):
        logits = jnp.asarray([[9.0, 0.0], [0.0, 9.0], [9.0, 0.0]])
        labels = jnp.asarray([0, 1, 1])
        cm = losses.confusion_matrix(logits, labels, 2)
        numpy.testing.assert_array_equal(cm, [[1, 0], [1, 1]])

    def test_mse_err_matches_autodiff(self):
        rng = numpy.random.RandomState(4)
        out = jnp.asarray(rng.randn(6, 3).astype(numpy.float32))
        tgt = jnp.asarray(rng.randn(6, 3).astype(numpy.float32))
        err, loss, max_err = losses.mse(out, tgt)
        grad = jax.grad(lambda o: losses.mse(o, tgt)[1])(out)
        numpy.testing.assert_allclose(err, grad, rtol=1e-4, atol=1e-6)


class TestDataOps:
    def test_gather_minibatch(self):
        data = jnp.arange(20.0).reshape(10, 2)
        labels = jnp.arange(10)
        idx = jnp.asarray([3, 7, 1])
        batch, lab = ops.gather_minibatch(data, idx, labels)
        numpy.testing.assert_array_equal(lab, [3, 7, 1])
        numpy.testing.assert_array_equal(batch[0], [6.0, 7.0])

    def test_gather_with_normalize(self):
        data = jnp.ones((4, 3))
        idx = jnp.asarray([0, 1])
        batch = ops.gather_minibatch(data, idx, scale=2.0, shift=-1.0)
        numpy.testing.assert_array_equal(batch, numpy.ones((2, 3)))

    def test_rng_reproducible(self):
        key = jax.random.PRNGKey(42)
        a = ops.uniform(key, (4, 4))
        b = ops.uniform(key, (4, 4))
        numpy.testing.assert_array_equal(a, b)
        assert float(jnp.min(a)) >= -1.0 and float(jnp.max(a)) <= 1.0

    def test_reduce(self):
        x = jnp.arange(12.0).reshape(3, 4)
        numpy.testing.assert_array_equal(ops.reduce_sum(x, 0),
                                         [12.0, 15.0, 18.0, 21.0])
        assert float(ops.reduce_max(x, None)) == 11.0


class TestDenseEpilogue:
    """Fused matmul+bias+activation kernel (the Pallas product consumer)
    — forward parity in interpret mode, and the custom
    VJP against jax.grad of the XLA path."""

    def test_pallas_dense_interpret_matches_xla(self):
        import numpy
        from veles_tpu.ops.gemm import pallas_dense

        rng = numpy.random.RandomState(0)
        x = rng.randn(96, 80).astype(numpy.float32)
        w = rng.randn(80, 64).astype(numpy.float32)
        b = rng.randn(64).astype(numpy.float32)
        got = pallas_dense(jnp.asarray(x), jnp.asarray(w),
                           jnp.asarray(b), activation="tanh",
                           bm=32, bn=32, bk=16, interpret=True)
        # the library "tanh" is Znicz's scaled 1.7159*tanh(0.6666x)
        from veles_tpu.ops import activations as act_lib
        want = act_lib.ACTIVATIONS["tanh"][0](jnp.asarray(x @ w + b))
        numpy.testing.assert_allclose(numpy.asarray(got),
                                      numpy.asarray(want),
                                      rtol=2e-5, atol=2e-5)

    def test_dense_layer_custom_vjp_matches_xla_grads(self, monkeypatch):
        import numpy
        from veles_tpu.core.config import root
        from veles_tpu.ops import gemm

        rng = numpy.random.RandomState(1)
        x = jnp.asarray(rng.randn(64, 48).astype(numpy.float32))
        w = jnp.asarray(rng.randn(48, 32).astype(numpy.float32))
        b = jnp.asarray(rng.randn(32).astype(numpy.float32))

        # force the pallas path through interpret-mode (CPU) by
        # monkeypatching eligibility + the kernel call
        monkeypatch.setattr(gemm, "_pallas_eligible",
                            lambda a, bb: True)
        real = gemm.pallas_dense

        def interp(a, bb, bias, activation="linear", **kw):
            kw.update(bm=32, bn=32, bk=16, interpret=True)
            return real(a, bb, bias, activation=activation, **kw)

        monkeypatch.setattr(gemm, "pallas_dense", interp)
        real_mm = gemm.pallas_matmul

        def interp_mm(a, bb, **kw):
            # the custom bwd's matmuls hit the patched eligibility too
            kw.update(bm=32, bn=32, bk=16, interpret=True)
            return real_mm(a, bb, **kw)

        monkeypatch.setattr(gemm, "pallas_matmul", interp_mm)
        monkeypatch.setattr(root.common.engine, "precision_level", 1,
                            raising=False)
        gemm._dense_with_vjp.cache_clear()

        def loss_pallas(x, w, b):
            return jnp.sum(gemm.dense_layer(x, w, b, activation="tanh",
                                            use_pallas=True) ** 2)

        def loss_xla(x, w, b):
            return jnp.sum(gemm.dense_layer(x, w, b, activation="tanh",
                                            use_pallas=False) ** 2)

        got = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, w, b)
        want = jax.grad(loss_xla, argnums=(0, 1, 2))(x, w, b)
        for g, e in zip(got, want):
            numpy.testing.assert_allclose(numpy.asarray(g),
                                          numpy.asarray(e),
                                          rtol=2e-4, atol=2e-4)
        gemm._dense_with_vjp.cache_clear()
