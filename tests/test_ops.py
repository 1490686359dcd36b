"""Tests for the veles_tpu.ops library (the Znicz-kernel equivalents)."""

import numpy
import pytest

import jax
import jax.numpy as jnp

from veles_tpu import ops
from veles_tpu.ops import activations, losses
from veles_tpu.ops.gemm import dense_layer, matmul


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

    @pytest.mark.parametrize("level", (0, 1, 2))
    def test_lowers_to_one_dot_without_a_custom_call(self, level):
        """Lowered for the TPU (no chip needed), ``matmul`` and
        ``dense_layer`` are XLA's dot and nothing of ours: one
        ``dot_general`` each, no custom call, at every precision."""
        a = jax.ShapeDtypeStruct((512, 1024), jnp.float32)
        b = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
        bias = jax.ShapeDtypeStruct((512,), jnp.float32)
        for fn, args in (
                (lambda a, b: matmul(a, b, precision_level=level),
                 (a, b)),
                (lambda a, b, bias: dense_layer(
                    a, b, bias, activation="tanh",
                    precision_level=level), (a, b, bias))):
            text = jax.export.export(
                jax.jit(fn), platforms=["tpu"])(*args).mlir_module()
            assert text.count("= stablehlo.dot_general") == 1, text
            assert "stablehlo.custom_call" not in text, text


def _rule_cases():
    """(id, module name, platform is the TPU, call on the module, the
    rule's answer): every kernel rule on both sides of each of its
    conditions."""
    def qk(t, d):
        x = jax.ShapeDtypeStruct((1, t, 2, d), jnp.bfloat16)
        return lambda m: m.use_flash(x, x)

    def int8(rows, k, n):
        return lambda m: m.use_int8_kernel(rows, k, n)

    def paged(mesh):
        def call(m):
            devices = numpy.array(jax.devices()[:2])
            return m.use_paged_kernel(
                jax.sharding.Mesh(devices, ("model",)) if mesh else None)
        return call

    def experts(rows, width, inner):
        leaf = jax.ShapeDtypeStruct((4, width, inner), jnp.bfloat16)
        return lambda m: m.expert_plan(rows, {"w_gate": leaf})[0]

    return [
        ("flash-t4096-d128", "attention", True, qk(4096, 128), True),
        ("flash-t2048", "attention", True, qk(2048, 128), False),
        ("flash-d64", "attention", True, qk(4096, 64), False),
        ("flash-cpu", "attention", False, qk(4096, 128), False),
        ("int8-decode-rows", "quant", True, int8(8, 1024, 3072), True),
        ("int8-prefill-rows", "quant", True,
         int8(1024, 1024, 3072), False),
        ("int8-n-off-512", "quant", True, int8(8, 1024, 1000), False),
        ("int8-k-off-32", "quant", True, int8(8, 1000, 3072), False),
        ("int8-cpu", "quant", False, int8(8, 1024, 3072), False),
        ("paged-tpu", "paged_attention", True, paged(False), True),
        ("paged-mesh", "paged_attention", True, paged(True), False),
        ("paged-cpu", "paged_attention", False, paged(False), False),
        ("experts-decode-rows", "moe", True,
         experts(256, 2048, 768), "streamed"),
        ("experts-prefill-rows", "moe", True,
         experts(1024, 2048, 768), "tiled"),
        ("experts-prefill-rows-cpu", "moe", False,
         experts(1024, 2048, 768), "grouped"),
        ("experts-prefill-off-lane", "moe", True,
         experts(1024, 2000, 768), "grouped"),
        ("experts-off-lane", "moe", True,
         experts(256, 2048, 200), "grouped"),
        ("experts-cpu", "moe", False,
         experts(256, 2048, 768), "grouped"),
    ]


class TestKernelRules:
    """Every kernel is chosen by one function in the module that owns
    it, over the platform, static shapes and the mesh
    (``ops/platform.py``): nothing else moves the answer."""

    @pytest.mark.parametrize("case", _rule_cases(), ids=lambda c: c[0])
    def test_rule(self, case, monkeypatch):
        import importlib

        _, name, tpu, call, want = case
        module = importlib.import_module("veles_tpu.ops." + name)
        monkeypatch.setattr(module, "on_tpu", lambda: tpu)
        assert call(module) == want



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
