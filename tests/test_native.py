"""Native runtime tests: export a trained workflow, build the C++ runtime,
and check its inference matches the JAX forward pass bit-for-bit-ish
(the reference's libVeles/tests tier, driven from Python)."""

import io
import os
import subprocess
import tarfile

import numpy
import pytest

import jax.numpy as jnp

from veles_tpu.dummy import DummyLauncher
from veles_tpu.export import package_export
from veles_tpu.inference import BUILD_DIR, NativeWorkflow, build_native
from veles_tpu.models.mlp import MLPWorkflow
from veles_tpu.models.standard import StandardWorkflow


def _digits():
    from sklearn.datasets import load_digits
    d = load_digits()
    X = d.data.astype(numpy.float32)
    y = d.target.astype(numpy.int32)
    return X, y


@pytest.fixture(scope="module")
def native_lib():
    try:
        return build_native()
    except subprocess.CalledProcessError as e:
        pytest.fail("native build failed:\n%s" % e.stderr.decode()[-3000:])


@pytest.fixture(scope="module")
def trained_mlp():
    X, y = _digits()
    wf = MLPWorkflow(
        DummyLauncher(), layers=(16, 10),
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 297, 1500],
                           minibatch_size=300,
                           normalization_type="linear"),
        learning_rate=0.1, max_epochs=2, name="export-test")
    wf.initialize()
    wf.run()
    return wf


def test_cpp_unit_tests(native_lib, trained_mlp, tmp_path_factory):
    """Run the C++ test binary against generated fixtures."""
    fixture_dir = str(tmp_path_factory.mktemp("fixtures"))
    # npy fixture
    buf = io.BytesIO()
    numpy.save(buf, numpy.arange(6, dtype=numpy.float32).reshape(2, 3))
    with tarfile.open(os.path.join(fixture_dir, "npy_fixture.tar"),
                      "w") as tar:
        info = tarfile.TarInfo("m.npy")
        blob = buf.getvalue()
        info.size = len(blob)
        tar.addfile(info, io.BytesIO(blob))
    package_export(trained_mlp,
                   os.path.join(fixture_dir, "mlp_package.tar"))
    proc = subprocess.run(
        [os.path.join(BUILD_DIR, "veles_rt_tests"), fixture_dir],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_native_matches_jax_forward(native_lib, trained_mlp, tmp_path):
    package = str(tmp_path / "mlp.tar")
    package_export(trained_mlp, package)
    rt = NativeWorkflow(package)
    assert rt.unit_count == 2
    assert rt.input_size == 64
    assert rt.output_size == 10

    X, _ = _digits()
    batch = X[:32] / numpy.abs(X).max()  # loader-normalized scale
    native_out = rt.run(batch)

    # jax forward with the same weights (softmax applied to the logits)
    w0 = trained_mlp.forwards[0].weights.data
    b0 = trained_mlp.forwards[0].bias.data
    w1 = trained_mlp.forwards[1].weights.data
    b1 = trained_mlp.forwards[1].bias.data
    h = 1.7159 * jnp.tanh(0.6666 * (jnp.asarray(batch) @ w0 + b0))
    logits = h @ w1 + b1
    jax_out = numpy.asarray(jnp.exp(logits) /
                            jnp.sum(jnp.exp(logits), -1, keepdims=True))
    numpy.testing.assert_allclose(native_out, jax_out, rtol=2e-3,
                                  atol=1e-5)
    # agreement on predictions
    numpy.testing.assert_array_equal(native_out.argmax(-1),
                                     jax_out.argmax(-1))


def test_native_convnet(native_lib, tmp_path):
    """Conv + pooling + dense export path."""
    from sklearn.datasets import load_digits
    d = load_digits()
    X = (d.images.astype(numpy.float32) / 16.0)[..., None]
    y = d.target.astype(numpy.int32)
    wf = StandardWorkflow(
        DummyLauncher(),
        layers=[
            {"type": "conv_strict_relu", "n_kernels": 4, "kx": 3, "ky": 3},
            {"type": "max_pooling", "kx": 2, "ky": 2},
            {"type": "softmax", "output_sample_shape": (10,)},
        ],
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 297, 1500],
                           minibatch_size=300),
        learning_rate=0.1, decision_kwargs=dict(max_epochs=1),
        name="conv-export")
    wf.initialize()
    wf.run()
    package = str(tmp_path / "conv.tar")
    from veles_tpu.export import package_export as export
    export(wf, package)
    rt = NativeWorkflow(package)
    assert rt.unit_count == 3

    batch = X[:8]
    native_out = rt.run(batch)
    # compare against the python units' own forward
    wf.loader.minibatch_data.data = jnp.asarray(batch)
    for fwd in wf.forwards:
        fwd.run()
    jax_logits = numpy.asarray(wf.forwards[-1].output.mem)[:8]
    jax_probs = numpy.exp(jax_logits) / numpy.exp(jax_logits).sum(
        -1, keepdims=True)
    numpy.testing.assert_allclose(native_out, jax_probs, rtol=2e-2,
                                  atol=2e-4)


def test_native_transformer(native_lib, tmp_path):
    """The complete pre-LN transformer block — layer_norm → residual
    self_attention → layer_norm → residual ffn → softmax head — through
    export: the C++ runtime's transformer tier must match the JAX
    units' forward."""
    rng = numpy.random.RandomState(0)
    n, t, e = 400, 6, 16
    X = rng.randn(n, t, e).astype(numpy.float32) * 0.2
    y = rng.randint(0, 2, n).astype(numpy.int32)
    wf = StandardWorkflow(
        DummyLauncher(),
        layers=[
            {"type": "layer_norm"},
            {"type": "self_attention", "heads": 4, "residual": True},
            {"type": "layer_norm"},
            {"type": "ffn", "ratio": 2},
            {"type": "softmax", "output_sample_shape": (2,)},
        ],
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 100, 300],
                           minibatch_size=100),
        learning_rate=0.05, decision_kwargs=dict(max_epochs=1),
        name="attn-export")
    wf.initialize()
    wf.run()
    package = str(tmp_path / "attn.tar")
    package_export(wf, package)
    rt = NativeWorkflow(package)
    assert rt.unit_count == 5

    batch = X[:8]
    native_out = rt.run(batch)
    wf.loader.minibatch_data.data = jnp.asarray(batch)
    for fwd in wf.forwards:
        fwd.run()
    jax_logits = numpy.asarray(wf.forwards[-1].output.mem)[:8]
    jax_probs = numpy.exp(jax_logits) / numpy.exp(jax_logits).sum(
        -1, keepdims=True)
    numpy.testing.assert_allclose(native_out, jax_probs, rtol=2e-2,
                                  atol=2e-4)


def test_native_causal_attention(native_lib, tmp_path):
    """The causal mask must match (build an untrained causal stack and
    compare raw forwards)."""
    rng = numpy.random.RandomState(1)
    n, t, e = 300, 5, 8
    X = rng.randn(n, t, e).astype(numpy.float32) * 0.3
    y = rng.randint(0, 2, n).astype(numpy.int32)
    wf = StandardWorkflow(
        DummyLauncher(),
        layers=[
            {"type": "self_attention", "heads": 2, "causal": True},
            {"type": "softmax", "output_sample_shape": (2,)},
        ],
        loader_kwargs=dict(data=X, labels=y, class_lengths=[0, 100, 200],
                           minibatch_size=100),
        learning_rate=0.0, decision_kwargs=dict(max_epochs=1),
        name="causal-export")
    wf.initialize()
    wf.run()
    package = str(tmp_path / "causal.tar")
    package_export(wf, package)
    rt = NativeWorkflow(package)
    batch = X[:4]
    native_out = rt.run(batch)
    wf.loader.minibatch_data.data = jnp.asarray(batch)
    for fwd in wf.forwards:
        fwd.run()
    jax_logits = numpy.asarray(wf.forwards[-1].output.mem)[:4]
    jax_probs = numpy.exp(jax_logits) / numpy.exp(jax_logits).sum(
        -1, keepdims=True)
    numpy.testing.assert_allclose(native_out, jax_probs, rtol=2e-2,
                                  atol=2e-4)


class TestMalformedPackages:
    """The runtime consumes arbitrary packages: malformed input must
    produce a clean Python error (the C API catches std::exception),
    never a crash or an out-of-bounds read."""

    def _load(self, path):
        from veles_tpu.inference import NativeWorkflow
        return NativeWorkflow(path)

    def _tar_with(self, tmp_path, members):
        path = str(tmp_path / "pkg.tar")
        with tarfile.open(path, "w") as tar:
            for name, payload in members.items():
                info = tarfile.TarInfo(name)
                info.size = len(payload)
                tar.addfile(info, io.BytesIO(payload))
        return path

    @staticmethod
    def _all2all_contents():
        """One shared minimal all2all package manifest — the schema under
        test lives in one place."""
        import json
        return json.dumps({
            "workflow": "x", "input_shape": [4],
            "units": [{"name": "u0", "type": "all2all",
                       "config": {"activation": "tanh",
                                  "out_features": 2},
                       "arrays": {"weights": "@w.npy",
                                  "bias": "@b.npy"}}]}).encode()

    def test_not_a_tar(self, native_lib, tmp_path):
        bad = tmp_path / "junk.tar"
        bad.write_bytes(os.urandom(512))
        with pytest.raises(RuntimeError):
            self._load(str(bad))

    def test_missing_contents(self, native_lib, tmp_path):
        path = self._tar_with(tmp_path, {"other.npy": b"\x00" * 16})
        with pytest.raises(RuntimeError):
            self._load(path)

    def test_broken_json(self, native_lib, tmp_path):
        path = self._tar_with(tmp_path, {"contents.json": b"{unclosed"})
        with pytest.raises(RuntimeError):
            self._load(path)

    def test_unknown_unit_type(self, native_lib, tmp_path):
        import json
        contents = json.dumps({
            "workflow": "x", "input_shape": [4],
            "units": [{"name": "u0", "type": "quantum_flux",
                       "config": {}, "arrays": {}}]}).encode()
        path = self._tar_with(tmp_path, {"contents.json": contents})
        with pytest.raises(RuntimeError, match="quantum_flux"):
            self._load(path)

    def test_missing_array_member(self, native_lib, tmp_path):
        path = self._tar_with(
            tmp_path, {"contents.json": self._all2all_contents()})
        with pytest.raises(RuntimeError):
            self._load(path)

    def test_truncated_npy(self, native_lib, tmp_path):
        path = self._tar_with(tmp_path, {
            "contents.json": self._all2all_contents(),
            "w.npy": b"\x93NUMPY garbage",
            "b.npy": b"\x00" * 8})
        with pytest.raises(RuntimeError):
            self._load(path)

    def test_shape_mismatch_rejected(self, native_lib, tmp_path):
        """weights rows != input size must throw at load/infer time."""
        def npy(arr):
            buf = io.BytesIO()
            numpy.save(buf, arr)
            return buf.getvalue()

        path = self._tar_with(tmp_path, {
            "contents.json": self._all2all_contents(),
            "w.npy": npy(numpy.zeros((7, 2), numpy.float32)),  # 7 != 4
            "b.npy": npy(numpy.zeros(2, numpy.float32))})
        with pytest.raises(RuntimeError):
            self._load(path)

    def test_f16_export_half_size_and_parity(self, native_lib,
                                             tmp_path):
        """``precision=16`` (the reference workflow.py:864-975 API):
        float16 weights, ~half the package size, and the native
        runtime's f2->f32 widening keeps inference within the f16
        quantization tolerance of the f32 package."""
        from sklearn.datasets import load_digits

        from veles_tpu.core import prng

        # seeded: the argmax comparison below is exact, and weights
        # drawn from whatever state an earlier test of the same
        # process left can put one sample of 64 at a near tie
        prng.get("default").seed(1)
        prng.get("loader").seed(1)
        d = load_digits()
        X = d.data.astype(numpy.float32)
        y = d.target.astype(numpy.int32)
        wf = MLPWorkflow(
            DummyLauncher(), layers=(16, 10),
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 297, 1500],
                               minibatch_size=300,
                               normalization_type="linear"),
            learning_rate=0.1, max_epochs=2, name="f16-export")
        wf.initialize()
        wf.run()
        p32 = str(tmp_path / "w32.tar")
        p16 = str(tmp_path / "w16.tar")
        package_export(wf, p32, precision=32)
        package_export(wf, p16, precision=16)
        # the .npy members dominate the tar: halving the dtype must
        # show up in the file size (tar rounds members to 512B blocks)
        assert os.path.getsize(p16) < 0.65 * os.path.getsize(p32)
        with tarfile.open(p16) as tar:
            blob = tar.extractfile("fwd0_weights.npy").read()
            assert numpy.load(io.BytesIO(blob)).dtype == numpy.float16
        batch = X[:64] / numpy.abs(X).max()
        out32 = self._load(p32).run(batch)
        out16 = self._load(p16).run(batch)
        numpy.testing.assert_allclose(out16, out32, atol=5e-3)
        # and the predictions agree
        numpy.testing.assert_array_equal(out16.argmax(-1),
                                         out32.argmax(-1))
        with pytest.raises(ValueError):
            package_export(wf, str(tmp_path / "bad.tar"), precision=8)

    def test_random_mutations_never_crash(self, native_lib, tmp_path):
        """Byte-flip fuzzing of a VALID package: every mutation loads
        or errors cleanly (no SIGSEGV/SIGFPE would mean pytest dies)."""
        from sklearn.datasets import load_digits
        d = load_digits()
        X = d.data.astype(numpy.float32)[:60]
        y = d.target.astype(numpy.int32)[:60]
        wf = MLPWorkflow(
            DummyLauncher(), layers=(4, 10),
            loader_kwargs=dict(data=X, labels=y,
                               class_lengths=[0, 10, 50],
                               minibatch_size=10),
            learning_rate=0.1, max_epochs=1, name="fuzz-base")
        wf.initialize()
        wf.run()
        base = str(tmp_path / "base.tar")
        package_export(wf, base)
        assert self._load(base).unit_count == 2  # the base itself loads
        blob = bytearray(open(base, "rb").read())
        rng = numpy.random.RandomState(0)
        outcomes = {"loaded": 0, "rejected": 0}
        for trial in range(40):
            mutated = bytearray(blob)
            for _ in range(rng.randint(1, 8)):
                mutated[rng.randint(0, len(mutated))] = rng.randint(256)
            path = str(tmp_path / "mut.tar")
            open(path, "wb").write(bytes(mutated))
            try:
                # a mutant that loads must also RUN cleanly: payload
                # flips that dodge the shape checks exercise inference
                rt = self._load(path)
                rt.run(X[:2])
                outcomes["loaded"] += 1  # harmless flip (padding bytes)
            except (RuntimeError, ValueError):
                outcomes["rejected"] += 1
        # reaching here alive is the crash-free property; every mutation
        # must have resolved to exactly one clean outcome
        assert outcomes["loaded"] + outcomes["rejected"] == 40
