"""Accuracy-parity harness: a pass/fail artifact against the reference
anchors.

The reference publishes three MNIST validation-error anchors
(``docs/source/manualrst_veles_example.rst:55-66``):

    MNIST784 (784→100 tanh→10 softmax)   1.92%  → bound 2.2%
    mnist "caffe" (LeNet-style convnet)   0.86%  → bound 1.0%
    mnist conv (tanh convnet)             0.73%  → bound 0.9%

``run_parity(mnist_dir=...)`` trains the three topologies with the
reference hyperparameters on real idx files and asserts those bounds.

Without MNIST (this build environment has zero egress and the idx
files exist nowhere in the image) the harness runs the same three
topology FAMILIES on the sklearn ``load_digits`` set — **real scanned
handwriting** (the UCI Optical Recognition of Handwritten Digits test
fold: 1797 8x8 scans from 43 writers; earlier rounds mislabeled this
tier "synthetic"), 1500 train / 297 validation — with ABSOLUTE bounds
chosen at the reference anchors' tightness class (the
earlier 6% bounds were loose; these are sub-1% for both convnets):

    digits784 MLP                         measured 2.36%  → bound 3.0%
    digits "caffe" (relu convnet)         measured 0.00%  → bound 0.7%
    digits conv (tanh convnet)            measured 0.34%  → bound 0.7%

The convnet families train with the ``shift1`` in-jit augmentation
(``ops/augment.py`` — the reference ImageLoader's random crop-offset
role), which is what carries them past the anchor-class error rates.
Either way the outcome is written to ``PARITY.json``.

One command: ``python -m veles_tpu parity [--mnist-dir DIR] [--out F]``.
The exact layer stacks of the two convnets live in the absent znicz
submodule (SURVEY preamble); they are reconstructed LeNet-style from the
documented anchors and the caffe naming.
"""

import json
import os
import time

from veles_tpu.core import prng
from veles_tpu.core.config import root
from veles_tpu.core.logger import Logger
from veles_tpu.loader.base import VALID

#: (name, layer specs for 28x28x1 MNIST, trainer kwargs, bound %)
MNIST_TOPOLOGIES = (
    ("mnist784", [
        {"type": "all2all_tanh", "output_sample_shape": (100,)},
        {"type": "softmax", "output_sample_shape": (10,)},
    ], dict(learning_rate=0.03, gradient_moment=0.9, minibatch_size=100,
            max_epochs=50, fail_iterations=25, flat=True), 2.2),
    ("mnist_caffe", [
        {"type": "conv", "n_kernels": 20, "kx": 5, "ky": 5},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "conv", "n_kernels": 50, "kx": 5, "ky": 5},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "all2all_relu", "output_sample_shape": (500,)},
        {"type": "softmax", "output_sample_shape": (10,)},
    ], dict(learning_rate=0.01, gradient_moment=0.9, weights_decay=5e-4,
            minibatch_size=100, max_epochs=40, fail_iterations=20,
            flat=False), 1.0),
    ("mnist_conv", [
        {"type": "conv_tanh", "n_kernels": 32, "kx": 5, "ky": 5},
        {"type": "maxabs_pooling", "kx": 2, "ky": 2},
        {"type": "conv_tanh", "n_kernels": 64, "kx": 5, "ky": 5},
        {"type": "maxabs_pooling", "kx": 2, "ky": 2},
        {"type": "all2all_tanh", "output_sample_shape": (100,)},
        {"type": "softmax", "output_sample_shape": (10,)},
    ], dict(learning_rate=0.02, gradient_moment=0.9, minibatch_size=100,
            max_epochs=40, fail_iterations=20, flat=False), 0.9),
)

#: the same families on the real 8x8 UCI digits (297 validation
#: samples; 1 error = 0.337%); bounds are ABSOLUTE and deterministic
#: under the pinned seeds. All three train on NHWC data with the
#: shift1 augmentation (measured: 2.36% / 0.00% / 0.34%)
DIGITS_TOPOLOGIES = (
    ("digits784", [
        {"type": "all2all_tanh", "output_sample_shape": (100,)},
        {"type": "softmax", "output_sample_shape": (10,)},
    ], dict(learning_rate=0.03, gradient_moment=0.9, minibatch_size=100,
            max_epochs=170, fail_iterations=60, flat=False), 3.0),
    ("digits_caffe", [
        {"type": "conv", "n_kernels": 32, "kx": 3, "ky": 3},
        {"type": "max_pooling", "kx": 2, "ky": 2},
        {"type": "conv", "n_kernels": 64, "kx": 3, "ky": 3},
        {"type": "all2all_relu", "output_sample_shape": (128,)},
        {"type": "softmax", "output_sample_shape": (10,)},
    ], dict(learning_rate=0.01, gradient_moment=0.9, weights_decay=5e-4,
            minibatch_size=100, max_epochs=150, fail_iterations=60,
            flat=False), 0.7),
    ("digits_conv", [
        {"type": "conv_tanh", "n_kernels": 32, "kx": 3, "ky": 3},
        {"type": "maxabs_pooling", "kx": 2, "ky": 2},
        {"type": "conv_tanh", "n_kernels": 64, "kx": 3, "ky": 3},
        {"type": "all2all_tanh", "output_sample_shape": (128,)},
        {"type": "softmax", "output_sample_shape": (10,)},
    ], dict(learning_rate=0.02, gradient_moment=0.9, minibatch_size=100,
            max_epochs=220, fail_iterations=110, flat=False), 0.7),
)


#: THE canonical digits split: sklearn digits, RandomState(0)
#: permutation, [test=0, valid=297, train=1500]. The fusion/pod/fleet
#: parity tests (via ``tests/dataset_fixtures.py``) and this harness all
#: depend on the exact same bytes — change it HERE only.
DIGITS_CLASS_LENGTHS = [0, 297, 1500]


def digits_dataset(flat=True):
    import numpy
    from sklearn.datasets import load_digits
    digits = load_digits()
    X = digits.data.astype(numpy.float32)
    y = digits.target.astype(numpy.int32)
    perm = numpy.random.RandomState(0).permutation(len(X))
    X, y = X[perm], y[perm]
    if not flat:
        X = X.reshape(-1, 8, 8, 1)
    return X, y


def _train_one(name, layers, trainer, mnist_dir, log):
    """Train one topology; returns (val_error_pct, epochs, best_epoch)."""
    from veles_tpu.dummy import DummyLauncher
    from veles_tpu.models.standard import StandardWorkflow

    trainer = dict(trainer)
    flat = trainer.pop("flat")
    minibatch_size = trainer.pop("minibatch_size")
    max_epochs = trainer.pop("max_epochs")
    fail_iterations = trainer.pop("fail_iterations")
    prng.get("default").seed(1234)
    prng.get("loader").seed(5678)
    if mnist_dir:
        from veles_tpu.loader.mnist import MNISTLoader
        loader_cls = MNISTLoader
        loader_kwargs = dict(directory=mnist_dir, url_base=None,
                             flat=flat, minibatch_size=minibatch_size,
                             normalization_type="linear")
    else:
        from veles_tpu.loader.fullbatch import FullBatchLoader
        X, y = digits_dataset(flat)
        loader_cls = FullBatchLoader
        loader_kwargs = dict(data=X, labels=y,
                             class_lengths=DIGITS_CLASS_LENGTHS,
                             minibatch_size=minibatch_size,
                             normalization_type="linear")
        if not flat:
            # the +-1 px random-shift augmentation (in-jit, both
            # engines) is what carries the digits families to the
            # anchor-class error rates — see module docstring
            loader_kwargs["train_transform"] = "shift1"
    wf = StandardWorkflow(
        DummyLauncher(), layers=layers, loader_cls=loader_cls,
        loader_kwargs=loader_kwargs,
        decision_kwargs=dict(max_epochs=max_epochs,
                             fail_iterations=fail_iterations),
        name=name, **trainer)
    wf.initialize()
    wf.run()
    decision = wf.decision
    n_valid = max(wf.loader.effective_class_lengths[VALID], 1)
    best = decision.best_n_err[VALID]
    error_pct = 100.0 * best / n_valid if best is not None else 100.0
    log.info("%s: best validation error %.2f%% (%s/%d) at epoch %d "
             "after %d epochs", name, error_pct, best, n_valid,
             decision.best_epoch, decision.epochs_done)
    return error_pct, decision.epochs_done, decision.best_epoch


def run_parity(mnist_dir=None, out="PARITY.json", topologies=None):
    """Train the parity set and write the verdict artifact. Returns the
    verdict dict; ``pass`` is the overall outcome."""
    log = Logger(logger_name="parity")
    if mnist_dir is None:
        mnist_dir = os.environ.get("VELES_TPU_MNIST_DIR") or None
    mode = "real-mnist" if mnist_dir else "real-digits-8x8"
    table = topologies or (MNIST_TOPOLOGIES if mnist_dir
                           else DIGITS_TOPOLOGIES)
    if not mnist_dir:
        log.warning("no MNIST directory (set VELES_TPU_MNIST_DIR or "
                    "pass --mnist-dir): running the real-data 8x8 "
                    "digits tier (UCI handwritten scans) with absolute "
                    "bounds")
    saved = (root.common.disable.get("plotting", False),
             root.common.disable.get("snapshotting", False))
    root.common.disable.plotting = True
    root.common.disable.snapshotting = True
    results = []
    try:
        for name, layers, trainer, bound in table:
            start = time.time()
            try:
                error_pct, epochs, best_epoch = _train_one(
                    name, layers, trainer, mnist_dir, log)
                entry = {"name": name,
                         "val_error_pct": round(error_pct, 3),
                         "bound_pct": bound, "pass": error_pct <= bound,
                         "epochs": epochs, "best_epoch": best_epoch}
            except Exception as exc:  # one failure must not hide the rest
                log.exception("%s failed", name)
                entry = {"name": name, "error": "%s: %s"
                         % (type(exc).__name__, exc), "pass": False,
                         "bound_pct": bound}
            entry["seconds"] = round(time.time() - start, 1)
            results.append(entry)
    finally:
        # restore: callers (a pytest session, a notebook) keep their
        # own plotting/snapshotting behavior after the harness returns
        root.common.disable.plotting, \
            root.common.disable.snapshotting = saved
    verdict = {
        "mode": mode,
        "anchors": "docs/source/manualrst_veles_example.rst:55-66 "
                   "(1.92% / 0.86% / 0.73%)",
        "results": results,
        "pass": all(r["pass"] for r in results),
    }
    if out:
        with open(out, "w") as fout:
            json.dump(verdict, fout, indent=1)
        log.info("parity verdict (%s): %s -> %s", mode,
                 "PASS" if verdict["pass"] else "FAIL", out)
    return verdict


def main(argv=None):
    """``python -m veles_tpu parity`` entry."""
    import argparse
    parser = argparse.ArgumentParser(
        prog="veles_tpu parity",
        description="train the reference parity topologies and write "
                    "a pass/fail PARITY.json")
    parser.add_argument("--mnist-dir", default=None,
                        help="directory with the 4 MNIST idx(.gz) files "
                             "(default: $VELES_TPU_MNIST_DIR, else the "
                             "synthetic-digits analogue runs)")
    parser.add_argument("--out", default="PARITY.json")
    args = parser.parse_args(argv)
    from veles_tpu.core.logger import setup_logging
    setup_logging()
    verdict = run_parity(mnist_dir=args.mnist_dir, out=args.out)
    print(json.dumps(verdict, indent=1))
    return 0 if verdict["pass"] else 1
