"""Synthetic twins of the BASELINE training workflows: no dataset files.

``samples/mnist784.py`` needs the MNIST idx files and an ImageNet run
needs ImageNet; a sealed machine has neither. These twins keep every
WIDTH of the two ``BASELINE.json`` workflows — MNIST784 (784 -> 100 tanh
-> 10 softmax, minibatch 100, 10k validation / 50k train) and AlexNet
(227x227x3 -> the full single-tower stack -> 1000-way softmax,
minibatch 128, ``mean_disp``) — and replace only the pixels, which are
generated from a seed. ``chip_smoke.py`` trains both on the TPU.

Run:  python -m veles_tpu samples/synthetic_twins.py - \
          root.synthetic.model=alexnet

The pixels are learnable on purpose: each sample is its class's fixed
random prototype blended with noise, and labels are drawn from the
first ``label_classes`` classes only, so a few epochs lower the train
loss measurably even at AlexNet's 1000-wide head (random labels would
leave the loss at ln(classes) and prove nothing about the gradients).
"""

import numpy

from veles_tpu.core.config import root
from veles_tpu.models.alexnet import AlexNetWorkflow
from veles_tpu.models.mlp import MLPWorkflow

MODELS = {
    "mnist784": dict(sample_shape=(784,), n_valid=10000, n_train=50000,
                     minibatch_size=100, max_epochs=3, n_classes=10,
                     learning_rate=0.03, normalization_type="linear"),
    "alexnet": dict(sample_shape=(227, 227, 3), n_valid=1000,
                    n_train=1000, minibatch_size=128, max_epochs=2,
                    n_classes=1000, learning_rate=0.001,
                    normalization_type="mean_disp"),
}
# AlexNet's learning rate: AlexNetWorkflow's default 0.01 overshoots on
# this set at mb 128 — on the v5e the train loss went 4.6, 7.7, 4.0, 3.2
# over four epochs, where 0.001 falls to the 10-class floor (2.35)
# within one and stays (chip runs, PR 21) — so the twin takes 0.001.

#: any key of the chosen MODELS row can be overridden here
#: (``root.synthetic.n_train=256``); ``scale`` is AlexNet's width knob
root.synthetic.update({"model": "mnist784", "seed": 0,
                       "label_classes": 10, "scale": 1.0})


def dataset(seed, sample_shape, n_valid, n_train, label_classes):
    """``(data float32 (N, *sample_shape) in [0, 255], labels int32)``
    laid out [validation | train], each split class-balanced."""
    rng = numpy.random.RandomState(seed)
    prototypes = rng.rand(label_classes, *sample_shape).astype(
        numpy.float32)
    labels = numpy.concatenate([
        rng.permutation(numpy.arange(n, dtype=numpy.int32)
                        % label_classes)
        for n in (n_valid, n_train)])
    data = rng.rand(len(labels), *sample_shape).astype(numpy.float32)
    data += prototypes[labels]
    data *= 127.5
    return data, labels


def run(load, main):
    cfg = root.synthetic
    spec = {key: cfg.get(key, default)
            for key, default in MODELS[cfg.model].items()}
    sample_shape = tuple(spec["sample_shape"])
    data, labels = dataset(int(cfg.seed), sample_shape, spec["n_valid"],
                           spec["n_train"], int(cfg.label_classes))
    loader_kwargs = dict(
        data=data, labels=labels,
        class_lengths=[0, spec["n_valid"], spec["n_train"]],
        minibatch_size=spec["minibatch_size"],
        normalization_type=spec["normalization_type"])
    if cfg.model == "mnist784":
        load(MLPWorkflow, name="MNIST784-synthetic",
             layers=(100, spec["n_classes"]), loader_kwargs=loader_kwargs,
             learning_rate=spec["learning_rate"],
             max_epochs=spec["max_epochs"])
    else:
        load(AlexNetWorkflow, name="AlexNet-synthetic",
             n_classes=spec["n_classes"], scale=float(cfg.scale),
             learning_rate=spec["learning_rate"],
             loader_kwargs=loader_kwargs,
             decision_kwargs=dict(max_epochs=spec["max_epochs"]))
    main()
