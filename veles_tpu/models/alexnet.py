"""AlexNet topology for the ImageNet parity anchor.

``BASELINE.json`` names "Znicz ImageNet AlexNet workflow with
fullbatch_loader + mean_disp_normalizer" as the conv-scale parity target.
This module declares the AlexNet layer stack as StandardWorkflow specs —
conv/pool geometry per Krizhevsky et al. 2012 — plus a ``scale`` knob
that shrinks every kernel/channel count proportionally so the SAME
topology smoke-trains on small synthetic inputs in CI (the build
environment has no ImageNet; the full-size run on real images is
a deployment exercise, not a code change).

Deltas from 2012 AlexNet, chosen deliberately for TPU:

- no local response normalization (superseded; XLA-unfriendly
  cross-channel windows for negligible accuracy — modern consensus);
- no dropout (the reference Znicz config era predates batch-level
  regularization tradeoffs; add weights_decay instead);
- single tower (the original's two GPU groups were a memory workaround).
"""

from veles_tpu.models.standard import StandardWorkflow


def alexnet_layers(n_classes=1000, scale=1.0):
    """The AlexNet spec list; ``scale`` shrinks widths for smoke runs."""
    def ch(n):
        return max(4, int(n * scale))

    def units(n):
        return max(16, int(n * scale))

    return [
        {"type": "conv_relu", "n_kernels": ch(96), "kx": 11, "ky": 11,
         "sliding": (4, 4), "padding": "SAME"},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "conv_relu", "n_kernels": ch(256), "kx": 5, "ky": 5},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "conv_relu", "n_kernels": ch(384), "kx": 3, "ky": 3},
        {"type": "conv_relu", "n_kernels": ch(384), "kx": 3, "ky": 3},
        {"type": "conv_relu", "n_kernels": ch(256), "kx": 3, "ky": 3},
        {"type": "max_pooling", "kx": 3, "ky": 3, "sliding": (2, 2)},
        {"type": "all2all_relu", "output_sample_shape": units(4096)},
        {"type": "all2all_relu", "output_sample_shape": units(4096)},
        {"type": "softmax", "output_sample_shape": n_classes},
    ]


class AlexNetWorkflow(StandardWorkflow):
    """AlexNet through the standard declarative workflow; pair with an
    image loader + ``normalization_type="mean_disp"`` for the BASELINE
    configuration."""

    def __init__(self, workflow, n_classes=1000, scale=1.0, **kwargs):
        kwargs.setdefault("layers", alexnet_layers(n_classes, scale))
        kwargs.setdefault("learning_rate", 0.01)
        kwargs.setdefault("gradient_moment", 0.9)
        kwargs.setdefault("weights_decay", 5e-4)
        super().__init__(workflow, **kwargs)
