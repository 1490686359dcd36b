"""FullBatchLoader: whole dataset resident on device, minibatch by gather.

Reference ``veles/loader/fullbatch.py``: the dataset lives in
``original_data``/``original_labels`` Arrays, optionally device-resident,
and minibatches are gathered by the ``fill_minibatch_data_labels`` kernel
(``cuda/fullbatch_loader.cu``). TPU design: the originals are jax.Arrays in
HBM and the fill is one jitted gather+normalize (``ops.gather_minibatch``) —
for MNIST-scale sets this keeps the whole data path on device; the
graceful OOM fallback (reference ``fullbatch.py:170-242``) keeps originals
in host numpy and gathers there instead.

Subclasses (or callers via ``data=``/``labels=`` kwargs) provide the actual
dataset; class splits come from ``class_lengths`` or the
``validation_ratio`` resplit.
"""

import numpy

import jax
import jax.numpy as jnp

from veles_tpu.loader.base import (Loader, LoaderMSEMixin, TRAIN, VALID,
                                   register_loader)
from veles_tpu.loader.normalization import make_normalizer
from veles_tpu.memory import Array
from veles_tpu.ops.gather import gather_minibatch


@register_loader("full_batch")
class FullBatchLoader(Loader):
    """Device-resident full-batch loader (reference ``fullbatch.py:79``)."""

    def __init__(self, workflow, **kwargs):
        self.on_device = kwargs.pop("on_device", True)
        self.normalization_type = kwargs.pop("normalization_type", "none")
        self.normalization_parameters = kwargs.pop(
            "normalization_parameters", {})
        self.validation_ratio = kwargs.pop("validation_ratio", None)
        #: in-jit TRAIN-minibatch augmentation by name ("mirror",
        #: "shift1" — ops/augment.TRANSFORMS); needs NHWC data. The
        #: reference reached augmentation only through the image-loader
        #: family (mirror/crop offsets, ``loader/image.py``); array
        #: datasets get the same tier here
        self.train_transform = kwargs.pop("train_transform", None)
        data = kwargs.pop("data", None)
        labels = kwargs.pop("labels", None)
        lengths = kwargs.pop("class_lengths", None)
        super().__init__(workflow, **kwargs)
        if self.train_transform is not None:
            from veles_tpu.ops.augment import TRANSFORMS
            if self.train_transform not in TRANSFORMS:
                raise ValueError(
                    "unknown train_transform %r (known: %s)"
                    % (self.train_transform,
                       ", ".join(sorted(TRANSFORMS))))
        self.original_data = Array()
        self.original_labels = Array()
        self._provided_data = data
        self._provided_labels = labels
        self._provided_lengths = lengths
        self._raw_labels = None
        self.normalizer = None

    # -- ILoader --------------------------------------------------------------
    def load_data(self):
        if self._provided_data is None:
            raise NotImplementedError(
                "%s: override load_data() or pass data=" % self.name)
        data = numpy.asarray(self._provided_data, numpy.float32)
        if self.train_transform is not None and data.ndim != 4:
            raise ValueError(
                "train_transform %r needs NHWC data, got shape %s"
                % (self.train_transform, data.shape))
        self.original_data.reset(data)
        if self._provided_labels is not None:
            self._raw_labels = numpy.asarray(self._provided_labels)
        if self._provided_lengths is not None:
            self.class_lengths = list(self._provided_lengths)
        else:
            self.class_lengths = [0, 0, len(data)]
        if self.validation_ratio:
            self._resplit_validation()
        self._analyze_normalization()
        self._upload(self.original_data, "data")

    def _upload(self, array, what):
        """Move a dataset array into HBM. ONLY running out of device
        memory keeps it on the host (the reference's OOM path,
        ``fullbatch.py:170-242``) — and says at error level what that
        costs; any other device error is a fault and propagates."""
        if not self.on_device:
            return
        try:
            array.to_device()
        except (MemoryError, jax.errors.JaxRuntimeError) as exc:
            if not (isinstance(exc, MemoryError)
                    or "RESOURCE_EXHAUSTED" in str(exc)):
                raise
            self.error(
                "device out of memory uploading the dataset %s (%s): "
                "keeping the dataset on the HOST — the fused tick is "
                "disabled, and every minibatch is gathered on the host "
                "and copied to the device each step", what, exc)
            self.on_device = False

    def get_raw_labels(self):
        return self._raw_labels

    def analyze_dataset(self):
        """Label mapping first (base), then materialize the int32 label
        array the device gather uses."""
        super().analyze_dataset()
        if self._raw_labels is not None:
            self.original_labels.reset(self.map_labels(self._raw_labels))
            self._upload(self.original_labels, "labels")

    def _resplit_validation(self):
        """Move the tail of TRAIN into VALID (reference
        ``validation_ratio`` resplit)."""
        n_valid = int(self.class_lengths[TRAIN] * self.validation_ratio)
        # layout is [test | valid | train]; splice the LAST n_valid train
        # rows in after the existing valid block so all three stay contiguous
        valid_end = self.class_offset(TRAIN)
        total = self.total_samples
        self.class_lengths[VALID] += n_valid
        self.class_lengths[TRAIN] -= n_valid
        perm = numpy.concatenate([
            numpy.arange(valid_end),
            numpy.arange(total - n_valid, total),
            numpy.arange(valid_end, total - n_valid)])
        self._apply_resplit(perm)

    def _apply_resplit(self, perm):
        """Apply the resplit permutation to every per-sample array; MSE
        subclasses extend this to keep targets row-aligned."""
        self.original_data.reset(self.original_data.mem[perm])
        if self._raw_labels is not None:
            self._raw_labels = self._raw_labels[perm]

    def _analyze_normalization(self):
        """One pass over the train set accumulating normalizer statistics
        (reference ``loader/base.py:755-802``). Host-side numpy: a device
        transfer of the whole train split here would defeat the OOM
        fallback in load_data."""
        self.normalizer = make_normalizer(self.normalization_type,
                                          **self.normalization_parameters)
        if self.normalizer.STATELESS:
            return
        start = self.class_offset(TRAIN)
        train = self.original_data.mem[
            start:start + self.class_lengths[TRAIN]]
        if not len(train):  # no train split (e.g. pure evaluation runs)
            train = self.original_data.mem
        self.normalizer.analyze(train)

    def create_minibatch_data(self):
        size = self.max_minibatch_size
        sample_shape = self.original_data.shape[1:]
        self.minibatch_data.reset(
            numpy.zeros((size,) + sample_shape, numpy.float32))
        if self.original_labels:
            self.minibatch_labels.reset(numpy.zeros(size, numpy.int32))
        self.minibatch_indices.reset(numpy.zeros(size, numpy.int64))
        self.sample_mask.reset(numpy.zeros(size, numpy.float32))

    #: fused-engine contract (same as the image loaders): fill-time
    #: transforms force graph mode unless the tick replicates them
    @property
    def has_fill_transforms(self):
        return self.train_transform is not None

    @property
    def jit_transform(self):
        return self.train_transform

    def init_unpickled(self):
        super().init_unpickled()
        self._fill_jit_ = None
        self._zero_labels_ = None
        self._transform_jit_ = None

    @property
    def _fill_jit(self):
        if self._fill_jit_ is None:
            normalizer = self.normalizer

            @jax.jit
            def fill(data, labels, indices, valid):
                batch, lab = gather_minibatch(data, indices, labels)
                # normalizer coefficients fold in as XLA constants and the
                # elementwise math fuses into the gather (retires the
                # reference's mean_disp_normalizer kernel)
                batch = normalizer.apply_batch(jnp, batch)
                mask = (jnp.arange(indices.shape[0]) < valid).astype(
                    jnp.float32)
                return batch, lab, mask

            self._fill_jit_ = fill
        return self._fill_jit_

    def labels_for_gather(self):
        """The label lane every in-jit gather consumes (the loader's
        fill, the fused tick, the sweep tier): the device labels, or —
        for label-less (MSE) datasets — a cached dataset-length zeros
        placeholder (a fresh jnp.zeros would be an eager dispatch plus
        a full-length allocation per tick)."""
        if self.original_labels:
            return self.original_labels.data
        if self._zero_labels_ is None \
                or len(self._zero_labels_) != len(self.original_data):
            self._zero_labels_ = jnp.zeros(
                len(self.original_data), jnp.int32)
        return self._zero_labels_

    def fill_minibatch(self, indices, valid):
        data = self.original_data.data
        labels = self.labels_for_gather()
        if not self.on_device and not isinstance(data, jax.Array):
            # host gather path
            batch = numpy.take(numpy.asarray(data), indices, axis=0)
            lab = numpy.take(numpy.asarray(labels), indices, axis=0)
            mask = (numpy.arange(len(indices)) < valid).astype(numpy.float32)
            batch = self.normalizer.apply_batch(numpy, batch)
            self.minibatch_data.data = jnp.asarray(batch)
            self.minibatch_labels.data = jnp.asarray(lab)
            self.sample_mask.data = jnp.asarray(mask)
            self.minibatch_indices.data = jnp.asarray(indices)
            return
        # the host indices and valid count ride the jit dispatch itself —
        # eager jnp.asarray/jnp.int32 here would each be a separate
        # device_put dispatch per tick
        batch, lab, mask = self._fill_jit(data, labels, indices,
                                          numpy.int32(valid))
        if self.train_transform and self.minibatch_class == TRAIN:
            if self._transform_jit_ is None:
                from veles_tpu.ops.augment import TRANSFORMS
                self._transform_jit_ = jax.jit(
                    TRANSFORMS[self.train_transform])
            batch = self._transform_jit_(
                batch, int(self.draw_transform_seeds(1)[0]))
        self.minibatch_data.data = batch
        self.minibatch_labels.data = lab
        self.sample_mask.data = mask
        # host numpy: consumers (fused tick, snapshot replays) feed it
        # back into jit calls, where it rides those dispatches — an
        # eager jnp.asarray here would re-upload it a second time
        self.minibatch_indices.data = indices


@register_loader("full_batch_mse")
class FullBatchLoaderMSE(LoaderMSEMixin, FullBatchLoader):
    """Full-batch loader with regression targets (reference
    ``loader/fullbatch.py`` FullBatchLoaderMSE + ``base.py:1147``).

    Targets live beside the data as a device-resident ``original_targets``
    array; the minibatch target gather rides the same jitted fill. The
    target normalizer accumulates over the train split and its
    ``denormalize()`` maps network output back to target units."""

    def __init__(self, workflow, **kwargs):
        targets = kwargs.pop("targets", None)
        super().__init__(workflow, **kwargs)
        self.original_targets = Array()
        self._provided_targets = targets

    def _apply_resplit(self, perm):
        super()._apply_resplit(perm)
        # targets must stay row-aligned with the respliced data
        self._provided_targets = self._provided_targets[perm]

    def load_data(self):
        if self._provided_targets is None:
            raise NotImplementedError(
                "%s: override load_data() or pass targets=" % self.name)
        self._provided_targets = numpy.asarray(
            self._provided_targets, numpy.float32)
        super().load_data()
        targets = self._provided_targets
        if len(targets) != self.total_samples:
            raise ValueError(
                "targets length %d != total samples %d"
                % (len(targets), self.total_samples))
        self.target_normalizer = make_normalizer(
            self.target_normalization_type,
            **self.target_normalization_parameters)
        start = self.class_offset(TRAIN)
        train = targets[start:start + self.class_lengths[TRAIN]]
        if not self.target_normalizer.STATELESS:
            self.target_normalizer.analyze(
                train if len(train) else targets)
        self.original_targets.reset(
            numpy.asarray(self.target_normalizer.apply_batch(
                numpy, targets), numpy.float32))
        if not self.targets_shape:
            self.targets_shape = targets.shape[1:]
        self._upload(self.original_targets, "targets")

    def create_minibatch_data(self):
        super().create_minibatch_data()
        size = self.max_minibatch_size
        self.minibatch_targets.reset(numpy.zeros(
            (size,) + tuple(self.targets_shape), numpy.float32))

    def fill_minibatch(self, indices, valid):
        super().fill_minibatch(indices, valid)
        targets = self.original_targets.data
        if isinstance(targets, jax.Array):
            gathered = jnp.take(targets, jnp.asarray(indices), axis=0)
        else:
            gathered = jnp.asarray(
                numpy.take(numpy.asarray(targets), indices, axis=0))
        self.minibatch_targets.data = gathered
