"""Loader base: the minibatch server.

TPU-native re-design of reference ``veles/loader/base.py`` (1181 LoC). Kept
semantics:

- three sample classes TEST(0)/VALID(1)/TRAIN(2) with per-class lengths and
  a fixed serving order TEST → VALID → TRAIN inside each epoch
  (``loader/base.py:72-80``);
- train-set reshuffling each epoch from the named "loader" PRNG stream,
  bounded by ``shuffle_limit`` (``loader/base.py:711-724``);
- epoch flags consumed by Decision/GD gating: ``minibatch_class``,
  ``last_minibatch``, ``epoch_ended_for_class``, ``epoch_ended``,
  ``epoch_number``;
- fleet-mode distribution: the master serves only (indices, class, epoch)
  payloads; slaves fill data locally; un-acked minibatches are requeued on
  slave drop (``loader/base.py:631-687``) — index payloads are tiny, so DCN
  traffic stays negligible;
- ``--train-ratio`` partial-train support and validation resplit hooks.

TPU deltas: minibatch tensors have **static shapes** (jit requirement) — a
short final minibatch keeps ``max_minibatch_size`` rows and exposes
``minibatch_valid_size`` + a 0/1 ``sample_mask`` that the evaluator folds
into loss/metrics (the reference instead re-served tail rows). Filling
happens on device (see FullBatchLoader) so the gather fuses into the tick.
"""

import collections

import numpy

from veles_tpu.core import prng
from veles_tpu.core.config import root
from veles_tpu.core.errors import NoMoreJobsError
from veles_tpu.core.mutable import Bool
from veles_tpu.core.units import Unit
from veles_tpu.memory import Array
from veles_tpu.observe.tracing import get_tracer

TEST, VALID, TRAIN = 0, 1, 2
CLASS_NAMES = ("test", "validation", "train")

#: Name → loader-class map (reference ``loader/base.py:83``
#: UserLoaderRegistry); populated by the @register_loader decorator.
loader_registry = {}


def register_loader(name):
    def wrap(cls):
        loader_registry[name] = cls
        return cls
    return wrap


class Loader(Unit):
    """Minibatch server base (reference ``loader/base.py:120``)."""

    hide_from_registry = True
    VIEW_GROUP = "LOADER"

    def __init__(self, workflow, **kwargs):
        self.minibatch_size = kwargs.pop("minibatch_size", 100)
        self.train_ratio = kwargs.pop(
            "train_ratio", root.common.get("train_ratio", 1.0))
        # config-driven default (reference root.common.loader.shuffle_limit)
        self.shuffle_limit = kwargs.pop(
            "shuffle_limit", root.common.loader.get("shuffle_limit", None))
        self.prng_key = kwargs.pop("prng_key", "loader")
        on_initialized = kwargs.pop("on_initialized", None)
        super().__init__(workflow, **kwargs)
        # after super(): init_unpickled resets the slot (trailing-underscore
        # attrs are rebuilt, not pickled — the callback does not survive
        # snapshots, like the reference's marshal-pickled variant)
        self._on_initialized_ = on_initialized
        #: raw label -> contiguous class index (reference
        #: ``loader/base.py:925-944`` auto-mapping)
        self.labels_mapping = {}
        self._reversed_labels_mapping = []
        self.class_lengths = [0, 0, 0]
        self.epoch_number = 0
        self.samples_served = 0
        self.minibatch_class = TRAIN
        self.minibatch_epoch = 0
        self.minibatch_valid_size = 0
        self.minibatch_offset = 0
        self.last_minibatch = Bool(False)
        self.epoch_ended = Bool(False)
        self.epoch_ended_for_class = Bool(False)
        self.complete = Bool(False)
        # served tensors (static-shape device slots):
        self.minibatch_data = Array()
        self.minibatch_labels = Array()
        self.minibatch_indices = Array()
        self.sample_mask = Array()
        self.shuffled_indices = [None, None, None]
        self._position = [0, 0, 0]
        self._served_this_epoch = 0
        # fleet mode: minibatches handed to slaves but not yet acked, and
        # dropped slaves' work queued for re-serving
        self.pending_minibatches_ = collections.defaultdict(list)
        self.failed_minibatches = []

    def init_unpickled(self):
        super().init_unpickled()
        self.pending_minibatches_ = collections.defaultdict(list)
        self._on_initialized_ = None

    # -- the ILoader contract (reference loader/base.py:100-115) -------------
    def load_data(self):
        """Populate class_lengths and dataset storage. Abstract."""
        raise NotImplementedError

    def create_minibatch_data(self):
        """Allocate the static-shape minibatch slots. Abstract."""
        raise NotImplementedError

    def fill_minibatch(self, indices, valid):
        """Fill minibatch slots for ``indices`` (global sample ids);
        entries beyond ``valid`` are padding. Abstract."""
        raise NotImplementedError

    # -- derived sizes --------------------------------------------------------
    @property
    def total_samples(self):
        return int(sum(self.class_lengths))

    @property
    def max_minibatch_size(self):
        return self.minibatch_size

    def class_offset(self, klass):
        return int(sum(self.class_lengths[:klass]))

    @property
    def effective_class_lengths(self):
        """class_lengths with --train-ratio applied to TRAIN."""
        lengths = list(self.class_lengths)
        if self.train_ratio < 1.0:
            lengths[TRAIN] = max(1, int(lengths[TRAIN] * self.train_ratio))
        return lengths

    # -- lifecycle ------------------------------------------------------------
    def initialize(self, **kwargs):
        from veles_tpu.core.verified import ILOADER, verify_interface
        verify_interface(self, ILOADER, "ILoader")
        self.load_data()
        if self.total_samples == 0:
            raise ValueError("%s loaded an empty dataset" % self.name)
        self.info("dataset: test=%d validation=%d train=%d",
                  *self.class_lengths)
        if not self.restored_from_snapshot():
            for klass in (TEST, VALID, TRAIN):
                length = self.class_lengths[klass]
                self.shuffled_indices[klass] = (
                    numpy.arange(length, dtype=numpy.int64)
                    + self.class_offset(klass))
            self._shuffle_train()
        self.analyze_dataset()
        self.create_minibatch_data()
        # observability bridge (docs/observability.md): epoch progress
        # and serving tallies on /metrics. Weakly referenced — a loader
        # that goes away unregisters itself; scrape-time only, so a
        # run that never mounts /metrics pays nothing here.
        from veles_tpu.observe.metrics import (bridge,
                                               get_metrics_registry,
                                               publish_loader)
        bridge(get_metrics_registry(), self, publish_loader)
        if self._on_initialized_ is not None:
            self._on_initialized_()

    # -- label analysis (reference loader/base.py:925-1018) ------------------
    def get_raw_labels(self):
        """Full-length label array aligned with the [test|valid|train] row
        layout, or None when the dataset has no labels. Hook for
        subclasses; drives label mapping and distribution checks."""
        return None

    @property
    def has_labels(self):
        return self.get_raw_labels() is not None

    @property
    def unique_labels_count(self):
        return len(self.labels_mapping)

    @property
    def reversed_labels_mapping(self):
        """index -> raw label (for denormalizing predictions)."""
        return self._reversed_labels_mapping

    def map_labels(self, raw):
        """Raw labels -> contiguous int32 indices via labels_mapping."""
        raw = numpy.asarray(raw)
        if not self.labels_mapping:
            return raw.astype(numpy.int32)
        return numpy.fromiter(
            (self.labels_mapping[l] for l in raw.tolist()),
            numpy.int32, count=len(raw))

    def analyze_dataset(self):
        """Build the label auto-mapping from the train split, check the
        test/validation labels are a subset, log per-class cardinality
        stats, and chi-square-compare the split distributions (reference
        ``loader/base.py:925-1018``)."""
        raw = self.get_raw_labels()
        if raw is None:
            return
        counters = []
        for klass in (TEST, VALID, TRAIN):
            start = self.class_offset(klass)
            values = numpy.asarray(
                raw[start:start + self.class_lengths[klass]],
                dtype=object).tolist()
            missing = sum(1 for v in values if v is None)
            if missing:
                raise ValueError(
                    "%s: %d %s sample(s) have no label — label every "
                    "sample or provide none" % (
                        self.name, missing, CLASS_NAMES[klass]))
            counters.append(collections.Counter(values))
        self._setup_labels_mapping(counters)

    def _setup_labels_mapping(self, counters):
        test_counts, valid_counts, train_counts = counters
        if not self.labels_mapping:
            # evaluation-only datasets (empty train split) map over ALL
            # labels; the subset check below is train-relative so it only
            # applies when a train split exists
            source = sorted(train_counts) if train_counts else sorted(
                set(test_counts) | set(valid_counts))
            self.labels_mapping.update(
                {k: i for i, k in enumerate(source)})
            self._reversed_labels_mapping = sorted(self.labels_mapping)
        self._print_label_stats(train_counts, CLASS_NAMES[TRAIN])
        for klass, counts in ((TEST, test_counts), (VALID, valid_counts)):
            if not self.class_lengths[klass] or not train_counts:
                continue
            unknown = set(counts) - set(self.labels_mapping)
            if unknown:
                raise ValueError(
                    "%s: %s labels missing from the training set: %s"
                    % (self.name, CLASS_NAMES[klass], sorted(unknown)))
            missing = set(self.labels_mapping) - set(counts)
            if missing:
                self.warning("no %s samples for labels: %s",
                             CLASS_NAMES[klass], sorted(missing))
                for label in missing:
                    counts[label] = 0
            self._print_label_stats(counts, CLASS_NAMES[klass])
            self._compare_label_distributions(train_counts, counts,
                                              CLASS_NAMES[klass])

    def _print_label_stats(self, counts, set_name):
        values = numpy.array([v for _, v in sorted(counts.items())])
        if not values.sum():
            self.info("no %s labels specified", set_name)
            return
        mean = float(values.mean())
        std = float(values.std())
        self.info(
            "%s label cardinalities: min=%d max=%d avg=%d sigma=%d (%d%%)",
            set_name, values.min(), values.max(), mean, std,
            std * 100 // max(mean, 1))
        if std > mean / 2:
            self.warning("%s labels are heavily imbalanced", set_name)

    def _compare_label_distributions(self, train_counts, other_counts,
                                     other_name):
        """Chi-square test that the split's label distribution matches the
        train split's (reference ``loader/base.py:1006-1018``)."""
        try:
            from scipy.stats import chisquare
        except ImportError:  # scipy is optional
            return
        train = numpy.array(
            [v for _, v in sorted(train_counts.items())], numpy.float64)
        other = numpy.array(
            [v for _, v in sorted(other_counts.items())], numpy.float64)
        if not other.sum() or not train.sum():
            return
        # observed COUNTS against expected counts scaled to the observed
        # total — normalizing both to proportions would discard sample
        # size and make the test degenerate
        _, p = chisquare(other, train / train.sum() * other.sum())
        if p > 0.95:
            self.info("OK: train and %s label distributions match "
                      "(chi-square p=%.3f)", other_name, p)
        else:
            self.warning("train and %s label distributions differ "
                         "(chi-square p=%.3f)", other_name, p)

    def restored_from_snapshot(self):
        wf = self.workflow
        return bool(getattr(wf, "restored_from_snapshot", False)) \
            and self.shuffled_indices[TRAIN] is not None

    def draw_transform_seeds(self, n):
        """``n`` augmentation seeds in the SAME stream order graph-mode
        ``fill_minibatch`` draws them — one per TRAIN minibatch (any
        loader that exposes a ``jit_transform`` inherits this)."""
        gen = prng.get(self.prng_key)
        return numpy.asarray(
            [int(gen.randint(0, 2 ** 31 - 1)) for _ in range(n)],
            numpy.int64)

    def _shuffle_train(self):
        if self.shuffle_limit is not None \
                and self.epoch_number >= self.shuffle_limit:
            return
        prng.get(self.prng_key).shuffle(self.shuffled_indices[TRAIN])

    # -- serving --------------------------------------------------------------
    def _next_block(self):
        """Compute the next (class, start, size) to serve, advancing epoch
        state. Returns None when a full epoch just completed."""
        lengths = self.effective_class_lengths
        for klass in (TEST, VALID, TRAIN):
            pos = self._position[klass]
            if pos < lengths[klass]:
                size = min(self.max_minibatch_size, lengths[klass] - pos)
                self._position[klass] = pos + size
                return klass, pos, size
        return None

    def _roll_epoch(self):
        self.epoch_number += 1
        self._position = [0, 0, 0]
        self._shuffle_train()

    def serve_next_minibatch(self, slave_id=None):
        """Pick the next minibatch (failed ones first — reference
        ``loader/base.py:726-753``), record it pending for the slave, and
        return (klass, indices, valid_size, last_of_class, last_of_epoch,
        epoch). The epoch tag lets the master's Decision bucket updates
        that arrive out of order across epoch boundaries."""
        if self.failed_minibatches:
            # re-serve with the ORIGINAL last_of_class/last_of_epoch
            # flags: a requeued job must be bit-identical to the one the
            # dead slave held, or an epoch-closing minibatch would lose
            # its epoch-end semantics on retry (the chaos harness asserts
            # faulted == fault-free convergence on exactly this)
            (klass, indices, valid, last_of_class, last_of_epoch,
             epoch) = self.failed_minibatches.pop()
        else:
            block = self._next_block()
            if block is None:
                self._roll_epoch()
                block = self._next_block()
            klass, pos, valid = block
            epoch = self.epoch_number
            # copy, not view: the epoch reshuffle mutates shuffled_indices
            # in place, which would corrupt pending/requeued payloads
            indices = self.shuffled_indices[klass][pos:pos + valid].copy()
            lengths = self.effective_class_lengths
            last_of_class = self._position[klass] >= lengths[klass]
            last_of_epoch = last_of_class and all(
                self._position[k] >= lengths[k] or lengths[k] == 0
                for k in (TEST, VALID, TRAIN))
        if slave_id is not None:
            self.pending_minibatches_[slave_id].append(
                (klass, indices, valid, last_of_class, last_of_epoch,
                 epoch))
        return klass, indices, valid, last_of_class, last_of_epoch, epoch

    def serve_next_class_sweep(self):
        """Serve one ENTIRE sample-class sweep at once: the fused sweep
        engine scans the minibatches inside one XLA computation, so the
        host loop runs once per class per epoch instead of once per
        minibatch (per-minibatch dispatch latency is the killer).

        Returns (klass, index_matrix(n_batches, mb), valid_sizes
        (n_batches,), total_valid, last_of_epoch, epoch)."""
        lengths = self.effective_class_lengths
        klass = next((k for k in (TEST, VALID, TRAIN)
                      if self._position[k] < lengths[k]), None)
        if klass is None:
            self._roll_epoch()
            klass = next(k for k in (TEST, VALID, TRAIN) if lengths[k])
        mb = self.max_minibatch_size
        start = self._position[klass]
        n = lengths[klass] - start
        n_batches = (n + mb - 1) // mb
        idx = self.shuffled_indices[klass][start:start + n]
        matrix = numpy.zeros((n_batches, mb), dtype=numpy.int64)
        matrix.reshape(-1)[:n] = idx
        valid_sizes = numpy.full(n_batches, mb, dtype=numpy.int32)
        if n % mb:
            valid_sizes[-1] = n % mb
        self._position[klass] = lengths[klass]
        last_of_epoch = all(self._position[k] >= lengths[k]
                            or lengths[k] == 0
                            for k in (TEST, VALID, TRAIN))
        return (klass, matrix, valid_sizes, n, last_of_epoch,
                self.epoch_number)

    def run(self):
        """Standalone: pick the next indices and fill on device. On a slave
        the minibatch was already applied from the master's job payload
        (``apply_data_from_master``) — serving locally here would silently
        train on the wrong data (reference ``loader/base.py:641-663``)."""
        if self.is_slave:
            return
        if getattr(self, "sweep_serving", False):
            with get_tracer().span("loader.serve_sweep"):
                self._serve_sweep()
            return
        (klass, indices, valid, last_of_class,
         last_of_epoch, epoch) = self.serve_next_minibatch()
        self._apply_minibatch(klass, indices, valid, last_of_class,
                              last_of_epoch, epoch)

    def _serve_sweep(self):
        """One whole class sweep served at once: the index matrix and
        the valid sizes the fused tick scans over."""
        (klass, matrix, valid_sizes, total, last_of_epoch,
         epoch) = self.serve_next_class_sweep()
        self._publish_flags(klass, matrix.reshape(-1), total, True,
                            last_of_epoch, epoch)
        self.minibatch_indices.data = matrix
        self.sweep_valid_sizes = valid_sizes
        # per-minibatch augmentation seeds for the fused tick, drawn
        # in the same stream order graph mode would (one per TRAIN
        # minibatch at fill time)
        if klass == TRAIN and getattr(self, "jit_transform", None):
            self.sweep_transform_seeds = self.draw_transform_seeds(
                len(matrix))
        else:
            self.sweep_transform_seeds = None
        self._account_served(total, last_of_epoch)

    def _publish_flags(self, klass, indices, valid, last_of_class,
                       last_of_epoch, epoch):
        """The serve-side state every consumer reads — single source for
        both per-minibatch and sweep serving."""
        self.minibatch_class = klass
        self.minibatch_epoch = epoch
        self.minibatch_valid_size = valid
        self.minibatch_offset = int(indices[0]) if len(indices) else 0
        self.last_minibatch.set(last_of_class)
        self.epoch_ended_for_class.set(last_of_class)
        self.epoch_ended.set(last_of_epoch)

    def _account_served(self, valid, last_of_epoch):
        self.samples_served += valid
        self._served_this_epoch += valid
        if last_of_epoch:
            self.event("epoch", "single", number=self.epoch_number)
            self._served_this_epoch = 0

    def _apply_minibatch(self, klass, indices, valid, last_of_class,
                         last_of_epoch, epoch=0):
        self._publish_flags(klass, indices, valid, last_of_class,
                            last_of_epoch, epoch)
        padded = self._pad_indices(indices)
        if getattr(self, "fill_data", True):
            self.fill_minibatch(padded, valid)
        else:
            # fused-tick mode: the tick gathers in-jit from the originals;
            # the loader only publishes the served indices (host numpy —
            # the transfer rides the fused step's dispatch)
            self.minibatch_indices.data = padded
            if klass == TRAIN and getattr(self, "jit_transform", None):
                self.minibatch_transform_seed = int(
                    self.draw_transform_seeds(1)[0])
            else:
                self.minibatch_transform_seed = 0
        self._account_served(valid, last_of_epoch)

    def _pad_indices(self, indices):
        """Static shapes: pad short index blocks by repeating index 0; the
        mask zeroes their contribution."""
        size = self.max_minibatch_size
        padded = numpy.zeros(size, dtype=numpy.int64)
        padded[:len(indices)] = indices
        return padded

    # -- fleet-mode distribution (reference loader/base.py:631-687) ----------
    def generate_data_for_slave(self, slave=None):
        slave_id = getattr(slave, "id", slave)
        if self.complete:
            raise NoMoreJobsError()
        return self.serve_next_minibatch(slave_id)

    def apply_data_from_master(self, data):
        klass, indices, valid, last_of_class, last_of_epoch, epoch = data
        self._apply_minibatch(klass, numpy.asarray(indices), valid,
                              last_of_class, last_of_epoch, epoch)

    def generate_data_for_master(self):
        return {"samples_served": self.samples_served}

    def apply_data_from_slave(self, data, slave=None):
        slave_id = getattr(slave, "id", slave)
        if self.pending_minibatches_.get(slave_id):
            self.pending_minibatches_[slave_id].pop(0)

    def drop_slave(self, slave=None):
        """Requeue the dropped slave's un-acked minibatches so no sample is
        lost (reference ``loader/base.py:679-687``)."""
        slave_id = getattr(slave, "id", slave)
        pending = self.pending_minibatches_.pop(slave_id, [])
        self.failed_minibatches.extend(pending)
        if pending:
            self.warning("requeued %d minibatches from dropped slave %s",
                         len(pending), slave_id)

    @property
    def has_data_for_slave(self):
        # backpressure means "not ready YET"; exhaustion is signalled by
        # NoMoreJobsError from generate_data_for_slave — returning False
        # here on completion would park job requests forever
        return True

    # -- results --------------------------------------------------------------
    # (the "epochs" metric belongs to the Decision unit — its completed-epoch
    # count, not this serving-side counter, is the published one)
    def get_metric_names(self):
        return ["total_samples"]

    def get_metric_values(self):
        return [self.total_samples]


class LoaderMSEMixin:
    """Adds regression targets to a Loader (reference
    ``loader/base.py:1034-1155`` LoaderMSEMixin/LoaderMSE).

    Serves ``minibatch_targets`` alongside data/labels, normalized by a
    *separate* target normalizer whose state supports ``denormalize()`` —
    stateless normalizers (other than "none") are rejected because the
    network output could never be mapped back to target units (reference
    ``base.py:1100-1111``)."""

    def __init__(self, workflow, **kwargs):
        self.targets_shape = kwargs.pop("targets_shape", ())
        self.target_normalization_type = kwargs.pop(
            "target_normalization_type",
            kwargs.get("normalization_type", "none"))
        self.target_normalization_parameters = kwargs.pop(
            "target_normalization_parameters",
            kwargs.get("normalization_parameters", {}))
        super().__init__(workflow, **kwargs)
        from veles_tpu.loader.normalization import normalizer_registry
        cls = normalizer_registry.get(self.target_normalization_type)
        if cls is None:
            raise ValueError("unknown target_normalization_type %r"
                             % self.target_normalization_type)
        if not cls.INVERTIBLE_FROM_STATE:
            raise ValueError(
                "target normalization %r needs per-sample stats to invert: "
                "test-time forward propagation could not be denormalized"
                % self.target_normalization_type)
        self.minibatch_targets = Array()
        self.target_normalizer = None
