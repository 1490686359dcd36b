"""Tests for the file/image loader pipeline (reference test_loader
image-loading coverage)."""

import os

import numpy
import pytest

from veles_tpu.dummy import DummyLauncher, DummyWorkflow
from veles_tpu.loader.base import TRAIN, VALID
from veles_tpu.loader.file_loader import (AutoLabelMixin, FileFilter,
                                          FileListScannerMixin)
from veles_tpu.loader.image import (AutoLabelFileImageLoader,
                                    FileListImageLoader, crop_image,
                                    decode_image, scale_image)

PIL = pytest.importorskip("PIL")
from PIL import Image  # noqa: E402


def write_png(path, color, size=(12, 12)):
    arr = numpy.zeros(size + (3,), numpy.uint8)
    arr[:, :] = color
    # distinguishing texture: a bright corner square
    arr[:3, :3] = 255
    Image.fromarray(arr).save(path)


@pytest.fixture
def image_tree(tmp_path):
    """<split>/<label>/<n>.png tree: red vs blue squares."""
    rng = numpy.random.RandomState(3)
    for split, count in (("train", 20), ("validation", 8)):
        for label, base in (("red", (200, 30, 30)), ("blue", (30, 30, 200))):
            d = tmp_path / split / label
            d.mkdir(parents=True)
            for i in range(count):
                jitter = rng.randint(-20, 20, 3)
                color = numpy.clip(numpy.array(base) + jitter, 0, 255)
                write_png(str(d / ("%02d.png" % i)), color)
    return tmp_path


class TestHelpers:
    def test_decode_scale_crop(self, tmp_path):
        p = str(tmp_path / "img.png")
        write_png(p, (10, 20, 30), size=(20, 10))
        arr = decode_image(p)
        assert arr.shape == (20, 10, 3)
        scaled = scale_image(arr, (8, 8))
        assert scaled.shape == (8, 8, 3)
        fitted = scale_image(arr, (8, 8), maintain_aspect_ratio=True,
                             background_color=0)
        assert fitted.shape == (8, 8, 3)
        # aspect preserved: 20x10 -> 8x4 centered, columns 0-1 background
        assert float(fitted[:, 0].max()) == 0.0
        cropped = crop_image(scaled, (4, 4), offset="center")
        assert cropped.shape == (4, 4, 3)

    def test_decode_gray(self, tmp_path):
        p = str(tmp_path / "img.png")
        write_png(p, (100, 100, 100))
        assert decode_image(p, "GRAY").shape == (12, 12, 1)

    def test_file_filter(self):
        f = FileFilter(file_type="image", file_subtypes=["png"],
                       ignored_files=[".*bad.*"])
        assert f.is_valid_filename("/data/x.png")
        assert not f.is_valid_filename("/data/x.jpg")
        assert not f.is_valid_filename("/data/bad.png")
        assert not f.is_valid_filename("/data/x.txt")

    def test_file_filter_alternatives_fully_anchored(self):
        # regression: '^a|b$' would anchor only the outer alternatives
        f = FileFilter(file_type="image", file_subtypes=["png"],
                       ignored_files=["junk.png", "bad.png"])
        assert f.is_valid_filename("junk.pngXXX.png")
        assert not f.is_valid_filename("junk.png")
        assert not f.is_valid_filename("bad.png")

    def test_fractional_crop(self, tmp_path):
        d = tmp_path / "c" / "lab"
        d.mkdir(parents=True)
        write_png(str(d / "0.png"), (90, 90, 90))
        loader = AutoLabelFileImageLoader(
            DummyWorkflow(), train_paths=[str(tmp_path / "c")],
            size=(12, 12), crop=(0.5, 0.5), minibatch_size=1)
        loader.initialize()
        assert loader.minibatch_data.shape == (1, 6, 6, 3)

    def test_auto_label(self):
        m = AutoLabelMixin()
        assert m.get_label_from_filename(
            os.path.join("data", "cats", "1.png")) == "cats"
        with pytest.raises(ValueError):
            m.get_label_from_filename("orphan.png")


class TestAutoLabelFileImageLoader:
    def make(self, tree, **kwargs):
        loader = AutoLabelFileImageLoader(
            DummyWorkflow(),
            train_paths=[str(tree / "train")],
            validation_paths=[str(tree / "validation")],
            size=(12, 12), minibatch_size=8, **kwargs)
        loader.initialize()
        return loader

    def test_scans_and_labels(self, image_tree):
        loader = self.make(image_tree)
        assert loader.class_lengths == [0, 16, 40]
        assert loader.labels_mapping == {"blue": 0, "red": 1}
        loader.run()
        assert loader.minibatch_data.shape == (8, 12, 12, 3)
        assert loader.minibatch_class == VALID

    def test_crop(self, image_tree):
        loader = self.make(image_tree, crop=(8, 8))
        assert loader.minibatch_data.shape[1:] == (8, 8, 3)

    def test_mirror_augmentation_train_only(self, image_tree):
        loader = self.make(image_tree, mirror="random")
        assert loader.has_fill_transforms
        # drain validation (not augmented)
        loader.run()
        valid_batch = numpy.asarray(loader.minibatch_data.mem)
        idx = numpy.asarray(loader.minibatch_indices.mem)
        raw = numpy.asarray(loader.original_data.mem)[idx]
        numpy.testing.assert_array_equal(valid_batch, raw)
        loader.run()
        # train minibatches: some samples mirrored
        mirrored_any = False
        for _ in range(5):
            loader.run()
            if loader.minibatch_class != TRAIN:
                continue
            got = numpy.asarray(loader.minibatch_data.mem)
            idx = numpy.asarray(loader.minibatch_indices.mem)
            raw = numpy.asarray(loader.original_data.mem)[idx]
            flipped = raw[:, :, ::-1]
            for i in range(len(got)):
                if numpy.array_equal(got[i], flipped[i]) \
                        and not numpy.array_equal(got[i], raw[i]):
                    mirrored_any = True
        assert mirrored_any


class TestFileListImageLoader:
    def test_index_file(self, image_tree, tmp_path):
        index = tmp_path / "train.txt"
        lines = []
        for label in ("red", "blue"):
            d = image_tree / "train" / label
            for name in sorted(os.listdir(d)):
                lines.append("%s %s" % (d / name, label))
        index.write_text("\n".join(lines) + "\n")
        loader = FileListImageLoader(
            DummyWorkflow(), path_to_train_text_file=str(index),
            size=(12, 12), minibatch_size=10, validation_ratio=0.2)
        loader.initialize()
        assert loader.class_lengths == [0, 8, 32]
        assert set(loader.labels_mapping) == {"red", "blue"}

    def test_json_index(self, image_tree, tmp_path):
        d = image_tree / "train" / "red"
        entries = {
            name: {"path": str(d / name), "label": ["red"]}
            for name in sorted(os.listdir(d))}
        index = tmp_path / "train.json"
        import json
        index.write_text(json.dumps(entries))
        m = FileListScannerMixin()
        m.info = lambda *a: None
        m.warning = lambda *a: None
        files = m.scan_files(str(index))
        assert len(files) == 20
        assert m.get_label_from_filename(files[0]) == "red"


class TestImageMSE:
    def _tree(self, tmp_path, n=6, labeled=False):
        rng = numpy.random.RandomState(5)
        (tmp_path / "in").mkdir()
        (tmp_path / "targets").mkdir()
        for i in range(n):
            color = tuple(int(c) for c in rng.randint(0, 255, 3))
            write_png(str(tmp_path / "in" / ("s%02d.png" % i)), color)
            write_png(str(tmp_path / "targets" / ("t%02d.png" % i)),
                      tuple(255 - c for c in color))
        return tmp_path

    def test_unlabeled_pairs_by_sorted_order(self, tmp_path):
        """i-th sample <-> i-th sorted target (reference image_mse.py
        unlabeled contract); targets ride the device gather."""
        from veles_tpu.loader.image import FileImageLoaderMSE

        tree = self._tree(tmp_path)
        wf = DummyWorkflow()
        loader = FileImageLoaderMSE(
            wf, train_paths=[str(tree / "in")],
            target_paths=[str(tree / "targets")],
            size=(12, 12), minibatch_size=3,
            target_normalization_type="none")
        loader.initialize()
        assert loader.class_lengths == [0, 0, 6]
        assert loader.original_targets.shape == (6, 12, 12, 3)
        loader.run()
        assert loader.minibatch_targets.shape == (3, 12, 12, 3)
        # the served target rows match the stored per-sample targets
        idx = numpy.asarray(loader.minibatch_indices.data)[:3]
        numpy.testing.assert_allclose(
            numpy.asarray(loader.minibatch_targets.data),
            numpy.asarray(loader.original_targets.data)[idx])

    def test_labeled_maps_by_label(self, tmp_path):
        """Labeled datasets look targets up by label (target_label_map
        role); duplicate target labels are rejected."""
        from veles_tpu.loader.image import FileImageLoaderMSE

        tree = self._tree(tmp_path, n=4)

        class Labeled(FileImageLoaderMSE):
            def get_label_from_filename(self, filename):
                # s00/t00 -> 0 ... pairs by trailing number
                return int(os.path.basename(filename)[1:3]) % 4

        wf = DummyWorkflow()
        loader = Labeled(
            wf, train_paths=[str(tree / "in")],
            target_paths=[str(tree / "targets")],
            size=(8, 8), minibatch_size=2,
            target_normalization_type="none")
        loader.initialize()
        assert loader.original_targets.shape == (4, 8, 8, 3)
        # sample i carries label i -> target row must be target t0i
        t2 = decode_image(str(tree / "targets" / "t02.png"))
        t2 = scale_image(t2, (8, 8))
        numpy.testing.assert_allclose(
            numpy.asarray(loader.original_targets.data)[2], t2)

    def test_count_mismatch_rejected(self, tmp_path):
        from veles_tpu.loader.image import FileImageLoaderMSE

        tree = self._tree(tmp_path)
        os.unlink(str(tree / "targets" / "t05.png"))
        wf = DummyWorkflow()
        loader = FileImageLoaderMSE(
            wf, train_paths=[str(tree / "in")],
            target_paths=[str(tree / "targets")],
            size=(12, 12), minibatch_size=3,
            target_normalization_type="none")
        with pytest.raises(ValueError):
            loader.initialize()


@pytest.mark.slow
class TestConvnetEndToEnd:
    def test_convnet_trains_through_image_pipeline(self, image_tree):
        """The 'done' criterion: a CIFAR-style convnet
        trains end-to-end through the image pipeline."""
        from veles_tpu.models.standard import StandardWorkflow

        wf = StandardWorkflow(
            DummyLauncher(),
            loader_cls=AutoLabelFileImageLoader,
            loader_kwargs=dict(
                train_paths=[str(image_tree / "train")],
                validation_paths=[str(image_tree / "validation")],
                size=(12, 12), minibatch_size=8,
                normalization_type="internal_mean"),
            layers=[
                {"type": "conv_relu", "n_kernels": 8, "kx": 3, "ky": 3},
                {"type": "max_pooling", "kx": 2, "ky": 2},
                {"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 2},
            ],
            learning_rate=0.02,
            decision_kwargs=dict(max_epochs=6), name="image-convnet")
        wf.initialize()
        wf.run()
        best = wf.decision.best_n_err[1]
        assert best is not None and best <= 4, \
            "convnet at %s/16 validation errors" % best


class TestFusedAugmentation:
    """In-jit mirror augmentation ON the fused path: the tick applies
    the loader's transform itself, seeded identically to graph mode."""

    def _build(self, image_tree, fused):
        from veles_tpu.core import prng
        from veles_tpu.models.standard import StandardWorkflow

        prng.get("default").seed(42)
        prng.get("loader").seed(24)
        return StandardWorkflow(
            DummyLauncher(),
            loader_cls=AutoLabelFileImageLoader,
            loader_kwargs=dict(
                train_paths=[str(image_tree / "train")],
                validation_paths=[str(image_tree / "validation")],
                size=(12, 12), minibatch_size=8, mirror="random",
                normalization_type="internal_mean"),
            layers=[
                {"type": "all2all_tanh", "output_sample_shape": 16},
                {"type": "softmax", "output_sample_shape": 2},
            ],
            learning_rate=0.05, fused=fused,
            decision_kwargs=dict(max_epochs=3), name="aug-fused")

    def test_mirror_loader_fuses_and_matches_graph_mode(self, image_tree):
        """If the fused tick silently dropped the augmentation, the
        graph run (which DOES augment) would diverge — this identity IS
        the dead-augmentation guard."""
        graph = self._build(image_tree, fused=False)
        graph.initialize()
        assert graph.fused_tick is None, "fused=False must not splice"
        graph.run()

        fused = self._build(image_tree, fused=True)
        fused.initialize()
        assert fused.fused_tick is not None, \
            "mirror loader must fuse now (jit_transform)"
        fused.run()
        # identical seeds -> identical augmentation -> identical metrics
        assert fused.decision.best_n_err[1] == graph.decision.best_n_err[1]
        assert fused.decision.last_epoch_n_err == \
            graph.decision.last_epoch_n_err
        numpy.testing.assert_allclose(
            numpy.asarray(fused.forwards[0].weights.data),
            numpy.asarray(graph.forwards[0].weights.data), atol=2e-2)

    def test_shift_transform_fused_matches_graph(self):
        """train_transform="shift1" on a plain FullBatchLoader: the
        fused tick replicates the shift in-jit (same seeds), so both
        engines land identical metrics — the dead-augmentation guard
        for the second transform."""
        from veles_tpu.core import prng
        from veles_tpu.models.standard import StandardWorkflow

        rng = numpy.random.RandomState(3)
        data = rng.rand(120, 8, 8, 1).astype(numpy.float32)
        labels = rng.randint(0, 4, 120).astype(numpy.int32)

        def build(fused):
            prng.get("default").seed(42)
            prng.get("loader").seed(24)
            return StandardWorkflow(
                DummyLauncher(),
                loader_kwargs=dict(
                    data=data, labels=labels,
                    class_lengths=[0, 40, 80], minibatch_size=20,
                    train_transform="shift1",
                    normalization_type="none"),
                layers=[
                    {"type": "all2all_tanh", "output_sample_shape": 16},
                    {"type": "softmax", "output_sample_shape": 4},
                ],
                learning_rate=0.05, fused=fused,
                decision_kwargs=dict(max_epochs=3), name="shift-fused")

        graph = build(False)
        graph.initialize()
        assert graph.fused_tick is None
        graph.run()
        fused = build(True)
        fused.initialize()
        assert fused.fused_tick is not None, \
            "shift1 loader must fuse (jit_transform)"
        fused.run()
        assert fused.decision.best_n_err[1] == graph.decision.best_n_err[1]
        numpy.testing.assert_allclose(
            numpy.asarray(fused.forwards[0].weights.data),
            numpy.asarray(graph.forwards[0].weights.data), atol=2e-2)

    def test_shift_batch_semantics(self):
        """shift_batch: every output sample is a zero-filled integer
        translation of its input within +-max_shift."""
        from veles_tpu.ops.augment import shift_batch

        rng = numpy.random.RandomState(1)
        batch = rng.rand(12, 5, 7, 2).astype(numpy.float32) + 1.0
        out = numpy.asarray(shift_batch(batch, 11, max_shift=1))

        def shifted(img, dh, dw):
            ref = numpy.zeros_like(img)
            hs = slice(max(dh, 0), img.shape[0] + min(dh, 0))
            ws = slice(max(dw, 0), img.shape[1] + min(dw, 0))
            hsrc = slice(max(-dh, 0), img.shape[0] + min(-dh, 0))
            wsrc = slice(max(-dw, 0), img.shape[1] + min(-dw, 0))
            ref[hs, ws] = img[hsrc, wsrc]
            return ref

        matched = 0
        moved = 0
        for i in range(len(batch)):
            candidates = [(dh, dw) for dh in (-1, 0, 1)
                          for dw in (-1, 0, 1)]
            hits = [(dh, dw) for dh, dw in candidates
                    if numpy.array_equal(out[i],
                                         shifted(batch[i], dh, dw))]
            assert hits, "sample %d is not any +-1 shift" % i
            matched += 1
            if (0, 0) not in hits:
                moved += 1
        assert matched == len(batch)
        assert moved > 0, "seeded shifts must actually move samples"
        numpy.testing.assert_array_equal(
            out, numpy.asarray(shift_batch(batch, 11, max_shift=1)))

    def test_shared_mirror_math(self):
        """Both engines trace ops.augment.mirror_batch: check its
        semantics directly — per-sample flip over the W axis, seeded."""
        from veles_tpu.ops.augment import mirror_batch

        rng = numpy.random.RandomState(0)
        batch = rng.rand(16, 4, 6, 3).astype(numpy.float32)
        out = numpy.asarray(mirror_batch(batch, 7))
        flipped = batch[:, :, ::-1]
        per_sample = [numpy.array_equal(out[i], flipped[i])
                      or numpy.array_equal(out[i], batch[i])
                      for i in range(16)]
        assert all(per_sample), "samples must be kept or W-flipped"
        n_flipped = sum(numpy.array_equal(out[i], flipped[i])
                        and not numpy.array_equal(out[i], batch[i])
                        for i in range(16))
        assert 0 < n_flipped < 16, "seeded bernoulli must mix"
        # deterministic per seed, different across seeds
        numpy.testing.assert_array_equal(
            out, numpy.asarray(mirror_batch(batch, 7)))
        assert not numpy.array_equal(
            out, numpy.asarray(mirror_batch(batch, 8)))
