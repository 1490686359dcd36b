"""Synthetic image sets from a seed — the benchmark's copy of
``samples/synthetic_twins.dataset`` (sound generator; copied so that a
later PR can change the sample but not the yardstick).

Differences from the original: float32 is drawn directly
(``Generator.random(dtype=float32)``; the original draws float64 with
``RandomState.rand`` and casts), and rows are made in blocks of
``BLOCK`` rows, each from its own generator seeded ``[seed, block]``,
so a few threads fill the array at once and any block can be made
again alone.
"""

from concurrent.futures import ThreadPoolExecutor

import numpy

BLOCK = 256


def labels_for(seed, n_valid, n_train, label_classes):
    """int32 labels laid out [validation | train], each split
    class-balanced and permuted."""
    rng = numpy.random.Generator(numpy.random.PCG64([int(seed), 1 << 20]))
    return numpy.concatenate([
        rng.permutation(numpy.arange(n, dtype=numpy.int32)
                        % label_classes)
        for n in (n_valid, n_train)])


def prototypes_for(seed, sample_shape, label_classes):
    rng = numpy.random.Generator(numpy.random.PCG64([int(seed), 1 << 21]))
    return rng.random((label_classes,) + tuple(sample_shape),
                      dtype=numpy.float32)


def fill_rows(out, seed, first_row, labels, prototypes):
    """Fill ``out`` (rows ``first_row`` ... of the set; ``first_row`` a
    multiple of BLOCK) in place."""
    if first_row % BLOCK:
        raise ValueError("first_row %d is not a multiple of %d"
                         % (first_row, BLOCK))
    for start in range(0, len(out), BLOCK):
        rows = out[start:start + BLOCK]
        rng = numpy.random.Generator(numpy.random.PCG64(
            [int(seed), (first_row + start) // BLOCK]))
        rng.random(out=rows.reshape(len(rows), -1), dtype=numpy.float32)
        rows += prototypes[labels[first_row + start:
                                  first_row + start + len(rows)]]
        rows *= numpy.float32(127.5)


def dataset(seed, sample_shape, n_valid, n_train, label_classes,
            threads=4):
    """``(data float32 (N, *sample_shape) in [0, 255], labels int32)``
    laid out [validation | train]: each sample is its class's fixed
    random prototype plus uniform noise, so a few sweeps lower the
    loss measurably (random labels would leave it at ln(classes))."""
    total = n_valid + n_train
    labels = labels_for(seed, n_valid, n_train, label_classes)
    prototypes = prototypes_for(seed, sample_shape, label_classes)
    data = numpy.empty((total,) + tuple(sample_shape), numpy.float32)
    per = max(BLOCK, -(-total // (threads * BLOCK)) * BLOCK)
    spans = [(s, min(s + per, total)) for s in range(0, total, per)]
    with ThreadPoolExecutor(max(1, threads)) as pool:
        for future in [pool.submit(fill_rows, data[a:b], seed, a, labels,
                                   prototypes) for a, b in spans]:
            future.result()
    return data, labels
