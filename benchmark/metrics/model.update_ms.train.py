"""model.update_ms.train: Device time of a minibatch step's optimizer pass: the ops of the
``*train_sweep*`` modules under ``update`` (``parallel/fused.py``
``build_tick``, one inner scope a layer), by the program's scope table,
over the minibatch steps scanned."""

LAYER = 'Model step (parallel/fused.py tick)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.train_ms(ctx, "update")
