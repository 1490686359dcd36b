"""model.backward_ms.train: Device time of a minibatch step's backward half: the ops of the
``*train_sweep*`` modules under ``fwd`` whose ``op_name`` holds
``transpose(`` (JAX's own mark of a gradient) or under ``reduce`` (the
mesh's gradient merge), by the program's scope table, over the minibatch
steps scanned."""

LAYER = 'Model step (parallel/fused.py tick)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.train_ms(ctx, "backward")
