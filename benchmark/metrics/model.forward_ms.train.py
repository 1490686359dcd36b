"""model.forward_ms.train: Device time of a minibatch step's forward half: the ops of the
``*train_sweep*`` modules that the program's scope table
(``observe/xla_stats.scope_table``) puts under ``data`` (gather, normalise,
augment) or under ``fwd`` without ``transpose(`` in the ``op_name``
(``parallel/fused.py`` ``build_tick``), over the minibatch steps scanned."""

LAYER = 'Model step (parallel/fused.py tick)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.train_ms(ctx, "forward")
