"""model.unscoped_share.train: Share of the ``*train_sweep*`` modules' op time that no scope of
the program claims: ops whose instruction carries no ``op_name`` or one
under none of ``data``/``fwd``/``update``/``reduce`` (XLA's own copies and
layout changes), and every op of a module that matches no program of
the scope table. forward + backward + update + this is all of it."""

LAYER = 'Model step (parallel/fused.py tick)'
MOVES = 'train_images_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.unscoped_share(ctx, "train_sweep", scopes.train_part)
