"""engine.decision_wait_ms.train: Host time an epoch spends waiting for the device's scalars, median:
the program's own span ``decision.settle`` (``nn/decision.py``, round
the ``jax.device_get`` calls), summed between one ``engine.train_sweep``
dispatch and the next."""

LAYER = 'Workflow engine (models/standard.py, parallel/fused.py, nn/decision.py)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'program_span'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.median_ms_between(ctx, "decision.settle",
                                    "engine.train_sweep")
