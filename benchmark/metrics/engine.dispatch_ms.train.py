"""engine.dispatch_ms.train: Host self time of one sweep dispatch, median: the program's own
spans ``engine.train_sweep`` and ``engine.eval_sweep``
(``parallel/fused.py`` ``FusedTick.run``, from argument preparation to
the jitted call's return), each less what spans inside it cover."""

LAYER = 'Workflow engine (models/standard.py, parallel/fused.py, nn/decision.py)'
MOVES = 'train_images_per_s_chip'
UNIT = 'ms'
SOURCE = 'program_span'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.median_self_ms(ctx, "engine.train_sweep",
                                 "engine.eval_sweep")
