"""model.decode_unscoped_share.serve: Share of the ``*slot_step_many*`` modules' op time that no scope of
the program claims (XLA's own copies and layout changes, and every op of
a module that matches no program of the scope table). cache + attend +
matmul + head + this is all of it."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = '%'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.unscoped_share(ctx, "slot_step_many",
                                 scopes.serve_part)
