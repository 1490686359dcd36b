"""model.decode_head_ms.serve: Device time a decode step spends outside the blocks: the ops of
the ``*slot_step_many*`` modules under ``embed``, ``head`` and ``sample``,
by the program's scope table, over the decode steps."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.serve_ms(ctx, "head")
