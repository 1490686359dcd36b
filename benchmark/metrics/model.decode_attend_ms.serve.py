"""model.decode_attend_ms.serve: Device time a decode step spends in the attend proper: the ops of
the ``*slot_step_many*`` modules under ``attn.attend`` (mask, scores,
softmax, weighted sum), by the program's scope table, over the decode
steps."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.serve_ms(ctx, "attend")
