"""model.decode_matmul_ms.serve: Device time a decode step spends in the blocks' weight products:
the ops of the ``*slot_step_many*`` modules under ``attn.qkv``,
``attn.out`` and ``mlp``, by the program's scope table, over the decode
steps."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.serve_ms(ctx, "matmul")
