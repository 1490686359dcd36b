"""model.decode_global_ms.serve: Device time a decode step spends in its global layers' attend over
their rows a position and the chunk's staged columns (``nope.attend``,
inside ``attn.attend``), by the program's scope table, over the decode
steps. A program without such layers has no such scope and the reader
returns None."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "nope.attend")
