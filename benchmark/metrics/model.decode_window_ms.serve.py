"""model.decode_window_ms.serve: Device time a decode step spends in its window layers' ring: the
attend of the window layers over the ring and the chunk's staged
columns (``swa.ring``, inside ``attn.attend``) and the ring's share of
the chunk's block write (``cache.ring``, inside ``cache.append``), by
the program's scope table, over the decode steps. The rotation of q and
k is ``swa.rope``, inside ``attn.qkv``. A program without window
layers has no such scope and the reader returns None."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "swa.ring", "cache.ring")
