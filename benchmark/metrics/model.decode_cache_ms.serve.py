"""model.decode_cache_ms.serve: Device time a decode step spends moving the K/V slab: the ops of
the ``*slot_step_many*`` modules under ``cache.append`` (the per-slot
``dynamic_update_slice``s) and ``cache.read`` (the slices handed to the
attend) of ``parallel/decode.py`` ``_slot_step``, by the program's scope
table (``observe/xla_stats.scope_table``), over the decode steps."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import scopes

    return scopes.serve_ms(ctx, "cache_append", "cache_read")
