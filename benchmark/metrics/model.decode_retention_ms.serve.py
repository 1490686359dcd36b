"""model.decode_retention_ms.serve: Device time a decode step spends in what power retention puts in a
block: the gate's projection and its log-sigmoid (``ret.gate``, inside
``attn.qkv``), the feature rows of the new key and of the group's
queries (``ret.phi``, inside ``attn.attend``) and the state's pass
through the chip, read, decayed, added to, multiplied into the
queries and written (``ret.state``, inside ``attn.attend``; the write
is part of it, so ``model.decode_cache_ms.serve`` reads 0 for such a
model), by the program's scope table, over the decode steps. The
projections of q, k and v are ``model.decode_matmul_ms.serve``'s and
the head norms and RoPE ``model.decode_gqa_ms.serve``'s. A program
without such blocks has no such scope and the reader returns None."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "ret.gate", "ret.phi", "ret.state")
