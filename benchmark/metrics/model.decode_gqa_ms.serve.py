"""model.decode_gqa_ms.serve: Device time a decode step spends in what grouped-query attention adds
to a block's projection: RMSNorm over each head's q and k
(``gqa.norm``) and RoPE at the slot's own position (``gqa.rope``),
both inside ``attn.qkv``, by the program's scope table, over the
decode steps. The projections themselves are
``model.decode_matmul_ms.serve``'s and the attend over the window
``model.decode_attend_ms.serve``'s. A program without such blocks has
no such scope and the reader returns None."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "gqa.norm", "gqa.rope")
