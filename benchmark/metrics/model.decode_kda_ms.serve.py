"""model.decode_kda_ms.serve: Device time a decode step spends in what the gated delta rule (KDA)
puts in a block: the decays, the write strength and the output gate's
projection (``kda.gate``, inside ``attn.qkv``), the three depthwise
convolutions over the carried inputs, their roll and the norms of q
and k (``kda.conv``, inside ``attn.qkv``), the state's pass through the
chip, read, decayed, corrected, read with q and written (``kda.state``,
inside ``attn.attend``; the write is part of it) and the gated head
norm (``kda.norm``, inside ``attn.out``), by the program's scope table,
over the decode steps. The projections of q, k, v and the output are
``model.decode_matmul_ms.serve``'s. A program without such blocks (the
parent, every other model) has no such scope and the reader returns
None."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "kda.conv", "kda.gate", "kda.state",
                               "kda.norm")
