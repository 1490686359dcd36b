"""model.decode_latent_ms.serve: Device time a decode step spends in what latent attention adds to a
block: the low-rank query and the latent row (``mla.q``, ``mla.kv``),
RoPE (``mla.rope``), all inside ``attn.qkv``, and taking the query
into the latent space and the gathered rows out of it
(``mla.absorb``, inside ``attn.attend``), by the program's scope
table, over the decode steps. The attend over the window itself is
``model.decode_attend_ms.serve``'s."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "mla.q", "mla.kv", "mla.rope",
                               "mla.absorb")
