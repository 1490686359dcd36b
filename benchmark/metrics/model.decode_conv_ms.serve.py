"""model.decode_conv_ms.serve: Device time a decode step spends in what the gated short convolution
puts in a block: the input projection to ``B``, ``C`` and ``u``
(``conv.in``, inside ``attn.qkv``), the gate, the three taps over the
slot's carried state and the gate by ``C`` (``conv.mix``, inside
``attn.attend``), the output projection (``conv.out``, inside
``attn.out``) and the state's roll and write-back, which an idle lane
is left out of (``cache.state``, inside ``cache.append``), by the
program's scope table, over the decode steps. A program without such
blocks has no such scope and the reader returns None."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "conv.in", "conv.mix", "conv.out",
                               "cache.state")
