"""model.decode_moe_experts_ms.serve: Device time a decode step spends in the routed experts' three grouped
products and the gate between them: the ops of the ``*slot_step_many*``
modules under ``moe.experts`` (inside ``mlp``), by the program's scope
table, over the decode steps."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "moe.experts")
