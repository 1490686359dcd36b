"""model.decode_moe_route_ms.serve: Device time a decode step spends round the routed experts' products:
the router and its top-k (``moe.route``), sorting the assignments by
expert and gathering their rows (``moe.dispatch``), and weighting and
summing what comes back (``moe.combine``), all inside ``mlp``, by the
program's scope table, over the decode steps."""

LAYER = 'Model step (parallel/decode.py, transformer_step.py)'
MOVES = 'serve_tokens_per_s_chip'
UNIT = 'ms'
SOURCE = 'device_trace'


def read(ctx):
    from benchmark.harness import moe_scopes

    return moe_scopes.inner_ms(ctx, "moe.route", "moe.dispatch",
                               "moe.combine")
